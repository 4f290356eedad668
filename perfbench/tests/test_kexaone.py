"""The checks of the cell ``kexaone-serve-mixedlen`` and of what it
brought: the configuration against the catalog's published keys, the
shapes functions against hand counts, the roofline reader on a small
recorded slice, and the ``--tiny`` rehearsal's result line.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_kexaone.py -q
"""
import importlib
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH_DIR)

import shapes_kexaone as shp     # noqa: E402
import trafficgen                # noqa: E402

CELL = "kexaone-serve-mixedlen"
CONFIG = "k-exaone-236b-a23b"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CFG = json.load(open(os.path.join(BENCH_DIR, "configs", CONFIG + ".json")))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

#: the published config.json's numbers (catalog row K-EXAONE-236B-A23B)
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 128, "hidden_size": 6144,
    "intermediate_size": 18432, "max_position_embeddings": 262144,
    "moe_intermediate_size": 2048, "n_group": 1, "num_attention_heads": 64,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 8, "num_nextn_predict_layers": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "routed_scaling_factor": 2.5, "sliding_window": 128, "topk_group": 1,
    "vocab_size": 153600}


def test_the_configuration_keeps_every_published_width():
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    assert entry["file"] == "perfbench/configs/%s.json" % CONFIG
    assert entry["source"] == CFG["source"]
    assert set(entry["reduced"]) == set(CFG["changed"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert CFG["published"][key] == value
        else:
            assert CFG[key] == value, key
    # the nested groups are the published ones, whole
    assert CFG["rope_parameters"] == {"rope_theta": 1000000,
                                      "rope_type": "default"}
    assert CFG["layer_types"] == (["sliding_attention"] * 3
                                  + ["full_attention"]) * 12
    assert CFG["sliding_windows"] == [128, 128, 128, 0] * 12
    assert CFG["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert (CFG["scoring_func"], CFG["norm_topk_prob"],
            CFG["tie_word_embeddings"], CFG["model_type"]) == (
        "sigmoid", True, False, "exaone_moe")
    # the cut keeps to the guide's floors: the dense layer once, then
    # one whole period in its published order
    assert CFG["num_hidden_layers"] == len(CFG["layers_kept"]) == 5
    assert CFG["layers_kept"] == [0, 4, 5, 6, 7]
    assert [CFG["layer_types"][l] for l in CFG["layers_kept"][1:]] \
        == CFG["layer_types"][:4]
    assert CFG["num_experts"] == CFG["experts_held"][1] >= 8
    assert CFG["num_experts"] * CFG["chips_sharing_a_layer"] == 128
    assert CFG["chips_sharing_a_layer"] == 8
    assert CFG["vocab_size"] * 8 == 153600
    # the four readings of what config.json leaves open, and the MTP
    assert {"qk_norm", "rope_layers", "norm_placement", "router_bias",
            "left_out"} <= set(CFG["assumed"])
    assert "multi-token-prediction" in CFG["assumed"]["left_out"]
    # the model file states the same sizes
    ex = importlib.import_module("mxnet_tpu.gluon.model_zoo.exaone_moe")
    for key, value in ex.PUBLISHED.items():
        if key == "rope_theta":
            assert value == CFG["rope_parameters"]["rope_theta"]
        elif key == "layer_types":
            assert value == CFG["layer_types"]
        else:
            assert PUBLISHED[key] == value, key


def test_the_cell_is_the_issues():
    cell = json.load(open(os.path.join(BENCH_DIR, "workloads",
                                       CELL + ".json")))
    eng = cell["engine"]
    assert (eng["num_slots"], eng["page_size"], eng["max_seq_len"],
            eng["max_prefill_len"], eng["kv_dtype"], eng["spec_k"],
            eng["decode_ahead"]) == (64, 64, 19456, 2048, "bf16", 0, 2)
    assert eng["num_pages"] == 12288
    assert cell["runner"] == "serve_kexaone"
    assert cell["runner_params"]["queue_depth_x_slots"] == 2
    # the window's edges are the schedule's, not a number fitted to a pace
    assert "warm_decode_steps" not in cell["runner_params"]
    check = cell["correct"]
    assert (check["prompt_len"], check["max_new"]) == (4608, 8)
    assert check["after"] == {"prompt_len": 16384, "max_new": 4}
    mix = json.load(open(os.path.join(BENCH_DIR, "traffic",
                                      "mixedlen-backlog.json")))
    assert mix["arrivals"] == {"process": "backlog"} and not mix["apps"]
    assert (mix["pool"], mix["block"]) == (128, 16)
    assert mix["prompt"] == {"dist": "lognormal", "median": 4096,
                             "sigma": 1.0, "min": 512, "max": 16384}
    assert mix["output"] == {"dist": "lognormal", "median": 768,
                             "sigma": 0.7, "min": 192, "max": 3072}
    assert mix["prompt"]["max"] + mix["output"]["max"] <= eng["max_seq_len"]
    # ISSUE 34's numbers of the mix, by the generator itself
    prompts = trafficgen.lognormal_set(mix["prompt"], 128)
    assert round(float(prompts.mean())) == 5752
    assert round(float(-(-prompts // 2048).mean()), 2) == 3.26
    assert round(float(trafficgen.lognormal_set(
        mix["output"], 128).mean())) == 957
    stream = trafficgen.requests(mix, 0, CFG["vocab_size"], 64, stagger=64)
    first = [next(stream) for _ in range(64)]
    assert sum(-(-p.size // 2048) for _, p, _ in first) == 209
    # the starting population's pages fit the pool with room for decode
    assert sum(-(-(p.size + n) // 64) for _, p, n in first) \
        < eng["num_pages"]
    entry = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "mixedlen-backlog", 1)


def test_shapes_against_hand_counts():
    """ISSUE 34's arithmetic: 113.2M of attention a layer, a dense
    SwiGLU of 339.7M, an expert of 37.7M, 3.71B parameters, 7.42 GB;
    4,096 B a token in pages, 2.1 MB a slot in rings."""
    assert shp.attention_params(CFG) == 6144 * 10240 + 8192 * 6144 \
        == 113_246_208
    assert shp.expert_params(CFG) == 3 * 6144 * 2048 == 37_748_736
    assert shp.layer_kinds(CFG) == ["sliding_attention"] * 4 \
        + ["full_attention"]
    assert shp.ffn_kinds(CFG) == ["dense"] + ["moe"] * 4
    dense = 5 * 113_246_208 + 3 * 6144 * 18432 \
        + 4 * (37_748_736 + 6144 * 128)
    assert shp.dense_params(CFG) == dense == 1_060_110_336
    assert shp.params(CFG) == dense + 2 * 19200 * 6144 \
        + 4 * 16 * 37_748_736 == 3_711_959_040
    assert round(shp.params(CFG) * 2 / 1e9, 2) == 7.42
    assert shp.kv_row_bytes(CFG) == shp.page_bytes_per_token(CFG) == 4096 \
        == CFG["cache"]["page_bytes_per_token"]
    assert shp.ring_bytes_per_slot(CFG) == 4 * 128 * 4096 == 2_097_152 \
        == CFG["cache"]["ring_bytes_per_slot"]
    assert shp.token_matmul_flops(CFG) == 2 * dense
    assert shp.head_flops(CFG) == 2 * 19200 * 6144
    assert shp.expert_flops(CFG, 10) == 20 * 37_748_736
    # a (query, head, key): 2 x 128 for the score, 2 x 128 for the value
    assert shp.attention_flops(CFG, 1000) == 1000 * 64 * 4 * 128
    assert shp.kv_bytes(CFG, 1000) == 4_096_000
    assert shp.moe_gmm_bytes(CFG, 3) == 3 * 37_748_736 * 2
    # a decode run: the matrices and the head once, the hit experts, the
    # rows read
    assert shp.decode_bytes(CFG, 2, 60, 1000) == 2 * 2 * (
        dense + 19200 * 6144) + 60 * 75_497_472 + 4_096_000
    # a chunk of 2048 rows at offset 4096 on the full layer
    assert shp.chunk_full_attention(CFG, 2048, 4096) \
        == sum(4096 + i + 1 for i in range(2048))
    assert shp.step_flops(CFG, 100, 3, 10, 1000) == 100 * 2 * dense \
        + 3 * 2 * 19200 * 6144 + 20 * 37_748_736 + 1000 * 64 * 512
    # a chunk run at the mix's longest offset: ~5.9 TFLOP
    rows = 2048 * 14336 + 2048 * 2049 // 2
    window = sum(min(14336 + i + 1, 128) for i in range(2048))
    total = shp.step_flops(CFG, 2048, 1, 2048 * 4, rows + 4 * window)
    assert 5.8e12 < total < 6.2e12


def _record(**over):
    """A small recorded slice: 10 decode steps and 2 chunk runs in the
    traced slice of a window of 100 decode steps and 20 chunk runs; 60
    slots decode at a context of 6,000."""
    c = {"decode_steps": 100, "prefill_chunks": 20,
         "kv_rows_full": 100 * 60 * 5 * 6000,
         "kv_rows_read": 100 * 60 * (6000 + 4 * 128),
         "moe_local_assignments": 100 * 240, "moe_experts_hit": 100 * 62,
         "moe_assignments": 100 * 1920,
         "chunk_rows": 20 * 1800,
         "kv_prefill_rows_read": 20 * 1800 * (3000 + 4 * 128),
         "kv_prefill_rows_full": 20 * 1800 * 5 * 3000,
         "moe_prefill_local_assignments": 20 * 8192,
         "moe_prefill_experts_hit": 20 * 64}
    c.update(over)
    spans = [{"decode_steps": 1, "prefills": 0, "tokens": 60}] * 10
    return {"counters": c, "config": CFG, "peaks": PEAKS, "trace": {
        "spans": spans, "busy_s": 0.5, "window_s": 0.6,
        "modules": [["jit_decode", 0.2, 10], ["jit_prefill", 0.3, 2]],
        "device_ops": [["paged_decode.1", 0.03], ["moe_gmm.2", 0.12],
                       ["fusion.9", 0.2]]}}


def test_roofline_reader_on_a_small_recorded_slice():
    reader = importlib.import_module("readers.kexaone_roofline")
    rec = _record()
    assert reader.value(rec, {"what": "rows_read_pct"}) \
        == pytest.approx(100 * (6000 + 512) / (5 * 6000))
    # the paged kernel: 10 steps of 60 slots x 6,000 rows of 4,096 B
    rows = 10 * 60 * 6000
    least = max(rows * 4096 / 819e9, rows * 64 * 512 / 197e12)
    assert reader.value(rec, {"what": "paged_attn",
                              "match": "paged_decode"}) \
        == pytest.approx(100 * least / 0.03)
    least = max(2 * (10 * 240 + 2 * 8192) * 37_748_736 / 197e12,
                (10 * 62 + 2 * 64) * 37_748_736 * 2 / 819e9)
    assert reader.value(rec, {"what": "moe_gmm", "match": "moe_gmm"}) \
        == pytest.approx(100 * least / 0.12)
    nbytes = shp.decode_bytes(CFG, 10, 10 * 62, 10 * 60 * (6000 + 512))
    assert reader.value(rec, {"what": "decode_hbm",
                              "match": "^jit_decode"}) \
        == pytest.approx(100 * nbytes / 819e9 / 0.2)
    flops = shp.step_flops(
        CFG, 600 + 2 * 1800, 600 + 2, 10 * 240 + 2 * 8192,
        10 * 60 * (6000 + 512) + 2 * 1800 * (3000 + 512))
    assert reader.value(rec, {"what": "step_mfu"}) == pytest.approx(
        100 * flops / 197e12 / 0.5)
    # no share of a roofline or of the peak can pass 100
    for args in ({"what": "paged_attn", "match": "paged_decode"},
                 {"what": "moe_gmm", "match": "moe_gmm"},
                 {"what": "decode_hbm", "match": "^jit_decode"},
                 {"what": "step_mfu"}):
        assert 0 < reader.value(rec, args) < 100


@pytest.mark.parametrize("broken", ["counters", "trace", "peaks", "kernel"])
def test_roofline_reader_reads_nothing_where_nothing_is(broken):
    """The parent of the PR that added the counts, an untraced run, the
    CPU rehearsal, a program without the kernel: no number, no error."""
    reader = importlib.import_module("readers.kexaone_roofline")
    rec = _record()
    if broken == "counters":
        del rec["counters"]["kv_rows_full"]
    elif broken == "trace":
        rec["trace"] = None
    elif broken == "peaks":
        rec["peaks"] = None
    else:
        rec["trace"]["device_ops"] = [["fusion.9", 0.2]]
    for args in ({"what": "paged_attn", "match": "paged_decode"},
                 {"what": "moe_gmm", "match": "moe_gmm"}):
        assert reader.value(rec, args) is None
    if broken != "kernel":
        assert reader.value(rec, {"what": "step_mfu"}) is None
        assert reader.value(rec, {"what": "decode_hbm",
                                  "match": "^jit_decode"}) is None


METRICS = {
    "decode.device_ms.mixedlen", "prefill.device_ms.mixedlen",
    "prefill.chunks_per_step.mixedlen", "engine.occupancy.mixedlen",
    "engine.host_ms_per_step.mixedlen", "device_idle_pct.mixedlen",
    "setup.cache_misses.mixedlen", "kv.rows_read_pct.mixedlen",
    "moe.local_share_pct.mixedlen", "paged_decode.device_ms.mixedlen",
    "paged_attn.roofline_pct.mixedlen", "moe_gmm.device_ms.mixedlen",
    "moe_gmm.roofline_pct.mixedlen", "decode.hbm_roofline_pct.mixedlen",
    "step.mfu_pct.mixedlen"}


def test_every_metric_of_the_cell_has_its_files():
    mine = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", [])]
    assert {m["name"] for m in mine} >= METRICS
    for m in mine:
        assert m["workloads"] == [CELL]
        assert m["moves"] == ("setup_s" if m["name"].startswith("setup.")
                              else "serve_tok_s")
        spec = json.load(open(os.path.join(BENCH_DIR, "layer_metrics",
                                           m["name"] + ".json")))
        importlib.import_module("readers." + spec["reader"])
    serve = {m["name"]: m for m in BENCH["end_to_end"]}["serve_tok_s"]
    assert CELL in serve["workloads"] and serve["bound"] == 0.01
    # every file the harness finds by the cell's names
    for parts in (("workloads", CELL + ".json"),
                  ("traffic", "mixedlen-backlog.json"),
                  ("runners", "serve_kexaone.py"),
                  ("reference", "kexaone.py")):
        assert os.path.exists(os.path.join(BENCH_DIR, *parts)), parts


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_rehearsal_prints_every_metric_name_with_null(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         CELL, "--seed", str(2 ** 31 + 11), "--seconds", "4", "--trace",
         str(trace), "--tiny"], env=env, cwd=ROOT, capture_output=True,
        text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(l) for l in p.stdout.splitlines()
             if l.startswith("{")]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    assert all(m["value"] is None for m in last["metrics"].values())
    if not trace:
        assert set(last["metrics"]) == {"serve_tok_s", "setup_s"}
        return
    # what a CPU run can count is there by name; what needs the device's
    # trace is left out, as in every cell
    assert {"engine.occupancy.mixedlen", "prefill.chunks_per_step.mixedlen",
            "kv.rows_read_pct.mixedlen", "moe.local_share_pct.mixedlen",
            "setup.cache_misses.mixedlen"} <= set(last["metrics"])
    notes = {l["note"]: l for l in lines if "note" in l}
    compared = notes["compared"]
    for pre in ("", "after_"):
        for got, limit in (("probe_logit_err", "tol_logit"),
                           ("probe_max_gap", "tol_gap"),
                           ("route_delta_needed", "route_delta")):
            assert compared[pre + got] <= compared[pre + limit]
        assert compared[pre + "route_mismatch"] == 0
    assert compared["probe_positions"] == 29 + 4 - 1
    assert compared["after_probe_positions"] == 44 + 3 - 1
    model = notes["window_model"]
    assert model["prefill_chunks"] == model["chunk_events"] > 0
    assert model["kv_rows_read"] < model["kv_rows_full"]
    assert notes["engine_built"]["kv_bytes_per_token"] == 2 * 2 * 16 * 2
    assert notes["engine_built"]["state_bytes_per_slot"] \
        == 4 * 2 * 8 * 2 * 16 * 2
