"""The checks of the cell ``mellum2-serve-codeassist`` and of what it
brought: the configuration against the catalog's published keys, the
shapes functions against hand counts, the roofline reader on a small
recorded slice, and the ``--tiny`` rehearsal's result line.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_mellum2.py -q
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

import shapes_mellum2 as shp     # noqa: E402
import trafficgen                # noqa: E402

CELL = "mellum2-serve-codeassist"
CONFIG = "mellum2-12b-a2.5b"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CFG = json.load(open(os.path.join(BENCH_DIR, "configs", CONFIG + ".json")))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SLIDING, FULL = "sliding_attention", "full_attention"

#: the published config.json's numbers (catalog row
#: Mellum2-12B-A2.5B-Instruct)
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2304, "intermediate_size": 7168,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "moe_intermediate_size": 896, "num_attention_heads": 32,
    "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "sliding_window": 1024, "vocab_size": 98304}


def test_the_configuration_keeps_every_published_width():
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    assert entry["file"] == "perfbench/configs/%s.json" % CONFIG
    assert entry["source"] == CFG["source"]
    assert entry["reduced"] == list(CFG["changed"]) == ["num_hidden_layers"]
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert CFG["published"][key] == value
        else:
            assert CFG[key] == value, key
    # the nested groups are the published ones, whole
    assert CFG["rope_parameters"] == {
        FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
               "original_max_position_embeddings": 8192, "beta_fast": 32,
               "beta_slow": 1, "attention_factor": 1.2772588722239782},
        SLIDING: {"rope_type": "default", "rope_theta": 500000}}
    assert CFG["layer_types"] == ([SLIDING] * 3 + [FULL]) * 7
    assert CFG["mlp_layer_types"] == ["sparse"] * 28
    assert (CFG["norm_topk_prob"], CFG["tie_word_embeddings"],
            CFG["use_sliding_window"], CFG["attention_bias"],
            CFG["model_type"]) == (True, False, True, False, "mellum")
    assert "scoring_func" not in CFG
    # the cut: two whole periods, every expert, the whole vocabulary
    assert CFG["num_hidden_layers"] == len(CFG["layers_kept"]) == 8
    assert CFG["layers_kept"] == list(range(8))
    assert CFG["experts_held"] == [0, 64] and CFG["chips_sharing_a_layer"] == 1
    assert {"qk_norm", "norm_placement", "router", "yarn", "window",
            "left_out", "weights"} <= set(CFG["assumed"])
    assert "MTP" in CFG["assumed"]["left_out"]


def test_the_cell_is_the_one_specified():
    cell = json.load(open(os.path.join(BENCH_DIR, "workloads",
                                       CELL + ".json")))
    eng = cell["engine"]
    assert (eng["num_slots"], eng["page_size"], eng["max_seq_len"],
            eng["max_prefill_len"], eng["kv_dtype"], eng["spec_k"],
            eng["decode_ahead"]) == (128, 64, 14336, 2048, "bf16", 0, 2)
    assert eng["num_pages"] == 12288
    assert cell["runner"] == "serve_mellum2"
    assert cell["runner_params"]["queue_depth_x_slots"] == 2
    assert "warm_decode_steps" not in cell["runner_params"]
    check = cell["correct"]
    assert (check["prompt_len"], check["max_new"]) == (4608, 8)
    assert check["after"] == {"prompt_len": 12288, "max_new": 4}
    mix = json.load(open(os.path.join(BENCH_DIR, "traffic",
                                      "codeassist-backlog.json")))
    assert mix["arrivals"] == {"process": "backlog"} and not mix["apps"]
    assert (mix["pool"], mix["block"]) == (128, 16)
    assert mix["prompt"] == {"dist": "lognormal", "median": 3072,
                             "sigma": 0.8, "min": 512, "max": 12288}
    assert mix["output"] == {"dist": "lognormal", "median": 384,
                             "sigma": 0.8, "min": 32, "max": 2048}
    assert mix["prompt"]["max"] + mix["output"]["max"] <= eng["max_seq_len"]
    # the mix's own numbers, by the generator itself: a mean
    # prompt of ~4.1k in 2-3 chunk runs, a mean output of ~490
    prompts = trafficgen.lognormal_set(mix["prompt"], 128)
    assert round(float(prompts.mean())) == 4000
    assert round(float(-(-prompts // 2048).mean()), 2) == 2.42
    assert round(float(trafficgen.lognormal_set(
        mix["output"], 128).mean())) == 514
    stream = trafficgen.requests(mix, 0, CFG["vocab_size"], 64, stagger=128)
    first = [next(stream) for _ in range(128)]
    assert sum(-(-p.size // 2048) for _, p, _ in first) == 310
    # the starting population's pages fit the pool with room for decode
    assert sum(-(-(p.size + n) // 64) for _, p, n in first) == 8952 \
        < eng["num_pages"]
    entry = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "codeassist-backlog", 1)


def test_shapes_against_hand_counts():
    """The cut's arithmetic: 21.23M of attention a layer, an expert of
    6.19M, 417.7M a layer, 3.795B parameters, 7.59 GB; 4,096 B a token in
    pages, 12.6 MB a slot in rings."""
    assert shp.attention_params(CFG) == 2304 * 5120 + 4096 * 2304 \
        == 21_233_664
    assert shp.expert_params(CFG) == 3 * 2304 * 896 == 6_193_152
    assert shp.layer_kinds(CFG) == ([SLIDING] * 3 + [FULL]) * 2
    dense = 8 * (21_233_664 + 2304 * 64)
    assert shp.dense_params(CFG) == dense
    assert dense + 8 * 64 * 6_193_152 == 8 * 417_742_848
    assert shp.params(CFG) == 8 * 417_742_848 + 2 * 98304 * 2304 \
        == 3_794_927_616
    assert round(shp.params(CFG) * 2 / 1e9, 2) == 7.59
    assert shp.kv_row_bytes(CFG) == 2048
    assert shp.page_bytes_per_token(CFG) == 4096 \
        == CFG["cache"]["page_bytes_per_token"]
    assert shp.ring_bytes_per_slot(CFG) == 6 * 1024 * 2048 == 12_582_912 \
        == CFG["cache"]["ring_bytes_per_slot"]
    # 129 rings (128 slots and the scratch row) and 12,288 pages
    assert round(129 * shp.ring_bytes_per_slot(CFG) / 1e9, 2) == 1.62
    assert round(12288 * 64 * shp.page_bytes_per_token(CFG) / 1e9, 2) \
        == 3.22
    assert shp.token_matmul_flops(CFG) == 2 * dense
    assert shp.head_flops(CFG) == 2 * 98304 * 2304
    assert shp.expert_flops(CFG, 10) == 20 * 6_193_152
    assert shp.attention_flops(CFG, 1000) == 1000 * 32 * 4 * 128
    assert shp.kv_bytes(CFG, 1000) == 2_048_000
    assert shp.moe_gmm_bytes(CFG, 3) == 3 * 6_193_152 * 2
    # a decode run with every expert hit: ~6.34 GB of expert weights
    assert round(shp.moe_gmm_bytes(CFG, 8 * 64) / 1e9, 2) == 6.34
    assert shp.decode_bytes(CFG, 2, 60, 1000) == 2 * 2 * (
        dense + 98304 * 2304) + 60 * 12_386_304 + 2_048_000
    # a chunk run of 2,048 rows at offset 2,048: ~2.8 TFLOP
    rows = 2 * shp.chunk_full_attention(CFG, 2048, 2048)
    window = 6 * sum(min(2048 + i + 1, 1024) for i in range(2048))
    total = shp.step_flops(CFG, 2048, 1, 2048 * 8 * 8, rows + window)
    assert 2.6e12 < total < 3.0e12


def _record(**over):
    """A small recorded slice: 10 decode steps and 2 chunk runs in the
    traced slice of a window of 100 decode steps and 20 chunk runs; 120
    slots decode at a context of 4,000."""
    c = {"decode_steps": 100, "prefill_chunks": 20,
         "kv_rows_full": 100 * 120 * 8 * 4000,
         "kv_rows_read": 100 * 120 * (2 * 4000 + 6 * 1024),
         "kv_window_rows_read": 100 * 120 * 6 * 1024,
         "moe_local_assignments": 100 * 120 * 64,
         "moe_experts_hit": 100 * 512,
         "moe_assignments": 100 * 128 * 64,
         "chunk_rows": 20 * 1800,
         "kv_prefill_rows_read": 20 * 1800 * (2 * 3000 + 6 * 1024),
         "kv_prefill_rows_full": 20 * 1800 * 8 * 3000,
         "moe_prefill_local_assignments": 20 * 1800 * 64,
         "moe_prefill_experts_hit": 20 * 512}
    c.update(over)
    spans = [{"decode_steps": 1, "prefills": 0, "tokens": 120}] * 10
    return {"counters": c, "config": CFG, "peaks": PEAKS, "trace": {
        "spans": spans, "busy_s": 0.3, "window_s": 0.31,
        "modules": [["jit_decode", 0.2, 10], ["jit_prefill", 0.1, 2]],
        "device_ops": [["paged_decode.1", 0.03], ["moe_gmm.2", 0.12],
                       ["fusion.9", 0.2]]}}


def test_roofline_reader_on_a_small_recorded_slice(monkeypatch):
    reader = importlib.import_module("readers.mellum2_roofline")
    rec = _record()
    assert reader.value(rec, {"what": "rows_read_pct"}) \
        == pytest.approx(100 * (8000 + 6144) / (8 * 4000))
    # the paged kernel: 10 steps of 120 slots x 4,000 rows x 2 layers
    rows = 10 * 120 * 4000 * 2
    least = max(rows * 2048 / 819e9, rows * 32 * 512 / 197e12)
    assert reader.value(rec, {"what": "paged_attn",
                              "match": "paged_decode"}) \
        == pytest.approx(100 * least / 0.03)
    least = max(2 * (10 * 120 * 64 + 2 * 1800 * 64) * 6_193_152 / 197e12,
                (10 * 512 + 2 * 512) * 6_193_152 * 2 / 819e9)
    assert reader.value(rec, {"what": "moe_gmm", "match": "moe_gmm"}) \
        == pytest.approx(100 * least / 0.12)
    nbytes = shp.decode_bytes(CFG, 10, 10 * 512,
                              10 * 120 * (8000 + 6144))
    assert reader.value(rec, {"what": "decode_hbm",
                              "match": "^jit_decode"}) \
        == pytest.approx(100 * nbytes / 819e9 / 0.2)
    flops = shp.step_flops(
        CFG, 1200 + 2 * 1800, 1200 + 2, 10 * 120 * 64 + 2 * 1800 * 64,
        10 * 120 * (8000 + 6144) + 2 * 1800 * (6000 + 6144))
    assert reader.value(rec, {"what": "step_mfu"}) == pytest.approx(
        100 * flops / 197e12 / 0.3)
    # the window layers: a decode run's ring rows over attn.window's time
    scopes = importlib.import_module("readers.device_scopes")
    seen = []
    monkeypatch.setattr(scopes, "value",
                        lambda rec, args: seen.append(args) or 2.5)
    want = 120 * 6 * 1024 * 2048 / 819e9 / 2.5e-3
    assert reader.value(rec, {"what": "window_hbm"}) \
        == pytest.approx(100 * want)
    assert seen == [{"what": "ms_per_run", "program": "^jit_decode",
                     "scope": r"(^|/)attn\.window(/|$)"}]
    # no share of a roofline or of the peak can pass 100
    for args in ({"what": "paged_attn", "match": "paged_decode"},
                 {"what": "moe_gmm", "match": "moe_gmm"},
                 {"what": "decode_hbm", "match": "^jit_decode"},
                 {"what": "step_mfu"}, {"what": "window_hbm"}):
        assert 0 < reader.value(rec, args) < 100


@pytest.mark.parametrize("broken", ["counters", "trace", "peaks", "kernel"])
def test_roofline_reader_reads_nothing_where_nothing_is(broken):
    """The parent of the PR that added the counts, an untraced run, the
    CPU rehearsal, a program without the kernel: no number, no error."""
    reader = importlib.import_module("readers.mellum2_roofline")
    rec = _record()
    if broken == "counters":
        del rec["counters"]["kv_window_rows_read"]
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
        assert reader.value(rec, {"what": "window_hbm"}) is None


METRICS = {name + ".codeassist" for name in (
    "decode.device_ms", "prefill.device_ms", "attn_window.decode_ms",
    "attn_window.prefill_ms", "attn_full.prefill_ms",
    "attn_window.hbm_roofline_pct", "paged_attn.roofline_pct",
    "moe_gmm.roofline_pct", "moe_gmm.device_ms", "moe.local_share_pct",
    "kv.rows_read_pct", "decode.hbm_roofline_pct", "step.mfu_pct",
    "engine.occupancy", "engine.host_ms_per_step", "device_idle_pct",
    "prefill.chunks_per_step", "setup.cache_misses",
    "scope.unattributed_pct")}


def test_every_metric_of_the_cell_has_its_files():
    mine = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", [])]
    assert {m["name"] for m in mine} == METRICS
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
                  ("traffic", "codeassist-backlog.json"),
                  ("runners", "serve_mellum2.py"),
                  ("reference", "mellum2.py")):
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
    assert {"engine.occupancy.codeassist",
            "prefill.chunks_per_step.codeassist",
            "kv.rows_read_pct.codeassist", "moe.local_share_pct.codeassist",
            "setup.cache_misses.codeassist"} <= set(last["metrics"])
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
    assert 0 < model["kv_window_rows_read"] < model["kv_rows_read"]
    assert notes["engine_built"]["kv_bytes_per_token"] == 2 * 2 * 16 * 2
    assert notes["engine_built"]["state_bytes_per_slot"] \
        == 3 * 2 * 8 * 2 * 16 * 2
