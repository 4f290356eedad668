"""The checks of the cell ``dsv32-serve-longctx`` and of what it brought:
the configuration against the catalog's published keys, the shapes
functions against hand counts, the roofline reader on a small recorded
slice, and the ``--tiny`` rehearsal's result line.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_dsv32.py -q
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

import shapes_dsv32 as shp     # noqa: E402

CELL = "dsv32-serve-longctx"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CFG = json.load(open(os.path.join(BENCH_DIR, "configs",
                                  "deepseek-v3.2.json")))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

#: the published config.json's numbers (catalog row DeepSeek-V3.2)
PUBLISHED = {
    "first_k_dense_replace": 3, "hidden_size": 7168, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 8,
    "n_routed_experts": 256, "n_shared_experts": 1,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_theta": 10000, "routed_scaling_factor": 2.5, "topk_group": 4,
    "v_head_dim": 128, "vocab_size": 129280, "ep_size": 1}


def test_the_configuration_keeps_every_published_width():
    entry = {c["name"]: c for c in BENCH["configs"]}["deepseek-v3.2"]
    assert set(entry["reduced"]) == set(CFG["changed"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert CFG["published"][key] == value
        else:
            assert CFG[key] == value, key
    assert CFG["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    # the cut keeps to the guide's floors
    assert CFG["num_hidden_layers"] == len(CFG["layers_kept"]) == 5
    assert CFG["layers_kept"][0] < CFG["first_k_dense_replace"] \
        <= CFG["layers_kept"][1] and len(CFG["layers_kept"][1:]) >= 4
    assert CFG["n_routed_experts"] == CFG["experts_held"][1] >= 8
    assert CFG["n_routed_experts"] * CFG["chips_sharing_a_layer"] == 256
    assert CFG["vocab_size"] * 8 == 129280
    # the model file states the same sizes
    ds = importlib.import_module("mxnet_tpu.gluon.model_zoo.deepseek_v32")
    for key, value in ds.PUBLISHED.items():
        if key == "num_experts":
            assert value == PUBLISHED["n_routed_experts"]
        elif key != "rope_scaling":
            assert PUBLISHED[key] == value, key


def test_the_cell_is_the_issues():
    cell = json.load(open(os.path.join(BENCH_DIR, "workloads",
                                       CELL + ".json")))
    eng = cell["engine"]
    assert (eng["num_slots"], eng["page_size"], eng["max_seq_len"],
            eng["max_prefill_len"], eng["kv_dtype"], eng["spec_k"],
            eng["decode_ahead"]) == (16, 64, 32768, 2048, "bf16", 0, 2)
    assert eng["num_pages"] >= 6144
    assert cell["correct"]["prompt_len"] >= 4608
    assert cell["correct"]["timed_selection"]["chunk_offset_min"] >= 16384
    # the window's edges are the schedule's, not a number fitted to a pace
    assert "warm_decode_steps" not in cell["runner_params"]
    mix = json.load(open(os.path.join(BENCH_DIR, "traffic",
                                      "longctx-backlog.json")))
    assert mix["arrivals"] == {"process": "backlog"} and not mix["apps"]
    assert (mix["prompt"]["median"], mix["prompt"]["min"],
            mix["prompt"]["max"]) == (16384, 4096, 30720)
    assert (mix["output"]["median"], mix["output"]["min"],
            mix["output"]["max"]) == (512, 128, 2048)
    assert mix["prompt"]["max"] + mix["output"]["max"] <= eng["max_seq_len"]
    entry = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "deepseek-v3.2", "longctx-backlog", 1)


def test_shapes_against_hand_counts():
    """ISSUE 32's arithmetic: 187.1M + 14.0M a layer of attention and
    indexer, 597.4M the dense layer, 951.6M an expert layer, 231.7M
    embedding and head: 4.64B parameters."""
    assert shp.attention_params(CFG) == (
        7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 + 512 * 128 * 256
        + 128 * 128 * 7168) == 187_105_280
    assert shp.indexer_params(CFG) == 1536 * 8192 + 7168 * 128 + 7168 * 64 \
        == 13_959_168
    assert shp.expert_params(CFG) == 3 * 7168 * 2048 == 44_040_192
    dense = 187_105_280 + 13_959_168 + 3 * 7168 * 18432
    expert = 187_105_280 + 13_959_168 + 17 * 44_040_192 + 7168 * 256
    assert round(dense / 1e6, 1) == 597.4
    assert round(expert / 1e6, 1) == 951.6
    assert shp.params(CFG) == dense + 4 * expert + 2 * 16160 * 7168
    assert round(shp.params(CFG) / 1e9, 2) == 4.64
    # a token: every matrix outside the routed experts and the
    # absorbed W_UK / W_UV, twice (multiply-add)
    per_layer = (7168 * 1536 + 1536 * 128 * 192 + 7168 * 576
                 + 128 * 128 * 7168 + 13_959_168)
    assert shp.token_matmul_flops(CFG) == 2 * (
        5 * per_layer + 3 * 7168 * 18432
        + 4 * (44_040_192 + 7168 * 256))
    assert shp.head_flops(CFG) == 2 * 16160 * 7168
    assert shp.expert_flops(CFG, 3) == 2 * 3 * 44_040_192
    # a query-key pair of the indexer: 64 heads x 128 multiply-adds
    assert shp.index_flops(CFG, 10) == 2 * 10 * 64 * 128
    assert shp.index_bytes(CFG, 10) == 10 * 128 * 2
    # a (query, head, selected row): 576 for the score, 512 for the
    # value; a query's absorption: 512 x (128 + 128) a head
    assert shp.sparse_attention_flops(CFG, 2048, 1) == 2 * 128 * (
        2048 * (576 + 512) + 512 * 256)
    assert shp.sparse_attention_bytes(CFG, 2048) == 2048 * 576 * 2
    assert shp.moe_gmm_bytes(CFG, 6) == 6 * 44_040_192 * 2
    # ISSUE 32: a 2,048-row chunk at a 16k offset is ~6.8 TFLOP of
    # weights' matmuls, ~2.7 of index scores, ~5.8 of attention
    rows = 2048
    ctx = sum(16384 + t + 1 for t in range(rows)) * 5
    # (the absorbed W_UK and W_UV, 0.34 of the 6.8, are counted with the
    # attention here)
    assert 6.75 < (rows * shp.token_matmul_flops(CFG)
                   + shp.expert_flops(CFG, rows * 4 * 8 // 16)
                   + shp.sparse_attention_flops(CFG, 0, rows * 5)) / 1e12 \
        < 6.9
    assert 2.7 < shp.index_flops(CFG, ctx) / 1e12 < 3.0
    assert round(shp.sparse_attention_flops(CFG, rows * 2048 * 5, 0)
                 / 1e12, 1) == 5.8
    assert shp.step_flops(CFG, rows, 1, 0, 0, 0) == (
        rows * shp.token_matmul_flops(CFG) + shp.head_flops(CFG)
        + shp.sparse_attention_flops(CFG, 0, rows * 5))


def _record(**over):
    """A small recorded slice: 10 decode steps and 2 chunk runs in the
    traced slice of a window of 100 decode steps and 20 chunk runs."""
    c = {"decode_steps": 100, "prefill_chunks": 20,
         "dsa_rows_in_context": 100 * 16 * 5 * 16000,
         "dsa_rows_attended": 100 * 16 * 5 * 2048,
         "moe_local_assignments": 100 * 32, "moe_experts_hit": 100 * 24,
         "chunk_rows": 20 * 2048, "chunk_context_rows": 20 * 10240,
         "dsa_prefill_rows_in_context": 20 * 5 * 2048 * 9216,
         "dsa_prefill_rows_attended": 20 * 5 * 2048 * 2048,
         "moe_prefill_local_assignments": 20 * 4096,
         "moe_prefill_experts_hit": 20 * 64}
    c.update(over)
    spans = [{"decode_steps": 1, "prefills": 0, "tokens": 16}] * 10
    return {"counters": c, "config": CFG, "peaks": PEAKS, "trace": {
        "spans": spans, "busy_s": 0.5, "window_s": 0.6,
        "modules": [["jit_decode", 0.2, 10], ["jit_prefill", 0.3, 2]],
        "device_ops": [["dsa_index.1", 0.05], ["mla_sparse.3", 0.1],
                       ["moe_gmm.2", 0.02], ["fusion.9", 0.2]]}}


def test_roofline_reader_on_a_small_recorded_slice():
    reader = importlib.import_module("readers.dsv32_roofline")
    rec = _record()
    assert reader.value(rec, {"what": "rows_attended_pct"}) \
        == pytest.approx(100 * 2048 / 16000)
    # index: 10 steps and 2 chunks of the window's mean work
    pairs = 10 * 16 * 5 * 16000 + 2 * 5 * 2048 * 9216
    keys = 10 * 16 * 5 * 16000 + 5 * 2 * 10240
    least = max(2 * pairs * 64 * 128 / 197e12, keys * 256 / 819e9)
    assert reader.value(rec, {"what": "dsa_index", "match": "dsa_index"}) \
        == pytest.approx(100 * least / 0.05)
    att = 10 * 16 * 5 * 2048 + 2 * 5 * 2048 * 2048
    queries = (10 * 16 + 2 * 2048) * 5
    least = max(2 * 128 * (att * 1088 + queries * 512 * 256) / 197e12,
                att * 576 * 2 / 819e9)
    assert reader.value(rec, {"what": "mla_sparse", "match": "mla_sparse"}) \
        == pytest.approx(100 * least / 0.1)
    least = max(2 * (10 * 32 + 2 * 4096) * 44_040_192 / 197e12,
                (10 * 24 + 2 * 64) * 44_040_192 * 2 / 819e9)
    assert reader.value(rec, {"what": "moe_gmm", "match": "moe_gmm"}) \
        == pytest.approx(100 * least / 0.02)
    flops = shp.step_flops(CFG, 160 + 4096, 160 + 2, 10 * 32 + 2 * 4096,
                           pairs, att)
    assert reader.value(rec, {"what": "step_mfu"}) == pytest.approx(
        100 * flops / 197e12 / 0.5)


@pytest.mark.parametrize("broken", ["counters", "trace", "peaks", "kernel"])
def test_roofline_reader_reads_nothing_where_nothing_is(broken):
    """The parent of the PR that added the counts, an untraced run, the
    CPU rehearsal, a program without the kernel: no number, no error."""
    reader = importlib.import_module("readers.dsv32_roofline")
    rec = _record()
    if broken == "counters":
        del rec["counters"]["dsa_rows_in_context"]
    elif broken == "trace":
        rec["trace"] = None
    elif broken == "peaks":
        rec["peaks"] = None
    else:
        rec["trace"]["device_ops"] = [["fusion.9", 0.2]]
    for args in ({"what": "dsa_index", "match": "dsa_index"},
                 {"what": "mla_sparse", "match": "mla_sparse"},
                 {"what": "moe_gmm", "match": "moe_gmm"}):
        assert reader.value(rec, args) is None
    if broken != "kernel":
        assert reader.value(rec, {"what": "step_mfu"}) is None


def test_every_metric_of_the_cell_has_its_files():
    mine = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", [])]
    assert {m["name"] for m in mine} == {
        "decode.device_ms.longctx", "prefill.device_ms.longctx",
        "device_idle_pct.longctx", "engine.host_ms_per_step.longctx",
        "engine.occupancy.longctx", "prefill.chunks_per_step.longctx",
        "dsa.rows_attended_pct.longctx", "dsa_index.device_ms.longctx",
        "dsa_index.roofline_pct.longctx", "mla_sparse.device_ms.longctx",
        "mla_sparse.roofline_pct.longctx", "moe_gmm.roofline_pct.longctx",
        "step.mfu_pct.longctx", "setup.cache_misses.longctx"}
    for m in mine:
        assert m["workloads"] == [CELL]
        assert m["moves"] == ("setup_s" if m["name"].startswith("setup.")
                              else "serve_tok_s")
        spec = json.load(open(os.path.join(BENCH_DIR, "layer_metrics",
                                           m["name"] + ".json")))
        importlib.import_module("readers." + spec["reader"])
    serve = {m["name"]: m for m in BENCH["end_to_end"]}["serve_tok_s"]
    assert serve["workloads"][-1] == CELL and serve["bound"] == 0.01


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
    assert {"engine.occupancy.longctx", "prefill.chunks_per_step.longctx",
            "dsa.rows_attended_pct.longctx",
            "setup.cache_misses.longctx"} <= set(last["metrics"])
    notes = {l["note"]: l for l in lines if "note" in l}
    compared = notes["compared"]
    for got, limit in (("probe_logit_err", "tol_logit"),
                       ("probe_max_gap", "tol_gap"),
                       ("route_delta_needed", "route_delta"),
                       ("select_delta_needed", "select_delta")):
        assert compared[got] <= compared[limit]
    assert compared["route_mismatch"] == compared["select_mismatch"] == 0
    # the selection at the window's own sizes, after the window
    assert compared["timed_select_mismatch"] == 0
    assert compared["timed_select_rows"] > compared["timed_decode_rows"]
    assert compared["timed_select_delta_needed"] \
        <= compared["timed_select_delta"]
    model = notes["window_model"]
    # the window opens on the event the schedule defines
    assert model["starting_prefilling_at_open"] == 0
    assert model["prefill_chunks"] == model["chunk_events"] > 0
    assert model["dsa_rows_attended"] < model["dsa_rows_in_context"]
