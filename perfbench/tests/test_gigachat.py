"""The checks of the cell ``gigachat-serve-longdoc`` and of what it
brought: the configuration against the catalog's published keys, the
shapes functions against hand counts, the roofline reader on a small
recorded slice, and the ``--tiny`` rehearsal's result line.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_gigachat.py -q
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

import shapes_gigachat as shp    # noqa: E402
import trafficgen                # noqa: E402

CELL = "gigachat-serve-longdoc"
CONFIG = "gigachat3.5-432b-a28b"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CFG = json.load(open(os.path.join(BENCH_DIR, "configs", CONFIG + ".json")))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

#: the published config.json's numbers (catalog row GigaChat3.5-432B-A28B)
PUBLISHED = {
    "vocab_size": 128256, "max_position_embeddings": 262144,
    "hidden_size": 7168, "intermediate_size": 18432,
    "moe_intermediate_size": 2048, "num_hidden_layers": 40,
    "num_attention_heads": 64, "n_shared_experts": 1,
    "n_routed_experts": 256, "routed_scaling_factor": 2.5,
    "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "qk_nope_head_dim": 128, "qk_head_dim": 192,
    "n_group": 1, "topk_group": 1, "num_experts_per_tok": 8,
    "first_k_dense_replace": 3, "num_key_value_heads": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 100000,
    "layernorm_gating_weight": 2, "linear_key_head_dim": 128,
    "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4,
    "linear_num_key_heads": 32, "linear_num_value_heads": 64,
    "linear_sigmoid_gate_scale": 2, "linear_attn_o_norm_eps": 1e-06,
    "swiglu_limit": 10, "num_nextn_predict_layers": 2}


def test_the_configuration_keeps_every_published_width():
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    assert entry["file"] == "perfbench/configs/%s.json" % CONFIG
    assert entry["source"] == CFG["source"]
    assert set(entry["reduced"]) == set(CFG["changed"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert CFG["published"][key] == value
        else:
            assert CFG[key] == value, key
    assert CFG["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 32768,
        "type": "yarn"}
    assert CFG["full_attention_layers"] == list(range(3, 40, 4))
    assert (CFG["layernorm_type"], CFG["norm_type"], CFG["gated_attention"],
            CFG["use_shared_expert_sigmoid"], CFG["use_mla_scaling_factor"],
            CFG["rope_interleave"], CFG["linear_attention_type"],
            CFG["linear_gating_type"], CFG["model_type"]) == (
        "pre_post", "ZeroCenteredGatedNorm", True, False, True, True,
        "GigaChat35GatedDeltaNet", "gated_rmsnorm_sigmoid_zero_centered",
        "gigachat3_5")
    # the cut keeps each kind of layer: a leading dense layer once,
    # then one whole period (three delta-rule layers, one latent)
    assert CFG["num_hidden_layers"] == len(CFG["layers_kept"]) == 5
    assert CFG["layers_kept"] == [0, 4, 5, 6, 7]
    assert shp.layer_kinds(CFG) == [("gdn", "dense")] \
        + [("gdn", "moe")] * 3 + [("mla", "moe")]
    assert CFG["n_routed_experts"] == CFG["experts_held"][1] >= 8
    assert CFG["n_routed_experts"] * CFG["chips_sharing_a_layer"] == 256
    assert CFG["vocab_size"] * 8 == 128256
    assert {"zero_centred_norms", "norm_placement", "delta_rule",
            "delta_rule_gate", "latent_gate", "router", "swiglu_clamp",
            "left_out"} <= set(CFG["assumed"])
    assert "multi-token-prediction" in CFG["assumed"]["left_out"]
    # the model file states the same sizes
    gc = importlib.import_module("mxnet_tpu.gluon.model_zoo.gigachat35")
    for key, value in gc.PUBLISHED.items():
        want = {"num_experts": CFG["published"]["n_routed_experts"],
                "vocab_size": CFG["published"]["vocab_size"],
                "num_hidden_layers": CFG["published"]["num_hidden_layers"]
                }.get(key, CFG.get(key))
        assert value == want, key


def test_the_cell_is_as_specified():
    cell = json.load(open(os.path.join(BENCH_DIR, "workloads",
                                       CELL + ".json")))
    eng = cell["engine"]
    assert (eng["num_slots"], eng["page_size"], eng["max_seq_len"],
            eng["max_prefill_len"], eng["kv_dtype"], eng["spec_k"],
            eng["decode_ahead"]) == (32, 64, 34816, 2048, "bf16", 0, 2)
    # the worst case of the schedule: every slot at its longest
    assert eng["num_pages"] == 32 * 34816 // 64 + 1
    assert cell["runner"] == "serve_gigachat"
    assert cell["runner_params"]["queue_depth_x_slots"] == 2
    assert "warm_decode_steps" not in cell["runner_params"]
    check = cell["correct"]
    assert (check["prompt_len"], check["max_new"]) == (4608, 8)
    assert check["after"] == {"prompt_len": 32768, "max_new": 4}
    mix = json.load(open(os.path.join(BENCH_DIR, "traffic",
                                      "longdoc-backlog.json")))
    assert mix["arrivals"] == {"process": "backlog"} and not mix["apps"]
    assert (mix["pool"], mix["block"]) == (128, 16)
    assert mix["prompt"] == {"dist": "lognormal", "median": 8192,
                             "sigma": 0.7, "min": 2048, "max": 32768}
    assert mix["output"] == {"dist": "lognormal", "median": 512,
                             "sigma": 0.7, "min": 128, "max": 2048}
    assert mix["prompt"]["max"] + mix["output"]["max"] <= eng["max_seq_len"]
    # the mix's numbers, by the generator itself
    prompts = trafficgen.lognormal_set(mix["prompt"], 128)
    assert round(float(prompts.mean())) == 10208
    assert round(float(-(-prompts // 2048).mean()), 2) == 5.46
    assert round(float(trafficgen.lognormal_set(
        mix["output"], 128).mean())) == 638
    entry = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "longdoc-backlog", 1)


def test_shapes_against_hand_counts():
    """The counts by hand: 235.8M a delta-rule layer, 159.8M of latent
    attention with its gate, a dense SwiGLU of 396.4M, an expert of
    44.04M, 4.73B parameters, 9.46 GB; 17.6 MB a slot of state."""
    assert shp.gdn_params(CFG) == 7168 * 16384 + 4 * 16384 + 7168 * 8192 \
        + 2 * 7168 * 64 + 2 * 64 + 8192 * 7168 == 235_864_192
    assert shp.mla_params(CFG) == 7168 * 1536 + 1536 * 12288 + 7168 * 576 \
        + 512 * 16384 + 7168 * 8192 + 8192 * 7168 == 159_842_304
    assert shp.expert_params(CFG) == 3 * 7168 * 2048 == 44_040_192
    dense = 4 * 235_864_192 + 159_842_304 + 3 * 7168 * 18432 \
        + 4 * (44_040_192 + 7168 * 256)
    assert shp.dense_params(CFG) == dense
    total = dense + 2 * 16032 * 7168 + 4 * 16 * 44_040_192
    assert shp.params(CFG) == total
    assert round(total / 1e9, 2) == 4.73 and round(total * 2 / 1e9, 2) \
        == 9.46
    assert shp.state_bytes_per_slot(CFG) \
        == CFG["cache"]["state_bytes_per_slot"] == 17_563_648
    assert shp.latent_row_values(CFG) == 576
    # a decode step's state: every live slot's read and written once
    assert shp.state_bytes(CFG, 32) == 2 * 32 * 4 * 64 * 128 * 128 * 4
    assert shp.latent_bytes(CFG, 1000) == 1000 * 576 * 2
    assert shp.recurrence_flops(CFG) == 4 * 6 * 64 * 128 * 128
    assert shp.attention_flops(CFG, 10) == 10 * 64 * 2 * (192 + 128)
    assert shp.head_flops(CFG) == 2 * 16032 * 7168
    assert shp.moe_gmm_bytes(CFG, 3) == 3 * 44_040_192 * 2
    assert shp.decode_bytes(CFG, 2, 60, 64, 1000) == 2 * 2 * (
        dense + 16032 * 7168) + 60 * 88_080_384 \
        + shp.state_bytes(CFG, 64) + 1000 * 1152
    assert shp.chunk_attention_rows(2048, 4096) \
        == sum(4096 + i + 1 for i in range(2048))
    # a chunk run at a mean offset of ~4k: ~7.7 TFLOP
    pairs = shp.chunk_attention_rows(2048, 4096)
    flops = shp.step_flops(CFG, 2048, 1, 2048 * 8 // 16, pairs)
    assert 7.0e12 < flops < 8.5e12


def _record(**over):
    """A small recorded slice: 10 decode steps and 2 chunk runs in the
    traced slice of a window of 100 decode steps and 20 chunk runs; 30
    slots decode at a context of 10,000."""
    c = {"decode_steps": 100, "prefill_chunks": 20,
         "latent_rows_read": 100 * 30 * 10000,
         "moe_local_assignments": 100 * 16, "moe_experts_hit": 100 * 40,
         "moe_assignments": 100 * 960,
         "chunk_rows": 20 * 1900,
         "latent_prefill_rows_read": 20 * 1900 * 5000,
         "moe_prefill_local_assignments": 20 * 950,
         "moe_prefill_experts_hit": 20 * 64}
    c.update(over)
    spans = [{"decode_steps": 1, "prefills": 0, "tokens": 30}] * 10
    return {"counters": c, "config": CFG, "peaks": PEAKS, "trace": {
        "spans": spans, "busy_s": 0.5, "window_s": 0.6,
        "modules": [["jit_decode", 0.14, 10], ["jit_prefill", 0.2, 2]],
        "device_ops": [["kda_step.1", 0.02], ["mla_paged_decode.3", 0.04],
                       ["moe_gmm.2", 0.08], ["fusion.9", 0.2]]}}


def test_roofline_reader_on_a_small_recorded_slice():
    reader = importlib.import_module("readers.gigachat_roofline")
    rec = _record()
    # 300 live slot-steps, each state read and written in 4 layers
    nbytes = 2 * 300 * 4 * 64 * 128 * 128 * 4
    assert reader.value(rec, {"what": "kda_step", "match": "kda_step"}) \
        == pytest.approx(100 * nbytes / 819e9 / 0.02)
    rows = 10 * 30 * 10000
    assert reader.value(rec, {"what": "mla_decode",
                              "match": "mla_paged_decode"}) \
        == pytest.approx(100 * rows * 1152 / 819e9 / 0.04)
    least = max(2 * (10 * 16 + 2 * 950) * 44_040_192 / 197e12,
                (10 * 40 + 2 * 64) * 44_040_192 * 2 / 819e9)
    assert reader.value(rec, {"what": "moe_gmm", "match": "moe_gmm"}) \
        == pytest.approx(100 * least / 0.08)
    nbytes = shp.decode_bytes(CFG, 10, 10 * 40, 300, rows)
    assert reader.value(rec, {"what": "decode_hbm",
                              "match": "^jit_decode"}) \
        == pytest.approx(100 * nbytes / 819e9 / 0.14)
    flops = shp.step_flops(CFG, 300 + 2 * 1900, 300 + 2,
                           10 * 16 + 2 * 950, rows + 2 * 1900 * 5000)
    assert reader.value(rec, {"what": "step_mfu"}) == pytest.approx(
        100 * flops / 197e12 / 0.5)
    for args in ({"what": "kda_step", "match": "kda_step"},
                 {"what": "mla_decode", "match": "mla_paged_decode"},
                 {"what": "moe_gmm", "match": "moe_gmm"},
                 {"what": "decode_hbm", "match": "^jit_decode"},
                 {"what": "step_mfu"}):
        assert 0 < reader.value(rec, args) < 100


@pytest.mark.parametrize("broken", ["counters", "trace", "peaks", "kernel"])
def test_roofline_reader_reads_nothing_where_nothing_is(broken):
    """A program without the count (the parent), an untraced run, the
    CPU rehearsal, a program without the kernel: no number, no error."""
    reader = importlib.import_module("readers.gigachat_roofline")
    rec = _record()
    if broken == "counters":
        del rec["counters"]["latent_rows_read"]
    elif broken == "trace":
        rec["trace"] = None
    elif broken == "peaks":
        rec["peaks"] = None
    else:
        rec["trace"]["device_ops"] = [["fusion.9", 0.2]]
    for args in ({"what": "kda_step", "match": "kda_step"},
                 {"what": "mla_decode", "match": "mla_paged_decode"},
                 {"what": "moe_gmm", "match": "moe_gmm"}):
        assert reader.value(rec, args) is None
    if broken != "kernel":
        assert reader.value(rec, {"what": "step_mfu"}) is None
        assert reader.value(rec, {"what": "decode_hbm",
                                  "match": "^jit_decode"}) is None


METRICS = {
    "decode.device_ms.longdoc", "prefill.device_ms.longdoc",
    "kda_step.device_ms.longdoc", "kda_step.roofline_pct.longdoc",
    "gdn_scan.prefill_ms.longdoc", "attn_mla.prefill_ms.longdoc",
    "mla_decode.device_ms.longdoc", "mla_decode.roofline_pct.longdoc",
    "moe_gmm.roofline_pct.longdoc", "decode.hbm_roofline_pct.longdoc",
    "step.mfu_pct.longdoc", "engine.occupancy.longdoc",
    "engine.host_ms_per_step.longdoc", "prefill.chunks_per_step.longdoc",
    "device_idle_pct.longdoc", "setup.cache_misses.longdoc",
    "moe.local_share_pct.longdoc", "scope.unattributed_pct.longdoc",
    "moe.decode_ms.longdoc", "moe_experts.prefill_ms.longdoc",
    "moe_layout.prefill_ms.longdoc", "moe_gmm.device_ms.longdoc",
    "kv_write.decode_ms.longdoc", "kv_write.prefill_ms.longdoc"}


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
    for parts in (("workloads", CELL + ".json"),
                  ("traffic", "longdoc-backlog.json"),
                  ("runners", "serve_gigachat.py"),
                  ("reference", "gigachat35.py")):
        assert os.path.exists(os.path.join(BENCH_DIR, *parts)), parts


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_rehearsal_prints_every_metric_name_with_null(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         CELL, "--seed", str(2 ** 31 + 13), "--seconds", "4", "--trace",
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
    assert {"engine.occupancy.longdoc", "prefill.chunks_per_step.longdoc",
            "moe.local_share_pct.longdoc",
            "setup.cache_misses.longdoc"} <= set(last["metrics"])
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
    assert 0 < model["state_handoffs"] < model["prefill_chunks"]
    assert notes["engine_built"]["state_bytes_per_slot"] \
        == 4 * (4 * 16 * 16 + 3 * 128) * 4
