"""The benchmark's own checks.  Not part of the repo's tier-1 suite:

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q
"""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH_DIR)

import shapes           # noqa: E402
import trace_reduce     # noqa: E402
import trafficgen       # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


# -- the trace reduction ---------------------------------------------------

def test_reduce_synthetic_trace():
    """Overlapping ops count once, gaps take the innermost host span's
    name, and busy + idle is the window."""
    doc = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["step", 0.0, 100.0], ["sync", 60.0, 30.0],
            ["step", 100.0, 100.0]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["fusion.1", 10.0, 30.0], ["copy.2", 30.0, 20.0],
                ["fusion.1", 110.0, 90.0]]},
            {"name": "XLA Modules", "events": [
                ["jit_f(1)", 10.0, 40.0], ["jit_f(1)", 110.0, 90.0]]}]}]}
    red = trace_reduce.reduce(doc, 1, [{"steps": 1}, {"steps": 1}])
    assert red["window_s"] == pytest.approx(200e-9)
    assert red["busy_s"] == pytest.approx(130e-9)
    assert red["busy_s"] + red["idle_s"] == pytest.approx(red["window_s"])
    assert dict(red["device_ops"]) == pytest.approx(
        {"fusion.1": 120e-9, "copy.2": 20e-9})
    assert red["modules"][0][:1] == ["jit_f"] and red["modules"][0][2] == 2
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"step": 40e-9, "sync": 30e-9})
    assert [s["busy_s"] for s in red["spans"]] == pytest.approx(
        [40e-9, 90e-9])
    assert red["spans"][0]["steps"] == 1


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(os.path.join(HERE, "data"))
    if f.endswith(".slice.json.gz")) or [None])
def test_reduce_recorded_slice(name):
    """A slice cut from a real chip trace: a device plane is found, its
    busy union, per-op totals and labelled gaps are consistent."""
    if name is None:
        pytest.skip("no recorded slice in tests/data")
    red = trace_reduce.reduce(
        trace_reduce.read_doc(os.path.join(HERE, "data", name)), 1)
    assert red["chips_traced"] == 1 and red["busy_s"] > 0
    assert red["busy_s"] + red["idle_s"] == pytest.approx(
        red["window_s"], rel=1e-9)
    assert red["busy_s"] <= sum(s for _, s in red["device_ops"]) * (1 + 1e-9)
    assert red["spans"], "no benchmark span in the slice"
    assert sum(s["busy_s"] for s in red["spans"]) <= red["busy_s"] * (1 + 1e-9)


# -- traffic ---------------------------------------------------------------

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH_DIR, "traffic")))


def take(stream, n):
    return [next(stream) for _ in range(n)]


@pytest.mark.parametrize("mix_name", MIXES)
def test_traffic_is_seeded(mix_name):
    mix = load("traffic", mix_name + ".json")
    if mix["kind"] == "batches":
        if "seq_len" in mix:
            mk = lambda s: trafficgen.token_batches(mix, s, 50257)
            flat = lambda r: np.concatenate([b["x"].ravel() for b in r])
        else:
            small = dict(mix, batch=2)
            mk = lambda s: trafficgen.image_batches(small, s, (3, 8, 8), 10)
            flat = lambda r: np.concatenate([d.ravel() for d, _ in r])
        a, b, c = flat(mk(2 ** 31 + 5)), flat(mk(2 ** 31 + 5)), flat(mk(7))
        assert (a == b).all() and (a != c).any()
        return
    n = 2 * mix["pool"]
    a = take(trafficgen.requests(mix, 2 ** 31 + 5, 50257, 16, 8), n)
    b = take(trafficgen.requests(mix, 2 ** 31 + 5, 50257, 16, 8), n)
    c = take(trafficgen.requests(mix, 7, 50257, 16, 8), n)
    for (d1, p1, m1), (d2, p2, m2) in zip(a, b):
        assert d1 == d2 and m1 == m2 and (p1 == p2).all()
    assert all((p1 != p3).any() for (_, p1, _), (_, p3, _) in zip(a, c))
    # the schedule is the mix's own: another seed offers the same sizes
    # at the same moments, with other tokens
    assert [(r[0], r[1].size, r[2]) for r in a] \
        == [(r[0], r[1].size, r[2]) for r in c]


def test_dealt_blocks_hold_the_whole_distribution():
    """Every ``block`` consecutive values hold one from each of ``block``
    slices of the sorted set; all values are dealt once; the seed
    changes the order."""
    vals = trafficgen.lognormal_set(
        {"median": 64, "sigma": 0.8, "min": 8, "max": 256}, 128)
    edges = np.sort(vals).reshape(16, 8)
    a = trafficgen.dealt(vals, 16, np.random.default_rng(1))
    b = trafficgen.dealt(vals, 16, np.random.default_rng(2))
    assert sorted(a) == sorted(b) == sorted(vals) and (a != b).any()
    for hand in a.reshape(8, 16):
        hand = np.sort(hand)
        assert ((edges[:, 0] <= hand) & (hand <= edges[:, -1])).all()
    sums = a.reshape(8, 16).sum(1)
    assert sums.max() - sums.min() < 0.1 * sums.mean()
    with pytest.raises(ValueError):
        trafficgen.dealt(vals, 24, np.random.default_rng(1))


@pytest.mark.parametrize("mix_name", [m for m in MIXES if load(
    "traffic", m + ".json")["kind"] == "requests"])
def test_traffic_clips_and_alignment(mix_name):
    mix = load("traffic", mix_name + ".json")
    page = 16
    # a request has to fit the engines it is offered to
    longest = min(
        load("workloads", w["name"] + ".json")["engine"]["max_seq_len"]
        for w in BENCH["workloads"] if w["traffic"] == mix_name)
    rows = take(trafficgen.requests(mix, 11, 50257, page, 4), 2 * mix["pool"])
    for due, prompt, max_new in rows:
        assert 1 <= prompt.size <= mix["prompt_max_total"]
        assert 1 <= max_new <= mix["output"]["max"]
        assert prompt.size + max_new <= longest
        assert prompt.dtype == np.int32 and 0 <= prompt.min() \
            and prompt.max() < 50257
    dues = [r[0] for r in rows]
    assert dues == sorted(dues) and dues[:4] == [0.0] * 4
    if mix["arrivals"]["process"] == "poisson":
        rate = (len(dues) - 4) / (dues[-1] - dues[3])
        assert rate == pytest.approx(mix["arrivals"]["rate_per_s"], rel=0.02)
    if mix.get("apps"):
        # requests of one application share a page-aligned system prompt
        lo, hi = mix["apps"]["system_len"]
        heads = {}
        for _, prompt, _ in rows:
            heads.setdefault(tuple(prompt[:lo]), []).append(prompt)
        assert len(heads) == mix["apps"]["count"]
        for group in heads.values():
            shared = min(len(os.path.commonprefix([list(p) for p in group])),
                         hi)
            assert shared >= lo and any(
                shared >= n and n % page == 0 for n in range(lo, hi + 1, page))


# -- the reference ---------------------------------------------------------

def test_reference_agrees_with_model_zoo():
    import jax
    jax.config.update("jax_default_matmul_precision", "float32")
    import common
    from mxnet_tpu.gluon.model_zoo import gpt
    from mxnet_tpu.ndarray import NDArray
    from reference import gpt2 as reference
    net = gpt.gpt2_tiny()
    common.seeded_gpt_weights(net, 2 ** 31 + 3, keep_grads=True)
    toks = np.random.default_rng(0).integers(0, 256, (2, 24)).astype(np.int32)
    want = np.asarray(net(NDArray(jax.numpy.asarray(toks)))._data)
    w, n_head = reference.weights_from_net(net)
    got = np.asarray(reference.forward(w, toks, n_head))
    assert np.abs(got - want).max() < 2e-4
    assert float(reference.loss(w, toks[:, :-1], toks[:, 1:], n_head)) \
        == pytest.approx(np.log(256), rel=0.05)


def test_shapes_of_gpt2_medium():
    cfg = load("configs", "gpt2-medium.json")
    assert shapes.gpt2_params(cfg) == 354823168 + (50304 - 50257) * 1024
    step = shapes.gpt2_train_step_flops(cfg, 8, 1024)
    assert 18.0e12 < step < 19.2e12
    assert shapes.flash_fwd_bwd_flops(8, 16, 1024, 64) == \
        3 * 2 * 2 * 8 * 16 * 1024 * 1024 * 64 // 2
    assert shapes.paged_attention_bytes([100, 28], 16, 64, 2) == \
        2 * 128 * 16 * 64 * 2
    assert shapes.roofline_seconds(197e12, 1, {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}) == \
        (1.0, "compute")


# -- BENCHMARK.json against the files --------------------------------------

def test_names_and_units():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    assert all(0.01 <= m["bound"] <= 0.1 for m in BENCH["end_to_end"])


def test_everything_named_resolves_to_a_file():
    configs = {c["name"]: c for c in BENCH["configs"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for c in BENCH["configs"]:
        assert c["file"].startswith(tuple(BENCH["paths"]))
        doc = json.load(open(os.path.join(ROOT, c["file"])))
        assert set(c["reduced"]) == set(doc["changed"])
    for w in BENCH["workloads"]:
        assert w["config"] in configs
        cell = load("workloads", w["name"] + ".json")
        assert os.path.exists(os.path.join(
            BENCH_DIR, "runners", cell["runner"] + ".py"))
        load("traffic", w["traffic"] + ".json")
        assert sum(1 for m in BENCH["end_to_end"] if m["name"] != "setup_s"
                   and w["name"] in m.get("workloads", CELLS)) >= 1
        assert any(w["name"] in m.get("workloads", CELLS)
                   for m in BENCH["per_layer"])
    # a backlog is one kind of deployment: its cells set the engine's
    # host loop up alike (SERVING.md section 3)
    ahead = {w["name"]: load("workloads", w["name"] + ".json")["engine"]
             .get("decode_ahead", 0) for w in BENCH["workloads"]
             if load("traffic", w["traffic"] + ".json").get(
                 "arrivals", {}).get("process") == "backlog"}
    assert len(set(ahead.values())) == 1, ahead
    for m in BENCH["per_layer"]:
        spec = load("layer_metrics", m["name"] + ".json")
        assert os.path.exists(os.path.join(
            BENCH_DIR, "readers", spec["reader"] + ".py"))
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert cell in moved.get("workloads", CELLS), (m["name"], cell)


def test_run_py_names_no_cell_model_mix_or_metric():
    text = open(os.path.join(BENCH_DIR, "run.py")).read().lower()
    names = ["gpt2", "resnet", "backlog", "chat"] + CELLS + \
        [m["name"] for m in BENCH["end_to_end"] if m["name"] != "setup_s"] + \
        [m["name"] for m in BENCH["per_layer"]]
    assert not [n for n in names if n.lower() in text]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_tiny_run_prints_the_contract_line(cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         cell, "--seed", str(2 ** 31 + 9), "--seconds", "4", "--trace",
         str(trace), "--tiny"], env=env, cwd=ROOT, capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    keys = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(last) == keys | ({"breakdown"} if trace else set())
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    assert {"kind", "count", "memory_peak_bytes"} <= set(last["device"])
    assert last["metrics"] and all(
        m["value"] is None for m in last["metrics"].values())
    group = "per_layer" if trace else "end_to_end"
    allowed = {m["name"] for m in BENCH[group]
               if cell in m.get("workloads", CELLS)}
    assert set(last["metrics"]) <= allowed
    if not trace:
        assert "setup_s" in last["metrics"] and len(last["metrics"]) >= 2


def test_no_tpu_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout
