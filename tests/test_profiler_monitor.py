"""Tests for mx.profiler, mx.monitor, mx.telemetry, mx.visualization."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu", name="relu1")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def test_profiler_dump(tmp_path):
    fname = str(tmp_path / "profile.json")
    mx.profiler.profiler_set_config(mode="symbolic", filename=fname)
    mx.profiler.profiler_set_state("run")
    exe = _mlp().simple_bind(ctx=mx.cpu(), data=(4, 10), softmax_label=(4,))
    exe.arg_dict["data"][:] = np.random.rand(4, 10)
    exe.forward()
    exe.forward(is_train=True)
    exe.backward()
    mx.profiler.profiler_set_state("stop")
    out = mx.profiler.dump_profile()
    assert out == fname and os.path.exists(fname)
    doc = json.load(open(fname))
    names = [e["name"] for e in doc["traceEvents"]]
    assert "executor_forward" in names
    assert "executor_backward" in names
    for e in doc["traceEvents"]:
        assert e["ph"] == "X" and e["dur"] >= 0


def test_profiler_pause_resume(tmp_path):
    fname = str(tmp_path / "p2.json")
    mx.profiler.profiler_set_config(filename=fname)
    mx.profiler.profiler_set_state("run")
    mx.profiler.pause()
    exe = _mlp().simple_bind(ctx=mx.cpu(), data=(2, 10), softmax_label=(2,))
    exe.forward()
    mx.profiler.resume()
    exe.forward()
    mx.profiler.profiler_set_state("stop")
    mx.profiler.dump_profile()
    doc = json.load(open(fname))
    assert len(doc["traceEvents"]) == 1  # only the resumed forward


def test_monitor_taps_all_nodes():
    mon = mx.Monitor(interval=1, pattern=".*")
    exe = _mlp().simple_bind(ctx=mx.cpu(), data=(4, 10), softmax_label=(4,))
    for name, arr in exe.arg_dict.items():
        arr[:] = np.random.RandomState(0).uniform(-1, 1, arr.shape)
    mon.install(exe)
    mon.tic()
    exe.forward()
    res = mon.toc()
    names = [k for _, k, _ in res]
    assert any("fc1" in n for n in names)
    assert any("relu1" in n for n in names)
    assert any("softmax" in n for n in names)
    # monitored forward must agree with compiled forward
    exe2 = _mlp().simple_bind(ctx=mx.cpu(), data=(4, 10), softmax_label=(4,))
    for name, arr in exe2.arg_dict.items():
        arr[:] = exe.arg_dict[name].asnumpy()
    out_plain = exe2.forward()[0].asnumpy()
    out_mon = exe.outputs[0].asnumpy()
    assert np.allclose(out_plain, out_mon, atol=1e-5)


def test_print_summary(capsys):
    total = mx.viz.print_summary(_mlp(), shape={"data": (4, 10), "softmax_label": (4,)})
    out = capsys.readouterr().out
    assert "fc1" in out and "softmax" in out
    # fc1: 10*8+8 params; fc2: 8*4+4
    assert total == (10 * 8 + 8) + (8 * 4 + 4)


def test_plot_network_graceful():
    try:
        dot = mx.viz.plot_network(_mlp(), shape={"data": (4, 10), "softmax_label": (4,)})
        assert "fc1" in dot.source
    except ImportError:
        pass  # graphviz not installed — informative error is the contract


# -- telemetry: metrics registry -------------------------------------------

def test_telemetry_registry_semantics():
    telemetry.reset()
    c = telemetry.counter("t.c")
    c.inc()
    c.inc(2)
    assert c.value == 3
    assert telemetry.counter("t.c") is c  # get-or-create is idempotent

    g = telemetry.gauge("t.g")
    assert g.value is None
    g.set(2.5)
    g.set(7)
    assert telemetry.gauge("t.g").value == 7

    h = telemetry.histogram("t.h")
    for v in [0.001] * 50 + [0.002] * 49 + [10.0]:
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 100
    assert abs(snap["sum"] - (0.05 + 0.098 + 10.0)) < 1e-9
    assert snap["min"] == 0.001 and snap["max"] == 10.0
    # log2 buckets: p50 lands in the 0.001-holding bucket (within one
    # power of two), p99 in the 0.002 bucket, both clamped to [min, max]
    assert 0.001 <= snap["p50"] <= 0.002
    assert snap["p50"] <= snap["p90"] <= snap["p99"] <= 10.0
    assert snap["p99"] < 0.01
    h.observe(0.0)
    assert h.snapshot()["zeros"] == 1

    # batch fold must agree with the per-value path (sum via approx:
    # numpy's pairwise summation may differ from sequential += by ulps)
    h2 = telemetry.histogram("t.h2")
    h2.observe_many([0.001] * 50 + [0.002] * 49 + [10.0] + [0.0])
    s2, s1 = h2.snapshot(), h.snapshot()
    assert s2.pop("sum") == pytest.approx(s1.pop("sum"), rel=1e-12)
    assert s2 == s1

    rep = telemetry.report()
    assert rep["schema"] == "mxtpu-telemetry-2"
    assert rep["counters"]["t.c"] == 3
    assert rep["gauges"]["t.g"] == 7
    assert rep["histograms"]["t.h"]["count"] == 101


def test_telemetry_span_nesting_in_trace(tmp_path):
    fname = str(tmp_path / "spans.json")
    telemetry.reset()
    mx.profiler.profiler_set_config(filename=fname)
    mx.profiler.profiler_set_state("run")
    with telemetry.span("outer.phase", cat="test"):
        time.sleep(0.002)
        with telemetry.span("inner.phase", cat="test"):
            time.sleep(0.002)
    mx.profiler.profiler_set_state("stop")
    mx.profiler.dump_profile()
    doc = json.load(open(fname))
    evs = {e["name"]: e for e in doc["traceEvents"]}
    outer, inner = evs["outer.phase"], evs["inner.phase"]
    # nested span events sit inside the parent's [ts, ts+dur] window and
    # carry an explicit depth arg
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert inner["args"]["depth"] == outer["args"]["depth"] + 1
    assert outer["cat"] == "test"
    # spans are always-on histograms too (phase-time breakdown)
    rep = telemetry.report()
    assert rep["phases"]["outer.phase"]["count"] == 1
    assert rep["phases"]["inner.phase"]["count"] == 1
    assert rep["phases"]["outer.phase"]["sum"] >= \
        rep["phases"]["inner.phase"]["sum"]


def test_flight_recorder_ring_bounds():
    telemetry.reset()
    cap = telemetry.flight_capacity()
    t0 = time.perf_counter_ns()
    for i in range(cap + 36):
        telemetry.note_train_step(t0 + i, t0 + i + 1000, t0 + i + 3000,
                                  i % 7 == 0, None)
    recs = telemetry.flight_records()
    assert len(recs) == cap  # bounded: oldest records evicted
    assert recs[0]["step"] == 36
    assert recs[-1]["step"] == cap + 35
    assert recs[-1]["dispatch_s"] == pytest.approx(1e-6)
    assert recs[-1]["sync_s"] == pytest.approx(2e-6)
    skipped = [r["step"] for r in recs if r["skipped"]]
    assert skipped == [s for s in range(36, cap + 36) if s % 7 == 0]
    assert telemetry.report()["flight"]["len"] == cap


def test_telemetry_emitter(tmp_path):
    telemetry.reset()
    path = str(tmp_path / "timeline.jsonl")
    telemetry.counter("emit.test").inc(5)
    telemetry.start_emitter(path, interval=0.05)
    time.sleep(0.25)
    telemetry.stop_emitter()
    lines = [json.loads(ln) for ln in open(path) if ln.strip()]
    assert len(lines) >= 2  # periodic lines plus the final flush
    assert lines[-1]["schema"] == "mxtpu-telemetry-2"
    assert lines[-1]["counters"]["emit.test"] == 5
    # the job-scope transport contract (OBSERVABILITY.md §8): every
    # line carries identity + clock anchor; only the final line carries
    # the flight ring
    for ln in lines:
        assert ln["identity"]["pid"] == os.getpid()
        assert ln["clock"]["perf_ns"] > 0
    assert lines[-1]["final"] is True
    assert "last_steps" in lines[-1]
    assert all("last_steps" not in ln for ln in lines[:-1])
    assert telemetry._parse_emitter_spec("a/b.jsonl:2.5") == \
        ("a/b.jsonl", 2.5)
    assert telemetry._parse_emitter_spec("a:b/c.jsonl") == \
        ("a:b/c.jsonl", 10.0)


_POSTMORTEM_WORKER = """
import os, sys
sys.path.insert(0, %(repo)r)
import numpy as np
import mxnet_tpu as mx

rs = np.random.RandomState(0)
X = rs.randn(64, 8).astype(np.float32)
y = rs.randint(0, 3, 64).astype(np.float32)
it = mx.io.NDArrayIter(X, y, batch_size=16, label_name="softmax_label")
net = mx.sym.SoftmaxOutput(
    mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=3,
                          name="fc"), name="softmax")
mod = mx.mod.Module(net, context=mx.cpu())
mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
mod.init_params(mx.initializer.Uniform(0.1))
mod.init_optimizer(kvstore=None, optimizer="sgd",
                   optimizer_params=(("learning_rate", 0.05),))
for epoch in range(10):
    it.reset()
    for b in it:
        mod.fit_step(b)  # grad.nan fires, guard skips, limit raises
"""


@pytest.mark.fault
def test_postmortem_on_fault_injected_crash(tmp_path):
    """A fault-injected run that dies on the divergence guard's
    K-consecutive-skips MXNetError must leave a postmortem JSON whose
    last records are the skipped steps, consistent with the profiler's
    step_stats deltas."""
    pm_dir = str(tmp_path / "pm")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "MXTPU_FAULT": "grad.nan:10",
        "MXTPU_MAX_CONSECUTIVE_SKIPS": "3",
        "MXTPU_POSTMORTEM_DIR": pm_dir,
    })
    r = subprocess.run(
        [sys.executable, "-c", _POSTMORTEM_WORKER % {"repo": REPO}],
        env=env, capture_output=True, timeout=300, text=True)
    assert r.returncode != 0
    assert "divergence guard" in r.stderr
    files = os.listdir(pm_dir)
    assert len(files) == 1 and files[0].startswith("postmortem-")
    doc = json.load(open(os.path.join(pm_dir, files[0])))
    assert doc["schema"] == "mxtpu-postmortem-2"
    assert doc["identity"]["pid"] == doc["pid"]  # job-scope stamp
    assert doc["reason"].startswith("MXNetError")
    assert "divergence guard" in doc["reason"]
    # every step fired grad.nan and was skipped; the crash came on the
    # 3rd consecutive skip
    stats = doc["step_stats"]
    assert stats["skipped_steps"] == 3
    assert doc["fault_fires"] == {"grad.nan": 3}
    recs = doc["last_steps"]
    assert [r_["skipped"] for r_ in recs] == [True] * 3
    assert all(r_["faults"] == ["grad.nan"] for r_ in recs)
    # flight records reconcile with the profiler's counters
    assert sum(r_["dispatch_delta"] for r_ in recs) == \
        stats["dispatch_count"]
    assert sum(r_["compile_delta"] for r_ in recs) == \
        stats["compile_count"]
    assert doc["counters"]["fault.fire.grad.nan"] == 3
    # and the CLI pretty-printer renders it
    sys.path.insert(0, os.path.join(REPO, "tools", "perf_probe"))
    try:
        import io as _io
        import telemetry_report
        out = _io.StringIO()
        telemetry_report.render_file(os.path.join(pm_dir, files[0]),
                                     out=out)
        text = out.getvalue()
        assert "POSTMORTEM" in text and "grad.nan" in text
        assert "SKIP" in text
    finally:
        sys.path.pop(0)


def test_telemetry_fit_step_phases_and_consistency():
    """The fused fit loop feeds fit_step.dispatch / fit_step.sync phase
    histograms and the flight ring in lockstep with step_stats()."""
    from mxnet_tpu import profiler
    rs = np.random.RandomState(0)
    X = rs.randn(64, 10).astype(np.float32)
    y = rs.randint(0, 4, 64).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16,
                           label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Uniform(0.1))
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.05),))
    batches = list(it)
    for b in batches:  # warm
        mod.fit_step(b)
    telemetry.reset()
    profiler.reset_step_stats()
    for _ in range(3):
        for b in batches:
            mod.fit_step(b)
    n = 3 * len(batches)
    stats = profiler.step_stats()
    rep = telemetry.report()
    assert stats["dispatch_count"] == n
    assert rep["phases"]["fit_step.dispatch"]["count"] == n
    assert rep["phases"]["fit_step.sync"]["count"] == n
    recs = telemetry.flight_records()
    assert len(recs) == min(n, telemetry.flight_capacity())
    assert all(r["dispatch_delta"] == 1 and not r["skipped"]
               for r in recs)


def test_dataloader_telemetry_phases():
    from mxnet_tpu.gluon.data import DataLoader
    from mxnet_tpu.gluon.data.dataset import ArrayDataset
    telemetry.reset()
    ds = ArrayDataset(np.arange(64, dtype=np.float32).reshape(16, 4),
                      np.arange(16, dtype=np.float32))
    loader = DataLoader(ds, batch_size=4, prefetch=2)
    n = sum(1 for _ in loader)
    assert n == 4
    rep = telemetry.report()
    assert rep["counters"]["data.batches"] == 4
    assert rep["phases"]["data.batchify"]["count"] == 4
    assert rep["phases"]["data.h2d"]["count"] == 4
    assert rep["phases"]["data.prefetch_wait"]["count"] >= 4


def test_atomic_dump_profile_no_tmp_litter(tmp_path):
    """dump_profile rides the checkpoint layer's atomic writer: valid
    JSON at the
    final path, no .tmp-* litter left behind."""
    fname = str(tmp_path / "trace.json")
    mx.profiler.profiler_set_config(filename=fname)
    mx.profiler.profiler_set_state("run")
    with telemetry.span("x"):
        pass
    mx.profiler.profiler_set_state("stop")
    out = mx.profiler.dump_profile()
    assert out == fname
    assert json.load(open(fname))["traceEvents"]
    assert [p for p in os.listdir(str(tmp_path))] == ["trace.json"]
