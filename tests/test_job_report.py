"""Job-scope observability (OBSERVABILITY.md §8).

Fast layers: the rank/clock stamping of every telemetry line (schema
mxtpu-telemetry-2), the crash-proof single-write emitter, the
``step.slow``/``data.slow`` straggler delay sites with per-slot scoping
(MXTPU_FAULT_SLOTS), job_report.py's rank matrix / straggler blame /
attempt segmentation / merged-trace generation against a synthetic run
dir, telemetry_report.py's run-dir dispatch, the compile-time
cost/memory attribution gauges (incl. the measured-collective HLO
parser and the ZeRO-1 ±20% argument-bytes cross-check), and the AOT
cache's attribution-metadata sidecar.

Launcher-driven: telemetry identity across a real 3→2 elastic reshard
(append-only per-slot streams — old attempt lines preserved, new lines
stamped with the new world).  The slow e2e drives the acceptance
scenario end-to-end: an injected straggler named by job_report, one
merged Perfetto-loadable trace, the timeline segmented at an elastic
transition, cost gauges populated, 1.0 dispatch/step intact.

Every spawned process is wrapped in a ``timeout -k`` guard (the hang
suite's rule).
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import fault, telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAUNCH = os.path.join(REPO, "tools", "launch.py")
PERF_PROBE = os.path.join(REPO, "tools", "perf_probe")
JOB_REPORT = os.path.join(PERF_PROBE, "job_report.py")
TELEMETRY_REPORT = os.path.join(PERF_PROBE, "telemetry_report.py")


def _run(argv, timeout_s=180, env=None, **kw):
    full = ["timeout", "-k", "10", str(timeout_s)] + argv
    return subprocess.run(full, capture_output=True, text=True,
                          timeout=timeout_s + 30, env=env, **kw)


def _mlp_module(batch=16, n=64, dim=10, classes=2):
    rs = np.random.RandomState(0)
    X = rs.randn(n, dim).astype(np.float32)
    Y = rs.randint(0, classes, n).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=batch,
                           label_name="softmax_label")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"),
                              num_hidden=classes, name="fc"),
        name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.05),))
    return mod, list(it)


# -- transport: identity + clock stamping ------------------------------------

@pytest.mark.jobview
def test_report_identity_from_membership_env(monkeypatch):
    monkeypatch.setenv("MXTPU_NUM_WORKERS", "3")
    monkeypatch.setenv("MXTPU_WORKER_RANK", "1")
    monkeypatch.setenv("MXTPU_WORKER_SLOT", "2")
    monkeypatch.setenv("MXTPU_RESTART_ATTEMPT", "4")
    rep = telemetry.report()
    assert rep["schema"] == "mxtpu-telemetry-2"
    assert rep["identity"] == {"world_size": 3, "rank": 1, "slot": 2,
                               "attempt": 4, "pid": os.getpid()}
    # the clock anchor maps this process's perf stamps to unix time:
    # anchoring "now" must land within a breath of time.time()
    clock = rep["clock"]
    now_via_anchor = clock["unix"] + \
        (time.perf_counter_ns() - clock["perf_ns"]) * 1e-9
    assert abs(now_via_anchor - time.time()) < 1.0
    # a postmortem carries the same stamp
    doc = json.loads(json.dumps(rep))  # JSON-able end to end
    assert doc["identity"]["slot"] == 2


@pytest.mark.jobview
def test_postmortem_schema2_identity(monkeypatch, tmp_path):
    monkeypatch.setenv("MXTPU_NUM_WORKERS", "2")
    monkeypatch.setenv("MXTPU_WORKER_RANK", "1")
    path = str(tmp_path / "pm.json")
    telemetry.dump_postmortem("jobview test", path=path)
    doc = json.load(open(path))
    assert doc["schema"] == "mxtpu-postmortem-2"
    assert doc["identity"]["rank"] == 1
    assert doc["clock"]["perf_ns"] > 0


# -- emitter hardening -------------------------------------------------------

_CRASH_EMITTER_WORKER = """
import os, sys, time
sys.path.insert(0, %(repo)r)
os.environ["JAX_PLATFORMS"] = "cpu"
from mxnet_tpu import telemetry
# fat registry: every line far exceeds one stdio buffer, so a buffered
# chunked writer WOULD tear on the crash below
for i in range(1500):
    telemetry.counter("crash.test.%%05d" %% i).inc(i)
telemetry.start_emitter(%(path)r, interval=0.02)
time.sleep(%(sleep)r)
os._exit(9)   # hard crash mid-interval: no atexit, no final flush
"""


@pytest.mark.jobview
def test_emitter_crash_mid_interval_leaves_complete_lines(tmp_path):
    """The satellite contract: a process dying mid-interval (hard
    os._exit — no cleanup) must leave a stream whose every line,
    including the last, is complete JSON.  Lines here are >64 KiB (1500
    counters), far past stdio buffering; the emitter's single
    O_APPEND write per line is what makes the tail atomic."""
    path = str(tmp_path / "stream.jsonl")
    code = _CRASH_EMITTER_WORKER % {"repo": REPO, "path": path,
                                    "sleep": 0.6}
    r = _run([sys.executable, "-c", code], timeout_s=120)
    assert r.returncode == 9, r.stderr[-2000:]
    raw = open(path).read()
    lines = raw.splitlines()
    assert len(lines) >= 3  # several periodic lines landed pre-crash
    for i, ln in enumerate(lines):
        doc = json.loads(ln)  # every line complete — incl. the last
        assert doc["schema"] == "mxtpu-telemetry-2", i
    assert json.loads(lines[-1])["counters"]["crash.test.01499"] == 1499
    assert raw.endswith("\n")  # the last write was whole


@pytest.mark.jobview
def test_emitter_final_flush_serialized_once(tmp_path):
    """A clean stop writes exactly ONE final line (flight ring
    attached), even with a concurrent report() reader hammering the
    registry while the emitter drains."""
    import threading
    telemetry.reset()
    path = str(tmp_path / "stream.jsonl")
    t0 = time.perf_counter_ns()
    for i in range(5):
        telemetry.note_train_step(t0 + i, t0 + i + 1000, t0 + i + 2000,
                                  False, None)
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            telemetry.report()
    t = threading.Thread(target=reader, daemon=True)
    t.start()
    try:
        telemetry.start_emitter(path, interval=0.03)
        time.sleep(0.12)
        telemetry.stop_emitter()
    finally:
        stop.set()
        t.join(timeout=5)
    lines = [json.loads(ln) for ln in open(path) if ln.strip()]
    finals = [ln for ln in lines if ln.get("final")]
    assert len(finals) == 1
    assert len(finals[0]["last_steps"]) == 5
    assert lines[-1] is finals[-1] or lines[-1]["final"]


# -- straggler delay sites ---------------------------------------------------

@pytest.mark.jobview
@pytest.mark.fault
def test_delay_if_sleeps_bounded(monkeypatch):
    fault.configure("step.slow:2")
    monkeypatch.setenv("MXTPU_FAULT_DELAY_SECS", "0.05")
    t0 = time.perf_counter()
    fault.delay_if("step.slow")
    dt = time.perf_counter() - t0
    assert 0.04 <= dt < 1.0
    fault.delay_if("step.slow")          # second armed firing
    t0 = time.perf_counter()
    fault.delay_if("step.slow")          # disarmed: no sleep
    assert time.perf_counter() - t0 < 0.02
    assert fault.fire_count("step.slow") == 2
    fault.reset()


@pytest.mark.jobview
@pytest.mark.fault
def test_fault_slots_scopes_env_spec(monkeypatch):
    """MXTPU_FAULT_SLOTS restricts an ENV spec to the named slots; an
    explicit configure(spec) always applies (a worker script that arms
    its own rule means it)."""
    monkeypatch.setenv("MXTPU_FAULT", "step.slow:1")
    monkeypatch.setenv("MXTPU_FAULT_SLOTS", "1,3")
    monkeypatch.setenv("MXTPU_WORKER_SLOT", "2")
    fault.configure()
    assert not fault.is_active("step.slow")  # slot 2 not targeted
    monkeypatch.setenv("MXTPU_WORKER_SLOT", "3")
    fault.configure()
    assert fault.is_active("step.slow")      # slot 3 targeted
    monkeypatch.setenv("MXTPU_WORKER_SLOT", "2")
    fault.configure("step.slow:1")           # explicit: never scoped
    assert fault.is_active("step.slow")
    fault.reset()


@pytest.mark.jobview
@pytest.mark.fault
def test_step_slow_inflates_dispatch_phase(monkeypatch):
    """The e2e straggler signal at unit scale: an armed step.slow delay
    lands inside fit_step's timed dispatch window, so THIS rank's
    fit_step.dispatch percentiles inflate — exactly what job_report's
    blame keys off."""
    mod, batches = _mlp_module()
    for b in batches:
        mod.fit_step(b)  # warm
    telemetry.reset()
    for b in batches:
        mod.fit_step(b)
    clean_p50 = telemetry.report()["phases"]["fit_step.dispatch"]["p50"]
    monkeypatch.setenv("MXTPU_FAULT_DELAY_SECS", "0.05")
    fault.configure("step.slow:100")
    try:
        telemetry.reset()
        for b in batches:
            mod.fit_step(b)
    finally:
        fault.reset()
    slow_p50 = telemetry.report()["phases"]["fit_step.dispatch"]["p50"]
    assert slow_p50 >= 0.04
    assert slow_p50 > 5 * clean_p50
    assert telemetry.counter("fault.fire.step.slow").value == \
        len(batches)


# -- job_report on a synthetic run dir ---------------------------------------

def _hist(p50, count=20):
    return {"count": count, "sum": p50 * count, "min": p50 / 2,
            "max": p50 * 2, "p50": p50, "p90": p50 * 1.5,
            "p99": p50 * 2, "buckets": {}, "zeros": 0}


def _stream_line(t, slot, rank, world, attempt, d50, final=False,
                 steps=40):
    doc = {
        "schema": "mxtpu-telemetry-2", "time_unix": t, "pid": 100 + slot,
        "identity": {"world_size": world, "rank": rank, "slot": slot,
                     "attempt": attempt, "pid": 100 + slot},
        "clock": {"unix": t, "perf_ns": 1},
        "counters": {}, "gauges": {},
        "phases": {"fit_step.dispatch": _hist(d50),
                   "fit_step.sync": _hist(d50 / 10)},
        "histograms": {},
        "step_stats": {"steps": steps, "dispatch_count": steps,
                       "compile_count": 1, "skipped_steps": 0,
                       "step_time_ema_s": d50},
        "flight": {"len": 4, "maxlen": 64},
    }
    if final:
        doc["final"] = True
        doc["last_steps"] = [
            {"step": i, "t_unix": t + i * d50, "dispatch_s": d50,
             "sync_s": d50 / 10, "dispatch_delta": 1, "compile_delta": 0,
             "skipped": False, "loss": 0.4, "faults": []}
            for i in range(4)]
    return doc


def _write_synthetic_run(tmp_path, straggler_slot=1, factor=20.0):
    """A 3-slot job: attempt 0 at world 3 loses slot 2 (evicted),
    attempt 1 completes at world 2 with survivors re-ranked.  Slot
    ``straggler_slot`` is ``factor``x slower throughout."""
    run = tmp_path / "run"
    tdir = run / "telemetry"
    tdir.mkdir(parents=True)
    t0 = 1_700_000_000.0
    base = 0.002
    for slot in range(3):
        d50 = base * factor if slot == straggler_slot else base
        lines = [_stream_line(t0 + 1, slot, slot, 3, 0, d50),
                 _stream_line(t0 + 5, slot, slot, 3, 0, d50, final=True)]
        if slot != 2:  # survivors run attempt 1, re-ranked contiguously
            rank = 0 if slot == 0 else 1
            lines += [
                _stream_line(t0 + 12, slot, rank, 2, 1, d50),
                _stream_line(t0 + 18, slot, rank, 2, 1, d50,
                             final=True)]
        with open(tdir / ("stream-slot%d.jsonl" % slot), "w") as f:
            f.write("\n".join(json.dumps(d) for d in lines) + "\n")
    mem = {"schema": "mxtpu-membership-1", "total_slots": 3,
           "transitions": [
               {"time": t0, "attempt": 0, "event": "launch",
                "world_size": 3, "active_slots": [0, 1, 2],
                "evicted_slots": []},
               {"time": t0 + 0.5, "attempt": 0, "event": "attempt_start",
                "world_size": 3, "active_slots": [0, 1, 2],
                "evicted_slots": [], "port": 1234},
               {"time": t0 + 6, "attempt": 0, "event": "failure",
                "world_size": 3, "active_slots": [0, 1, 2],
                "evicted_slots": [], "slot": 2, "rank": 2, "rc": 77,
                "kind": "retryable"},
               {"time": t0 + 6.1, "attempt": 0, "event": "evict",
                "world_size": 2, "active_slots": [0, 1],
                "evicted_slots": [2], "slot": 2},
               {"time": t0 + 10, "attempt": 1, "event": "attempt_start",
                "world_size": 2, "active_slots": [0, 1],
                "evicted_slots": [2], "port": 1235},
               {"time": t0 + 20, "attempt": 1, "event": "complete",
                "world_size": 2, "active_slots": [0, 1],
                "evicted_slots": [2]}]}
    with open(run / "membership.json", "w") as f:
        json.dump(mem, f)
    return run


@pytest.mark.jobview
def test_job_report_names_straggler_and_segments_attempts(tmp_path):
    run = _write_synthetic_run(tmp_path, straggler_slot=1)
    r = _run([sys.executable, JOB_REPORT, str(run),
              "--straggler-factor", "2.0"])
    assert r.returncode == 0, r.stderr[-2000:]
    out = r.stdout
    # straggler named by rank AND slot, in the attempt-0 (3-rank) segment
    assert "STRAGGLER: rank 1 (slot 1)" in out
    # membership-aware segmentation: one section per attempt with its
    # world size and the transition that ended attempt 0
    assert "-- attempt 0 (world size 3" in out
    assert "-- attempt 1 (world size 2" in out
    assert "evict slot 2" in out
    # the per-rank matrix shows every rank of attempt 0
    for rank in (0, 1, 2):
        assert "\n  %d     %d" % (rank, rank) in out


@pytest.mark.jobview
def test_straggler_blamed_at_world_size_two():
    """Leave-one-out baseline regression pin: with exactly 2 scoring
    ranks a plain all-ranks median caps the ratio below 2.0 for ANY
    slowdown (median = midpoint of the two scores), silently disabling
    the detector at world size 2 — the very world an elastic 3→2
    shrink leaves behind."""
    sys.path.insert(0, PERF_PROBE)
    try:
        import job_report
    finally:
        sys.path.pop(0)
    rows = [{"rank": 0, "slot": 0, "score": 0.002},
            {"rank": 1, "slot": 1, "score": 0.060}]
    hits = job_report.find_stragglers(rows, 2.0)
    assert len(hits) == 1
    row, ratio = hits[0]
    assert row["rank"] == 1
    assert ratio == pytest.approx(30.0)
    # healthy pair: nothing blamed
    assert not job_report.find_stragglers(
        [{"rank": 0, "slot": 0, "score": 0.002},
         {"rank": 1, "slot": 1, "score": 0.003}], 2.0)
    # one scoring rank: no baseline, no blame
    assert not job_report.find_stragglers(
        [{"rank": 0, "slot": 0, "score": 0.05},
         {"rank": 1, "slot": 1, "score": None}], 2.0)


@pytest.mark.jobview
def test_job_report_straggler_factor_configurable(tmp_path):
    run = _write_synthetic_run(tmp_path, straggler_slot=1, factor=3.0)
    hit = _run([sys.executable, JOB_REPORT, str(run),
                "--straggler-factor", "2.0"])
    missed = _run([sys.executable, JOB_REPORT, str(run),
                   "--straggler-factor", "4.0"])
    assert "STRAGGLER: rank 1" in hit.stdout
    assert "STRAGGLER" not in missed.stdout
    assert "no straggler" in missed.stdout


@pytest.mark.jobview
def test_job_report_merged_trace_loadable(tmp_path):
    run = _write_synthetic_run(tmp_path)
    trace = tmp_path / "job-trace.json"
    r = _run([sys.executable, JOB_REPORT, str(run), "--trace-out",
              str(trace)])
    assert r.returncode == 0, r.stderr[-2000:]
    doc = json.load(open(trace))  # ONE loadable chrome-trace document
    events = doc["traceEvents"]
    spans = [e for e in events if e["ph"] == "X"]
    # all three slots' spans in one file, on one non-negative time axis
    assert {e["pid"] for e in spans} == {0, 1, 2}
    assert all(e["ts"] >= 0 for e in events if "ts" in e)
    names = {e["name"] for e in spans}
    assert names == {"fit_step.dispatch", "fit_step.sync"}
    # membership transitions ride as instant events on the job track
    instants = [e for e in events if e["ph"] == "i"]
    assert any("evict" in e["name"] for e in instants)
    # track metadata names slots and per-attempt threads
    metas = [e for e in events if e["ph"] == "M"]
    assert any(e["name"] == "process_name" and
               e["args"]["name"] == "slot 1" for e in metas)
    assert any(e["name"] == "thread_name" and
               "attempt 1" in e["args"]["name"] for e in metas)


@pytest.mark.jobview
def test_merged_trace_dedups_postmortem_vs_final_line(tmp_path):
    """A rank dying on an uncaught exception leaves the SAME flight
    ring twice — excepthook postmortem AND atexit final stream line;
    the merged trace must render each span once, not twice."""
    run = _write_synthetic_run(tmp_path)
    # a postmortem for slot 0's attempt-0 process (pid 100), carrying
    # the same ring its final stream line already carries
    line = _stream_line(1_700_000_000.0 + 5, 0, 0, 3, 0, 0.002,
                        final=True)
    pm = dict(line)
    pm["schema"] = "mxtpu-postmortem-2"
    pm["reason"] = "boom"
    with open(run / "telemetry" / "postmortem-100.json", "w") as f:
        json.dump(pm, f)
    sys.path.insert(0, PERF_PROBE)
    try:
        import job_report
    finally:
        sys.path.pop(0)
    job = job_report.load_job(str(run))
    doc, _ = job_report.merged_trace(job)
    slot0_a0 = [e for e in doc["traceEvents"]
                if e["ph"] == "X" and e["pid"] == 0 and e["tid"] == 0
                and e["name"] == "fit_step.dispatch"]
    # 4 records in the ring -> exactly 4 dispatch spans, not 8
    assert len(slot0_a0) == 4, len(slot0_a0)


@pytest.mark.jobview
def test_telemetry_report_renders_run_dir(tmp_path):
    """The satellite: one positional run-dir arg renders membership +
    every stream + postmortems together, identity-stamped."""
    run = _write_synthetic_run(tmp_path)
    # drop a postmortem into the tree too
    pm = {"schema": "mxtpu-postmortem-2", "pid": 102, "reason": "boom",
          "identity": {"world_size": 3, "rank": 2, "slot": 2,
                       "attempt": 0, "pid": 102},
          "step_stats": {"steps": 7}, "last_steps": [], "counters": {},
          "gauges": {}, "phases": {}, "histograms": {},
          "flight": {"len": 0, "maxlen": 64}}
    with open(run / "telemetry" / "postmortem-102.json", "w") as f:
        json.dump(pm, f)
    r = _run([sys.executable, TELEMETRY_REPORT, str(run)])
    assert r.returncode == 0, r.stderr[-2000:]
    out = r.stdout
    assert "RUN DIR" in out
    assert "MEMBERSHIP: 3 slot(s)" in out
    assert out.count("telemetry report") >= 3  # one per stream
    assert "[rank 1/2 slot 1 attempt 1]" in out  # identity surfaced
    assert "POSTMORTEM (pid 102) [rank 2/3 slot 2 attempt 0]" in out
    # single-file invocations still work unchanged
    r2 = _run([sys.executable, TELEMETRY_REPORT,
               str(run / "membership.json")])
    assert "MEMBERSHIP" in r2.stdout


# -- compile-time cost attribution -------------------------------------------

@pytest.mark.jobview
def test_fused_step_cost_gauges_populated():
    mod, batches = _mlp_module()
    mod.fit_step(batches[0])
    g = telemetry.report()["gauges"]
    assert g.get("xla.cost.flops_per_step", 0) > 0
    assert g.get("xla.cost.bytes_accessed_per_step", 0) > 0
    assert g.get("xla.memory.argument_bytes", 0) > 0
    assert g.get("xla.memory.output_bytes", 0) > 0
    doc = mod._exec._cost_doc
    assert doc["memory"]["argument_bytes"] == \
        g["xla.memory.argument_bytes"]
    # probes reset the registry after warmup; republish restores
    telemetry.reset()
    assert telemetry.gauge("xla.cost.flops_per_step").value is None
    mod._exec.publish_cost_telemetry()
    assert telemetry.gauge("xla.cost.flops_per_step").value == \
        doc["cost"]["flops"]


@pytest.mark.jobview
def test_hlo_collective_bytes_parser():
    from mxnet_tpu.executor import Executor
    hlo = """
  %ar = f32[16,8]{1,0} all-reduce(f32[16,8]{1,0} %x), replica_groups={}
  %ag = f32[64,4]{1,0} all-gather(f32[8,4]{1,0} %y), channel_id=1
  %rs = f32[8,4]{1,0} reduce-scatter(f32[64,4]{1,0} %z), channel_id=2
  %st = (f32[9999], u32[]) all-gather-start(f32[9999] %w)
  %dn = f32[16]{0} all-gather-done((f32[9999], u32[]) %st)
  %tok = token[] after-all()
"""
    n = 8
    total, counts = Executor._hlo_collective_bytes(hlo, n)
    ar = 16 * 8 * 4          # full buffer
    ag = 64 * 4 * 4          # gathered output
    rs_out = 8 * 4 * 4       # 1/n shard
    expect = int(ar * 2 * (n - 1) / n) + int(ag * (n - 1) / n) + \
        int(rs_out * (n - 1)) + int(16 * 4 * (n - 1) / n)  # the -done
    assert total == expect
    assert counts == {"all-reduce": 1, "all-gather": 2,
                      "reduce-scatter": 1}
    # n=1 (no peers): zero bytes moved, ops still counted
    total1, _ = Executor._hlo_collective_bytes(hlo, 1)
    assert total1 == 0


@pytest.mark.jobview
def test_zero1_argument_bytes_cross_check():
    """The acceptance cross-check at unit scale: on the 8-device ZeRO-1
    bind, the compiled program's own per-device argument accounting
    agrees ±20% with the bytes the sharded live arrays occupy — the 1/N
    state economics measured from the executable, not the placement
    model — and the collective gauge is measured (it diverges from the
    ring model on CPU, which lowers reduce-scatter as all-reduce+slice)."""
    import jax
    assert jax.device_count() >= 8, "conftest must force 8 CPU devices"
    sys.path.insert(0, PERF_PROBE)
    try:
        import steptrace
    finally:
        sys.path.pop(0)
    prev = os.environ.get("MXTPU_ZERO")
    os.environ["MXTPU_ZERO"] = "1"
    try:
        ctx = [mx.cpu(i) for i in range(8)]
        mod, train = steptrace.build_module(
            ctx=ctx, optimizer="adam",
            opt_params=(("learning_rate", 0.01),))
        b = next(iter(train))
        mod.fit_step(b)
    finally:
        if prev is None:
            os.environ.pop("MXTPU_ZERO", None)
        else:
            os.environ["MXTPU_ZERO"] = prev
    g = telemetry.report()["gauges"]
    arg_bytes = g.get("xla.memory.argument_bytes")
    assert arg_bytes, "attribution gauges missing on the mesh bind"
    exe = mod._exec
    fused = mod._fused

    def per_device_bytes(leaf):
        shards = {s.data.shape for s in leaf.addressable_shards}
        return int(np.prod(next(iter(shards)))) * leaf.dtype.itemsize

    expected = 0
    for sub in fused["state"].values():
        for leaf in jax.tree_util.tree_leaves(sub):
            expected += per_device_bytes(leaf)
    for d in (exe.arg_dict, exe.aux_dict):
        for arr in d.values():
            expected += per_device_bytes(arr._data)
    assert abs(arg_bytes - expected) <= 0.2 * expected, \
        (arg_bytes, expected)
    # measured collective bytes replaced the model in the main gauge;
    # the model stays published for comparison
    assert g.get("sharding.collective_bytes_per_step", 0) > 0
    assert g.get("sharding.collective_bytes_modeled", 0) > 0
    coll = exe._cost_doc["collectives"]
    assert coll["ops"] and coll["participants"] == 8


@pytest.mark.jobview
def test_aot_entry_carries_attribution_meta(tmp_path, monkeypatch):
    """The cache sidecar: an entry stores the original compile's
    attribution doc and load() hands it back — a warm restart
    republishes real numbers without re-deriving them from a
    deserialized executable."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import aot_cache
    monkeypatch.setenv("MXTPU_AOT_CACHE_DIR", str(tmp_path))

    def f(a, b):
        return a * b + 1
    x = jnp.ones((8,), jnp.float32)
    compiled = jax.jit(f).lower(x, x).compile()
    key = aot_cache.cache_key("meta-test", (x, x))
    meta = {"cost": {"flops": 123.0}, "memory": {"argument_bytes": 64}}
    assert aot_cache.store(key, compiled, aot_cache.VARIANT_PLAIN, meta)
    loaded = aot_cache.load(key)
    assert loaded is not None
    _, var, got = loaded
    assert var == aot_cache.VARIANT_PLAIN
    assert got == meta


# -- telemetry identity across an elastic reshard (launcher-driven) ----------

_IDENTITY_WORKER = """
import os, sys, time
sys.path.insert(0, %(repo)r)
from mxnet_tpu import elastic, telemetry

mem = elastic.membership()
# a couple of periodic lines before anything else happens
time.sleep(0.45)
if mem["slot"] == 1 and mem["attempt"] == 0:
    # uncaught crash: excepthook dumps the postmortem (stamped with THIS
    # membership), exit 1 classifies retryable, --evict-after 1 drops
    # the slot, survivors re-rank at world 2
    raise RuntimeError("jobview identity test: slot 1 dies once")
time.sleep(0.6)
"""


@pytest.mark.jobview
@pytest.mark.elastic
def test_identity_across_elastic_reshard(tmp_path):
    """Drive a real 3→2 membership change and assert the transport
    contract: every post-transition line carries the new world/rank,
    the evicted slot's attempt-0 lines survive untouched (append-only
    per-slot streams), and the crash postmortem is stamped with the
    membership it died under."""
    script = tmp_path / "worker.py"
    script.write_text(_IDENTITY_WORKER % {"repo": REPO})
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    run_dir = tmp_path / "run"
    r = _run([sys.executable, LAUNCH, "-n", "3", "--elastic",
              "--evict-after", "1", "--max-restarts", "3",
              "--restart-backoff", "0.01", "--run-dir", str(run_dir),
              "--telemetry-interval", "0.1",
              "--", sys.executable, str(script)],
             timeout_s=300, env=env)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-3000:])
    tdir = run_dir / "telemetry"

    def lines(slot):
        path = tdir / ("stream-slot%d.jsonl" % slot)
        return [json.loads(ln) for ln in open(path) if ln.strip()]

    # slot 1 (evicted): attempt-0 lines only, stamped world 3 rank 1
    s1 = lines(1)
    assert s1 and all(d["identity"]["attempt"] == 0 for d in s1)
    assert all(d["identity"]["world_size"] == 3 and
               d["identity"]["rank"] == 1 for d in s1)

    # survivors: attempt-0 lines preserved (world 3, old rank) AND
    # attempt-1 lines appended (world 2, re-ranked) — never overwritten
    for slot, new_rank in ((0, 0), (2, 1)):
        docs = lines(slot)
        a0 = [d for d in docs if d["identity"]["attempt"] == 0]
        a1 = [d for d in docs if d["identity"]["attempt"] == 1]
        assert a0 and a1, (slot, len(a0), len(a1))
        assert all(d["identity"]["world_size"] == 3 and
                   d["identity"]["rank"] == slot for d in a0)
        assert all(d["identity"]["world_size"] == 2 and
                   d["identity"]["rank"] == new_rank and
                   d["identity"]["slot"] == slot for d in a1)
        # the order on disk is append order: attempt 0 first
        assert docs.index(a1[0]) > docs.index(a0[-1])
        # clean attempt-1 exit left a final flight-bearing line
        assert any(d.get("final") for d in a1)

    # the crash postmortem carries the membership it died under
    pms = sorted(tdir.glob("postmortem-*.json"))
    assert pms, "slot 1's crash left no postmortem in the telemetry dir"
    pm_docs = [json.load(open(p)) for p in pms]
    crash = [d for d in pm_docs
             if "slot 1 dies once" in str(d.get("reason"))]
    assert crash
    assert crash[0]["identity"]["world_size"] == 3
    assert crash[0]["identity"]["rank"] == 1
    assert crash[0]["membership"]["world_size"] == 3

    # and job_report digests the real tree end to end
    rr = _run([sys.executable, JOB_REPORT, str(run_dir)])
    assert rr.returncode == 0, rr.stderr[-2000:]
    assert "-- attempt 0 (world size 3" in rr.stdout
    assert "-- attempt 1 (world size 2" in rr.stdout
    assert "postmortem: rank 1 slot 1 attempt 0" in rr.stdout


# -- slow e2e: straggler blame + merged trace + elastic segmentation ---------

_STRAGGLER_WORKER = """
import json, os, sys, time
sys.path.insert(0, %(repo)r)
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import elastic, fault, profiler, telemetry

OUT = sys.argv[1]
N, DIM, BATCH, EPOCHS = 60, 8, 5, 4
mem = elastic.membership()
rank, world = mem["rank"], mem["world_size"]
slot, attempt = mem["slot"], mem["attempt"]

rs = np.random.RandomState(0)
X = rs.randn(N, DIM).astype(np.float32)
Y = (X @ rs.randn(DIM) > 0).astype(np.float32)

net = mx.sym.SoftmaxOutput(
    mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                          name="fc"), name="softmax")
mod = mx.mod.Module(net, context=mx.cpu())

warm = [None]
for epoch in range(EPOCHS):
    idx = elastic.shard_for_epoch(N, epoch, rank, world)
    it = mx.io.NDArrayIter(X[idx], Y[idx], batch_size=BATCH,
                           shuffle=False)
    # the injected elastic transition: slot 2 dies once mid-run, AFTER
    # two epochs of steps every rank has emitted telemetry lines for
    if slot == 2 and attempt == 0 and epoch == 2:
        fault.configure("worker.lost:1")
    mod.fit(it, num_epoch=epoch + 1, begin_epoch=epoch, kvstore=None,
            optimizer="sgd", optimizer_params={"learning_rate": 0.2},
            initializer=mx.init.Xavier())
    if warm[0] is None:
        s0 = profiler.step_stats()
        warm[0] = (s0["steps"], s0["dispatch_count"])
    # epoch cadence >> the 0.15 s emit interval: every rank's stream
    # gets in-training lines (phases populated) before the injected
    # death, so the attempt-0 rank matrix is deterministic
    time.sleep(0.3)

st = profiler.step_stats()
g = telemetry.report()["gauges"]
with open(os.path.join(OUT, "stats-a%%d-r%%d.json" %% (attempt, rank)),
          "w") as f:
    json.dump({"slot": slot, "world": world,
               "steady_steps": st["steps"] - warm[0][0],
               "steady_dispatches": st["dispatch_count"] - warm[0][1],
               "slow_fires": fault.fire_count("step.slow"),
               "xla_flops": g.get("xla.cost.flops_per_step"),
               "xla_arg_bytes": g.get("xla.memory.argument_bytes"),
               "xla_temp_bytes": g.get("xla.memory.temp_bytes")}, f)
"""


@pytest.mark.slow
@pytest.mark.jobview
@pytest.mark.elastic
def test_e2e_straggler_blamed_across_elastic_transition(tmp_path):
    """The acceptance scenario end-to-end: a 3-worker launch.py run
    where slot 1 carries an injected per-step delay (step.slow via
    MXTPU_FAULT_SLOTS — only that rank) and slot 2 dies once mid-run
    (worker.lost → evict → attempt 1 at world 2).  job_report.py must
    name the delayed rank as the straggler from the real telemetry
    tree, render ONE merged Perfetto-loadable cross-rank trace, and
    segment the timeline at the elastic transition; the cost/memory
    gauges are populated on every rank and the 1.0 dispatch/step
    contract holds with the whole job plane enabled."""
    script = tmp_path / "worker.py"
    script.write_text(_STRAGGLER_WORKER % {"repo": REPO})
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update({
        "MXTPU_FAULT": "step.slow:0.97",
        "MXTPU_FAULT_SLOTS": "1",
        "MXTPU_FAULT_DELAY_SECS": "0.03",
    })
    run_dir = tmp_path / "run"
    r = _run([sys.executable, LAUNCH, "-n", "3", "--elastic",
              "--evict-after", "1", "--max-restarts", "3",
              "--restart-backoff", "0.01", "--run-dir", str(run_dir),
              "--telemetry-interval", "0.15",
              "--", sys.executable, str(script), str(tmp_path)],
             timeout_s=540, env=env)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-3000:])

    # the launcher journaled the injected transition
    mem = json.loads((run_dir / "membership.json").read_text())
    events = [(t["event"], t.get("slot")) for t in mem["transitions"]]
    assert ("evict", 2) in events

    trace_path = tmp_path / "job-trace.json"
    rr = _run([sys.executable, JOB_REPORT, str(run_dir),
               "--straggler-factor", "3.0", "--trace-out",
               str(trace_path)])
    assert rr.returncode == 0, (rr.stdout[-1500:], rr.stderr[-2000:])
    out = rr.stdout

    # (a) the injected straggler is NAMED — slot 1, whatever its rank
    assert "STRAGGLER" in out, out
    import re
    blamed = re.findall(r"STRAGGLER: rank (\d+) \(slot (\d+)\)", out)
    assert blamed and all(slot == "1" for _, slot in blamed), out

    # (b) the timeline is segmented at the elastic transition
    assert "-- attempt 0 (world size 3" in out
    assert "-- attempt 1 (world size 2" in out
    assert "evict slot 2" in out

    # (c) ONE merged chrome trace, loadable, spanning multiple ranks
    doc = json.load(open(trace_path))
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    pids = {e["pid"] for e in spans}
    assert len(pids) >= 2, "trace does not span multiple ranks"
    assert all(e["ts"] >= 0 for e in doc["traceEvents"] if "ts" in e)
    assert any("evict" in e["name"] for e in doc["traceEvents"]
               if e["ph"] == "i")
    # the victim's dispatch spans are visibly inflated in the merged
    # trace vs a healthy rank's
    by_pid = {}
    for e in spans:
        if e["name"] == "fit_step.dispatch":
            by_pid.setdefault(e["pid"], []).append(e["dur"])
    med = {pid: sorted(ds)[len(ds) // 2] for pid, ds in by_pid.items()}
    if 1 in med and len(med) > 1:
        healthy = [v for pid, v in med.items() if pid != 1]
        assert med[1] > 3 * max(healthy), med

    # (d) per-rank contracts from the workers themselves: the delay
    # fired only on slot 1, cost gauges populated everywhere, and the
    # fused step stayed at exactly 1.0 dispatch/step post-warmup with
    # the job plane enabled
    stats = [json.loads(p.read_text())
             for p in tmp_path.glob("stats-a*-r*.json")]
    # attempt 1 completed cleanly, so both of its ranks reported (the
    # torn attempt 0's killed ranks legitimately may not have)
    assert len(stats) >= 2
    assert any(st["slot"] == 1 for st in stats)
    for st in stats:
        if st["slot"] == 1:
            assert st["slow_fires"] > 0
        else:
            assert st["slow_fires"] == 0
        assert st["xla_flops"] and st["xla_flops"] > 0
        assert st["xla_arg_bytes"] and st["xla_arg_bytes"] > 0
        assert st["steady_steps"] > 0
        assert st["steady_dispatches"] == st["steady_steps"]
