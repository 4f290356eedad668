"""Elastic job control: evict a permanently failing rank, resume at
N-1, re-admit it at N (ROBUSTNESS.md §9).

Fast layers: the shard-partition laws (every sample exactly once at ANY
world size), membership env accounting, the launcher's
evict/re-rank/readmit policy driven by env-dump workers (no jax import
in the workers — pure process orchestration), the membership.json
journal + its renderer, and the worker.lost fault site's hard exit 77.
The slow end-to-end run trains a real model through kill→N-1→rejoin→N
with checkpoint resume and coverage/loss assertions.

Every spawned process is wrapped in a ``timeout -k`` guard (the hang
suite's rule): a policy regression surfaces as a failed assertion,
never a wedged suite.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAUNCH = os.path.join(REPO, "tools", "launch.py")
REPORT = os.path.join(REPO, "tools", "perf_probe", "telemetry_report.py")


def _run(argv, timeout_s=120, env=None, **kw):
    """subprocess.run under an external ``timeout -k`` guard."""
    full = ["timeout", "-k", "10", str(timeout_s)] + argv
    return subprocess.run(full, capture_output=True, text=True,
                          timeout=timeout_s + 30, env=env, **kw)


# -- shard partition laws ----------------------------------------------------

@pytest.mark.elastic
def test_shard_partition_covers_every_sample_once_any_world():
    from mxnet_tpu import elastic
    for n in (1, 7, 60, 61):
        for world in (1, 2, 3, 5, 8):
            shards = [elastic.shard_for_epoch(n, 4, r, world)
                      for r in range(world)]
            got = np.concatenate(shards)
            assert sorted(got.tolist()) == list(range(n)), (n, world)
            sizes = [len(s) for s in shards]
            assert max(sizes) - min(sizes) <= 1


@pytest.mark.elastic
def test_shard_permutation_independent_of_world_size():
    """The epoch order is ONE permutation; world size only cuts it.  A
    mid-epoch reshard therefore replays the same global order."""
    from mxnet_tpu import elastic
    full = [np.concatenate([elastic.shard_for_epoch(60, 2, r, w)
                            for r in range(w)])
            for w in (1, 2, 3, 4)]
    for other in full[1:]:
        np.testing.assert_array_equal(full[0], other)


@pytest.mark.elastic
def test_shard_epoch_seeded_and_reproducible():
    from mxnet_tpu import elastic
    a = elastic.shard_for_epoch(40, 1, 0, 2, seed=0)
    b = elastic.shard_for_epoch(40, 2, 0, 2, seed=0)
    assert not np.array_equal(a, b)  # epochs reshuffle
    np.testing.assert_array_equal(
        a, elastic.shard_for_epoch(40, 1, 0, 2, seed=0))  # replays exact
    c = elastic.shard_for_epoch(40, 1, 0, 2, seed=7)
    assert not np.array_equal(a, c)  # seed matters


@pytest.mark.elastic
def test_shard_validates_rank_and_world():
    from mxnet_tpu import elastic
    with pytest.raises(ValueError):
        elastic.shard_for_epoch(10, 0, 2, 2)
    with pytest.raises(ValueError):
        elastic.shard_for_epoch(10, 0, 0, 0)


# -- membership accounting ---------------------------------------------------

@pytest.fixture
def _reset_elastic(monkeypatch):
    """Isolate the module-level transition counters per test."""
    from mxnet_tpu import elastic
    monkeypatch.setattr(elastic, "_last_world", None)
    monkeypatch.setattr(elastic, "_transitions", 0)
    for var in ("MXTPU_NUM_WORKERS", "MXTPU_WORKER_RANK",
                "MXTPU_WORKER_SLOT", "MXTPU_RESTART_ATTEMPT",
                "MXTPU_PREV_WORLD_SIZE", "MXTPU_COORDINATOR"):
        monkeypatch.delenv(var, raising=False)
    return elastic


@pytest.mark.elastic
def test_membership_reads_env_contract(_reset_elastic, monkeypatch):
    elastic = _reset_elastic
    mem = elastic.membership()
    assert mem["world_size"] == 1 and mem["rank"] == 0
    assert mem["slot"] == 0 and mem["prev_world_size"] is None
    monkeypatch.setenv("MXTPU_NUM_WORKERS", "3")
    monkeypatch.setenv("MXTPU_WORKER_RANK", "1")
    monkeypatch.setenv("MXTPU_WORKER_SLOT", "2")
    monkeypatch.setenv("MXTPU_RESTART_ATTEMPT", "4")
    monkeypatch.setenv("MXTPU_PREV_WORLD_SIZE", "4")
    mem = elastic.membership()
    assert mem == {"world_size": 3, "rank": 1, "slot": 2, "attempt": 4,
                   "prev_world_size": 4, "coordinator": None}


@pytest.mark.elastic
def test_note_membership_counts_cross_attempt_transition(
        _reset_elastic, monkeypatch):
    """A restarted worker (fresh process) learns the previous attempt's
    world from MXTPU_PREV_WORLD_SIZE: its FIRST observation already
    counts the reshard."""
    elastic = _reset_elastic
    monkeypatch.setenv("MXTPU_NUM_WORKERS", "2")
    monkeypatch.setenv("MXTPU_PREV_WORLD_SIZE", "3")
    assert elastic.note_membership() is True
    assert elastic.transitions() == 1
    assert elastic.note_membership() is False  # same world: no change
    assert elastic.note_membership(3) is True  # in-process change
    assert elastic.transitions() == 2
    snap = elastic.snapshot()
    assert snap["transitions"] == 2 and snap["last_noted_world_size"] == 3
    from mxnet_tpu import telemetry
    assert telemetry.gauge("elastic.world_size").value == 3


@pytest.mark.elastic
def test_postmortem_carries_membership_block(_reset_elastic, monkeypatch,
                                             tmp_path):
    elastic = _reset_elastic
    monkeypatch.setenv("MXTPU_NUM_WORKERS", "2")
    monkeypatch.setenv("MXTPU_WORKER_RANK", "1")
    monkeypatch.setenv("MXTPU_WORKER_SLOT", "2")
    elastic.note_membership()
    from mxnet_tpu import telemetry
    path = str(tmp_path / "pm.json")
    telemetry.dump_postmortem("elastic test", path=path)
    doc = json.load(open(path))
    mem = doc["membership"]
    assert mem["world_size"] == 2 and mem["rank"] == 1 and mem["slot"] == 2
    # ...and the renderer surfaces it
    r = _run([sys.executable, REPORT, path])
    assert r.returncode == 0
    assert "membership: world_size=2 rank=1 slot=2" in r.stdout


# -- exit-code contract ------------------------------------------------------

@pytest.mark.elastic
def test_worker_lost_exit_code_contract():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import launch
    from mxnet_tpu import fault
    assert fault.EXIT_WORKER_LOST == launch.WORKER_LOST_EXIT == 77
    kind, reason = launch.classify_exit(77)
    assert kind == "retryable" and "worker lost" in reason


# -- launcher elastic policy (env-dump workers, no jax) ----------------------

ENV_DUMP_WORKER = """
import json, os, sys
out = sys.argv[1]
slot = os.environ["MXTPU_WORKER_SLOT"]
attempt = int(os.environ["MXTPU_RESTART_ATTEMPT"])
rec = {k: os.environ.get(k) for k in
       ("MXTPU_NUM_WORKERS", "MXTPU_WORKER_RANK", "MXTPU_WORKER_SLOT",
        "MXTPU_RESTART_ATTEMPT", "MXTPU_PREV_WORLD_SIZE",
        "DMLC_NUM_WORKER", "DMLC_WORKER_ID")}
with open(os.path.join(out, "env-a%%d-s%%s.json" %% (attempt, slot)),
          "w") as f:
    json.dump(rec, f)
%(failure_rule)s
"""


def _launch_elastic(tmp_path, failure_rule, extra_args, timeout_s=120):
    script = tmp_path / "worker.py"
    script.write_text(ENV_DUMP_WORKER % {"failure_rule": failure_rule})
    run_dir = tmp_path / "run"
    r = _run([sys.executable, LAUNCH, "-n", "3", "--elastic",
              "--max-restarts", "5", "--restart-backoff", "0.01",
              "--run-dir", str(run_dir)] + extra_args +
             ["--", sys.executable, str(script), str(tmp_path)],
             timeout_s=timeout_s)
    membership = {}
    mpath = run_dir / "membership.json"
    if mpath.exists():
        membership = json.loads(mpath.read_text())
    return r, membership


def _envs(tmp_path, attempt):
    out = {}
    for p in tmp_path.glob("env-a%d-s*.json" % attempt):
        rec = json.loads(p.read_text())
        out[int(rec["MXTPU_WORKER_SLOT"])] = rec
    return out


@pytest.mark.elastic
def test_evict_reranks_survivors_contiguously(tmp_path):
    """Slot 1 fails once under --evict-after 1: the next attempt runs at
    world 2 with survivors re-packed into ranks 0,1 (slot 2 -> rank 1)
    and the DMLC_* compat env re-exported to match — the launcher
    logging fix's fast re-ranking assertion."""
    r, mem = _launch_elastic(
        tmp_path, "if slot == '1' and attempt == 0: sys.exit(1)",
        ["--evict-after", "1"])
    assert r.returncode == 0, r.stderr[-2000:]
    a1 = _envs(tmp_path, 1)
    assert sorted(a1) == [0, 2]  # slot 1 evicted
    assert a1[0]["MXTPU_WORKER_RANK"] == "0"
    assert a1[2]["MXTPU_WORKER_RANK"] == "1"  # contiguous re-rank
    for rec in a1.values():
        assert rec["MXTPU_NUM_WORKERS"] == "2"
        assert rec["DMLC_NUM_WORKER"] == "2"
        assert rec["DMLC_WORKER_ID"] == rec["MXTPU_WORKER_RANK"]
        assert rec["MXTPU_PREV_WORLD_SIZE"] == "3"
    # the restart log names attempt, world sizes, and evicted slots
    assert "attempt 0 (world size 3): worker rank 1 (slot 1)" in r.stderr
    assert "evicting worker slot 1" in r.stderr
    assert "world size 3 -> 2" in r.stderr
    # journal: evict transition recorded with the reason
    events = [(t["event"], t.get("slot")) for t in mem["transitions"]]
    assert ("evict", 1) in events
    assert mem["transitions"][-1]["event"] == "complete"
    assert mem["transitions"][-1]["world_size"] == 2


@pytest.mark.elastic
def test_evicted_slot_readmitted_after_sitout(tmp_path):
    """The full 3 -> 2 -> 3 membership arc: slot 1 fails twice
    (--evict-after 2) and is evicted; while it sits out, slot 0 fails
    once (streak 1: NOT evicted); slot 1 rejoins on the next attempt and
    the job completes at full size."""
    rule = ("if slot == '1' and attempt <= 1: sys.exit(1)\n"
            "if slot == '0' and attempt == 2: sys.exit(1)")
    r, mem = _launch_elastic(tmp_path, rule, ["--evict-after", "2",
                                              "--readmit-after", "1"])
    assert r.returncode == 0, r.stderr[-2000:]
    events = [(t["event"], t.get("slot")) for t in mem["transitions"]]
    assert ("evict", 1) in events and ("readmit", 1) in events
    assert events.index(("evict", 1)) < events.index(("readmit", 1))
    # attempt 2 ran shrunk, the final attempt back at full size
    a2, a3 = _envs(tmp_path, 2), _envs(tmp_path, 3)
    assert sorted(a2) == [0, 2] and sorted(a3) == [0, 1, 2]
    assert all(rec["MXTPU_NUM_WORKERS"] == "3" for rec in a3.values())
    assert [a3[s]["MXTPU_WORKER_RANK"] for s in (0, 1, 2)] == \
        ["0", "1", "2"]
    assert "re-admitting recovered worker slot 1" in r.stderr
    last = mem["transitions"][-1]
    assert last["event"] == "complete" and last["world_size"] == 3
    # renderer digests the journal
    rr = _run([sys.executable, REPORT,
               str(tmp_path / "run" / "membership.json")])
    assert rr.returncode == 0
    assert "MEMBERSHIP" in rr.stdout and "evict" in rr.stdout \
        and "readmit" in rr.stdout


@pytest.mark.elastic
def test_min_workers_floor_blocks_eviction(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(ENV_DUMP_WORKER % {
        "failure_rule": "if slot == '1': sys.exit(1)"})
    r = _run([sys.executable, LAUNCH, "-n", "2", "--elastic",
              "--evict-after", "1", "--min-workers", "2",
              "--max-restarts", "2", "--restart-backoff", "0.01",
              "--run-dir", str(tmp_path / "run"),
              "--", sys.executable, str(script), str(tmp_path)])
    assert r.returncode == 1  # retries exhausted, never shrank
    assert "NOT evicting slot 1" in r.stderr
    mem = json.loads((tmp_path / "run" / "membership.json").read_text())
    assert all(t["event"] != "evict" for t in mem["transitions"])
    assert all(t["world_size"] == 2 for t in mem["transitions"])


@pytest.mark.elastic
def test_permanent_exit_after_first_attempt_evicts(tmp_path):
    """Once the job has proven it can run (attempt >= 1), elastic mode
    converts a single-rank permanent failure (exit 2 — e.g. the host's
    interpreter/deps went bad) into an eviction instead of killing the
    job."""
    rule = ("if slot == '2' and attempt == 0: sys.exit(1)\n"
            "if slot == '2' and attempt == 1: sys.exit(2)")
    r, mem = _launch_elastic(tmp_path, rule, ["--evict-after", "99"])
    assert r.returncode == 0, r.stderr[-2000:]
    events = [(t["event"], t.get("slot")) for t in mem["transitions"]]
    assert ("evict", 2) in events
    assert "exit classified permanent" in r.stderr
    assert sorted(_envs(tmp_path, 2)) == [0, 1]


@pytest.mark.elastic
def test_permanent_exit_on_first_attempt_fails_fast(tmp_path):
    """A permanent exit on attempt 0 (a usage/import error hits every
    rank identically) must stop the job like the pre-elastic contract —
    NOT evict healthy slots one per attempt until the budget burns.
    --evict-after 1 pins the regression where the streak branch (streak
    1 >= 1) would evict what the permanent branch correctly refused."""
    for evict_after in ("1", "99"):
        sub = tmp_path / ("ea%s" % evict_after)
        sub.mkdir()
        r, mem = _launch_elastic(sub, "sys.exit(2)",
                                 ["--evict-after", evict_after])
        assert r.returncode == 2, (evict_after, r.stderr[-1500:])
        assert "not restarting" in r.stderr
        assert all(t["event"] != "evict" for t in mem["transitions"])
        assert not list(sub.glob("env-a1-*.json"))  # no attempt 1


@pytest.mark.elastic
def test_non_elastic_behavior_unchanged(tmp_path):
    """Without --elastic a permanent exit still stops the job with the
    budget preserved — the pre-elastic contract."""
    r = _run([sys.executable, LAUNCH, "-n", "1", "--max-restarts", "3",
              "--restart-backoff", "0.01", "--",
              sys.executable, "-c", "import sys; sys.exit(2)"])
    assert r.returncode == 2
    assert "classified permanent" in r.stderr
    assert "restarting job" not in r.stderr


# -- worker.lost fault site --------------------------------------------------

LOST_WORKER = """
import sys
sys.path.insert(0, %(repo)r)
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import fault

rs = np.random.RandomState(0)
it = mx.io.NDArrayIter(rs.randn(20, 6).astype(np.float32),
                       rs.randint(0, 2, 20).astype(np.float32),
                       batch_size=5)
net = mx.sym.SoftmaxOutput(
    mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                          name="fc"), name="softmax")
mod = mx.mod.Module(net, context=mx.cpu())
fault.configure("worker.lost:1")
mod.fit(it, num_epoch=1, kvstore=None, optimizer="sgd")
print("UNREACHABLE: fit survived an armed worker.lost")
"""


@pytest.mark.elastic
@pytest.mark.fault
def test_worker_lost_site_hard_exits_77(tmp_path):
    """The fit loop's worker.lost site is a hard os._exit(77): no
    exception, no postmortem, the documented retryable code."""
    script = tmp_path / "lost.py"
    script.write_text(LOST_WORKER % {"repo": REPO})
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXTPU_POSTMORTEM_DIR"] = str(tmp_path)  # must stay empty: hard
    r = _run([sys.executable, str(script)], timeout_s=180, env=env)
    assert r.returncode == 77, (r.stdout[-1000:], r.stderr[-1000:])
    assert "worker.lost" in r.stderr
    assert "UNREACHABLE" not in r.stdout
    assert not list(tmp_path.glob("postmortem-*.json"))


# -- slow end-to-end: kill a rank -> resume at N-1 -> rejoin at N ------------

TRAIN_WORKER = """
import json, os, sys
sys.path.insert(0, %(repo)r)
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import elastic, fault, profiler
from mxnet_tpu.checkpoint import CheckpointManager

OUT = sys.argv[1]
N, DIM, BATCH, EPOCHS = 60, 8, 5, 6
mem = elastic.membership()
rank, world = mem["rank"], mem["world_size"]
slot, attempt = mem["slot"], mem["attempt"]

rs = np.random.RandomState(0)
X = rs.randn(N, DIM).astype(np.float32)
w_true = rs.randn(DIM).astype(np.float32)
Y = (X @ w_true > 0).astype(np.float32)

net = mx.sym.SoftmaxOutput(
    mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                          name="fc"), name="softmax")
mod = mx.mod.Module(net, context=mx.cpu())

prefix = os.path.join(OUT, "ckpt", "model")
os.makedirs(os.path.dirname(prefix), exist_ok=True)
mgr = CheckpointManager(prefix)
resume = mgr.latest()
args_ = auxs_ = None
start_epoch = 0
if resume is not None:
    # world-size-agnostic: the manifest may have been written at any
    # world size; params are replicated, only the data reshard differs
    _, args_, auxs_ = mgr.load(resume)
    start_epoch = resume
    info = mgr.manifest_info(resume) or {}
    with open(os.path.join(OUT, "resume-a%%d-r%%d.json"
                           %% (attempt, rank)), "w") as f:
        json.dump({"epoch": resume,
                   "ckpt_world": info.get("world_size"),
                   "world": world}, f)


def full_loss():
    w = mod.get_params()[0]
    logits = X @ w["fc_weight"].asnumpy().T + w["fc_bias"].asnumpy()
    logits -= logits.max(axis=1, keepdims=True)
    p = np.exp(logits)
    p /= p.sum(axis=1, keepdims=True)
    return float(-np.mean(np.log(p[np.arange(N), Y.astype(int)] + 1e-9)))


def barrier(name):
    # coordination-service barrier (works on the CPU backend, which has
    # no cross-process collectives): keeps ranks in epoch lockstep so a
    # mid-run death deterministically interrupts the SAME epoch on every
    # rank.  A dead peer blocks the survivors here until the launcher's
    # teardown reaps them — exactly the production strand.
    try:
        from jax._src.distributed import global_state
        client = global_state.client
    except Exception:
        client = None
    if client is not None:
        client.wait_at_barrier("%%s-a%%d" %% (name, attempt), 60000)


WARM_STEPS = None
for epoch in range(start_epoch, EPOCHS):
    idx = elastic.shard_for_epoch(N, epoch, rank, world)
    it = mx.io.NDArrayIter(X[idx], Y[idx], batch_size=BATCH,
                           shuffle=False)
    # deterministic mid-run deaths driving the 3 -> 2 -> 3 arc: slot 1
    # dies in attempts 0/1 (evicted at --evict-after 2), slot 0 dies
    # once at the shrunken world (streak 1: not evicted) so the rejoin
    # attempt actually happens
    if slot == 1 and attempt <= 1 and epoch == 2:
        fault.configure("worker.lost:1")
    if slot == 0 and attempt == 2 and epoch == 3:
        fault.configure("worker.lost:1")
    mod.fit(it, num_epoch=epoch + 1, begin_epoch=epoch, kvstore=None,
            optimizer="sgd", optimizer_params={"learning_rate": 0.3},
            arg_params=args_, aux_params=auxs_,
            initializer=mx.init.Xavier())
    if WARM_STEPS is None:
        # warmup boundary: everything after the first epoch is steady
        # state — the 1.0-dispatch/0-recompile contract must hold there
        # even across the elastic world-size change
        s0 = profiler.step_stats()
        WARM_STEPS = (s0["steps"], s0["dispatch_count"],
                      s0["compile_count"])
        # join the background AOT store now so even an attempt killed
        # moments later leaves its executable behind for the next
        # attempt's warm start (an epoch here is milliseconds; a real
        # job's attempt outlives the store by hours)
        from mxnet_tpu import aot_cache
        aot_cache.drain(timeout=120)
    with open(os.path.join(OUT, "cov-a%%d-e%%d-r%%d.json"
                           %% (attempt, epoch, rank)), "w") as f:
        json.dump({"slot": slot, "world": world,
                   "idx": sorted(int(i) for i in idx),
                   "loss": full_loss()}, f)
    # barrier BEFORE the save: the checkpoint for epoch E commits only
    # once every rank finished E, so a death at epoch E+1 resumes all
    # survivors at E — no rank's progress outruns the cohort's
    barrier("epoch-%%d" %% epoch)
    if rank == 0:
        mod.save_checkpoint(prefix, epoch + 1)

st = profiler.step_stats()
from mxnet_tpu import aot_cache, telemetry
with open(os.path.join(OUT, "stats-a%%d-r%%d.json"
                       %% (attempt, rank)), "w") as f:
    json.dump({"world": world, "slot": slot, "steps": st["steps"],
               "dispatches": st["dispatch_count"],
               "compiles": st["compile_count"],
               "aot_enabled": aot_cache.enabled(),
               "aot_dir": aot_cache.cache_dir(),
               "aot_hits": telemetry.counter("aot.cache_hits").value,
               "aot_misses": telemetry.counter("aot.cache_misses").value,
               "aot_errors": telemetry.counter("aot.cache_errors").value,
               "steady_steps": st["steps"] - WARM_STEPS[0],
               "steady_dispatches": st["dispatch_count"] - WARM_STEPS[1],
               "steady_compiles": st["compile_count"] - WARM_STEPS[2]},
              f)
"""


@pytest.mark.slow
@pytest.mark.elastic
def test_e2e_worker_loss_resumes_n_minus_1_then_rejoins(tmp_path):
    """The §9 runbook end-to-end: a 3-worker job loses rank 1 twice
    (worker.lost, hard exit 77) and evicts it; the 2-worker attempts
    resume from the newest complete checkpoint with the epoch re-
    partitioned 2 ways (every sample exactly once); the slot rejoins and
    the job finishes at world 3 with loss decreased and 1.0
    dispatch/step on the warm-restarted attempts."""
    script = tmp_path / "train.py"
    script.write_text(TRAIN_WORKER % {"repo": REPO})
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    run_dir = tmp_path / "run"
    r = _run([sys.executable, LAUNCH, "-n", "3", "--elastic",
              "--cpu-fake-devices", "--evict-after", "2",
              "--readmit-after", "1", "--max-restarts", "5",
              "--restart-backoff", "0.01", "--run-dir", str(run_dir),
              "--", sys.executable, str(script), str(tmp_path)],
             timeout_s=540)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])

    mem = json.loads((run_dir / "membership.json").read_text())
    events = [(t["event"], t.get("slot")) for t in mem["transitions"]]
    assert ("evict", 1) in events and ("readmit", 1) in events
    last = mem["transitions"][-1]
    assert last["event"] == "complete" and last["world_size"] == 3

    def cov(attempt, epoch):
        recs = {}
        for p in tmp_path.glob("cov-a%d-e%d-r*.json" % (attempt, epoch)):
            rank = int(p.stem.rsplit("-r", 1)[1])
            recs[rank] = json.loads(p.read_text())
        return recs

    # attempt 2 ran at world 2: the resumed epoch's shards cover every
    # sample exactly once across the two survivors (the reshard law)
    shrunk = cov(2, 2)
    assert len(shrunk) == 2
    assert all(rec["world"] == 2 for rec in shrunk.values())
    seen = sorted(i for rec in shrunk.values() for i in rec["idx"])
    assert seen == list(range(60))

    # the final attempt ran at world 3 and finished every epoch it
    # owned, each with exact single coverage
    final_epochs = sorted(
        int(p.stem.split("-e")[1].split("-r")[0])
        for p in tmp_path.glob("cov-a3-e*-r0.json"))
    assert final_epochs and final_epochs[-1] == 5
    for epoch in final_epochs:
        recs = cov(3, epoch)
        assert len(recs) == 3
        seen = sorted(i for rec in recs.values() for i in rec["idx"])
        assert seen == list(range(60))

    # a shrunken attempt resumed from a checkpoint written at world 3
    resumes = [json.loads(p.read_text())
               for p in tmp_path.glob("resume-a2-r*.json")]
    assert resumes and all(rec["ckpt_world"] == 3 for rec in resumes)
    assert all(rec["world"] == 2 for rec in resumes)

    # loss still decreasing across the whole membership arc
    first = json.loads((tmp_path / "cov-a0-e0-r0.json").read_text())
    last_cov = json.loads(
        (tmp_path / ("cov-a3-e%d-r0.json" % final_epochs[-1]))
        .read_text())
    assert last_cov["loss"] < first["loss"], (first["loss"],
                                              last_cov["loss"])

    # fused-step contract holds across the elastic restarts: on every
    # rank of the final attempt the post-warmup steady state is exactly
    # one dispatch per step with zero recompiles (the steptrace
    # contract), and the restart warm-started from the AOT executable
    # cache across the world-size change (per-replica shapes unchanged,
    # so the cache hits)
    stats = [json.loads(p.read_text())
             for p in tmp_path.glob("stats-a3-r*.json")]
    assert len(stats) == 3
    for st in stats:
        assert st["steady_steps"] > 0, st
        assert st["steady_dispatches"] == st["steady_steps"], st
        assert st["steady_compiles"] == 0, st
        assert st["aot_hits"] >= 1, st
