"""Async checkpoint pipeline: fault-tolerance off the hot path.

The PR-2 crash-safety contract (atomic writes, manifest-committed-last,
latest() falls back over torn checkpoints) must hold bit-for-bit when
the write happens on the background writer thread — these tests re-run
the recovery scenarios with MXTPU_ASYNC_CKPT=1 and add the async-only
semantics: snapshot isolation from donated buffers, bounded-queue
backpressure, sticky error surfacing on the next step/save/flush,
retention racing in-flight writes, and the atomic_write retry-jitter
audit.
"""
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import checkpoint as ckpt
from mxnet_tpu import fault, telemetry
from mxnet_tpu.checkpoint import CheckpointManager

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _async_env(monkeypatch):
    """Async on for every test here; drain + clear sticky state between
    tests so one test's writer failure can't poison the next."""
    monkeypatch.setenv("MXTPU_ASYNC_CKPT", "1")
    fault.reset()
    yield
    fault.reset()
    ckpt.flush_async(raise_errors=False)
    ckpt._async_error = None


def _make_module(batch=16, n=64, dim=10):
    rs = np.random.RandomState(0)
    X = rs.randn(n, dim).astype(np.float32)
    Y = rs.randint(0, 2, n).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=batch)
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=8,
                              name="fc1"), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    return mod, list(it)


# -- core async semantics ----------------------------------------------------

@pytest.mark.fault
def test_async_save_roundtrips_and_latest_sees_it(tmp_path):
    mod, batches = _make_module()
    prefix = str(tmp_path / "ck")
    for b in batches:
        mod.fit_step(b)
    mod.save_checkpoint(prefix, 1, save_optimizer_states=True)
    ckpt.flush_async()
    mgr = CheckpointManager(prefix)
    assert mgr.latest() == 1
    epoch, args, _ = mgr.load()
    want = mod.get_params()[0]
    for name, arr in args.items():
        np.testing.assert_array_equal(arr.asnumpy(),
                                      want[name].asnumpy())


@pytest.mark.fault
def test_async_saves_in_the_loop_add_no_dispatch(tmp_path):
    """A checkpoint an epoch inside a fused fit loop, the write
    overlapping the steps that follow: the snapshot (host fetch + owned
    copies) and the enqueue dispatch no compiled program, so the loop
    stays at exactly 1.0 dispatch a step with 0 compiles."""
    from mxnet_tpu import profiler
    mod, batches = _make_module()
    for b in batches:
        mod.fit_step(b)
    profiler.reset_step_stats()
    for epoch in range(3):
        for b in batches:
            mod.fit_step(b)
        mod.save_checkpoint(str(tmp_path / "ck"), epoch,
                            save_optimizer_states=True)
    stats = profiler.step_stats()
    ckpt.flush_async()
    assert stats["dispatch_count"] == 3 * len(batches), stats
    assert stats["compile_count"] == 0, stats
    assert CheckpointManager(str(tmp_path / "ck")).latest() == 2


@pytest.mark.fault
def test_snapshot_isolated_from_donated_buffers(tmp_path):
    """The queued snapshot must hold the params AS OF the save, even
    though the next fused steps donate (delete/reuse) the live buffers
    while the write is still in flight."""
    mod, batches = _make_module()
    prefix = str(tmp_path / "ck")
    for b in batches:
        mod.fit_step(b)
    want = {k: v.asnumpy().copy()
            for k, v in mod.get_params()[0].items()}
    # slow the writer so the fused steps below run while the write of
    # THIS snapshot is still pending
    fault.configure("ckpt.write.stall:1")
    os.environ["MXTPU_FAULT_STALL_SECS"] = "0.4"
    try:
        mod.save_checkpoint(prefix, 1)
        for _ in range(3):  # donates the old param buffers repeatedly
            for b in batches:
                mod.fit_step(b)
        ckpt.flush_async()
    finally:
        os.environ.pop("MXTPU_FAULT_STALL_SECS", None)
    _, args, _ = CheckpointManager(prefix).load(1)
    for name, arr in args.items():
        np.testing.assert_array_equal(arr.asnumpy(), want[name])
    # and training genuinely moved on past the snapshot
    now = mod.get_params()[0]["fc1_weight"].asnumpy()
    assert np.abs(now - want["fc1_weight"]).max() > 0


@pytest.mark.fault
def test_save_returns_before_write_lands(tmp_path):
    """The step-boundary cost is snapshot+enqueue; the write itself
    (stalled here for 0.5 s) happens behind the caller's back."""
    mod, batches = _make_module()
    prefix = str(tmp_path / "ck")
    for b in batches:
        mod.fit_step(b)
    fault.configure("ckpt.write.stall:1")
    os.environ["MXTPU_FAULT_STALL_SECS"] = "0.5"
    try:
        t0 = time.perf_counter()
        mod.save_checkpoint(prefix, 1)
        enqueue = time.perf_counter() - t0
        assert enqueue < 0.3, \
            "async save blocked %.3fs — write ran inline?" % enqueue
        assert CheckpointManager(prefix).latest() == 1  # flushes first
    finally:
        os.environ.pop("MXTPU_FAULT_STALL_SECS", None)


@pytest.mark.fault
def test_backpressure_blocks_at_depth(tmp_path, monkeypatch):
    """Depth-1 queue + a stalled writer: the second save must block in
    ckpt.async_wait until the first write finishes — bounded memory, not
    an unbounded backlog."""
    monkeypatch.setenv("MXTPU_ASYNC_CKPT_DEPTH", "1")
    mod, batches = _make_module()
    prefix = str(tmp_path / "ck")
    for b in batches:
        mod.fit_step(b)
    fault.configure("ckpt.write.stall:1")
    os.environ["MXTPU_FAULT_STALL_SECS"] = "0.4"
    try:
        t0 = time.perf_counter()
        mod.save_checkpoint(prefix, 1)   # writer stalls 0.4s on this
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        mod.save_checkpoint(prefix, 2)   # must wait out the stall
        second = time.perf_counter() - t0
    finally:
        os.environ.pop("MXTPU_FAULT_STALL_SECS", None)
    assert first < 0.3, "first async save should only enqueue"
    assert second > 0.2, \
        "second save returned in %.3fs — backpressure did not block" \
        % second
    ckpt.flush_async()
    assert CheckpointManager(prefix).latest() == 2


# -- PR-2 recovery semantics under the async writer --------------------------

@pytest.mark.fault
def test_torn_async_write_sticky_error_and_fallback(tmp_path):
    """ckpt.write.torn fires on the WRITER thread: the torn file must be
    skipped by latest() exactly like the sync path, and the failure must
    surface (once) on the next flush/save/step."""
    mod, batches = _make_module()
    prefix = str(tmp_path / "ck")
    for b in batches:
        mod.fit_step(b)
    mod.save_checkpoint(prefix, 1)
    ckpt.flush_async()
    fault.configure("ckpt.write.torn:1")
    mod.save_checkpoint(prefix, 2)
    with pytest.raises(fault.FaultInjected):
        ckpt.flush_async()
    # surfaced once — recovery then proceeds normally
    assert CheckpointManager(prefix).latest() == 1
    mod.fit_step(batches[0])  # sticky already consumed: must not raise


@pytest.mark.fault
def test_async_writer_failure_surfaces_on_next_step(tmp_path):
    mod, batches = _make_module()
    prefix = str(tmp_path / "ck")
    for b in batches:
        mod.fit_step(b)
    fault.configure("ckpt.write.crash:1")
    mod.save_checkpoint(prefix, 1)
    ckpt.flush_async(raise_errors=False)  # error now sticky
    with pytest.raises(fault.FaultInjected):
        mod.fit_step(batches[0])
    # nothing was published for epoch 1 (crash before os.replace)
    assert CheckpointManager(prefix).latest() is None


@pytest.mark.fault
def test_transient_ioerror_retried_on_writer_thread(tmp_path):
    mod, batches = _make_module()
    prefix = str(tmp_path / "ck")
    for b in batches:
        mod.fit_step(b)
    fault.configure("ckpt.write.ioerror:2")
    mod.save_checkpoint(prefix, 1, save_optimizer_states=True)
    ckpt.flush_async()  # retries absorbed the injected errors
    assert CheckpointManager(prefix).latest() == 1


@pytest.mark.fault
def test_crash_mid_queue_latest_returns_last_complete(tmp_path):
    """Hard process death with a write still queued: recovery in a fresh
    process sees the last COMPLETE epoch (the satellite's scenario).
    The child sync-writes epoch 1, enqueues epoch 2 behind a stalled
    writer, then dies with os._exit — no atexit, no drain."""
    prefix = str(tmp_path / "ck")
    code = """
import os, sys
sys.path.insert(0, %r)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["MXTPU_ASYNC_CKPT"] = "1"
os.environ["MXTPU_FAULT"] = "ckpt.write.stall:1"
os.environ["MXTPU_FAULT_STALL_SECS"] = "30"
sys.argv = [sys.argv[0]]
from tests.test_async_ckpt import _make_module
mod, batches = _make_module()
for b in batches:
    mod.fit_step(b)
mod.save_checkpoint(%r, 1, mode="sync")
mod.save_checkpoint(%r, 2)   # queued; writer wedged on the stall site
os._exit(1)                  # crash mid-queue
""" % (REPO, prefix, prefix)
    r = subprocess.run(["timeout", "-k", "5", "120", sys.executable,
                        "-c", code], cwd=REPO, capture_output=True,
                       text=True)
    assert r.returncode == 1, r.stderr[-2000:]
    mgr = CheckpointManager(prefix)
    assert mgr.latest() == 1
    mgr.load(1)


@pytest.mark.fault
def test_retention_races_inflight_async_writes(tmp_path):
    """keep-last-N pruning runs on the writer thread interleaved with
    discovery polls from the main thread: latest() must only ever see
    None or a valid epoch, never raise, load() (newest) must always
    hand back SOME complete checkpoint, and the final state must be the
    newest N complete checkpoints.

    Root-caused flake (PR 7 note): this test used to call
    ``load(latest())`` — a non-atomic pair.  Between the two calls the
    writer thread would commit two more epochs and keep-last-2 would
    prune the epoch latest() had just returned, so the EXPLICIT-epoch
    load raised the documented "pruned or never written" error ~1/3 of
    runs.  ``load()`` with no epoch is the concurrent-recovery entry
    point and retries against a re-resolved latest()
    (test_load_latest_retries_when_retention_prunes_underfoot pins that
    window deterministically); the explicit-epoch behavior is pinned in
    the same test."""
    mod, batches = _make_module()
    prefix = str(tmp_path / "ck")
    for b in batches:
        mod.fit_step(b)
    stop = threading.Event()
    seen, errors = [], []

    def poll():
        mgr = CheckpointManager(prefix)
        while not stop.is_set():
            try:
                e = mgr.latest()
                if e is not None:
                    seen.append(e)
                    loaded_epoch, _, _ = mgr.load()
                    assert loaded_epoch >= e
            except Exception as exc:  # noqa: BLE001 — the assertion
                errors.append(exc)
                return
    t = threading.Thread(target=poll, daemon=True)
    t.start()
    try:
        for epoch in range(1, 8):
            mod.save_checkpoint(prefix, epoch, keep_last=2,
                                save_optimizer_states=True)
            for b in batches[:1]:
                mod.fit_step(b)
    finally:
        ckpt.flush_async()
        stop.set()
        t.join(timeout=10)
    assert not errors, errors
    mgr = CheckpointManager(prefix, keep_last=2)
    assert mgr.latest() == 7
    assert mgr.complete_epochs() == [6, 7]
    assert seen == sorted(seen), "latest() went backwards: %s" % seen


@pytest.mark.fault
def test_load_latest_retries_when_retention_prunes_underfoot(
        tmp_path, monkeypatch):
    """The exact interleaving behind the old flake, pinned
    deterministically: latest() resolves epoch E, the writer commits
    E+1/E+2 and keep-last-N prunes E before the files are read.  A
    stale-latest() load() must retry and hand back the NEW newest;
    an explicit load(E) must raise the documented recovery error; and
    a genuinely-corrupt stable newest must still raise, not loop."""
    mod, batches = _make_module()
    prefix = str(tmp_path / "ck")
    for b in batches:
        mod.fit_step(b)
    for epoch in (1, 2, 3):
        mod.save_checkpoint(prefix, epoch, keep_last=2,
                            save_optimizer_states=True)
    ckpt.flush_async()
    mgr = CheckpointManager(prefix)
    assert mgr.latest() == 3
    assert not os.path.exists(mgr.params_path(1))  # epoch 1 pruned

    # deterministic race window: the FIRST latest() inside load()
    # resolves the pruned epoch 1 (as if retention ran right after),
    # later calls see the truth
    real_latest = CheckpointManager.latest
    calls = []

    def stale_then_real(self):
        calls.append(1)
        return 1 if len(calls) == 1 else real_latest(self)
    monkeypatch.setattr(CheckpointManager, "latest", stale_then_real)
    epoch, args, _ = mgr.load()
    assert epoch == 3 and args
    assert len(calls) >= 2, "load() never re-resolved latest()"
    monkeypatch.setattr(CheckpointManager, "latest", real_latest)

    # the explicit-epoch pin keeps its documented contract
    from mxnet_tpu.base import MXNetError
    with pytest.raises(MXNetError, match="pruned or never written"):
        mgr.load(1)

    # a STABLE (non-advancing) failing target raises instead of
    # retrying forever: latest() pinned to the pruned epoch — the
    # "genuine corruption, nothing newer" shape
    monkeypatch.setattr(CheckpointManager, "latest", lambda self: 1)
    with pytest.raises(MXNetError):
        mgr.load()


@pytest.mark.fault
def test_fit_flushes_at_exit_and_epoch_checkpoints_land(tmp_path):
    rs = np.random.RandomState(0)
    X = rs.randn(64, 10).astype(np.float32)
    Y = rs.randint(0, 2, 64).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=16)
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=8,
                              name="fc1"), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    prefix = str(tmp_path / "ck")
    mod.fit(it, num_epoch=3, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1}, kvstore=None,
            epoch_end_callback=mx.callback.module_checkpoint(
                mod, prefix, save_optimizer_states=True))
    # no explicit flush: fit() drained the queue before returning
    assert ckpt._async_pending == 0
    assert CheckpointManager(prefix).latest() == 3


@pytest.mark.fault
def test_trainer_async_save_states_and_sticky_step(tmp_path):
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon import Trainer, nn

    net = nn.Dense(4, in_units=6)
    net.initialize()
    X = mx.nd.array(np.random.RandomState(0).randn(8, 6)
                    .astype(np.float32))
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.05, "momentum": 0.9},
                      kvstore=None)

    def step():
        with autograd.record():
            loss = (net(X) ** 2).mean()
        loss.backward()
        trainer.step(batch_size=8)

    step()
    path = str(tmp_path / "t.states")
    trainer.save_states(path)
    trainer.load_states(path)  # flushes, then validated read
    step()
    # a failed background states write surfaces on the next step()
    fault.configure("ckpt.write.crash:1")
    trainer.save_states(path)
    ckpt.flush_async(raise_errors=False)
    with pytest.raises(fault.FaultInjected):
        step()


# -- satellite: atomic_write retry audit -------------------------------------

@pytest.mark.fault
def test_retry_backoff_jittered_and_no_sleep_after_final(tmp_path,
                                                         monkeypatch):
    """Exhausting retries must raise WITHOUT a trailing sleep (pure
    latency on a failure the caller is about to see), and the sleeps
    that do happen must be jittered around the exponential schedule so
    restarting ranks don't hammer a sick disk in lockstep."""
    sleeps = []
    monkeypatch.setattr(ckpt.time, "sleep", sleeps.append)
    fault.configure("ckpt.write.ioerror:10")
    with pytest.raises(OSError):
        ckpt.atomic_write(str(tmp_path / "x.bin"), b"p", retries=3,
                          backoff=0.1)
    # 4 attempts -> 3 sleeps between them, none after the final raise
    assert len(sleeps) == 3, sleeps
    for i, s in enumerate(sleeps):
        base = 0.1 * (2 ** i)
        assert 0.5 * base <= s <= 1.5 * base, (i, s, sleeps)
    # jitter present: three consecutive sleeps exactly on the schedule
    # would mean the multiplier collapsed to 1.0
    assert any(abs(s - 0.1 * (2 ** i)) > 1e-6
               for i, s in enumerate(sleeps)), sleeps


# -- satellite: manifest-verification cache ----------------------------------

@pytest.mark.fault
def test_latest_caches_verification_between_calls(tmp_path, monkeypatch):
    mod, batches = _make_module()
    prefix = str(tmp_path / "ck")
    for epoch in (1, 2, 3):
        mod.save_checkpoint(prefix, epoch, save_optimizer_states=True)
    ckpt.flush_async()
    mgr = CheckpointManager(prefix)
    assert mgr.latest() == 3
    calls = []
    real = ckpt.hashlib.sha256
    monkeypatch.setattr(ckpt.hashlib, "sha256",
                        lambda *a: calls.append(1) or real(*a))
    # unchanged files: repeated discovery must not re-hash anything
    assert mgr.latest() == 3
    assert CheckpointManager(prefix).latest() == 3  # cache is shared
    assert not calls, "latest() re-hashed %d times" % len(calls)
    # rewriting an artifact invalidates exactly that epoch's entry
    p = mgr.params_path(3)
    with open(p, "rb") as f:
        blob = f.read()
    os.unlink(p)
    with open(p, "wb") as f:
        f.write(blob[: len(blob) // 2])
    assert mgr.latest() == 2
    assert calls, "rewrite did not force re-verification"


@pytest.mark.fault
def test_validate_cache_never_resurrects_torn_checkpoint(tmp_path):
    mod, batches = _make_module()
    prefix = str(tmp_path / "ck")
    mod.save_checkpoint(prefix, 1)
    ckpt.flush_async()
    mgr = CheckpointManager(prefix)
    assert mgr.latest() == 1
    p = mgr.params_path(1)
    with open(p, "r+b") as f:
        f.write(b"\xff" * 16)
    assert mgr.latest() is None      # cached sig changed -> re-hash
    assert mgr.latest() is None      # negative result cached, stable
