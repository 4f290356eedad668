"""Fault-tolerance off the hot path: what does it actually cost?

Two measurements backing PERF.md §6 (CPU micro-bench, same MLP fit
loop family as steptrace.py but sized so checkpoint serialization and
XLA compilation are non-trivial):

- **per-checkpoint step stall** — wall time the training loop spends
  blocked inside ``Module.save_checkpoint`` at a step boundary, sync
  (serialize + sha256 + fsync + rename inline) vs async (host snapshot
  + bounded enqueue; the write overlaps the following steps).  p50/p99
  over many checkpoints, with a few train steps between saves so the
  async writer drains the way it does in production.
- **time-to-first-step** — fresh subprocess from backend-ready to the
  first completed ``fit_step``: cold (empty cache: trace + XLA compile)
  vs warm (same cache dir: the fused step deserializes from the AOT
  executable cache — on CPU the donation-free twin, with the donated
  program compiled in the background and hot-swapped in; donation-free
  eager-op programs hit jax's persistent compile cache) — the restart
  path tools/launch.py sets up via ``--aot-cache-dir``.

Usage: JAX_PLATFORMS=cpu python tools/perf_probe/restart_probe.py
Prints one JSON object: {"stall": {...}, "ttfs": {...}}.
"""
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def build_module(batch=64, dim=256, hidden=512, classes=16, n_batches=4):
    """~0.4 M-param MLP: big enough that a checkpoint write and the
    fused-step compile are both worth measuring, small enough for CI —
    the steptrace fixture, one layer deeper and much wider."""
    import steptrace
    mod, train = steptrace.build_module(batch=batch, dim=dim,
                                        classes=classes, hidden=hidden,
                                        depth=3, n_batches=n_batches)
    return mod, list(train)


def _pct(sorted_vals, q):
    if not sorted_vals:
        return None
    i = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[i]


def measure_stalls(mode, n_ckpt=None):
    """Per-checkpoint stall for one mode ('sync'|'async'): the wall time
    ``save_checkpoint`` blocks the step loop, measured at a real step
    boundary with training steps between checkpoints."""
    from mxnet_tpu import checkpoint as ckpt

    n_ckpt = n_ckpt or int(os.environ.get("BENCH_RESTART_CKPTS", "15"))
    tmpdir = tempfile.mkdtemp(prefix="restart-probe-%s-" % mode)
    mod, batches = build_module()
    for _ in range(2):  # warm: trace + compile + allocator steady state
        for b in batches:
            mod.fit_step(b)
    prefix = os.path.join(tmpdir, "ck")
    prev = os.environ.get("MXTPU_ASYNC_CKPT")
    os.environ["MXTPU_ASYNC_CKPT"] = "1" if mode == "async" else "0"
    stalls = []
    try:
        for i in range(n_ckpt):
            for b in batches:  # the writer drains behind these steps
                mod.fit_step(b)
            t0 = time.perf_counter()
            mod.save_checkpoint(prefix, i + 1, save_optimizer_states=True,
                                keep_last=4)
            stalls.append(time.perf_counter() - t0)
        # drain OUTSIDE the timed region: flush cost is paid once at
        # epoch/run end, not per checkpoint — that is the design
        ckpt.flush_async()
    finally:
        if prev is None:
            os.environ.pop("MXTPU_ASYNC_CKPT", None)
        else:
            os.environ["MXTPU_ASYNC_CKPT"] = prev
        shutil.rmtree(tmpdir, ignore_errors=True)
    stalls.sort()
    return {
        "mode": mode, "checkpoints": n_ckpt,
        "p50_ms": round(_pct(stalls, 0.50) * 1e3, 3),
        "p99_ms": round(_pct(stalls, 0.99) * 1e3, 3),
        "mean_ms": round(sum(stalls) / len(stalls) * 1e3, 3),
        "max_ms": round(stalls[-1] * 1e3, 3),
    }


def _ttfs_child():
    """Internal --ttfs-child mode: one fresh process's restart cost.
    The clock starts AFTER backend init (``jax.devices()``) — interpreter
    and jax import time is identical cold or warm and is not what the
    AOT cache (or the watchdog's startup grace) is about."""
    import jax
    jax.devices()
    t0 = time.perf_counter()
    mod, batches = build_module()
    mod.fit_step(batches[0])
    ttfs = time.perf_counter() - t0
    from mxnet_tpu import aot_cache, profiler, telemetry
    # outside the timed region: background work (CPU twin serialization,
    # donated hot-swap compile) must land before this process exits or
    # the next attempt finds an empty cache
    aot_cache.drain(timeout=120)
    c = telemetry.report()["counters"]
    print(json.dumps({
        "ttfs_s": ttfs,
        "aot_hits": c.get("aot.cache_hits", 0),
        "aot_misses": c.get("aot.cache_misses", 0),
        "fit_step_compiles": profiler.step_stats()["compile_count"],
    }), flush=True)


def measure_ttfs():
    """Cold vs warm restart: two subprocesses sharing one cache dir —
    exactly what two launch.py restart attempts see."""
    cache = tempfile.mkdtemp(prefix="restart-probe-aot-")
    env = dict(os.environ)
    env.update({
        "MXTPU_AOT_CACHE_DIR": cache,
        "JAX_COMPILATION_CACHE_DIR": os.path.join(cache, "xla"),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_PLATFORMS": "cpu",
    })
    out = {}
    try:
        for label in ("cold", "warm"):
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--ttfs-child"],
                env=env, capture_output=True, text=True, timeout=600)
            if r.returncode != 0:
                raise RuntimeError("ttfs child (%s) failed rc=%d:\n%s"
                                   % (label, r.returncode,
                                      r.stderr[-2000:]))
            child = json.loads(r.stdout.strip().splitlines()[-1])
            out[label] = child
    finally:
        shutil.rmtree(cache, ignore_errors=True)
    return {
        "cold_s": round(out["cold"]["ttfs_s"], 3),
        "warm_s": round(out["warm"]["ttfs_s"], 3),
        "speedup": round(out["cold"]["ttfs_s"] / out["warm"]["ttfs_s"], 2),
        "warm_aot_hits": out["warm"]["aot_hits"],
        "warm_fit_step_compiles": out["warm"]["fit_step_compiles"],
        "cold_fit_step_compiles": out["cold"]["fit_step_compiles"],
    }


def run():
    sync = measure_stalls("sync")
    async_ = measure_stalls("async")
    ttfs = measure_ttfs()
    return {
        "stall": {
            "sync": sync, "async": async_,
            "ratio_p50": round(sync["p50_ms"] / async_["p50_ms"], 2),
            "ratio_p99": round(sync["p99_ms"] / async_["p99_ms"], 2),
        },
        "ttfs": ttfs,
    }


if __name__ == "__main__":
    if "--ttfs-child" in sys.argv:
        _ttfs_child()
    else:
        print(json.dumps(run()))
