"""Profiler (mx.profiler): chrome://tracing dump + jax.profiler bridge.

Port of /root/reference/python/mxnet/profiler.py (:27-55) over the
reference's engine profiler (src/engine/profiler.{h,cc}: OprExecStat per
engine op, DumpProfile writes chrome tracing JSON).  TPU-native shape:

- step-level events are recorded by the Executor around each compiled
  program invocation (forward/backward/fused step) — the XLA analogue of
  the engine's per-op blocks, since ops fuse into one program;
- ``profiler_set_config(filename=...)`` + ``dump_profile()`` write the
  same chrome://tracing JSON format (load in chrome://tracing or perfetto);
- for intra-program (per-fusion/per-op) detail, ``profiler_set_state`` can
  also drive ``jax.profiler`` traces into ``<filename>.jaxtrace/`` —
  viewable in TensorBoard/XProf (set ``use_jax_profiler=True``).

Env autostart: MXNET_PROFILER_AUTOSTART=1 (reference env_var.md:101-108).
"""
from __future__ import annotations

import functools
import json
import os
import threading
import time

__all__ = ["profiler_set_config", "profiler_set_state", "dump_profile",
           "State", "set_config", "set_state", "pause", "resume",
           "count_dispatch", "count_compile", "note_step",
           "note_skipped_step", "step_stats", "reset_step_stats",
           "instrument"]

_lock = threading.Lock()
_state = "stop"
_mode = "symbolic"
_filename = "profile.json"
_use_jax = False
_events = []
_t0_us = None
_paused = False


class State:
    stop = "stop"
    run = "run"


def profiler_set_config(mode="symbolic", filename="profile.json",
                        use_jax_profiler=False):
    """Configure the profiler (reference profiler.py:27).

    mode: 'symbolic' (executor-level events) or 'all' (also imperative op
    calls; identical here since both run compiled programs)."""
    global _mode, _filename, _use_jax
    with _lock:
        _mode = mode
        _filename = filename
        _use_jax = use_jax_profiler


def profiler_set_state(state="stop"):
    """Start ('run') or stop ('stop') collecting (reference :43).

    jax is only imported when the jax-profiler bridge is actually
    requested (use_jax_profiler), so MXNET_PROFILER_AUTOSTART=1 at
    import time cannot drag in (or crash on) a backend."""
    global _state, _t0_us
    with _lock:
        if state == _state:
            return
        if state == "run":
            _events.clear()
            _t0_us = time.perf_counter_ns() // 1000
            if _use_jax:
                import jax
                logdir = _filename + ".jaxtrace"
                os.makedirs(logdir, exist_ok=True)
                try:
                    jax.profiler.start_trace(logdir)
                except RuntimeError:
                    pass
        elif state == "stop":
            if _use_jax:
                import jax
                try:
                    jax.profiler.stop_trace()
                except RuntimeError:
                    pass
        else:
            raise ValueError("state must be 'run' or 'stop'")
        _state = state


def pause():
    """Temporarily skip recording (reference profiler.py:pause)."""
    global _paused
    _paused = True


def resume():
    global _paused
    _paused = False


def is_running():
    return _state == "run" and not _paused


def record_event(name, start_us, dur_us, cat="operator", tid=None,
                 args=None):
    """Append one duration event (called by the Executor hot path and
    telemetry spans only when is_running()).  Appends under ``_lock``:
    dump_profile/profiler_set_state read/clear the buffer under the same
    lock, and spans record from prefetch worker threads too — an
    unlocked append could race a concurrent clear."""
    if not is_running():
        return
    ev = {
        "name": name, "cat": cat, "ph": "X",
        "ts": start_us - (_t0_us or 0), "dur": dur_us,
        "pid": os.getpid(),
        "tid": tid if tid is not None else threading.get_ident() & 0xffff,
    }
    if args is not None:
        ev["args"] = args
    with _lock:
        _events.append(ev)


class _timed(object):
    """Context manager the Executor wraps compiled calls in; forces device
    sync at exit so durations are real (only while profiling)."""

    def __init__(self, name, sync_arrays=()):
        self.name = name
        self.sync_arrays = sync_arrays

    def __enter__(self):
        self.active = is_running()
        if self.active:
            self.start = time.perf_counter_ns() // 1000
        return self

    def __exit__(self, *exc):
        if self.active:
            for a in self.sync_arrays:
                try:
                    a.block_until_ready()
                except Exception:
                    pass
            end = time.perf_counter_ns() // 1000
            record_event(self.name, self.start, end - self.start)
        return False


# -- step instrumentation (always on; a few integer adds per batch) --------
#
# The reference engine could count pushed ops per step; under XLA the
# equivalent health metric is "how many compiled programs did this batch
# dispatch, and did any of them recompile".  The fused fit path targets
# exactly ONE dispatch per steady-state step (vs N params + 1 today), and
# these counters are how tests/test_fused_step.py and
# tools/perf_probe/steptrace.py prove it.
_step_lock = threading.Lock()
_dispatch_count = 0
_compile_count = 0
_step_count = 0
_skipped_step_count = 0
_step_ema_s = None
_last_step_t = None
_EMA_ALPHA = 0.1


def count_dispatch(n=1):
    """Record n compiled-program dispatches (XLA executions).  Called by
    the Executor around every jitted invocation and by imperative_invoke
    for each eager op — so (dispatches per step) is comparable between the
    fused and unfused train paths.  Lock-free on purpose: this sits on the
    per-op hot path, and a GIL-raced increment merely miscounts telemetry
    under concurrent eager threads."""
    global _dispatch_count
    _dispatch_count += n


def count_compile(n=1):
    """Record n XLA compilations (first execution of a (program, shape)
    key).  Steady state should add zero."""
    global _compile_count
    _compile_count += n


def note_step():
    """Mark a train-step boundary; maintains an EMA of inter-step wall
    time.  The first call only arms the clock."""
    global _step_count, _step_ema_s, _last_step_t
    now = time.perf_counter()
    with _step_lock:
        if _last_step_t is not None:
            dt = now - _last_step_t
            _step_ema_s = dt if _step_ema_s is None else \
                (1 - _EMA_ALPHA) * _step_ema_s + _EMA_ALPHA * dt
            _step_count += 1
        _last_step_t = now


def note_skipped_step():
    """Record one divergence-guard skip: the fused step ran (and counted
    its dispatch) but the all-finite check vetoed the parameter update.
    A healthy run keeps this at 0; a rising count with training still
    progressing means occasional bad batches are being absorbed."""
    global _skipped_step_count
    with _step_lock:
        _skipped_step_count += 1


def step_stats():
    """Snapshot {dispatch_count, compile_count, steps, skipped_steps,
    step_time_ema_s}."""
    with _step_lock:
        return {"dispatch_count": _dispatch_count,
                "compile_count": _compile_count,
                "steps": _step_count,
                "skipped_steps": _skipped_step_count,
                "step_time_ema_s": _step_ema_s}


def reset_step_stats():
    global _dispatch_count, _compile_count, _step_count, \
        _skipped_step_count, _step_ema_s, _last_step_t
    # settle pending flight records against the OLD counters, then
    # re-baseline so the next record's delta starts from zero —
    # reset_step_stats and telemetry.reset compose in either order
    t = _telemetry()
    t._drain_steps()
    with _step_lock:
        _dispatch_count = 0
        _compile_count = 0
        _step_count = 0
        _skipped_step_count = 0
        _step_ema_s = None
        _last_step_t = None
    t._rebaseline()


_telemetry_mod = None


def _telemetry():
    global _telemetry_mod
    if _telemetry_mod is None:
        from . import telemetry
        _telemetry_mod = telemetry
    return _telemetry_mod


def instrument(fn, first_call_compiles=True):
    """Dispatch/compile accounting around a jitted program whose input
    shapes are fixed for its lifetime (executor programs are bound to one
    shape set; fused Trainer programs rebuild on shape change) — so the
    first invocation IS its one XLA compile, and every invocation is one
    dispatch.

    ``first_call_compiles=False`` is for programs that arrive already
    compiled — an AOT executable deserialized from the warm-start cache
    (executor.make_fit_step): its first call dispatches without
    compiling, and charging a phantom compile would hide exactly the
    warm-vs-cold signal tests/test_aot_warmstart.py reads.

    Steady-state recompiles — the cache key silently missing after
    warmup, the exact failure the 1-compile contract exists to catch —
    are invisible to the first-call heuristic, so post-warmup calls are
    bracketed by telemetry's monotonic jax.monitoring backend-compile
    event count: any compile event landing inside an instrumented call
    feeds count_compile too.

    The wrapper's ``__wrapped__`` is ``fn`` — for an AOT-compiled
    program that is the ``jax.stages.Compiled``, whose ``as_text()``
    says what the program holds (chip_smoke.py looks for the Mosaic
    custom call there)."""
    compiled = []

    @functools.wraps(fn, assigned=())
    def wrapper(*args):
        count_dispatch()
        if not compiled:
            compiled.append(True)
            if first_call_compiles:
                count_compile()
            return fn(*args)
        t = _telemetry()
        pre = t._xla_compiles
        out = fn(*args)
        post = t._xla_compiles
        if post != pre:
            count_compile(post - pre)
        return out
    return wrapper


def dump_profile():
    """Write the chrome tracing JSON (reference profiler.py:55 /
    src/engine/profiler.cc:152).  Snapshot under the lock, write via the
    checkpoint layer's atomic tmp+fsync+os.replace so a crash mid-dump
    can never leave a torn trace at the final path."""
    with _lock:
        doc = {"traceEvents": list(_events), "displayTimeUnit": "ms"}
        fname = _filename
    from .checkpoint import _plain_atomic_write
    _plain_atomic_write(fname, json.dumps(doc).encode("utf-8"))
    return fname


# aliases matching later-era reference spellings kept by examples
set_config = profiler_set_config
set_state = profiler_set_state

if os.environ.get("MXNET_PROFILER_AUTOSTART", "0") == "1":
    profiler_set_state("run")
