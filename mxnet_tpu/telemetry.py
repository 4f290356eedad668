"""Unified telemetry: metrics registry, cross-layer spans, flight recorder.

The reference MXNet engine profiled every pushed op
(src/engine/profiler.cc: one OprExecStat per engine op); the XLA-fused
rebuild collapsed the graph into one program per step, so per-op hooks
vanished and visibility shrank to profiler.py's five global counters.
This module is the always-on observability substrate the fused design
needs instead:

- **metrics registry** — named :class:`Counter` / :class:`Gauge` /
  :class:`Histogram` (fixed log2 buckets, so percentile queries need no
  sample storage).  Hot-path mutation is lock-free on purpose, exactly
  like ``profiler.count_dispatch``: a GIL-raced increment merely
  miscounts telemetry, and the fused step budget (<1% of a ~0.3 ms CPU
  MLP step) has no room for a lock acquire per observation.
- **span(name, cat, **args)** — a context manager timing one named
  phase.  Every span feeds a phase histogram (always on) and, while the
  profiler is collecting, a chrome-tracing duration event in the same
  stream the executor writes, so data-loading / checkpoint / kvstore
  phases land in the same trace as ``executor_forward``.  Nested spans
  carry a ``depth`` arg so the hierarchy survives trace viewers that
  don't infer nesting.  While a ``jax.profiler`` trace is being taken a
  span is also a ``TraceAnnotation`` in that trace: the phases of
  ``ServingEngine.step`` and ``Module.fit_step`` lie on the device
  trace's own clock, over the device ops they waited for.
- **flight recorder** — a bounded ring of the last K per-step records
  (dispatch/sync wall time, dispatch/compile deltas, skipped flag, loss
  when the step has a scalar head, fault-site firings).  On an unhandled
  exception (``MXNetError`` from the divergence guard included) or at
  exit with a nonzero skip count, the ring is dumped as a postmortem
  JSON into ``MXTPU_POSTMORTEM_DIR`` via the checkpoint layer's plain
  atomic writer (no fault sites — a postmortem must never tear) —
  the last seconds of a run that died are never lost.
- **XLA compile attribution** — a ``jax.monitoring`` listener counts
  every backend compile (``xla.compiles`` counter +
  ``xla.compile_seconds`` histogram); ``profiler.instrument`` uses the
  same monotonic event count to attribute *steady-state recompiles* of
  an instrumented program to ``profiler.count_compile`` (its own
  first-call heuristic only ever sees the initial compile).
- **periodic emitter** — ``MXTPU_TELEMETRY=path[:interval]`` appends one
  ``report()`` JSON line every ``interval`` seconds (default 10) so a
  soak run leaves a machine-readable timeline behind.

**Job scope** (schema ``mxtpu-telemetry-2``, OBSERVABILITY.md §8): every
report line and postmortem carries an ``identity`` block (world size /
rank / slot / attempt / pid from :mod:`mxnet_tpu.elastic`'s launch
contract) and a ``clock`` anchor — the one ``(unix, perf_counter_ns)``
base pair every perf-stamp in this process is relative to — so
``tools/perf_probe/job_report.py`` can merge N ranks' streams into one
job timeline and one cross-rank chrome trace on a common clock.  The
emitter's final line additionally carries the flight ring
(``last_steps``) so a cleanly-exited rank leaves its recent per-step
spans behind the way a crashed rank leaves them in its postmortem.

**Request scope** (ISSUE 13, OBSERVABILITY.md §12): the serving twin of
the per-step flight recorder.  :func:`mint_trace` issues a process-unique
trace id at ``Router.submit`` / ``ServingEngine.submit``;
:func:`note_request_event` records one lifecycle event (submit, place,
admit, prefill, token batches, retry, swap, terminal verdict) with the
SAME hot-path discipline as ``note_train_step`` — one tuple append, all
folding deferred to a batched drain into a bounded event ring.  The
periodic emitter ships each line's NEWLY-drained events
(``req_events``, a cursor over the monotonic per-process ``seq``) so the
stream accumulates the full lifecycle record while each line stays
bounded; ring evictions of never-emitted events are counted
(``serving.trace_dropped`` / per-line ``req_dropped`` — no silent caps).
Crash postmortems carry the whole ring (``request_trace``), and every
``report()`` from a process with live serving engines carries a
``serving`` status block (occupancy, free pages, SLO controller state,
current weights epoch) — the periodic serving status line.
``tools/perf_probe/serve_report.py`` merges router journal + replica
streams into the fleet view.

``tools/perf_probe/telemetry_report.py`` renders the per-rank artifacts
(JSON-lines timeline and postmortem) for humans;
``tools/perf_probe/job_report.py`` aggregates a whole run dir;
OBSERVABILITY.md is the metric-name / span-name / schema contract.

Env vars: ``MXTPU_TELEMETRY``, ``MXTPU_POSTMORTEM_DIR``,
``MXTPU_FLIGHT_RECORDER_STEPS`` (ring size, default 64),
``MXTPU_REQUEST_TRACE_EVENTS`` (request-event ring size, default 8192),
``MXTPU_TELEMETRY_OFF=1`` (disable hot-path recording).
"""
from __future__ import annotations

import atexit
import collections
import contextlib
import itertools
import json
import math
import os
import re
import sys
import threading
import time
import weakref

import numpy as _np

__all__ = ["Counter", "Gauge", "Histogram", "counter", "gauge",
           "histogram", "span", "stamp_span", "observe_phase", "report",
           "reset", "DEVICE_SCOPES", "device_scope", "note_program",
           "program_scopes",
           "note_train_step", "note_fault", "mark_last_step_verdict",
           "flight_records", "flight_capacity", "dump_postmortem",
           "start_emitter", "stop_emitter", "set_enabled", "enabled",
           "identity", "clock_anchor", "suppress_compile_accounting",
           "mint_trace", "note_request_event", "request_events",
           "consume_request_events", "count_token_events",
           "request_events_since", "flight_records_since",
           "pull_snapshot", "AlertRule", "add_alert_rule",
           "alert_rules", "clear_alert_rules",
           "install_default_alert_rules", "check_alerts"]

SCHEMA_REPORT = "mxtpu-telemetry-2"
SCHEMA_POSTMORTEM = "mxtpu-postmortem-2"


def _env_int(name, default):
    try:
        return int(os.environ.get(name, ""))
    except ValueError:
        return default


_DISABLED = os.environ.get("MXTPU_TELEMETRY_OFF", "0") == "1"


def set_enabled(flag):
    """Toggle hot-path recording (spans, per-step records).  Registry
    objects stay queryable either way."""
    global _DISABLED
    _DISABLED = not flag


def enabled():
    return not _DISABLED


# -- lazy intra-package bindings (telemetry must stay importable from the
# very bottom of the package: only .base above it) -------------------------
_prof = None


def _profiler():
    global _prof
    if _prof is None:
        from . import profiler
        _prof = profiler
    return _prof


# -- metrics registry ------------------------------------------------------
_reg_lock = threading.Lock()     # creation only; mutation is lock-free
_counters = {}
_gauges = {}
_histograms = {}
_span_names = set()              # histogram names that came from spans


class Counter(object):
    """Monotonic named counter.  ``inc`` is a bare int add — lock-free
    like profiler.count_dispatch; a GIL race miscounts, never corrupts."""

    __slots__ = ("name", "value")

    def __init__(self, name):
        self.name = name
        self.value = 0

    def inc(self, n=1):
        self.value += n


class Gauge(object):
    """Last-write-wins named value (queue depths, ring occupancy...)."""

    __slots__ = ("name", "value")

    def __init__(self, name):
        self.name = name
        self.value = None

    def set(self, v):
        self.value = v


class Histogram(object):
    """Fixed log2-bucket histogram for durations (seconds) and sizes
    (bytes).  Bucket ``e`` holds values in ``(2**(e-1), 2**e]`` (the
    ``math.frexp`` exponent), zeros are counted separately — the bucket
    map is sparse, observation is O(1), and percentiles come from linear
    interpolation inside the covering bucket (bounded by construction to
    one power of two of the truth, clamped to the observed min/max)."""

    __slots__ = ("name", "count", "sum", "min", "max", "_zeros", "_buckets")

    def __init__(self, name):
        self.name = name
        self.count = 0
        self.sum = 0.0
        self.min = None
        self.max = None
        self._zeros = 0
        self._buckets = {}

    def observe(self, v):
        v = float(v)
        if v > 0.0:
            e = math.frexp(v)[1]
            b = self._buckets
            b[e] = b.get(e, 0) + 1
        else:
            self._zeros += 1
        self.count += 1
        self.sum += v
        if self.min is None or v < self.min:
            self.min = v
        if self.max is None or v > self.max:
            self.max = v

    def observe_many(self, values, scale=1.0):
        """Batch observe (the flight-recorder drain): one numpy
        frexp+bincount replaces per-value Python bucketing — the reason
        the always-on per-step telemetry stays inside its <1% budget.
        ``scale`` converts raw units (e.g. ns deltas) in the same
        vectorized pass."""
        n = len(values)
        if not n:
            return
        arr = _np.asarray(values, dtype=_np.float64)
        if scale != 1.0:
            arr = arr * scale
        pos = arr[arr > 0.0]
        if pos.size:
            e = _np.frexp(pos)[1]
            lo = int(e.min())
            b = self._buckets
            for i, cnt in enumerate(_np.bincount(e - lo)):
                if cnt:
                    k = lo + i
                    b[k] = b.get(k, 0) + int(cnt)
        self._zeros += n - int(pos.size)
        self.count += n
        self.sum += float(arr.sum())
        amin, amax = float(arr.min()), float(arr.max())
        if self.min is None or amin < self.min:
            self.min = amin
        if self.max is None or amax > self.max:
            self.max = amax

    def percentile(self, q, _buckets=None):
        """Approximate q-quantile (q in [0, 1]) from the bucket counts."""
        if not self.count:
            return None
        if _buckets is None:
            # atomic copy: observers on other threads (prefetch workers)
            # may insert new bucket keys mid-iteration
            _buckets = dict(self._buckets)
        target = q * self.count
        cum = float(self._zeros)
        if target <= cum and self._zeros:
            return 0.0
        for e in sorted(_buckets):
            n = _buckets[e]
            if target <= cum + n:
                lo, hi = 2.0 ** (e - 1), 2.0 ** e
                v = lo + (target - cum) / n * (hi - lo)
                return min(max(v, self.min), self.max)
            cum += n
        return self.max

    def snapshot(self):
        buckets = dict(self._buckets)  # atomic vs concurrent observes
        return {
            "count": self.count, "sum": self.sum,
            "min": self.min, "max": self.max,
            "p50": self.percentile(0.50, buckets),
            "p90": self.percentile(0.90, buckets),
            "p99": self.percentile(0.99, buckets),
            "buckets": {str(e): n for e, n in sorted(buckets.items())},
            "zeros": self._zeros,
        }


def _get_or_create(table, name, cls):
    obj = table.get(name)
    if obj is None:
        with _reg_lock:
            obj = table.setdefault(name, cls(name))
    return obj


def counter(name):
    """Get-or-create the named Counter (idempotent; hot callers should
    hold the returned object instead of re-resolving the name)."""
    return _get_or_create(_counters, name, Counter)


def gauge(name):
    return _get_or_create(_gauges, name, Gauge)


def histogram(name):
    return _get_or_create(_histograms, name, Histogram)


def _span_hist(name):
    h = _histograms.get(name)
    if h is None:
        h = histogram(name)
        with _reg_lock:
            _span_names.add(name)
    return h


# -- spans -----------------------------------------------------------------
_tls = threading.local()
_TraceAnnotation = None


def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, bound at the first span (jax is
    not imported with this module: telemetry stays importable from the
    bottom of the package)."""
    global _TraceAnnotation
    import jax
    _TraceAnnotation = jax.profiler.TraceAnnotation
    return _TraceAnnotation


class span(object):
    """Time one named phase: always feeds the phase histogram ``name``
    (seconds); while ``mx.profiler`` collects, appends a chrome-tracing
    duration event of category ``cat`` with a ``depth`` arg reflecting
    span nesting on this thread; and while a ``jax.profiler`` trace is
    being taken, enters a ``TraceAnnotation`` of the same name, so the
    phase lies in the device trace's own file, on its clock, over the
    device ops it waited for.  ``args`` (and what :meth:`set` adds before
    the exit) ride both events.  "Tracing on" is "a trace is being
    taken": with none, a span costs one activity check and one histogram
    fold, and never a device sync.

    >>> with telemetry.span("data.batchify", cat="data"):
    ...     batch = batchify_fn(samples)
    """

    __slots__ = ("name", "cat", "args", "t0", "t1", "_depth", "_ann",
                 "_fold")

    def __init__(self, name, cat="phase", **args):
        self.name = name
        self.cat = cat
        self.args = args
        self._ann = None
        self._fold = True

    def set(self, **args):
        """Args known only inside the phase (how many were admitted)."""
        self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __enter__(self):
        self._depth = getattr(_tls, "depth", 0)
        _tls.depth = self._depth + 1
        ann = _TraceAnnotation or _trace_annotation()
        if ann.is_enabled():
            self._ann = ann(self.name, **self.args)
            self._ann.__enter__()
        else:
            self._ann = None
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = self.t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _tls.depth = self._depth
        if not self._fold:
            return False
        dur_ns = t1 - self.t0
        if not _DISABLED:
            _span_hist(self.name).observe(dur_ns * 1e-9)
        prof = _prof or _profiler()
        # trace events survive MXTPU_TELEMETRY_OFF (profiling is its own
        # explicit opt-in).  Trace-origin guard: a span opened before
        # profiler_set_state("run") must not emit a pre-origin
        # (negative-ts) phantom event.
        if prof.is_running() and self.t0 // 1000 >= (prof._t0_us or 0):
            prof.record_event(self.name, self.t0 // 1000,
                              dur_ns // 1000, cat=self.cat,
                              args=dict(self.args, depth=self._depth))
        return False


def stamp_span(name, **args):
    """A :class:`span` for the ``<where>.dispatch`` / ``<where>.sync``
    phases of a program call: it writes the profiler-clock annotation and
    keeps its ``t0`` / ``t1`` stamps, and the caller hands those to
    :func:`note_train_step`, which folds the histograms and flight
    record in batches -- so each interval is timed once."""
    s = span(name, "step", **args)
    s._fold = False
    return s


def observe_phase(name, seconds):
    """Feed one duration into the span histogram ``name`` without timing
    a block here — for phases measured somewhere this registry can't
    reach: a stream decode worker may be a separate PROCESS whose
    registry dies with it, so the measured duration rides the result
    back and the consumer folds it into THIS process's phase table
    (rendered exactly like a span of the same name)."""
    if not _DISABLED:
        _span_hist(name).observe(seconds)


# -- device-side scopes ------------------------------------------------------
#: every name a program may enter as a device-side scope
#: (:func:`device_scope`), with the row it has in OBSERVABILITY.md
#: section 2.  Declared, not collected while tracing: a program that
#: comes back from the AOT cache is never traced in its process, and its
#: text must still be read by the same names.
DEVICE_SCOPES = frozenset((
    # the serving programs of gluon/model_zoo
    "embed", "norm", "attn", "attn.proj", "attn.out", "attn.full",
    "attn.window", "attn.gather", "index", "index.select", "kv_write",
    "state_write", "kda_step", "kda_scan", "attn.mla", "gdn.proj",
    "gdn_scan", "gdn_step", "gdn.out", "mlp", "moe", "moe.route",
    "moe.scatter", "moe.experts", "moe.combine", "moe.shared", "lm_head",
    "sample",
    # the training steps (Module.fit_step, gluon.Trainer, gpt_spmd)
    "forward_backward", "divergence_guard", "optimizer_apply", "loss"))


def device_scope(name):
    """``jax.named_scope(name)`` for a name of :data:`DEVICE_SCOPES`:
    the one way this package names a stretch of a traced program.  The
    name goes into the ``op_name`` of every instruction traced under it
    (metadata: the executable and its cache key are the same with and
    without it) and costs nothing when the program runs;
    :func:`program_scopes` reads it back.  Scopes nest, and a reader
    takes an instruction by its innermost one."""
    if name not in DEVICE_SCOPES:
        raise ValueError("telemetry.device_scope: %r is not declared in "
                         "DEVICE_SCOPES" % (name,))
    import jax
    return jax.named_scope(name)


#: compiled program -> the name it was noted under; weak, so a program
#: nobody can run anymore leaves nothing here
_programs = weakref.WeakKeyDictionary()
#: compiled program -> ``(module, {instruction: scope path})``, filled
#: by :func:`program_scopes`
_scope_tables = weakref.WeakKeyDictionary()


def note_program(name, compiled):
    """Remember (weakly) that ``compiled``, a ``jax.stages.Compiled``,
    is a program of this process, under ``name``.  Called where a
    program is compiled, loaded or taken from the memo; one dictionary
    store, no text is read."""
    _programs[compiled] = name


_HLO_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{$")
_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = ")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLS = re.compile(r"\bfusion\(.*\bcalls=%([^\s,)]+)")
_NAME_TRANSFORM = re.compile(r"^(\w+)\((.*)\)$")


def _scope_path(op_name):
    """``jit(prefill)/jit(main)/while/body/moe/moe.scatter/scatter`` ->
    ``moe/moe.scatter``: the declared scopes of an ``op_name``, outermost
    first.  A transformation's wrapper (``jvp(attn)``,
    ``transpose(jvp(attn))``) is the scope it wraps; ``jit(name)`` is a
    function, never a scope.  Where the compiler merged instructions it
    joins their names with ``;``: the first one counts.  A jitted
    helper traced once and called again repeats the path that led to it
    (``jit(decode)/moe/jit(searchsorted)/jit(decode)/moe/...``): the
    path counts from the program's last mention.  A kernel named as the
    scope that holds it (``kda_step``) counts once."""
    parts = op_name.split(";", 1)[0].split("/")
    for i in range(len(parts) - 1, 0, -1):
        if parts[i] == parts[0]:
            parts = parts[i:]
            break
    keep = []
    for part in parts:
        m = _NAME_TRANSFORM.match(part)
        while m and m.group(1) not in ("jit", "pjit"):
            part = m.group(2)
            m = _NAME_TRANSFORM.match(part)
        if part in DEVICE_SCOPES and part not in keep[-1:]:
            keep.append(part)
    return "/".join(keep)


def _parse_scopes(text):
    """``(module name, {instruction name: scope path})`` of a compiled
    program's text.  A fusion the compiler left without ``op_name``
    takes the one its fused computation ends on (its root's, or the last
    named operation before a root that has none: a tuple); any other
    instruction without ``op_name`` (a layout ``copy``, the
    ``copy-done`` / ``slice-done`` of a prefetch) maps to the empty
    path."""
    module, table = None, {}
    ends_on = {}       # computation -> the op_name it ends on
    fusions = []       # fusions without op_name: (name, fused computation)
    computation = None
    for line in text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m:
            name = m.group(1)
            op = _HLO_OP_NAME.search(line, m.end())
            table[name] = _scope_path(op.group(1)) if op else ""
            if op:
                ends_on[computation] = op.group(1)
            else:
                calls = _HLO_CALLS.search(line, m.end())
                if calls:
                    fusions.append((name, calls.group(1)))
            continue
        m = _HLO_COMPUTATION.match(line)
        if m:
            computation = m.group(1)
        elif module is None:
            m = _HLO_MODULE.match(line)
            if m:
                module = m.group(1)
    for name, fused in fusions:
        table[name] = _scope_path(ends_on.get(fused, ""))
    return module, table


def program_scopes():
    """The scope of every compiled instruction of every noted program
    that is still alive: ``[{"program": the name it was noted under,
    "module": the text's HloModule name (``jit_decode``), "scopes":
    {instruction name as a device trace prints it (``fusion.1608``):
    scope path (``moe/moe.scatter``; empty for an instruction without
    ``op_name`` or with no declared scope in it)}}]``.  Two programs may
    share a module name (an engine per prefill length): a reader tells
    them apart by the instruction names a run holds.

    The read side of :func:`note_program`, for whoever wants the table
    (the benchmark's ``device_scopes`` reader; an operator with a
    ``jax.profiler`` trace): each program's ``as_text()`` is parsed
    once, here.  Its resolution is the compiler's: a fusion carries the
    ``op_name`` of its ROOT, so work the compiler fused across a scope's
    edge counts where the fusion's last operation was written (a norm's
    sum of squares computed in the matmul that feeds it is that
    matmul's), and what the compiler added by itself (a layout copy, a
    prefetch) lies in no scope: a reader counts it as unattributed."""
    out = []
    for compiled, name in list(_programs.items()):
        table = _scope_tables.get(compiled)
        if table is None:
            table = _scope_tables[compiled] = _parse_scopes(
                compiled.as_text())
        out.append({"program": name, "module": table[0],
                    "scopes": table[1]})
    return out


# -- XLA compile attribution (jax.monitoring bridge) -----------------------
# Monotonic count of backend compiles, read by profiler.instrument to
# attribute steady-state recompiles of an instrumented program to
# count_compile.  Never reset (delta readers depend on monotonicity).
_xla_compiles = 0
_compile_hook_installed = False
_compile_suppress = threading.local()


@contextlib.contextmanager
def suppress_compile_accounting():
    """Mark this thread's backend compiles as intentional background work
    (the AOT twin / hot-swap compiles, executor._twin_hotswap): they are
    counted under ``xla.background_compiles`` instead of bumping the
    monotonic ``_xla_compiles`` that profiler.instrument uses to charge
    recompiles to in-flight steps — a deliberate off-hot-path compile is
    exactly NOT the steady-state recompile that counter exists to catch."""
    prev = getattr(_compile_suppress, "on", False)
    _compile_suppress.on = True
    try:
        yield
    finally:
        _compile_suppress.on = prev


def _on_jax_event(event, duration, **kw):
    if "backend_compile" in event:
        if getattr(_compile_suppress, "on", False):
            counter("xla.background_compiles").inc()
            return
        global _xla_compiles
        _xla_compiles += 1
        counter("xla.compiles").inc()
        histogram("xla.compile_seconds").observe(duration)


def _install_compile_hook():
    """Listen for jax.monitoring's per-compile duration events (the
    log_compiles signal, structured).  Best-effort: jax versions without
    the monitoring module leave the first-call heuristic in charge."""
    global _compile_hook_installed
    if _compile_hook_installed:
        return True
    try:
        from jax import monitoring as _monitoring
        _monitoring.register_event_duration_secs_listener(_on_jax_event)
    except Exception:
        return False
    _compile_hook_installed = True
    return True


def xla_compile_events():
    """Monotonic backend-compile event count (survives reset())."""
    return _xla_compiles


# -- flight recorder -------------------------------------------------------
_FLIGHT_FIELDS = ("step", "t_unix", "dispatch_s", "sync_s",
                  "dispatch_delta", "compile_delta", "skipped", "loss",
                  "faults", "where")
_flight = collections.deque(
    maxlen=max(1, _env_int("MXTPU_FLIGHT_RECORDER_STEPS", 64)))
_step_seq = 0
_last_dispatch = 0
_last_compile = 0
# sites fired since the last step record; bounded (a fault-heavy run
# with no train steps — e.g. pure checkpoint I/O under ckpt.write.*
# rates — must not grow it forever)
_pending_faults = collections.deque(maxlen=256)
_train_hists = {}                # where -> (dispatch hist, sync hist)

# perf_counter↔unix correspondence, so the hot path never calls
# time.time(): records carry perf_counter_ns stamps and the drain
# reconstructs wall-clock time from this one base pair
_unix_base = time.time()
_perf_base = time.perf_counter_ns()

# The per-step hot path appends ONE compact tuple here; histograms, the
# flight ring, and trace events are folded in by _drain_steps in batches
# of _PENDING_MAX (or on any read).  Batching exists for the <1%-of-a-
# fused-step budget: folding touches a dozen Python objects, and doing
# that once per 128 steps with hot caches costs a fraction of doing it
# per step cold.
_pending_steps = []
_PENDING_MAX = 128
_drain_lock = threading.Lock()


def note_train_step(t0_ns, t1_ns, t2_ns=None, skipped=False, loss=None,
                    where="fit_step"):
    """Record one fused train step from three perf_counter_ns stamps:
    program dispatch [t0, t1] and device sync / verdict readback
    [t1, t2] (``t2_ns=None`` for paths that resolve the verdict lazily —
    the Trainer — in which case ``skipped`` is back-filled by
    :func:`mark_last_step_verdict`).

    Hot-path cost is one tuple append plus two profiler counter reads;
    everything else is deferred to the batched drain.  While the
    profiler collects, the drain runs per step so trace events stay
    timely (profiling already pays for accuracy with syncs)."""
    prof = _prof or _profiler()
    if _DISABLED:
        # metrics off, but an explicitly-running profiler still gets
        # its fused-step trace events (the _timed("module_fit_step")
        # signal this layer replaced must survive MXTPU_TELEMETRY_OFF)
        if prof.is_running():
            prof.record_event(where + ".dispatch", t0_ns // 1000,
                              (t1_ns - t0_ns) // 1000, cat="step")
            if t2_ns is not None:
                prof.record_event(where + ".sync", t1_ns // 1000,
                                  (t2_ns - t1_ns) // 1000, cat="step")
        return
    if _pending_faults:
        # popleft-until-empty: a note_fault append landing from another
        # thread (e.g. the prefetch worker) mid-snapshot survives for
        # the next record instead of vanishing
        popped = []
        while True:
            try:
                popped.append(_pending_faults.popleft())
            except IndexError:
                break
        faults = tuple(popped)
    else:
        faults = ()
    p = _pending_steps
    p.append((where, t0_ns, t1_ns, t2_ns, skipped, loss,
              prof._dispatch_count, prof._compile_count, faults))
    # per-step drain only while the profiler actually collects (paused
    # counts as not collecting — no trace events would be emitted, so
    # defeating the batching would buy nothing)
    if len(p) >= _PENDING_MAX or prof.is_running():
        _drain_steps()


def _drain_steps():
    """Fold pending step tuples into the phase histograms, the flight
    ring, and (while profiling) the trace stream.  Runs under a lock —
    callers are the hot path every _PENDING_MAX steps, every reader, and
    the emitter thread."""
    global _step_seq, _last_dispatch, _last_compile
    with _drain_lock:
        batch = list(_pending_steps)
        if not batch:
            return
        del _pending_steps[:len(batch)]
        prof = _prof or _profiler()
        running = prof.is_running()
        # records buffered before the trace started must not leak into
        # it as pre-origin (negative-ts) phantom events
        trace_t0_us = (prof._t0_us or 0) if running else None
        # histogram folds: vectorized per `where` over the whole batch
        # (record layout: where, t0, t1, t2, skipped, loss, d, c, faults)
        wheres = {r[0] for r in batch}
        for w in wheres:
            rs = batch if len(wheres) == 1 else \
                [r for r in batch if r[0] == w]
            pair = _train_hists.get(w)
            if pair is None:
                pair = (_span_hist(w + ".dispatch"),
                        _span_hist(w + ".sync"))
                _train_hists[w] = pair
            pair[0].observe_many([r[2] - r[1] for r in rs], scale=1e-9)
            pair[1].observe_many([r[3] - r[2] for r in rs
                                  if r[3] is not None], scale=1e-9)
        # ring fold: records past ring capacity would be appended then
        # immediately evicted — advance the counters over them instead
        seq, last_d, last_c = _step_seq, _last_dispatch, _last_compile
        skip = len(batch) - _flight.maxlen
        if skip > 0 and not running:
            seq += skip
            last_d, last_c = batch[skip - 1][6], batch[skip - 1][7]
            batch = batch[skip:]
        append = _flight.append
        t_off = _unix_base - _perf_base * 1e-9
        for (where, t0, t1, t2, skipped, loss, d, c, faults) in batch:
            sync_s = (t2 - t1) * 1e-9 if t2 is not None else None
            append([seq, t_off + t0 * 1e-9, (t1 - t0) * 1e-9, sync_s,
                    d - last_d, c - last_c, skipped, loss, faults,
                    where])
            seq += 1
            last_d, last_c = d, c
            if running and t0 // 1000 >= trace_t0_us:
                prof.record_event(where + ".dispatch", t0 // 1000,
                                  (t1 - t0) // 1000, cat="step")
                if t2 is not None:
                    prof.record_event(where + ".sync", t1 // 1000,
                                      (t2 - t1) // 1000, cat="step")
        _step_seq, _last_dispatch, _last_compile = seq, last_d, last_c


def _rebaseline(dispatch=0, compile_=0):
    """Settle pending records against the old counters, then restart the
    flight-recorder deltas from the given values — profiler.
    reset_step_stats calls this so the two resets compose in either
    order."""
    global _last_dispatch, _last_compile
    _drain_steps()
    with _drain_lock:
        _last_dispatch = dispatch
        _last_compile = compile_


def mark_last_step_verdict(ok):
    """Back-fill the newest flight record's skipped flag from the
    divergence-guard verdict — the Trainer records its step with
    ``skipped=None`` (pending) and resolves one step late by design
    (PERF.md "Divergence guard"), always before the next record is
    appended.  A crash in between leaves the honest ``None``
    ("verdict unknown"), never a false ``ok``."""
    if _DISABLED:
        return
    skipped = not ok
    # back-fill the NEWEST pending (None) record.  It usually still sits
    # in _pending_steps (the Trainer resolves every step, and forcing a
    # ring drain here would defeat the batching the <1% budget rests
    # on), else in the drained ring — a Module.fit_step record may land
    # in between, so scan tails, never touching resolved records.
    # (Two Trainers with simultaneously pending verdicts in one process
    # could still cross-attribute; verdicts resolve in step order, so
    # the window is one record and the skip COUNT stays exact.)
    # Under _drain_lock: concurrent drains/resets mutate both
    # containers, and deque iteration raises on mutation mid-scan.
    with _drain_lock:
        for i in range(len(_pending_steps) - 1, -1, -1):
            rec = _pending_steps[i]
            if rec[4] is None:
                _pending_steps[i] = rec[:4] + (skipped,) + rec[5:]
                return
        for rec in reversed(_flight):
            if rec[6] is None:
                rec[6] = skipped
                return


def note_fault(site):
    """Called by fault.trigger when a site fires: per-site counter (the
    registry stays live even when hot-path recording is off) plus
    attribution of the firing to the next flight-recorder step record
    (gated — nothing drains the pending list while recording is off,
    and stale firings must not be dumped onto a later step)."""
    counter("fault.fire.%s" % site).inc()
    if not _DISABLED:
        _pending_faults.append(site)


def flight_records():
    """The ring as a list of dicts, oldest first."""
    _drain_steps()
    return [dict(zip(_FLIGHT_FIELDS, rec)) for rec in list(_flight)]


def flight_capacity():
    return _flight.maxlen


def flight_records_since(step, max_records=None):
    """Non-destructive cursor slice over the flight ring for the RPC
    telemetry pull: ``(records, evicted, next_step, more)`` with the
    same contract as :func:`request_events_since`, keyed on the
    monotonic per-process ``step`` field.  ``step=None`` starts at the
    oldest surviving record."""
    _drain_steps()
    with _drain_lock:
        oldest = _flight[0][0] if _flight else _step_seq
        if step is None:
            step = oldest
        evicted = max(0, oldest - step)
        recs = [r for r in _flight if r[0] >= step]
        more = False
        if max_records is not None and len(recs) > max_records:
            recs = recs[:max_records]
            more = True
        next_step = (recs[-1][0] + 1) if recs else max(step, oldest)
        return ([dict(zip(_FLIGHT_FIELDS, r)) for r in recs],
                evicted, next_step, more)


# -- request-scope tracing (the serving plane, OBSERVABILITY.md §12) -------
# One bounded ring of per-request lifecycle events, the serving twin of
# the per-step flight ring: the hot path (a decode step's token batch)
# is ONE tuple append; the batched drain assigns a monotonic per-process
# ``seq`` and folds into the ring.  The periodic emitter ships each
# line's newly-drained events (a cursor over ``seq``), so a replica's
# stream accumulates the complete lifecycle record while every line
# stays bounded; evicting a never-emitted event is counted, never
# silent.  ``tools/perf_probe/serve_report.py`` reconstructs per-request
# lifecycles (and the fleet view) from these events.
_REQ_RING_CAP = max(64, _env_int("MXTPU_REQUEST_TRACE_EVENTS", 8192))
_req_ring = collections.deque(maxlen=_REQ_RING_CAP)
_req_seq = 0            # next event sequence number (monotonic)
# Per-consumer drain cursors (ISSUE 18): consumer name -> [next_seq,
# dropped].  The file emitter, the postmortem drain, and the RPC
# telemetry pull each hold their own cursor, so each sees every event
# exactly once without stealing another consumer's deliveries.  The
# "emitter" cursor is pre-registered at seq 0 so a process with no
# stream file still counts every never-shipped eviction
# (``serving.trace_dropped`` keeps its ISSUE-13 meaning).
_req_cursors = {"emitter": [0, 0]}
_pending_req = []
_REQ_PENDING_MAX = 256
_trace_seq = itertools.count()
# process-unique trace-id base: pid alone repeats across restart
# attempts, and a survivor's stream must never collide trace ids with
# its predecessor's (serve_report merges both)
_TRACE_BASE = "%x.%x" % (os.getpid(),
                         int(_unix_base * 1e3) & 0xffffffff)


def mint_trace():
    """A new process-unique request trace id (``Router.submit`` /
    ``ServingEngine.submit`` mint one per request; everything the
    request experiences — admission, prefill, every decode token, a
    failover re-decode on another replica — is recorded under it)."""
    return "%s-%x" % (_TRACE_BASE, next(_trace_seq))


def note_request_event(trace, event, t_ns=None, args=None):
    """Record one request-lifecycle event.  Hot-path discipline matches
    :func:`note_train_step`: one tuple append, everything else deferred
    to the batched drain.  ``trace=""`` marks an engine-scope event (a hot-swap
    pause naming the resident traces in ``args``); ``t_ns`` is a
    ``perf_counter_ns`` stamp (defaults to now — pass the step's
    existing stamp on hot paths to skip the clock read)."""
    if _DISABLED:
        return
    p = _pending_req
    p.append((trace, event,
              t_ns if t_ns is not None else time.perf_counter_ns(),
              args))
    if len(p) >= _REQ_PENDING_MAX:
        _drain_req_events()


def _req_cursor(consumer):
    """The named consumer's ``[next_seq, dropped]`` cell (callers hold
    ``_drain_lock``).  A new consumer registers at the OLDEST seq the
    ring still holds: it can drain everything that survives, and events
    evicted before it existed were never its loss to declare."""
    cur = _req_cursors.get(consumer)
    if cur is None:
        cur = _req_cursors[consumer] = [
            _req_ring[0][0] if _req_ring else _req_seq, 0]
    return cur


def _drain_req_events():
    global _req_seq
    with _drain_lock:
        batch = list(_pending_req)
        if not batch:
            return
        del _pending_req[:len(batch)]
        ring = _req_ring
        seq = _req_seq
        dropped = 0
        cursors = list(_req_cursors.values())
        t_off = _unix_base - _perf_base * 1e-9
        for (trace, event, t, args) in batch:
            if len(ring) == ring.maxlen:
                ev_seq = ring[0][0]
                missed = False
                for cur in cursors:
                    if ev_seq >= cur[0]:
                        cur[1] += 1     # evicting an event this consumer
                        missed = True   # never drained
                if missed:
                    dropped += 1
            ring.append((seq, t_off + t * 1e-9, trace, event, args))
            seq += 1
        _req_seq = seq
        if dropped:
            # counted once per evicted-before-anyone-shipped-it event,
            # however many consumers missed it (each cursor still carries
            # its own per-consumer count)
            counter("serving.trace_dropped").inc(dropped)


def _req_dicts(recs):
    return [{"seq": s, "t": t, "trace": tr, "event": ev,
             "args": args or {}} for (s, t, tr, ev, args) in recs]


def request_events():
    """The whole request-event ring as dicts, oldest first (postmortems
    and tests; does not advance the emitter cursor)."""
    _drain_req_events()
    with _drain_lock:
        return _req_dicts(list(_req_ring))


def consume_request_events(consumer="emitter"):
    """``(new_events, dropped)`` since this CONSUMER's last consume —
    the emitter's per-line payload.  Advances the consumer's own cursor,
    so each event ships exactly once per consumer across the stream's
    lines; ``dropped`` counts events evicted from the ring before this
    consumer could drain them (burst faster than its interval — the
    reader must know the record has a gap).  Distinct consumer names
    never steal each other's events (ISSUE 18: the file emitter and the
    RPC telemetry pull run concurrently against one ring)."""
    _drain_req_events()
    with _drain_lock:
        cur = _req_cursor(consumer)
        evs = [r for r in _req_ring if r[0] >= cur[0]]
        dropped, cur[1] = cur[1], 0
        cur[0] = _req_seq
        return _req_dicts(evs), dropped


def request_events_since(seq, max_events=None):
    """Non-destructive cursor slice for the RPC telemetry pull:
    ``(events, evicted, next_seq, more)`` — every surviving event with
    ``seq >= seq`` (oldest first, at most ``max_events``), the count of
    events the ring evicted after the client's cursor but before this
    pull could see them (declared loss, never silent), the cursor to
    present next, and whether more events remain right now (bounded
    chunking: the caller re-pulls instead of one reply stalling the
    single-threaded RPC/decode loop).  ``seq=None`` starts at the oldest
    surviving event with nothing declared lost.  The server holds no
    per-client state — the client-held cursor makes a re-pull after a
    dropped reply idempotent."""
    _drain_req_events()
    with _drain_lock:
        oldest = _req_ring[0][0] if _req_ring else _req_seq
        if seq is None:
            seq = oldest
        evicted = max(0, oldest - seq)
        evs = [r for r in _req_ring if r[0] >= seq]
        more = False
        if max_events is not None and len(evs) > max_events:
            evs = evs[:max_events]
            more = True
        next_seq = (evs[-1][0] + 1) if evs else max(seq, oldest)
        return _req_dicts(evs), evicted, next_seq, more


def count_token_events(events):
    """Traced token total over request-event dicts: singular ``token``
    events (prefill first tokens) plus len-weighted batched ``tokens``
    events (decode steps).  THE token-accounting law's left-hand side —
    one definition, shared by the bench probe and the law tests, equal
    to the ``serving.tokens`` counter delta bit-exactly."""
    n = 0
    for e in events:
        ev = e.get("event")
        if ev == "token":
            n += 1
        elif ev == "tokens":
            n += len((e.get("args") or {}).get("traces") or ())
    return n


def _unconsume_request_events(evs, dropped, consumer="emitter"):
    """Roll a failed emit's consume back: the events never reached the
    stream, so the consumer's cursor returns to the first unshipped seq
    and its drop count is restored — the next successful line carries
    them.  (Events the ring evicts while the cursor is transiently
    advanced escape the drop accounting — a write failing in the same
    instant the ring overflows — which is as far as best-effort
    telemetry reaches.)"""
    with _drain_lock:
        cur = _req_cursor(consumer)
        if evs:
            cur[0] = min(cur[0], evs[0]["seq"])
        if dropped:
            cur[1] += dropped


# -- alert rules (ISSUE 18) ------------------------------------------------
# Small declarative alerting over the live registry: a rule watches one
# metric (counter delta, gauge predicate, or counter-delta ratio) and,
# when it holds, emits a typed trace-less ``alert`` request event into
# the same stream every consumer already drains — the file emitter, the
# RPC telemetry pull, and postmortems all carry alerts for free, and
# ``serve_report`` / ``fleet_top`` render them.  Evaluated on drain:
# every ``report()`` (so every emitted line, every pull, every
# postmortem) runs :func:`check_alerts` first.

_ALERT_OPS = {
    ">": lambda a, b: a > b, ">=": lambda a, b: a >= b,
    "<": lambda a, b: a < b, "<=": lambda a, b: a <= b,
    "==": lambda a, b: a == b, "!=": lambda a, b: a != b,
}


class AlertRule(object):
    """One declarative alert rule.

    Kinds:

    - ``counter_delta`` — fires when the counter rose by more than
      ``threshold`` (default 0) since the previous evaluation; the
      firing's value is the delta.
    - ``gauge`` — fires while ``gauge <op> threshold`` holds.  A
      ``metric`` ending in ``.*`` watches every registered gauge under
      that prefix (one independent firing per matching name — e.g.
      ``rpc.breaker.*`` alerts per replica).
    - ``ratio`` — numerator/denominator counter DELTAS since the last
      evaluation (``metric`` / ``metric2``); fires when the denominator
      moved and the ratio satisfies ``<op> threshold``.

    ``window_s`` rate-limits firings: once a rule fires for a metric it
    stays quiet for that metric until the window elapses — a
    still-held gauge predicate re-alerts every window (a breaker still
    open a minute later should say so again), a counter burst within
    one window alerts once."""

    __slots__ = ("name", "kind", "metric", "metric2", "op", "threshold",
                 "severity", "window_s", "_prev", "_last_fired")

    def __init__(self, name, metric, kind="gauge", op=">", threshold=0,
                 metric2=None, severity="warn", window_s=60.0):
        if kind not in ("counter_delta", "gauge", "ratio"):
            raise ValueError("unknown alert kind: %r" % (kind,))
        if op not in _ALERT_OPS:
            raise ValueError("unknown alert op: %r" % (op,))
        if kind == "ratio" and not metric2:
            raise ValueError("ratio rules need metric2")
        self.name = name
        self.kind = kind
        self.metric = metric
        self.metric2 = metric2
        self.op = op
        self.threshold = threshold
        self.severity = severity
        self.window_s = window_s
        self._prev = {}        # metric name -> last counter value(s)
        self._last_fired = {}  # metric name -> monotonic fire time

    def _metric_names(self):
        if self.kind == "gauge" and self.metric.endswith(".*"):
            pre = self.metric[:-1]      # keep the trailing dot
            with _reg_lock:
                return [n for n in _gauges if n.startswith(pre)]
        return [self.metric]

    def _reset_state(self):
        self._prev.clear()
        self._last_fired.clear()

    def evaluate(self, now):
        """``[(metric_name, value), ...]`` firings this evaluation.
        Caller holds ``_alert_lock`` (rule state is mutated)."""
        fired = []
        op = _ALERT_OPS[self.op]
        for name in self._metric_names():
            if self.kind == "gauge":
                g = _gauges.get(name)
                v = None if g is None else g.value
                hold = v is not None and op(v, self.threshold)
                val = v
            elif self.kind == "counter_delta":
                c = _counters.get(name)
                v = 0 if c is None else c.value
                delta = v - self._prev.get(name, 0)
                self._prev[name] = v
                hold = delta > self.threshold
                val = delta
            else:  # ratio of deltas
                c1 = _counters.get(name)
                c2 = _counters.get(self.metric2)
                v1 = 0 if c1 is None else c1.value
                v2 = 0 if c2 is None else c2.value
                key = (name, self.metric2)
                p1, p2 = self._prev.get(key, (0, 0))
                d1, d2 = v1 - p1, v2 - p2
                self._prev[key] = (v1, v2)
                hold = d2 > 0 and op(d1 / d2, self.threshold)
                val = (d1 / d2) if d2 > 0 else None
            if not hold:
                continue
            last = self._last_fired.get(name)
            if last is not None and now - last < self.window_s:
                continue
            self._last_fired[name] = now
            fired.append((name, val))
        return fired


_alert_rules = []
_alert_lock = threading.Lock()


def add_alert_rule(name, metric, kind="gauge", op=">", threshold=0,
                   metric2=None, severity="warn", window_s=60.0):
    """Install (or replace, by name) one alert rule; returns it."""
    rule = AlertRule(name, metric, kind=kind, op=op, threshold=threshold,
                     metric2=metric2, severity=severity, window_s=window_s)
    with _alert_lock:
        _alert_rules[:] = [r for r in _alert_rules if r.name != name]
        _alert_rules.append(rule)
    return rule


def alert_rules():
    """The installed rules (live objects; treat as read-only)."""
    with _alert_lock:
        return list(_alert_rules)


def clear_alert_rules():
    with _alert_lock:
        del _alert_rules[:]


def install_default_alert_rules():
    """The stock fleet-health rules (OBSERVABILITY.md §14); installed at
    import, idempotent (add_alert_rule replaces by name)."""
    add_alert_rule("slo_shed_engaged", "serving.shed",
                   kind="counter_delta", severity="warn", window_s=30.0)
    add_alert_rule("watchdog_stall", "watchdog.stalls",
                   kind="counter_delta", severity="critical",
                   window_s=30.0)
    add_alert_rule("breaker_open", "rpc.breaker.*", kind="gauge",
                   op=">=", threshold=2, severity="critical",
                   window_s=30.0)
    add_alert_rule("replica_fenced", "rpc.confirmations.fence_expiry",
                   kind="counter_delta", severity="critical",
                   window_s=30.0)
    add_alert_rule("fenced_writeback", "rpc.fenced_results",
                   kind="counter_delta", severity="warn", window_s=30.0)
    add_alert_rule("goodput_collapse", "serving.goodput",
                   kind="ratio", metric2="serving.tokens", op="<",
                   threshold=0.5, severity="warn", window_s=30.0)
    add_alert_rule("orphan_reclaim", "serving.stream.abandoned",
                   kind="counter_delta", severity="warn", window_s=30.0)


def check_alerts(now=None):
    """Evaluate every installed rule against the live registry; each
    firing increments ``telemetry.alerts`` and records a trace-less
    ``alert`` request event (``args`` = rule/severity/metric/value) that
    rides the normal drain to every consumer.  Returns the fired args
    dicts.  Called from :func:`report` so every emitted line, RPC pull,
    and postmortem evaluates on drain; replicas also call it
    periodically from their serve loop."""
    if now is None:
        now = time.monotonic()
    fired = []
    with _alert_lock:
        for rule in _alert_rules:
            for (mname, val) in rule.evaluate(now):
                args = {"rule": rule.name, "severity": rule.severity,
                        "metric": mname}
                if val is not None:
                    args["value"] = (round(val, 6)
                                     if isinstance(val, float) else val)
                fired.append(args)
    for args in fired:
        # counter always counts (registry stays live under
        # MXTPU_TELEMETRY_OFF); the event records only while enabled
        counter("telemetry.alerts").inc()
        note_request_event("", "alert", args=args)
    return fired


# -- reporting -------------------------------------------------------------
def identity():
    """Who this stream belongs to inside the job: the elastic launch
    contract (world_size / rank / slot / attempt, re-read from env so a
    post-reshard process stamps its NEW membership) plus the pid.  The
    job aggregator keys every line by this block — a re-ranked survivor
    keeps its slot while its rank shifts, and the attempt field is what
    segments a merged timeline at elastic transitions."""
    try:
        from . import elastic as _elastic
        mem = _elastic.membership()
        return {"world_size": mem["world_size"], "rank": mem["rank"],
                "slot": mem["slot"], "attempt": mem["attempt"],
                "pid": os.getpid()}
    except Exception:
        # interpreter teardown: a final emitter line / late postmortem
        # must still be a complete document
        return {"world_size": None, "rank": None, "slot": None,
                "attempt": None, "pid": os.getpid()}


def clock_anchor():
    """The monotonic↔unix correspondence of this process: every
    perf_counter_ns stamp in its records maps to wall-clock time as
    ``unix + (perf_ns_stamp - perf_ns) * 1e-9`` — the base pair the
    flight recorder already uses for ``t_unix``.  Published on every
    report line so a cross-rank trace merge shares one time axis without
    trusting each rank's trace-local origin."""
    return {"unix": _unix_base, "perf_ns": _perf_base,
            "mono_ns": time.monotonic_ns() - time.perf_counter_ns()}


def report():
    """One JSON-able snapshot of everything: counters, gauges, phase
    histograms (from spans / train steps), free histograms, profiler
    step_stats, flight-ring occupancy, and the job-scope identity +
    clock anchor (schema mxtpu-telemetry-2).  This is the emitter's line
    format and StepStatsMonitor's data source.  Alert rules are
    evaluated first ("on drain"), so the snapshot and any consumer
    draining events right after it see this evaluation's firings."""
    check_alerts()
    _drain_steps()
    with _reg_lock:
        counters = {n: c.value for n, c in _counters.items()}
        gauges = {n: g.value for n, g in _gauges.items()}
        hists = dict(_histograms)
        spans = set(_span_names)
    doc = {
        "schema": SCHEMA_REPORT,
        "time_unix": time.time(),
        "pid": os.getpid(),
        "identity": identity(),
        "clock": clock_anchor(),
        "counters": counters,
        "gauges": gauges,
        "phases": {n: h.snapshot() for n, h in hists.items()
                   if n in spans},
        "histograms": {n: h.snapshot() for n, h in hists.items()
                       if n not in spans},
        "step_stats": _profiler().step_stats(),
        "flight": {"len": len(_flight), "maxlen": _flight.maxlen},
    }
    try:
        # the periodic serving status line (ISSUE 13): every report from
        # a process with live engines says what they are serving right
        # now — occupancy, free pages, SLO state, current weights epoch.
        # sys.modules-gated exactly like the postmortem block: a
        # training process must not import the serving stack for this.
        eng_mod = sys.modules.get("mxnet_tpu.serving.engine")
        if eng_mod is not None:
            snaps = eng_mod.live_snapshot()
            if snaps:
                doc["serving"] = snaps
    except Exception:
        pass  # a half-dead engine must never take a report down
    return doc


_PULL_EVENTS_DEFAULT = max(1, _env_int("MXTPU_TELEMETRY_PULL_EVENTS",
                                       2048))


def pull_snapshot(req_seq=None, step_seq=None, max_events=None):
    """One telemetry-pull payload (ISSUE 18): ``(line_doc, cursor,
    more)``.  ``line_doc`` is a full :func:`report` document on the
    ``mxtpu-telemetry-2`` schema, extended with the request events and
    flight records newer than the client-held cursor —
    ``req_events``/``req_dropped`` exactly as the file emitter writes
    them (``req_dropped`` here = events evicted past the CLIENT's
    cursor, declared per pull), plus ``last_steps``/``steps_dropped``
    for the flight-ring slice — so a collector can append the line
    verbatim to a ``stream-*.jsonl`` file and every existing report
    reads it unchanged.  ``cursor`` is ``{"req_seq", "step_seq"}`` to
    present next; ``more`` says a chunk boundary was hit (``max_events``
    bounds BOTH slices; default ``MXTPU_TELEMETRY_PULL_EVENTS``) and the
    client should pull again.  Purely read-only on the server: no
    consumer cursor moves, so a lost reply costs nothing — the client
    re-pulls with its old cursor."""
    if max_events is None:
        max_events = _PULL_EVENTS_DEFAULT
    doc = report()
    evs, evicted, next_seq, more_ev = request_events_since(
        req_seq, max_events)
    recs, steps_dropped, next_step, more_st = flight_records_since(
        step_seq, max_events)
    if evs:
        doc["req_events"] = evs
    if evicted:
        doc["req_dropped"] = evicted
    if recs:
        doc["last_steps"] = recs
    if steps_dropped:
        doc["steps_dropped"] = steps_dropped
    cursor = {"req_seq": next_seq, "step_seq": next_step}
    doc["pull"] = dict(cursor, more=bool(more_ev or more_st))
    return doc, cursor, bool(more_ev or more_st)


def reset():
    """Clear every metric, the flight ring, and the step sequence (tests
    and benches; the monotonic XLA compile-event count is exempt)."""
    global _step_seq, _last_dispatch, _last_compile, _dumped
    # _drain_lock around the WHOLE reset: a concurrent emitter-thread
    # drain must neither fold pre-reset pending records into the just-
    # zeroed histograms nor re-append them into the just-cleared ring.
    # Lock order _drain_lock -> _reg_lock matches _drain_steps (via
    # _span_hist); nothing takes them in the reverse order.
    global _req_seq
    with _drain_lock:
        del _pending_steps[:]
        del _pending_req[:]
        _pending_faults.clear()
        _req_ring.clear()
        _req_seq = 0
        _req_cursors.clear()
        _req_cursors["emitter"] = [0, 0]
        with _reg_lock:
            # zero IN PLACE: hot callers hold metric objects (counter()'s
            # documented contract), and clearing the dicts would orphan
            # those handles — their post-reset increments would vanish
            for c in _counters.values():
                c.value = 0
            for g in _gauges.values():
                g.value = None
            for h in _histograms.values():
                h.count = 0
                h.sum = 0.0
                h.min = None
                h.max = None
                h._zeros = 0
                h._buckets = {}
        _train_hists.clear()
        _flight.clear()
        _step_seq = 0
        prof = _profiler()
        _last_dispatch = prof._dispatch_count
        _last_compile = prof._compile_count
    # alert-rule deltas baseline against the just-zeroed counters (a
    # stale _prev would read the first post-reset increments as a
    # negative delta and go quiet); rate-limit windows re-arm too
    with _alert_lock:
        for r in _alert_rules:
            r._reset_state()
    _dumped = False


# -- postmortem ------------------------------------------------------------
_dumped = False


def dump_postmortem(reason, path=None):
    """Write the crash-postmortem JSON: the full report() plus the last-K
    step records and per-site fault firings, atomically (a crash during
    the dump must not leave a torn postmortem — and without the
    checkpoint layer's fault-injection sites, which must neither tear
    this record nor have their budgets consumed by it).

    Without an explicit ``path`` the file goes to
    ``$MXTPU_POSTMORTEM_DIR/postmortem-<pid>.json``; unset dir means
    postmortems are off and None is returned.  Only the first implicit
    dump per process wins (excepthook fires before atexit; both route
    here)."""
    global _dumped
    implicit = path is None
    if implicit:
        d = os.environ.get("MXTPU_POSTMORTEM_DIR")
        if not d or _dumped:
            return None
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, "postmortem-%d.json" % os.getpid())
    doc = report()
    doc["schema"] = SCHEMA_POSTMORTEM
    doc["reason"] = reason
    from . import fault as _fault
    doc["fault_fires"] = _fault.fire_counts()
    doc["last_steps"] = flight_records()
    recs = request_events()
    if recs:
        # the request-scope ring (ISSUE 13): a dying replica's record
        # carries the recent per-request lifecycle events the same way
        # it carries its per-step ring — serve_report dedups against
        # already-emitted stream lines by (pid, seq)
        doc["request_trace"] = recs
    try:
        # hang-defense context: lease ages/timeouts at the moment of
        # death — for a watchdog stall this names the wedged phase
        from . import watchdog as _watchdog
        doc["watchdog"] = _watchdog.snapshot()
    except Exception:
        pass  # interpreter teardown
    try:
        # elastic context: world_size/rank/slot/attempt at the moment of
        # death — a postmortem from a resharded job must say which
        # membership it died under (ROBUSTNESS.md §9)
        from . import elastic as _elastic
        doc["membership"] = _elastic.snapshot()
    except Exception:
        pass  # interpreter teardown
    try:
        # serving context (ISSUE 11): a dying/stalled REPLICA's record
        # must say what it was serving — resident slots, queue depth,
        # page accounting.  sys.modules-gated: a training process that
        # never imported the serving stack must not start importing the
        # jax-adjacent engine module mid-crash.
        eng_mod = sys.modules.get("mxnet_tpu.serving.engine")
        if eng_mod is not None:
            snaps = eng_mod.live_snapshot()
            if snaps:
                doc["serving"] = snaps
    except Exception:
        pass  # the postmortem must never fail on a half-dead engine
    # the plain writer: a ckpt.write.* fault armed for the checkpoint
    # layer must not fire here and tear the record of the crash itself
    from .checkpoint import _plain_atomic_write
    _plain_atomic_write(path, json.dumps(doc, indent=1).encode("utf-8"))
    if implicit:
        # explicit-path dumps (health snapshots) must not suppress the
        # one implicit crash/atexit postmortem this process gets
        _dumped = True
    return path


_orig_excepthook = None
_hooks_installed = False


def _excepthook(tp, val, tb):
    try:
        dump_postmortem("%s: %s" % (tp.__name__, val))
    except Exception:
        pass  # the postmortem must never mask the real crash
    (_orig_excepthook or sys.__excepthook__)(tp, val, tb)


def _at_exit():
    stop_emitter()
    try:
        skipped = _profiler().step_stats()["skipped_steps"]
        if skipped and not _dumped:
            dump_postmortem(
                "atexit: run ended with %d divergence-guard skipped "
                "steps" % skipped)
    except Exception:
        pass


def install_crash_hooks():
    """Chain the postmortem dump into sys.excepthook (covers unhandled
    MXNetError — e.g. the divergence guard's K-consecutive-skips raise —
    and every other crash) and register the atexit skipped-steps dump.
    Idempotent; installed at import."""
    global _orig_excepthook, _hooks_installed
    if _hooks_installed:
        return
    _hooks_installed = True
    _orig_excepthook = sys.excepthook
    sys.excepthook = _excepthook
    atexit.register(_at_exit)


# -- periodic JSON-lines emitter -------------------------------------------
_emitter = None
# serializes line emission: the periodic thread, the stop-path final
# line, and any future explicit flush must never interleave their bytes
# in the stream file (a report line easily exceeds stdio's buffer, so
# two concurrent buffered writers WOULD interleave mid-line)
_emit_lock = threading.Lock()


def _parse_emitter_spec(spec):
    """``path[:interval]`` — a trailing ``:<float>`` is the period in
    seconds (default 10); everything else is the path (so paths with
    colons still work as long as the last segment isn't a number)."""
    path, sep, tail = spec.rpartition(":")
    if sep:
        try:
            return path, max(0.05, float(tail))
        except ValueError:
            pass
    return spec, 10.0


def _emit_line(path, final=False, lock_timeout=None):
    """Append one report line as a SINGLE ``os.write`` on an O_APPEND
    fd: all-or-nothing against a crash (``os._exit``, SIGKILL) landing
    mid-line, where a buffered ``f.write`` flushes in stdio-buffer-sized
    chunks and a death between chunks leaves a torn line the reader must
    skip.  The final line (stop/atexit path) carries the flight ring —
    the same last-K per-step records a crash postmortem gets — plus a
    ``final`` marker, so the job aggregator can trace a cleanly-exited
    rank's recent steps too.

    ``lock_timeout`` bounds the ``_emit_lock`` acquire — the
    stop_emitter fallback runs at atexit and must skip its line rather
    than hang shutdown behind a thread wedged mid-write (e.g. os.write
    to a hung mount) still holding the lock."""
    try:
        doc = report()
        if final:
            doc["final"] = True
            doc["last_steps"] = flight_records()
        if not _emit_lock.acquire(
                timeout=-1 if lock_timeout is None else lock_timeout):
            return
        evs = dropped = None
        try:
            # request-scope events recorded since the previous line:
            # the stream accumulates the full lifecycle record one
            # bounded payload at a time (each event ships exactly once;
            # evictions that outran the emitter are declared, never
            # silent).  Consumed only once the lock is HELD — and
            # rolled back if the write fails below — so a skipped or
            # failed line never silently swallows the cursor advance.
            evs, dropped = consume_request_events()
            if evs:
                doc["req_events"] = evs
            if dropped:
                doc["req_dropped"] = dropped
            data = (json.dumps(doc) + "\n").encode("utf-8")
            fd = os.open(path, os.O_WRONLY | os.O_APPEND | os.O_CREAT,
                         0o644)
            try:
                os.write(fd, data)
            finally:
                os.close(fd)
        except Exception:
            _unconsume_request_events(evs, dropped)
            raise
        finally:
            _emit_lock.release()
    except Exception:
        pass  # telemetry must never take the run down


def start_emitter(path, interval=10.0):
    """Append one report() line to ``path`` every ``interval`` seconds
    from a daemon thread (plus a final line on stop/exit)."""
    global _emitter
    stop_emitter()
    stop = threading.Event()
    state = {"final": False}

    def loop():
        while not stop.wait(interval):
            _emit_line(path)
        # final line so short runs still leave a trace; the flag keeps
        # the stop path from double-writing it when the join times out —
        # set only AFTER the write returns, so a thread wedged INSIDE
        # its final flush (report() blocked on a lock, os.write to a
        # hung mount) still looks unfinished to stop_emitter's fallback
        _emit_line(path, final=True)
        state["final"] = True

    t = threading.Thread(target=loop, daemon=True,
                         name="mxtpu-telemetry-emitter")
    t.start()
    _emitter = (t, stop, path, state)
    return t


def stop_emitter():
    global _emitter
    if _emitter is None:
        return
    t, stop, path, state = _emitter
    _emitter = None
    stop.set()
    t.join(timeout=5.0)
    if t.is_alive() and not state["final"]:
        # emitter thread wedged mid-report (it never reached its final
        # flush): write the final line from the caller — bounded lock
        # acquire, because the wedged thread may be stuck INSIDE a
        # write still holding _emit_lock, and this path runs at atexit
        # where blocking forever would convert a lost final line into a
        # hung shutdown.  If the lock does come, the two lines land
        # whole, never interleaved.
        _emit_line(path, final=True, lock_timeout=2.0)


def _maybe_start_emitter():
    spec = os.environ.get("MXTPU_TELEMETRY")
    if not spec:
        return
    path, interval = _parse_emitter_spec(spec)
    if not path:
        # telemetry must never take the run down — and this runs at
        # import time, where a raise would kill every process in the env
        import logging
        logging.warning(
            "mxnet_tpu: bad MXTPU_TELEMETRY spec %r (want "
            "path[:interval]); emitter disabled", spec)
        return
    start_emitter(path, interval)


install_crash_hooks()
_install_compile_hook()
install_default_alert_rules()
_maybe_start_emitter()
