"""AOT executable cache: the compiled train step as a persistable artifact.

Every restart under ``tools/launch.py`` used to re-trace and re-compile
the fused fit step from scratch — the watchdog needs a startup grace of
``max(4×timeout, 120s)`` mostly to cover that XLA compile.  Treating the
compiled program as a deployable (the TVM ahead-of-time thesis,
PAPERS.md) removes it from restart latency entirely:

- on the first compile, ``executor.make_fit_step`` serializes the XLA
  executable (``jax.experimental.serialize_executable``) plus its
  pickled in/out pytree defs into this content-addressed cache;
- a restarted rank with the same cache key deserializes and runs, and
  tells the watchdog the startup grace can shrink
  (:func:`mxnet_tpu.watchdog.note_warm_start`).

**The donated-deserialize hazard.**  On the CPU backend (reproduced
against jaxlib 0.4.36; on 0.9.0 a handful of forced-donated warm starts
ran clean under ``MALLOC_CHECK_=3``, which is not yet proof — ROADMAP D5
settles it and then this machinery goes) executing a *deserialized*
executable whose
program has ``donate_argnums`` input-output aliasing corrupts the process
heap: flaky SIGSEGV/SIGABRT inside ``execute_sharded``, double-frees at
interpreter teardown, occasionally deterministic wrong numerics — all
reproduced standalone with ``MALLOC_CHECK_=3`` (ROBUSTNESS.md §8; jax's
own persistent compilation cache triggers the same bug when it replays a
donated program).  Donation-free deserialized executables are sound.  So
an entry stores ONE variant chosen per backend:

- ``donated`` (TPU-class backends): the real fused step, deserialized and
  run as-is — no trace, no compile;
- ``plain`` (CPU): a donation-free twin.  A warm restart deserializes the
  twin for an instant first step, then the executor compiles the donated
  program in a background thread and hot-swaps it in — restart latency
  AND steady-state throughput, neither paying for the other
  (``executor._twin_hotswap``).

An in-process memo fronts the disk layer: a module rebuild in the same
process (optimizer reconfiguration, divergence recovery) reuses the
ORIGINAL compiled object — always safe, zero cost, any backend.

The cache key covers everything that makes an executable unusable when
it changes: the backend/jax/jaxlib/XLA_FLAGS fingerprint (an executable
is object code for one runtime + compiler-flag set), the full input
tree structure + shapes + dtypes (params, optimizer state, data/label,
aux), and the graph + optimizer-config hash the Module passes in (the
symbol's ops and the mults/hyperparameters are baked into the traced
program — same-shape different-graph models must not collide).  A
changed key is simply a different sha256 — stale entries can never be
loaded, only missed.

Opt-in via ``MXTPU_AOT_CACHE_DIR`` (tools/launch.py exports a per-job
dir that survives restarts).  ``JAX_COMPILATION_CACHE_DIR`` — jax's own
persistent compile cache — is the fallback layer for the donation-free
programs this cache doesn't cover (eager init ops, rng, metrics); the
launcher exports both.  Donated programs are kept OUT of jax's cache by
:func:`bypass_persistent_cache` / :func:`donation_cache_guard` on
backends with the hazard.  Every failure path here (unpicklable,
version-mismatched, corrupt, unreadable) falls back to the normal
compile: the cache can only ever make a restart faster, never break it.

Telemetry (OBSERVABILITY.md): ``aot.cache_hits`` / ``aot.cache_misses``
/ ``aot.cache_errors`` / ``aot.memo_hits`` / ``aot.twin_compiles`` /
``aot.hotswaps`` counters, ``aot.deserialize`` / ``aot.serialize`` /
``aot.compile`` / ``aot.twin_compile`` / ``aot.hotswap_compile`` spans.
"""
from __future__ import annotations

import atexit
import contextlib
import hashlib
import os
import pickle
import threading

from . import telemetry as _telemetry

__all__ = ["enable_persistent_cache", "cache_dir", "enabled",
           "fingerprint", "cache_key", "load",
           "store", "variant", "deserialized_donation_safe",
           "deserialized_spmd_safe", "bypass_persistent_cache",
           "donation_cache_guard", "memo_get", "memo_put", "clear_memo",
           "drain", "spawn_variant_store", "twin_hotswap_cell"]

_FORMAT = "mxtpu-aot-5"  # bump to orphan every existing entry

#: variants an entry can carry (exactly one per entry; the writer picks
#: what its own backend can safely consume on restart)
VARIANT_DONATED = "donated"
VARIANT_PLAIN = "plain"


#: jax's persistent compilation cache when JAX_COMPILATION_CACHE_DIR is
#: unset: a FIXED path inside the checkout (git-ignored).  The path is
#: part of the cache's key, so a directory that moves never hits —
#: never derive it from a temporary name.  tools/launch.py exports the
#: same path to its workers.
DEFAULT_JAX_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable_persistent_cache():
    """Place jax's persistent compilation cache before the first
    compile (entry scripts call this: chip_smoke.py,
    tools/serve_worker.py).  Where the environment sets
    ``JAX_COMPILATION_CACHE_DIR`` jax already uses it and nothing is
    set in code; otherwise the cache goes to
    :data:`DEFAULT_JAX_CACHE_DIR`.  Returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax
        path = DEFAULT_JAX_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_dir():
    return os.environ.get("MXTPU_AOT_CACHE_DIR") or None


def enabled():
    return bool(cache_dir())


def deserialized_donation_safe():
    """Can this backend EXECUTE a deserialized executable that donates
    inputs?  False on CPU: the thunk runtime corrupted the heap
    replaying donated input-output aliasing from a deserialized
    executable (module docstring; ROBUSTNESS.md §8).  TPU/GPU PJRT
    serialization is the supported production path.  Override with
    ``MXTPU_AOT_FORCE_DONATED=1`` after a jaxlib upgrade proves clean."""
    if os.environ.get("MXTPU_AOT_FORCE_DONATED") == "1":
        return True
    import jax
    return jax.devices()[0].platform != "cpu"


def deserialized_spmd_safe():
    """Can this backend EXECUTE a deserialized MULTI-DEVICE (SPMD)
    executable at all?  False on CPU: beyond the donated hazard above,
    even the donation-FREE twin of an 8-device mesh program replayed
    from bytes flakily corrupts the heap ("corrupted double-linked
    list" aborts mid `execute_sharded`) or deadlocks its collective
    rendezvous (participants waiting forever at the all-gather) —
    reproduced standalone under MALLOC_CHECK_=3, PR-7 root cause
    (ROBUSTNESS.md §8; module docstring for what is known on 0.9.0).
    So on such backends mesh programs are never stored to or loaded
    from disk — the in-process memo (the ORIGINAL compiled object) is their only warm tier, and a
    cross-process restart pays one compile.  TPU-class PJRT
    serialization remains the supported production path.  Shares the
    ``MXTPU_AOT_FORCE_DONATED=1`` override (one jaxlib upgrade gate
    for both hazards)."""
    return deserialized_donation_safe()


def variant():
    """Which executable variant this process stores and loads."""
    return VARIANT_DONATED if deserialized_donation_safe() \
        else VARIANT_PLAIN


def fingerprint():
    """Runtime identity baked into every key: a serialized executable is
    object code for one (backend, jaxlib) pair, jax's x64 flag changes
    the avals Python scalars lower to, and compile-affecting environment
    (XLA_FLAGS, libtpu tuning args — jax's own persistent cache folds
    XLA flags into its key for the same reason) changes what the
    compiler would have produced.

    The **device topology** is part of that identity too: an executable
    embeds its device assignment (global device ids out of a
    process_count × local_device_count world), so a blob compiled by
    rank 1 of a 3-process job can neither run on rank 0 nor in the
    2-process world an elastic restart shrank to — before this was
    keyed, an elastic world-size change made every rank overwrite the
    shared entry with its own topology's blob and every OTHER topology
    deserialize-fail on it (discarding the entry, so the cache never
    warmed).  Keyed per (world, rank position, local device set), a
    survivor re-hits its own entry across restarts at the same world
    size — the "where shapes allow" half of the elastic warm-start
    contract (ROBUSTNESS.md §9).

    The SAME device set under a different **mesh shape / input
    sharding** is likewise a different program — that half of the
    identity is per-program, not per-process, so it rides the
    ``extra`` argument of :func:`cache_key`:
    ``Executor._mesh_cache_extra`` folds mesh axes+sizes, flat device
    order, every input's PartitionSpec and the ZeRO-1 state specs into
    the key (a dp=8 and a dp=4 bind over one 8-device pool must never
    clobber each other — the same class of bug as the elastic topology
    clobber above)."""
    import jax
    import jaxlib
    from . import graph as _graph
    local = jax.local_devices()
    dev = local[0]
    return "|".join((_FORMAT, jax.__version__, jaxlib.__version__,
                     dev.platform, dev.device_kind,
                     "x64" if jax.config.jax_enable_x64 else "x32",
                     "proc%d/%d" % (jax.process_index(),
                                    jax.process_count()),
                     "dev%s/%d" % (",".join(str(d.id) for d in local),
                                   jax.device_count()),
                     # the graph rewrite pipeline decides what program a
                     # symbol lowers to: its version + enabled-pass set
                     # are program identity, so a rewritten graph can
                     # never replay a pre-rewrite executable (stale
                     # entries miss, and unusable ones unlink on load —
                     # the PR-5/7 staleness discipline)
                     _graph.pipeline_fingerprint(),
                     os.environ.get("XLA_FLAGS", ""),
                     os.environ.get("LIBTPU_INIT_ARGS", "")))


def cache_key(kind, trees, extra=""):
    """sha256 over the runtime fingerprint + a structural description of
    the program's inputs + the caller's config hash.  ``trees`` is any
    pytree of arrays / ShapeDtypeStructs / scalars; structure, shapes,
    and dtypes all land in the digest."""
    import jax
    import numpy as _np
    leaves, treedef = jax.tree_util.tree_flatten(trees)
    desc = [fingerprint(), kind, str(treedef), extra]
    for leaf in leaves:
        shape = getattr(leaf, "shape", ())
        dtype = getattr(leaf, "dtype", None)
        if dtype is None:
            dtype = _np.result_type(type(leaf))
        desc.append("%s:%s:%s" % (tuple(shape), _np.dtype(dtype).name,
                                  getattr(leaf, "weak_type", "")))
    return hashlib.sha256("\n".join(desc).encode("utf-8")).hexdigest()


def _path(key):
    return os.path.join(cache_dir(), "%s.aotx" % key)


# -- in-process memo -------------------------------------------------------
# key -> the ORIGINAL donated jax.stages.Compiled.  A same-process module
# rebuild (optimizer reconfigured, divergence recovery re-bind) reuses it
# directly: no serialization round-trip, so no deserialize hazard on any
# backend.  Unbounded in principle; in practice one entry per distinct
# (shapes, optimizer config) this process ever trained.

_memo = {}
_memo_lock = threading.Lock()


def memo_get(key):
    with _memo_lock:
        fn = _memo.get(key)
    if fn is not None:
        _telemetry.counter("aot.memo_hits").inc()
    return fn


def memo_put(key, compiled):
    with _memo_lock:
        _memo[key] = compiled


def clear_memo():
    """Forget in-process executables (tests use this to make a rebuild
    exercise the disk path the way a real process restart would)."""
    with _memo_lock:
        _memo.clear()


# -- persistent-cache quarantine for donated programs ----------------------

_bypass_lock = threading.Lock()
_bypass_depth = 0
_bypass_prev = None
_spmd_quarantined = False


def quarantine_persistent_cache_for_spmd():
    """Permanently disable jax's persistent compilation cache in THIS
    process — called from mesh construction (parallel.mesh.make_mesh)
    on backends where a deserialized SPMD executable is unsound
    (:func:`deserialized_spmd_safe`).  Once a mesh exists, ANY jitted
    op touching mesh-sharded arrays (per-op nd dispatches on outputs,
    metric updates, eval forwards) becomes an SPMD program; with
    ``JAX_COMPILATION_CACHE_DIR`` exported (tools/launch.py does by
    default) the NEXT process would replay them all from bytes and
    flakily corrupt its heap — observed as restart attempts dying with
    SIGSEGV/SIGABRT mid-fit while reruns pass.  Sacrificing jax's
    persistent cache in mesh processes on such backends is the only
    sound option; our own executable cache (independent machinery) and
    the in-process memo are unaffected.  No-op where deserialized SPMD
    execution is safe."""
    global _spmd_quarantined
    if _spmd_quarantined or deserialized_spmd_safe():
        return
    import jax
    with _bypass_lock:
        _spmd_quarantined = True
        jax.config.update("jax_enable_compilation_cache", False)
    import logging
    logging.info(
        "mxnet_tpu.aot_cache: mesh created on a backend that cannot "
        "replay deserialized SPMD executables — jax's persistent "
        "compilation cache is disabled for this process (the AOT "
        "executable cache and in-process memo still apply)")


@contextlib.contextmanager
def bypass_persistent_cache():
    """Compile a DONATED program outside jax's persistent compilation
    cache on backends with the donated-deserialize hazard: a cache hit
    would hand back a deserialized executable whose donation aliasing
    corrupts the heap (module docstring).  No-op where deserialized
    donation is safe.

    The flag is process-global and donated compiles can overlap (the
    hot-swap/twin background threads vs a foreground compile), so this
    is depth-counted: the first entry disables the cache, only the last
    exit restores it — no interleaving can re-enable the cache under a
    still-running donated compile or leave it stuck disabled.  A
    concurrent compile of a cacheable program on another thread can
    still lose its cache write while any bypass is held — a benign
    re-miss, never corruption."""
    if deserialized_donation_safe():
        yield
        return
    import jax
    global _bypass_depth, _bypass_prev
    with _bypass_lock:
        if _bypass_depth == 0:
            _bypass_prev = jax.config.jax_enable_compilation_cache
            jax.config.update("jax_enable_compilation_cache", False)
        _bypass_depth += 1
    try:
        yield
    finally:
        with _bypass_lock:
            _bypass_depth -= 1
            if _bypass_depth == 0:
                # a quarantine that landed while this bypass was active
                # must win over the captured pre-bypass state
                jax.config.update("jax_enable_compilation_cache",
                                  _bypass_prev and not _spmd_quarantined)


def donation_cache_guard(fn):
    """Wrap a donated jitted callable so any compile it performs runs
    under :func:`bypass_persistent_cache`.  For donated programs that
    compile lazily at dispatch (the mesh / fallback fused paths, gluon
    Trainer, data_parallel, gradient compression) where there is no
    discrete ``.compile()`` moment to wrap.  EVERY call is covered, not
    just the first: a shape-polymorphic jit retraces and recompiles on a
    new input shape (a short final batch, a different gradient size) and
    that compile must stay out of the persistent cache too.  The bypass
    is ~1µs per call (a depth-counted flag toggle; toggling does not
    invalidate jit caches) and a no-op on donation-safe backends.

    The backend probe is deferred to the first call, so wrapping at
    module import time stays free of backend-initializing side effects
    (a multi-host driver imports before jax.distributed.initialize)."""
    cell = {}

    def call(*args, **kwargs):
        safe = cell.get("safe")
        if safe is None:
            safe = cell["safe"] = deserialized_donation_safe()
        if safe:
            return fn(*args, **kwargs)
        with bypass_persistent_cache():
            return fn(*args, **kwargs)

    return call


# -- serialization ---------------------------------------------------------
# jax.experimental.serialize_executable is the whole job: ``serialize``
# pickles the unloaded executable (device and client references by
# persistent id) and ``deserialize_and_load`` rebuilds a
# jax.stages.Compiled on this process's backend.  The one thing it
# cannot know is WHICH of the backend's devices the program ran on (it
# defaults to all of them, so a one-device program comes back expecting
# a shard per device): entries carry the executable's ordered device
# ids.


def _serialize(compiled):
    """(pickled-executable, in_tree, out_tree, device_ids) for a
    jax.stages.Compiled."""
    from jax.experimental import serialize_executable as _se
    ser, in_tree, out_tree = _se.serialize(compiled)
    ids = [d.id for d in compiled.runtime_executable().local_devices()]
    return ser, in_tree, out_tree, ids


def _deserialize(ser, in_tree, out_tree, device_ids):
    import jax
    from jax.experimental import serialize_executable as _se
    by_id = {d.id: d for d in jax.devices()}
    return _se.deserialize_and_load(
        ser, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids])


def load(key):
    """Deserialize the cached executable for ``key``.  Returns
    ``(compiled, variant, meta)`` or None (missing / unreadable /
    version-skewed — any failure is a miss or a counted error).  An entry
    whose variant this backend cannot safely execute (a ``donated`` blob
    on a donation-unsafe backend, e.g. written under
    MXTPU_AOT_FORCE_DONATED) is discarded, not executed.  ``meta`` is
    the writer's JSON-able sidecar (compile-time cost/memory analysis —
    a deserialized executable cannot always re-derive it, so the
    original compile's numbers ride along)."""
    path = _path(key)
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError:
        _telemetry.counter("aot.cache_misses").inc()
        return None
    try:
        with _telemetry.span("aot.deserialize", cat="aot"):
            fmt, var, ser, in_tree, out_tree, ids, meta = \
                pickle.loads(blob)
            if fmt != _FORMAT:
                raise ValueError("format %r != %r" % (fmt, _FORMAT))
            if var == VARIANT_DONATED and not deserialized_donation_safe():
                raise ValueError("donated executable is not safe to "
                                 "execute on this backend")
            compiled = _deserialize(ser, in_tree, out_tree, ids)
    except Exception as e:
        # a stale/corrupt entry must cost one compile, never the run.
        # Unlink it so the next restart doesn't pay the failed parse
        # again (content-addressed: the slot re-fills on re-store).
        _telemetry.counter("aot.cache_errors").inc()
        import logging
        logging.warning("mxnet_tpu.aot_cache: discarding unusable cache "
                        "entry %s (%s: %s)", path, type(e).__name__, e)
        try:
            os.unlink(path)
        except OSError:
            pass
        return None
    _telemetry.counter("aot.cache_hits").inc()
    return compiled, var, meta


def store(key, compiled, var, meta=None):
    """Serialize ``compiled`` into the cache atomically (tmp+rename via
    the checkpoint layer's plain writer: cache entries must not consume
    ckpt fault budgets or pollute checkpoint metrics).  ``meta`` is an
    optional JSON-able sidecar stored alongside (the compile-time
    cost/memory attribution, republished as gauges on a warm load).

    A store that cannot store RAISES (after counting
    ``aot.cache_errors``): a warm-start layer that silently stops
    storing turns every restart into a cold compile with nothing to
    show for it.  Background stores (:func:`spawn_variant_store`) hold
    the error for the next :func:`drain`."""
    try:
        with _telemetry.span("aot.serialize", cat="aot"):
            ser, in_tree, out_tree, ids = _serialize(compiled)
            blob = pickle.dumps((_FORMAT, var, ser, in_tree, out_tree,
                                 ids, meta))
        os.makedirs(cache_dir(), exist_ok=True)
        from .checkpoint import _plain_atomic_write
        _plain_atomic_write(_path(key), blob)
    except Exception:
        _telemetry.counter("aot.cache_errors").inc()
        raise
    _telemetry.histogram("aot.entry_bytes").observe(len(blob))
    return True


# -- the shared §8 tiers: variant store + twin hot-swap --------------------
# ONE copy of the donated-deserialize policy's moving parts, used by every
# consumer of this cache (executor.make_fit_step, serving.ServingEngine).
# The hazard rules here have been patched repeatedly (ROBUSTNESS.md §8,
# PR 5/6/7); a per-caller copy would silently miss the next fix.


def spawn_variant_store(mk_jit, examples, key, compiled, meta=None,
                        where="aot_cache"):
    """Serialize this backend's consumable variant of ``compiled`` into
    the cache off the hot path.  Donation-safe backends store the
    donated program as-is; on hazard (CPU) backends a donation-free twin
    — the only variant a restart there can execute — is compiled first
    in the background, with its backend-compile events kept out of step
    accounting.  ``mk_jit(donated=False)`` must build the twin jit;
    ``meta`` (compile-time cost attribution) rides along either way."""
    from . import telemetry as _tel

    def work():
        if deserialized_donation_safe():
            store(key, compiled, VARIANT_DONATED, meta)
            return
        with _tel.suppress_compile_accounting():
            with _tel.span("aot.twin_compile", cat="aot"):
                twin = mk_jit(donated=False) \
                    .lower(*examples).compile()
        _tel.counter("aot.twin_compiles").inc()
        store(key, twin, VARIANT_PLAIN, meta)

    return spawn_background(work, "mxtpu-aot-store", where)


def twin_hotswap_cell(mk_jit, examples, key, twin, where="aot_cache"):
    """Warm hazard-backend start: run the deserialized donation-free
    ``twin`` NOW (instant first step), compile the donated program in
    the background (outside jax's persistent cache — §8), and swap it in
    between steps.  Returns a plain callable whose per-call cost is one
    dict read — callers wrap it in their own instrumentation."""
    from . import telemetry as _tel

    cell = {"fn": twin}

    def work():
        try:
            with _tel.suppress_compile_accounting():
                with _tel.span("aot.hotswap_compile", cat="aot"):
                    with bypass_persistent_cache():
                        donated = mk_jit().lower(*examples).compile()
            memo_put(key, donated)
            cell["fn"] = donated
            _tel.counter("aot.hotswaps").inc()
        except Exception as e:
            _tel.counter("aot.cache_errors").inc()
            import logging
            logging.warning("%s: donated hot-swap compile failed "
                            "(%s: %s); continuing on the donation-free "
                            "twin", where, type(e).__name__, e)

    spawn_background(work, "mxtpu-aot-hotswap")

    def call(*args):
        return cell["fn"](*args)

    return call


# -- background work (twin compiles, stores) -------------------------------
# Off-hot-path tasks the executor schedules: compiling the CPU twin after
# the cold first step, compiling the donated program after a warm twin
# start, serializing entries.  Daemon threads: a crash mid-task costs the
# next restart a recompile, nothing else.

_bg_threads = []
_bg_errors = []     # failures of background tasks, re-raised by drain()
_bg_lock = threading.Lock()


@atexit.register
def _drain_at_exit():
    """Bounded join of in-flight background compiles/stores at interpreter
    exit.  Daemon threads torn down MID-XLA-COMPILE make the runtime call
    std::terminate (observed with the SPMD fused step's hot-swap compile
    on CPU) — turning a clean exit into an abort.  Ten seconds covers any
    realistic twin/store; a genuinely wedged thread still only delays
    exit, never hangs it."""
    try:
        drain(timeout=10)
    except Exception as e:
        import logging
        logging.error("mxnet_tpu.aot_cache: background task failed "
                      "(%s: %s)", type(e).__name__, e)


def spawn_background(fn, name, where="aot_cache"):
    """Run ``fn`` on a daemon thread.  An exception it raises is kept
    and re-raised by the next :func:`drain` — a background store that
    fails must not pass for one that succeeded."""
    def run():
        try:
            fn()
        except Exception as e:
            import logging
            logging.error("%s: background task %s failed (%s: %s)",
                          where, name, type(e).__name__, e)
            with _bg_lock:
                _bg_errors.append(e)

    t = threading.Thread(target=run, name=name, daemon=True)
    # start BEFORE publishing: a concurrent drain() joining an unstarted
    # thread raises RuntimeError
    t.start()
    with _bg_lock:
        _bg_threads.append(t)
        # drop finished threads so long trainers don't accumulate handles
        _bg_threads[:] = [x for x in _bg_threads if x.is_alive() or x is t]
    return t


def drain(timeout=None):
    """Join pending background work (tests; also safe to call before
    process exit to maximise what the next restart finds in the cache).
    ``timeout`` bounds the WHOLE drain, not each join — two wedged
    threads cost ``timeout`` once, not twice.  Re-raises the first
    failure a background task recorded since the last drain."""
    import time as _time
    deadline = None if timeout is None else _time.monotonic() + timeout
    with _bg_lock:
        threads = list(_bg_threads)
    for t in threads:
        if deadline is None:
            t.join()
        else:
            remaining = deadline - _time.monotonic()
            if remaining <= 0:
                break
            t.join(remaining)
    with _bg_lock:
        _bg_threads[:] = [x for x in _bg_threads if x.is_alive()]
        errors = _bg_errors[:]
        del _bg_errors[:]
    if errors:
        raise errors[0]
    return not _bg_threads
