"""Hang defense: progress leases, stall watchdog, worker heartbeats.

The reference framework's ps-lite servers carried heartbeat/recovery
hooks (src/kvstore/kvstore_dist.h:59-62); the all-reduce rebuild replaced
them with "the launcher notices a worker *exit*" — but a worker that
HANGS (wedged prefetcher, stuck NFS checkpoint write, peer loss inside a
collective, a coordinator that never comes up) strands the whole job
silently and forever.  This module converts hangs into the retryable
crashes the checkpoint-restart machinery (PR 2) already handles:

- **progress leases** — named monotonic-clock stores the training hot
  paths renew on every unit of progress (``fit_step`` per batch,
  ``trainer_step`` per Trainer.step, ``data`` per consumed batch).  One
  dict/list store per renewal, about the cost of the PR 3 flight-record
  append; no dispatches, no locks.
- **scoped guards** — ``with watchdog.guard("kv.barrier"):`` arms a
  lease for the duration of one blocking operation (collectives,
  checkpoint writes) so a hang *inside* it is detected even though the
  op never "progresses".
- **the watchdog thread** — armed per training run (auto-armed by the
  first renewal/guard when ``MXTPU_STALL_TIMEOUT`` is set; ``fit`` arms
  and disarms it explicitly).  On lease expiry — or no first renewal
  within ``MXTPU_STARTUP_GRACE``, the separate deadline covering XLA
  compile — it dumps all-thread stack traces plus the telemetry flight-
  recorder postmortem, then hard-exits with ``EXIT_STALL`` (75,
  EX_TEMPFAIL), which ``tools/launch.py:classify_exit`` maps to
  ``retryable: stall`` → kill + restart from checkpoints.
- **heartbeats** — when the launcher exports ``MXTPU_HEARTBEAT_DIR``,
  a daemon thread touches ``hb-<rank>.json`` (step + phase) every
  ``MXTPU_HEARTBEAT_INTERVAL`` seconds.  The launcher watches mtimes and
  escalates SIGTERM→SIGKILL on a rank gone quiet — catching the stalls
  the in-process watchdog can't see (a worker wedged in native code
  holding the GIL, or swapped out: nothing in this interpreter runs, so
  only an outside observer notices).

Telemetry (OBSERVABILITY.md): ``watchdog.stalls`` counter,
``watchdog.lease_age`` gauge (worst current age, maintained per poll),
``watchdog.heartbeats`` counter.  ROBUSTNESS.md §7 is the lease
names / exit-code / env-var contract.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
import traceback

__all__ = ["EXIT_STALL", "EXIT_PORT_IN_USE", "arm", "maybe_arm", "disarm",
           "armed", "renew", "release", "guard", "stall_timeout",
           "startup_grace", "note_warm_start", "dump_stacks", "snapshot",
           "start_heartbeat", "stop_heartbeat", "heartbeat_path"]

EXIT_STALL = 75         # EX_TEMPFAIL: stall detected — retryable by launcher
EXIT_PORT_IN_USE = 76   # coordinator port bind failure — retryable, re-pick

_lock = threading.Lock()          # arm/disarm/guard bookkeeping only
_leases = {}    # key -> [renewed_monotonic, timeout_or_None, step, display]
_guard_seq = 0
_progressed = False    # primary (step) renewal since arm — ends grace
_any_progress = False  # ANY renewal/completed guard — retires "startup"
_armed = False
_armed_at = 0.0
_timeout = 0.0
_grace = 0.0
_stop = None           # threading.Event of the live watchdog thread
_thread = None
_on_stall = None
_progress = {"step": 0, "phase": "startup"}   # heartbeat display state
_hb = None             # (thread, stop_event, path)
_warm_started = False  # AOT warm start seen (shrinks startup grace)


def _env_float(name, default):
    try:
        return float(os.environ.get(name, ""))
    except ValueError:
        return default


def stall_timeout():
    """Configured lease timeout in seconds (0 = hang defense off)."""
    return _env_float("MXTPU_STALL_TIMEOUT", 0.0)


def startup_grace(timeout=None):
    """First-progress deadline: XLA compilation of the fused step (plus
    distributed bring-up) legitimately dwarfs a steady-state step, so the
    no-lease-yet window gets its own, longer budget."""
    g = _env_float("MXTPU_STARTUP_GRACE", 0.0)
    if g > 0:
        return g
    t = stall_timeout() if timeout is None else timeout
    return max(4.0 * t, 120.0)


def note_warm_start():
    """An AOT warm start happened: the fused step deserialized from the
    executable cache (executor.make_fit_step), so the dominant cost the
    startup grace exists to cover — XLA compilation — is gone from this
    process.  Shrink the armed watchdog's grace window to
    ``max(2×timeout, 30s)`` so a wedged warm restart is diagnosed in
    seconds instead of minutes.  Only ever shrinks (a cold program may
    still compile later in mixed warm/cold processes), never drops below
    the steady-state timeout, and an explicit MXTPU_STARTUP_GRACE wins
    outright — the operator's number is a contract."""
    global _grace, _warm_started
    _warm_started = True  # a later arm() applies the shrink too
    with _lock:
        if _armed:
            _grace = _warm_grace(_grace, _timeout)


def _warm_grace(grace, timeout):
    """The warm-start grace clamp, shared by note_warm_start (shrink an
    armed watchdog in place) and arm (apply a shrink seen before
    arming): only ever narrows ``grace``, never below the steady-state
    ``timeout``; an explicit MXTPU_STARTUP_GRACE is an operator contract
    and wins outright."""
    if _env_float("MXTPU_STARTUP_GRACE", 0.0) > 0:
        return grace
    return max(timeout, min(grace, max(2.0 * timeout, 30.0)))


# -- progress leases --------------------------------------------------------
def renew(name, step=None, phase=None, primary=True):
    """Record progress for lease ``name``: one monotonic-clock store (the
    whole hot-path cost — same order as the flight-record append).  The
    first renewal creates the lease and, when MXTPU_STALL_TIMEOUT is set,
    arms the watchdog, so any training entrypoint self-arms.

    ``primary=False`` marks auxiliary leases (the DataLoader's ``data``):
    they are watched but must NOT end the startup-grace window — the
    first data batch is delivered *before* the first fused step compiles,
    and closing grace there would expire the data lease during the very
    compile the grace exists to cover.  (Auxiliary renewals still count
    as evidence of life for the empty-table "startup" rule: an
    inference-only process that consumed batches must never be declared
    stalled-at-startup after its loader closes.)"""
    global _progressed, _any_progress
    lease = _leases.get(name)
    now = time.monotonic()
    if lease is None:
        _leases[name] = lease = [now, None, 0, name]
        if not _armed:
            maybe_arm()
    lease[0] = now
    lease[2] = lease[2] + 1 if step is None else step
    _any_progress = True
    if primary and not _progressed:
        # the first completed STEP ends the grace window for everyone:
        # leases that aged through it (a data batch prefetched before
        # the first fused step finished compiling) restart their clocks
        # now, or they would be instantly over their steady-state limit
        for other in _leases.values():
            if other[0] < now:
                other[0] = now
        _progressed = True
    _progress["step"] = lease[2]
    _progress["phase"] = phase or name


def release(name):
    """Retire a lease (end of an iterator / training phase): a released
    lease can no longer expire."""
    _leases.pop(name, None)


class guard:
    """Scoped lease for one blocking operation: entering records the
    clock, exiting retires the lease — so a hang *inside* (a peer-loss
    deadlock in a collective, a stuck NFS write) expires it even though
    no renewal will ever come.  Concurrent same-name guards get distinct
    keys; ``timeout=None`` uses the global stall timeout."""

    __slots__ = ("name", "timeout", "_key")

    def __init__(self, name, timeout=None):
        self.name = name
        self.timeout = timeout

    def __enter__(self):
        global _guard_seq
        with _lock:
            _guard_seq += 1
            self._key = "%s#%d" % (self.name, _guard_seq)
        _leases[self._key] = [time.monotonic(), self.timeout, 0, self.name]
        if not _armed:
            maybe_arm()
        return self

    def __exit__(self, *exc):
        global _any_progress
        _leases.pop(self._key, None)
        # a completed guarded op (checkpoint written, barrier passed) is
        # evidence of life: the empty-table "startup" rule must not kill
        # a process that only ever does guarded work
        _any_progress = True
        return False


# -- the watchdog thread ----------------------------------------------------
def arm(timeout=None, grace=None, on_stall=None):
    """Start the watchdog thread.  ``timeout`` defaults to
    MXTPU_STALL_TIMEOUT (<=0 → not armed, return False); ``grace`` to
    MXTPU_STARTUP_GRACE.  ``on_stall(name, age, timeout)`` overrides the
    dump-and-exit(75) handler — tests observe stalls in-process with it.
    Idempotent while armed; returns True iff THIS call armed (the caller
    that armed is the one that should ``disarm()``)."""
    global _armed, _armed_at, _timeout, _grace, _stop, _thread, \
        _on_stall, _progressed, _any_progress
    t = stall_timeout() if timeout is None else float(timeout)
    if t <= 0:
        return False
    with _lock:
        if _armed:
            return False
        _armed = True
        _progressed = False    # the grace window restarts with arming
        _any_progress = False  # so does the startup-liveness record
        _armed_at = time.monotonic()
        _timeout = t
        _grace = startup_grace(t) if grace is None else float(grace)
        if _warm_started and grace is None:
            # the fused step already warm-started from the AOT cache
            # before arming: no compile left to cover (note_warm_start)
            _grace = _warm_grace(_grace, t)
        _on_stall = on_stall or _default_on_stall
        # age accrued while nobody was watching must not count: a lease
        # last renewed long before arming (a Trainer that trained a
        # while, then the run opted in) would otherwise expire on the
        # first poll tick
        for lease in _leases.values():
            if lease[0] < _armed_at:
                lease[0] = _armed_at
        _stop = threading.Event()
        _thread = threading.Thread(target=_watch, args=(_stop,),
                                   daemon=True, name="mxtpu-watchdog")
        _thread.start()
    return True


def maybe_arm():
    """Arm iff MXTPU_STALL_TIMEOUT is set — the env var is the opt-in;
    without it training runs exactly as before this module existed."""
    return arm()


def disarm():
    """Stop the watchdog and clear every lease (end of the training run:
    post-training phases must not trip over stale training leases)."""
    global _armed, _stop, _thread
    with _lock:
        if not _armed:
            _leases.clear()
            return
        _armed = False
        stop, thread = _stop, _thread
        _stop = _thread = None
    stop.set()
    if thread is not threading.current_thread():
        thread.join(timeout=5.0)
    _leases.clear()


def armed():
    return _armed


def _watch(stop):
    poll = min(1.0, max(0.02, min(_timeout, _grace) / 4.0))
    gauge = None
    while not stop.wait(poll):
        now = time.monotonic()
        worst = 0.0
        expired = None
        for key, lease in list(_leases.items()):
            age = now - lease[0]
            worst = max(worst, age)
            limit = lease[1] if lease[1] else _timeout
            if not _progressed:
                # grace extends to every lease until the first renewal:
                # a scoped guard or a prefetched-data lease alive while
                # the first fused step compiles must get the same
                # compile-sized budget as the step itself
                limit = max(limit, _grace)
            if age > limit:
                expired = (lease[3], age, limit)
                break
        if expired is None and not _leases and not _any_progress and \
                now - _armed_at > _grace:
            # nothing EVER happened within the grace window — bring-up
            # or the first step is wedged.  Once any renewal (primary or
            # auxiliary) or completed guard has been seen, an empty
            # lease table just means idle (training done, loader closed,
            # guard exited), never a stall: progress is only demanded of
            # code that holds a lease.
            expired = ("startup", now - _armed_at, _grace)
        if expired is not None:
            handler = _on_stall
            if handler is not None:
                handler(*expired)
            return
        try:
            if gauge is None:
                from . import telemetry as _telemetry
                gauge = _telemetry.gauge("watchdog.lease_age")
            gauge.set(worst)
        except Exception:
            pass  # interpreter teardown


def dump_stacks():
    """All-thread stack traces as one string (the "where is everyone
    wedged" half of the stall postmortem)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for ident, frame in sorted(sys._current_frames().items()):
        out.append("Thread %s (%s):\n%s" % (
            ident, names.get(ident, "?"),
            "".join(traceback.format_stack(frame))))
    return "\n".join(out)


def snapshot():
    """JSON-able watchdog state for the postmortem: armed flag, per-lease
    age/timeout/step, heartbeat path, current progress marker."""
    now = time.monotonic()
    hb = _hb  # capture: stop_heartbeat may null the slot mid-snapshot
    return {
        "armed": _armed,
        "warm_start": _warm_started,
        "timeout": _timeout if _armed else stall_timeout(),
        "grace": _grace if _armed else startup_grace(),
        "progress": dict(_progress),
        "heartbeat": hb[2] if hb else None,
        "leases": {
            lease[3]: {"age_s": now - lease[0],
                       "timeout_s": lease[1] if lease[1] else
                       (_timeout or stall_timeout()) or None,
                       "step": lease[2]}
            for lease in list(_leases.values())},
    }


def _default_on_stall(name, age, limit):
    """Diagnose, then die retryable: stderr + file stack dump, flight-
    recorder postmortem, ``os._exit(EXIT_STALL)``.  A hard exit on
    purpose — the stalled thread cannot be raised into, and a wedged
    native call would swallow anything softer."""
    try:
        from . import telemetry as _telemetry
        _telemetry.counter("watchdog.stalls").inc()
    except Exception:
        pass
    reason = ("stall: lease '%s' expired (age %.1fs > timeout %.1fs); "
              "dumping stacks + postmortem, exiting %d (retryable)"
              % (name, age, limit, EXIT_STALL))
    stacks = dump_stacks()
    try:
        sys.stderr.write("mxnet_tpu.watchdog: %s\n%s\n" % (reason, stacks))
        sys.stderr.flush()
    except Exception:
        pass
    pm_dir = os.environ.get("MXTPU_POSTMORTEM_DIR")
    d = pm_dir or os.environ.get("MXTPU_HEARTBEAT_DIR")
    if d:
        try:
            os.makedirs(d, exist_ok=True)
            from .checkpoint import _plain_atomic_write
            _plain_atomic_write(
                os.path.join(d, "stall-stacks-%d.txt" % os.getpid()),
                ("%s\n\n%s" % (reason, stacks)).encode("utf-8"))
        except Exception:
            pass
    # the postmortem walks telemetry's locks — if the stall is wedged
    # under one of them the dump would hang and defeat the watchdog, so
    # it runs in a side thread with a bounded join.  Without a
    # postmortem dir it falls back to the heartbeat run dir (which the
    # launcher preserves when diagnostics landed there), so
    # launcher-spawned workers always leave a full diagnosis.
    def _dump():
        try:
            from . import telemetry as _telemetry
            _telemetry.dump_postmortem(
                reason, path=None if pm_dir or not d else
                os.path.join(d, "postmortem-%d.json" % os.getpid()))
        except Exception:
            pass
    t = threading.Thread(target=_dump, daemon=True)
    t.start()
    t.join(timeout=10.0)
    os._exit(EXIT_STALL)


# -- heartbeats (the launcher-side liveness channel) ------------------------
def heartbeat_path(dirpath, rank):
    return os.path.join(dirpath, "hb-%s.json" % rank)


def start_heartbeat(dirpath=None, rank=None, interval=None):
    """Touch ``hb-<rank>.json`` under ``dirpath`` every ``interval``
    seconds from a daemon thread.  The *mtime* is the liveness signal the
    launcher watches; the content (step/phase from the newest lease
    renewal) is the human-facing "where was it" record.  Liveness means
    the interpreter scheduled this thread — a worker wedged in native
    code under the GIL, or swapped out, goes quiet and the launcher kills
    it; in-process logical stalls are the watchdog thread's job."""
    global _hb
    dirpath = dirpath or os.environ.get("MXTPU_HEARTBEAT_DIR")
    if not dirpath:
        return None
    if rank is None:
        rank = os.environ.get("MXTPU_WORKER_RANK",
                              os.environ.get("DMLC_WORKER_ID", "0"))
    if interval is None:
        interval = max(0.05, _env_float("MXTPU_HEARTBEAT_INTERVAL", 1.0))
    stop_heartbeat()
    try:
        os.makedirs(dirpath, exist_ok=True)
    except OSError:
        return None
    path = heartbeat_path(dirpath, rank)
    stop = threading.Event()

    def beat():
        counter = None
        while True:
            try:
                tmp = "%s.tmp-%d" % (path, os.getpid())
                with open(tmp, "w") as f:
                    f.write(json.dumps({
                        "pid": os.getpid(), "rank": str(rank),
                        "step": _progress["step"],
                        "phase": _progress["phase"],
                        "t_unix": time.time()}))
                os.replace(tmp, path)
                if counter is None:
                    from . import telemetry as _telemetry
                    counter = _telemetry.counter("watchdog.heartbeats")
                counter.inc()
            except Exception:
                pass  # a sick filesystem must not kill the worker
            if stop.wait(interval):
                return

    t = threading.Thread(target=beat, daemon=True,
                         name="mxtpu-heartbeat")
    t.start()
    _hb = (t, stop, path)
    return path


def stop_heartbeat():
    """Retire the heartbeat thread (tests use this to simulate a worker
    whose interpreter is wedged: the file goes quiet, the launcher
    escalates)."""
    global _hb
    if _hb is None:
        return
    t, stop, _ = _hb
    _hb = None
    stop.set()
    t.join(timeout=5.0)


def _maybe_start_heartbeat():
    """Import-time hook (mxnet_tpu/__init__): workers spawned by
    tools/launch.py find MXTPU_HEARTBEAT_DIR in their env and immediately
    become launcher-observable."""
    if os.environ.get("MXTPU_HEARTBEAT_DIR"):
        start_heartbeat()
