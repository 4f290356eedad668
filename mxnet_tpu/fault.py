"""Pluggable fault injection for the resilient-training runtime.

The recovery paths in this framework (atomic checkpoints, ``latest()``
fallback, the divergence-guarded fused step, launcher restarts) are only
trustworthy if they are *exercised*, not just written.  This layer lets
tests and soak runs inject faults deterministically at named sites:

    MXTPU_FAULT="ckpt.write.torn:1;grad.nan:0.1" python train.py

Spec grammar: ``site:spec`` pairs separated by ``;``.  ``spec`` is either
an integer count N (trigger the next N times the site is checked, then
disarm — deterministic) or a float probability in (0, 1) (trigger each
check with that probability from a seeded RNG — reproducible under
``MXTPU_FAULT_SEED``, default 0, and identical across worker ranks so
data-parallel replicas skip the same steps).

Sites wired in this package:

- ``ckpt.write.ioerror``  transient OSError inside atomic_write (exercises
                          the retry-with-backoff path; retried, recovers).
- ``ckpt.write.torn``     simulate the legacy non-atomic writer dying
                          mid-write: a truncated file appears at the FINAL
                          path, then FaultInjected (a crash stand-in).
- ``ckpt.write.crash``    crash after the tmp file is written but before
                          os.replace publishes it (no final-path artifact).
- ``nd.save``             crash at nd.save entry (nothing written).
- ``data.prefetch``       raise inside the DataLoader prefetch worker
                          (exercises cross-thread exception re-raise).
- ``grad.nan``            poison the global gradient tree of the fused
                          fit_step / Trainer step with NaN (exercises the
                          divergence guard's skip-update path).
- ``worker.stall``        wedge the train step (fit_step / Trainer.step)
                          in a lease-less sleep (watchdog detection).
- ``data.stall``          wedge the DataLoader prefetch producer.
- ``kv.hang``             wedge inside a KVStore collective/barrier
                          (peer-loss deadlock stand-in).
- ``ckpt.write.stall``    wedge an atomic_write (stuck NFS stand-in).
- ``worker.lost``         permanent rank death: hard ``os._exit(77)``
                          from the fit loop — no atexit hooks, no
                          cleanup, exactly a host vanishing.  Exit 77
                          is retryable to tools/launch.py, and elastic
                          mode (--elastic) evicts the rank after
                          ``--evict-after`` consecutive losses so the
                          job resumes at N-1 (ROBUSTNESS.md §9).
- ``step.slow``           bounded per-step delay inside Module.fit_step's
                          dispatch window (``MXTPU_FAULT_DELAY_SECS``,
                          default 0.05): a straggling rank — slow host,
                          thermal throttle, noisy neighbor — whose
                          inflated ``fit_step.dispatch`` p50 the job
                          aggregator's straggler blame must name
                          (tools/perf_probe/job_report.py).
- ``data.slow``           same bounded delay in the DataLoader prefetch
                          producer: input-starvation flavor of the
                          straggler (shows in ``data.prefetch_wait``,
                          not in the step phases).
- ``serve.decode.stall``  wedge the serving engine right before the
                          decode dispatch, renewing no lease — the
                          ``serve_step`` watchdog lease expires and the
                          replica dies 75 with a serving snapshot in
                          its postmortem (ISSUE 11).
- ``serve.prefill.error`` the admission prefill dispatch fails for ONE
                          request: it exits with the typed
                          ``prefill_error`` verdict, slot + reserved
                          pages released deterministically (no requeue
                          loop); the engine serves on.
- ``serve.replica.lost``  a serving replica dies mid-decode
                          (ReplicaLost from ServingReplica.step): the
                          router fails its accepted requests over to a
                          live replica at-most-once; standalone
                          replicas die retryable.
- ``serve.swap.torn``     poison a hot-swap's freshly loaded weight
                          tree (NaN) — the finite-logits canary decode
                          must catch it and roll the replica back to
                          its prior weights.
- ``io.shard.torn``       one stream decode task reads as a torn shard
                          tail (crashed-writer truncation stand-in):
                          the StreamLoader skips-and-counts it
                          (``io.torn_records``) and serves on.
- ``io.decode.error``     raise inside a stream decode worker
                          (exercises the worker-traceback-preserving
                          re-raise at the consumption point).
- ``serve.replica.sigkill``  REAL process death: hard
                          ``os.kill(os.getpid(), SIGKILL)`` from
                          ``ServingReplica.step`` — no cleanup, no
                          telemetry flush, no exception path; the
                          out-of-process fleet drill
                          (``tools/serve_worker.py``) the in-process
                          ``serve.replica.lost`` cannot fake.  The
                          launcher reaps rc -9 (retryable) and respawns
                          the slot; the router's proxy confirms the
                          death and fails accepted requests over.
- ``serve.spec.poison``   corrupt every speculative DRAFT token between
                          the drafter and the verify dispatch (ISSUE
                          16): batched verification must reject the
                          poisoned positions and the emitted stream
                          stay exactly the non-speculative one — the
                          self-correction law that makes draft quality
                          a throughput knob, never a correctness one.
- ``serve.kv.scale_poison`` corrupt one resident request's int8 page
                          scales (NaN into the K/V scale pools between
                          serving steps, ISSUE 20): the quantized
                          decode program's per-slot finite-logits
                          guard flags the victim, which rolls back and
                          re-prefills through the dense path —
                          ``serving.kv.scale_repairs`` counts it, the
                          repaired stream matches the unfaulted
                          reference, unpoisoned residents untouched.
- ``rpc.drop``            a serving RPC reply is blackholed: the server
                          processes the request (an accepted submit IS
                          journaled — the client retry dedups) but
                          never replies; the client's per-call deadline
                          is the only way out (serving/rpc.py).
- ``rpc.delay``           bounded server-side delay before an RPC reply
                          (``MXTPU_FAULT_DELAY_SECS``): the slow-wire
                          flavor — latency, not loss.
- ``rpc.conn.refused``    a serving RPC connection attempt fails
                          client-side (worker not up yet / already
                          gone): exercises the bounded retry + backoff
                          + jitter path deterministically.
- ``rpc.heartbeat.drop``  ONLY heartbeat replies are blackholed while
                          the data plane keeps answering (ISSUE 17):
                          the proxy must raise a suspicion (gauge +
                          counter) but NEVER confirm death — losing
                          the control plane alone is not a failover.
- ``rpc.partition``       asymmetric router→replica blackhole: every
                          RPC from the router parks unanswered while
                          the replica keeps decoding.  The router must
                          fail over AND fence the zombie — its late
                          completions come back under a fenced-out
                          incarnation and are rejected
                          (``rpc.fenced_results``), keeping
                          at-most-once through a split brain.
- ``serve.worker.zombie`` the worker swallows its ``drain`` RPC (no
                          ack, no drain): the supervisor's stop path
                          must escalate SIGTERM→SIGKILL and the
                          replacement come up under a fresh
                          incarnation the proxy confirms.
- ``io.decode.slow``      bounded per-task delay in the decode worker
                          (``MXTPU_FAULT_DELAY_SECS``): the INPUT
                          flavor of the straggler — shows in
                          ``io.queue_wait``/``data.prefetch_wait``,
                          never in the step phases, and job_report's
                          input-stall blame must name it.
- ``serve.stream.drop``   a ``poll`` reply is blackholed (delivery
                          plane only — submits, heartbeats and
                          telemetry pulls keep answering): the client's
                          per-call deadline expires and the idempotent
                          re-poll at the SAME cursor recovers exactly
                          the tokens the dropped reply carried — no
                          gap, no duplicate (ISSUE 19).

The ``*.slow`` DELAY sites are per-event and bounded (the run limps,
correctly); the ``*.stall``/``kv.hang`` sites simulate HANGS — they
sleep ``MXTPU_FAULT_STALL_SECS`` (default 3600) without renewing any
watchdog lease, so only the hang-defense layer (mxnet_tpu/watchdog.py,
tools/launch.py heartbeats) can end the run — exactly the production
failure mode they stand in for.

**Per-rank scoping**: ``MXTPU_FAULT_SLOTS="1,3"`` restricts an
env-provided ``MXTPU_FAULT`` spec to the worker slots listed (the
launcher exports one environment per job, but a straggler/loss drill
wants exactly one victim; slots are elastic-stable where ranks re-pack).
``MXTPU_FAULT_ATTEMPTS="0"`` additionally restricts it to specific
restart attempts (``MXTPU_RESTART_ATTEMPT``): a supervised RESPAWN
inherits its predecessor's environment, so a kill drill without attempt
scoping would re-arm in every replacement and crash-loop the slot.
Explicit ``configure(spec)`` calls are never scoped — a worker script
that arms its own rule means it.

``FaultInjected`` deliberately subclasses MXNetError, NOT OSError: the
retry loops treat OSError as transient but must never retry a simulated
crash.
"""
from __future__ import annotations

import os
import random as _random
import threading
import time as _time
import zlib

from .base import MXNetError

__all__ = ["FaultInjected", "EXIT_WORKER_LOST", "configure", "reset",
           "is_active", "trigger", "check", "stall_if", "delay_if",
           "exit_if", "fire_count", "fire_counts"]

# exit-code contract with tools/launch.py (WORKER_LOST_EXIT there):
# retryable, and the elastic policy counts it toward eviction
EXIT_WORKER_LOST = 77


class FaultInjected(MXNetError):
    """Raised at an injection site standing in for a crash/failure."""


_lock = threading.Lock()
_rules = {}        # site -> {"count": int} | {"rate": float, "rng": Random}
_fired = {}        # site -> times triggered
_loaded_env = None  # last MXTPU_FAULT value parsed (None = never)


def _parse(spec):
    rules = {}
    seed = int(os.environ.get("MXTPU_FAULT_SEED", "0"))
    for part in (spec or "").split(";"):
        part = part.strip()
        if not part:
            continue
        if ":" not in part:
            raise MXNetError(
                "bad MXTPU_FAULT entry %r (want site:count or site:rate)"
                % part)
        site, _, val = part.partition(":")
        site = site.strip()
        val = val.strip()
        try:
            if "." in val or "e" in val or "E" in val:
                rate = float(val)
                if not 0.0 < rate <= 1.0:
                    raise ValueError(val)
                # one RNG per site, seeded independently of check order at
                # other sites so a spec edit never reshuffles this site;
                # crc32 (NOT hash(): salted per process) keeps the draw
                # sequence identical across worker ranks and restarts
                rules[site] = {"rate": rate, "rng": _random.Random(
                    (seed << 32) ^ zlib.crc32(site.encode("utf-8")))}
            else:
                count = int(val)
                if count < 1:
                    raise ValueError(val)
                rules[site] = {"count": count}
        except ValueError:
            raise MXNetError("bad MXTPU_FAULT value %r for site %r"
                             % (val, site))
    return rules


def _scoped_out_by_slot():
    """True when MXTPU_FAULT_SLOTS names specific worker slots and this
    process's slot (MXTPU_WORKER_SLOT, falling back to rank) is not one
    of them — the env spec then applies to OTHER ranks of the job."""
    slots = os.environ.get("MXTPU_FAULT_SLOTS", "").strip()
    if not slots:
        return False
    mine = os.environ.get(
        "MXTPU_WORKER_SLOT",
        os.environ.get("MXTPU_WORKER_RANK", "0")).strip() or "0"
    return mine not in {s.strip() for s in slots.split(",") if s.strip()}


def _scoped_out_by_attempt():
    """True when MXTPU_FAULT_ATTEMPTS names specific restart attempts
    and this process's MXTPU_RESTART_ATTEMPT is not one of them.  The
    supervised-respawn drills need this: a launcher-spawned REPLACEMENT
    inherits the same environment as its predecessor, so an unscoped
    ``serve.replica.sigkill:1`` would re-arm in every respawn and
    kill-loop the slot forever — ``MXTPU_FAULT_ATTEMPTS=0`` arms the
    drill in the original incarnation only."""
    attempts = os.environ.get("MXTPU_FAULT_ATTEMPTS", "").strip()
    if not attempts:
        return False
    mine = os.environ.get("MXTPU_RESTART_ATTEMPT", "0").strip() or "0"
    return mine not in {a.strip() for a in attempts.split(",")
                        if a.strip()}


def configure(spec=None):
    """Install fault rules from ``spec`` (or the MXTPU_FAULT env when
    None).  Replaces any previous configuration; fire counters reset.
    Env-provided specs honor MXTPU_FAULT_SLOTS (module docstring);
    explicit specs always apply."""
    global _rules, _fired, _loaded_env
    if spec is None:
        spec = os.environ.get("MXTPU_FAULT", "")
        if spec and (_scoped_out_by_slot() or
                     _scoped_out_by_attempt()):
            spec = ""
    with _lock:
        _rules = _parse(spec)
        _fired = {}
        _loaded_env = spec


def reset():
    """Remove all rules and counters."""
    configure("")


def _ensure_loaded():
    # lazy env pickup so `import mxnet_tpu` stays side-effect free for
    # processes that never touch a fault site
    if _loaded_env is None:
        configure()


def is_active(site):
    """True if ``site`` still has a rule that can fire."""
    _ensure_loaded()
    with _lock:
        rule = _rules.get(site)
        if rule is None:
            return False
        if "count" in rule:
            return rule["count"] > 0
        return True


def trigger(site):
    """Roll the dice for ``site``; True means the caller must inject."""
    _ensure_loaded()
    fired = False
    with _lock:
        rule = _rules.get(site)
        if rule is None:
            return False
        if "count" in rule:
            if rule["count"] > 0:
                rule["count"] -= 1
                _fired[site] = _fired.get(site, 0) + 1
                fired = True
        elif rule["rng"].random() < rule["rate"]:
            _fired[site] = _fired.get(site, 0) + 1
            fired = True
    if fired:
        # outside _lock: telemetry takes its own registry lock and the
        # postmortem path reads fire_counts() under ours — never nest
        try:
            from . import telemetry as _telemetry
            _telemetry.note_fault(site)
        except Exception:
            pass  # interpreter teardown; the injection still happens
    return fired


def check(site, msg=None):
    """Raise FaultInjected when ``site`` triggers (crash-style sites)."""
    if trigger(site):
        raise FaultInjected("[fault injection] %s"
                            % (msg or "site %r fired" % site))


def stall_if(site):
    """Simulate a HANG when ``site`` triggers: sleep
    ``MXTPU_FAULT_STALL_SECS`` (default 3600) in short slices, renewing
    nothing.  Unlike :func:`check` nothing is raised — a real wedge has
    no exception either; detection belongs to the watchdog (lease
    expiry → exit 75) or the launcher (heartbeat mtime gone stale)."""
    if not trigger(site):
        return
    try:
        secs = float(os.environ.get("MXTPU_FAULT_STALL_SECS", "3600"))
    except ValueError:
        secs = 3600.0
    end = _time.monotonic() + secs
    while _time.monotonic() < end:
        _time.sleep(min(0.5, max(0.0, end - _time.monotonic())))


def delay_if(site, default_secs=0.05):
    """Inject a bounded per-event DELAY when ``site`` triggers: sleep
    ``MXTPU_FAULT_DELAY_SECS`` (default 0.05 s) and return.  Unlike
    :func:`stall_if` the run keeps making (slow) progress — this is the
    straggler stand-in, not the hang one: armed on one rank (via
    MXTPU_FAULT_SLOTS) it inflates that rank's phase percentiles so the
    job aggregator's skew detection has a deterministic victim to
    blame."""
    if not trigger(site):
        return
    try:
        secs = float(os.environ.get("MXTPU_FAULT_DELAY_SECS",
                                    str(default_secs)))
    except ValueError:
        secs = default_secs
    _time.sleep(max(0.0, secs))


def exit_if(site, code=EXIT_WORKER_LOST):
    """Simulate PERMANENT worker loss when ``site`` triggers: one stderr
    line naming the site, then ``os._exit(code)`` — hard, skipping
    atexit/excepthook/postmortem dumps, because the failure this stands
    in for (host dies, kernel OOM-kill, preemption) runs no cleanup
    either.  The launcher sees a retryable exit; with ``--elastic`` the
    rank is evicted once its consecutive-failure streak crosses
    ``--evict-after`` and the job resumes at N-1."""
    if not trigger(site):
        return
    import sys
    print("mxnet_tpu.fault: [fault injection] site %r fired — "
          "simulating permanent worker loss, hard exit %d"
          % (site, code), file=sys.stderr, flush=True)
    os._exit(code)


def fire_count(site):
    """How many times ``site`` has triggered since configure()."""
    with _lock:
        return _fired.get(site, 0)


def fire_counts():
    """Snapshot of {site: times fired} since configure() — the
    postmortem's fault attribution record."""
    with _lock:
        return dict(_fired)
