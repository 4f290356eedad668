#!/usr/bin/env python
"""Launch a distributed training job.

Port of /root/reference/tools/launch.py, re-targeted: the reference
spawned ps-lite scheduler/server/worker processes through dmlc_tracker
(ssh/mpi/sge/yarn, launch.py:59-84); the TPU-native framework has no
server processes — every worker is a JAX process in one collective mesh.

Launchers:
- ``local``: spawn N worker processes on this host wired together with
  ``jax.distributed`` (coordinator on 127.0.0.1).  Each worker sees the
  env contract DMLC_ROLE=worker, DMLC_NUM_WORKER, DMLC_WORKER_ID (kept
  for script compat) plus JAX_* coordination vars.  This is the
  reference's `--launcher local` fake-cluster test mode
  (tests/nightly/dist_sync_kvstore.py workflow).
- ``ssh``: run one worker per host from `-H hostfile` via ssh, pointing
  all of them at this host's coordinator port; monitored like local
  (first failure tears the job down, --max-restarts applies).

Failure handling: worker exits are classified retryable/permanent
(classify_exit) with exponential backoff between restarts; hangs are
caught by the per-rank heartbeat monitor (--heartbeat-timeout, files
touched by mxnet_tpu.watchdog under MXTPU_HEARTBEAT_DIR) and by the
in-process watchdog's stall exit code 75 — see ROBUSTNESS.md §5/§7.
Restarts warm-start: every attempt shares one AOT executable cache
(--aot-cache-dir → MXTPU_AOT_CACHE_DIR) and jax's persistent compile
cache (JAX_COMPILATION_CACHE_DIR where the environment sets it, else
the fixed ``<checkout>/.jax_cache`` — never a temporary name: the path
is part of the cache's key, so a directory that moves never hits), so a
restarted rank deserializes the compiled fit step instead of paying
trace+compile again.

The launcher itself stays off JAX (it imports neither jax nor
mxnet_tpu): a chip belongs to one process at a time, and a parent that
had touched JAX would hold the chip its workers need.

Elastic mode (``--elastic``, ROBUSTNESS.md §9): world size becomes a
per-restart decision.  Each worker slot (its stable identity across
attempts — hostfile line for ssh, original index locally) accumulates a
consecutive-failure count; when the same slot is blamed ``--evict-after``
times in a row, or its exit classifies permanent from attempt 1 on
(attempt-0 permanent failures still fail the job fast — a usage/import
error hits every rank identically), the next attempt drops
it — survivors are re-ranked contiguously (fresh
MXTPU_NUM_WORKERS/MXTPU_WORKER_RANK/DMLC_* exports, fresh coordinator
port) and resume from the newest complete checkpoint at N-1.  Evicted
slots sit out ``--readmit-after`` attempts, then rejoin (scale back up
toward ``-n``); ``--min-workers`` floors the shrink.  Every transition
is recorded in ``<run-dir>/membership.json``
(``tools/perf_probe/telemetry_report.py`` renders it).
Job-scope telemetry (``--telemetry-dir``, OBSERVABILITY.md §8): with a
run dir, every rank's JSON-lines telemetry stream (append-only per
slot), crash postmortem, and stall-stacks land in
``<run-dir>/telemetry/`` next to membership.json — one tree
``tools/perf_probe/job_report.py`` merges into a job timeline with
straggler blame and a cross-rank chrome trace.

- On real TPU pods, prefer the platform launcher (GKE/queued resources):
  every pod VM already runs one process; pass --use-env-ranks to adopt
  the platform-provided rank env instead of spawning.

Serving-fleet mode (``--serve``, SERVING.md §9): the command is run as
N INDEPENDENT serving-replica slots (tools/serve_worker.py) supervised
per-slot — serving has no collective, so one replica dying replaces
that replica instead of tearing the job down.  Exit 80 journals
drain/replace and respawns without blame; crashes/SIGKILL/stalls
respawn with backoff (AOT-warm via the shared cache) until
``--evict-after`` consecutive failures evict the slot; every
transition lands in ``<run-dir>/membership.json``.  Each slot
publishes ``<run-dir>/serve-port-slot<K>.json`` (the router-proxy
discovery + incarnation channel); ``<run-dir>/serve-stop`` stops the
fleet gracefully.
"""
from __future__ import annotations

import argparse
import json
import os
import shlex
import shutil
import signal
import socket
import struct
import subprocess
import sys
import tempfile
import time

# exit-code contract with mxnet_tpu/watchdog.py, mxnet_tpu/fault.py and
# mxnet_tpu/serving/replica.py (kept literal here: the launcher must
# work without the package importable on this host)
STALL_EXIT = 75         # EX_TEMPFAIL: watchdog stall — retryable
PORT_IN_USE_EXIT = 76   # coordinator port bind failure — retryable
WORKER_LOST_EXIT = 77   # worker.lost fault site: simulated permanent
                        # rank death — retryable; elastic mode evicts
SERVE_DRAIN_EXIT = 80   # graceful serving-replica drain — CLEAN: never
                        # blamed toward eviction; the restart spins an
                        # AOT-warm replacement (journaled drain/replace)

#: jax's persistent compilation cache when JAX_COMPILATION_CACHE_DIR is
#: unset — the same fixed in-checkout path
#: mxnet_tpu.aot_cache.enable_persistent_cache uses (kept literal here:
#: the launcher must not import the package)
DEFAULT_JAX_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


class _Membership:
    """Which worker slots are in the job, attempt by attempt.

    A *slot* is a worker's stable identity across the whole launch
    (locally its original index 0..n-1; over ssh its hostfile line), as
    opposed to its *rank*, the contiguous per-attempt index survivors
    are re-packed into.  Tracks per-slot consecutive-failure counts,
    evictions, and re-admissions, and journals every transition into
    ``<run-dir>/membership.json`` (schema ``mxtpu-membership-1``) so the
    job's shape over time survives the launcher process."""

    def __init__(self, args):
        self.total = args.num_workers
        self.active = list(range(args.num_workers))
        # consecutive-failure streak: only the LAST blamed slot can have
        # one (a failure blamed on any other slot resets it), so two
        # scalars state the invariant a per-slot map would only obscure
        self.blamed_slot = None
        self.streak = 0
        self.evicted_at = {}     # slot -> attempt whose failure evicted it
        self.transitions = []
        self.path = None
        run_dir = getattr(args, "run_dir", None)
        if run_dir:
            self.path = os.path.join(run_dir, "membership.json")
        self.record(0, "launch")

    @property
    def world_size(self):
        return len(self.active)

    def slot_of(self, rank):
        """Map a per-attempt contiguous rank back to its stable slot."""
        if 0 <= rank < len(self.active):
            return self.active[rank]
        return rank

    def record(self, attempt, event, **extra):
        entry = {"time": time.time(), "attempt": attempt, "event": event,
                 "world_size": self.world_size,
                 "active_slots": list(self.active),
                 "evicted_slots": sorted(self.evicted_at)}
        entry.update(extra)
        self.transitions.append(entry)
        self._flush()

    def _flush(self):
        if not self.path:
            return
        doc = {"schema": "mxtpu-membership-1", "total_slots": self.total,
               "transitions": self.transitions}
        tmp = "%s.tmp-%d" % (self.path, os.getpid())
        try:
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1)
            os.replace(tmp, self.path)
        except OSError as e:  # the journal must never take the job down
            print("launch.py: could not write %s: %s" % (self.path, e),
                  file=sys.stderr, flush=True)

    def note_failure(self, attempt, rank, rc, kind, reason):
        """Blame ``rank``'s slot for this attempt's failure; the streak
        is *consecutive* — a failure blamed on a different slot restarts
        it at 1.  Returns the blamed slot."""
        slot = self.slot_of(rank)
        self.streak = self.streak + 1 if slot == self.blamed_slot else 1
        self.blamed_slot = slot
        self.record(attempt, "failure", slot=slot, rank=rank, rc=rc,
                    kind=kind, reason=reason,
                    consecutive_failures=self.streak)
        return slot

    def evict(self, attempt, slot, reason):
        self.active.remove(slot)
        self.evicted_at[slot] = attempt
        self.record(attempt, "evict", slot=slot, reason=reason)

    def readmit_due(self, attempt, sit_out):
        """Evicted slots whose sit-out has elapsed by ``attempt``: a slot
        evicted after attempt k sits out attempts k+1..k+sit_out and is
        due again at k+sit_out+1."""
        return sorted(s for s, at in self.evicted_at.items()
                      if attempt > at + sit_out)

    def readmit(self, attempt, slot):
        del self.evicted_at[slot]
        if self.blamed_slot == slot:
            self.blamed_slot, self.streak = None, 0  # fresh on rejoin
        self.active = sorted(self.active + [slot])
        self.record(attempt, "readmit", slot=slot)


def _cache_env(args):
    """Warm-start env for workers: the AOT executable cache
    (mxnet_tpu.aot_cache — restarted ranks deserialize the compiled fit
    step instead of re-tracing + re-compiling it) plus jax's own
    persistent compilation cache as the fallback layer for every other
    program.  Both dirs outlive the launch and are reused across
    restart attempts — that persistence IS the feature.  Values already
    exported by the operator are never overridden."""
    if not getattr(args, "aot_cache_dir", None):
        return {}
    # Always export the resolved dirs: main() already made the operator's
    # choice (explicit flag > their env > the fixed default), and ssh
    # workers see ONLY this env string — the launcher's environment does
    # not ride along, so "already exported locally" must not suppress
    # the export.  Operator-set jax cache knobs are forwarded verbatim
    # for the same reason; the min-compile-time default of 0 exists
    # because jax's own threshold (1s) would skip most of a small
    # model's programs, and a restart wants them all.
    return {
        "MXTPU_AOT_CACHE_DIR": args.aot_cache_dir,
        "JAX_COMPILATION_CACHE_DIR":
            os.environ.get("JAX_COMPILATION_CACHE_DIR") or
            DEFAULT_JAX_CACHE_DIR,
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS":
            os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                           "0"),
    }


def _telemetry_env(args, slot):
    """Job-scope telemetry exports for one worker slot: a per-slot
    JSON-lines stream under ``<run-dir>/telemetry/`` plus the postmortem
    dir, so every rank's timeline, crash postmortem, and stall-stacks
    land in ONE tree next to membership.json (the input contract of
    tools/perf_probe/job_report.py).  Streams are keyed by SLOT, not
    rank: a slot's identity is stable across elastic re-rankings, the
    file is opened append-only by the worker, and every line carries the
    writing attempt's identity block — so attempt N's lines never
    overwrite attempt N-1's (schema mxtpu-telemetry-2).  Operator-set
    MXTPU_TELEMETRY / MXTPU_POSTMORTEM_DIR win (forwarded verbatim, for
    the same ssh-env reason as _cache_env)."""
    d = getattr(args, "telemetry_dir", None)
    if not d:
        return {}
    spec = os.environ.get("MXTPU_TELEMETRY")
    if not spec:
        spec = "%s:%s" % (os.path.join(d, "stream-slot%d.jsonl" % slot),
                          args.telemetry_interval)
    return {
        "MXTPU_TELEMETRY": spec,
        "MXTPU_POSTMORTEM_DIR":
            os.environ.get("MXTPU_POSTMORTEM_DIR") or d,
        # serving-scope layout (ISSUE 13): a Router in this slot
        # journals next to the replica streams (append-only per slot,
        # like the streams), so tools/perf_probe/serve_report.py finds
        # journal + streams + postmortems in ONE tree
        "MXTPU_SERVE_JOURNAL":
            os.environ.get("MXTPU_SERVE_JOURNAL") or
            os.path.join(d, "router-journal-slot%d.jsonl" % slot),
    }


def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _escalate_kill(procs, first_sig=signal.SIGTERM, grace=5.0):
    """Tear a job down with bounded patience: ``first_sig`` → wait up to
    ``grace`` → SIGTERM → ``grace`` → SIGKILL, then reap.  Every stop
    path (worker death, heartbeat stall, Ctrl-C) routes through here, so
    a worker that ignores polite signals — or is the very wedged process
    we are killing *because* it stopped responding — can delay teardown
    by at most 2×grace, never forever."""
    seq = []
    for sig in (first_sig, signal.SIGTERM, signal.SIGKILL):
        if not seq or seq[-1] != sig:
            seq.append(sig)
    for sig in seq:
        alive = [p for p in procs if p.poll() is None]
        if not alive:
            break
        for p in alive:
            try:
                p.send_signal(sig)
            except OSError:
                pass  # exited between poll and signal
        if sig == signal.SIGKILL:
            break
        deadline = time.time() + grace
        while time.time() < deadline and \
                any(p.poll() is None for p in procs):
            time.sleep(0.05)
    # bounded reap: even SIGKILL cannot collect a process stuck in
    # uninterruptible sleep (D-state — the hung-NFS case this defense
    # targets); waiting forever here would convert a detected worker
    # hang into an undetected launcher hang
    deadline = time.time() + max(grace, 5.0)
    for p in procs:
        try:
            p.wait(timeout=max(0.1, deadline - time.time()))
        except subprocess.TimeoutExpired:
            print("launch.py: giving up reaping pid %d (uninterruptible "
                  "sleep?); continuing teardown" % p.pid,
                  file=sys.stderr, flush=True)
        except Exception:
            pass


def _monitor_procs(args, procs, heartbeat_dir=None, label="worker"):
    """Watch a running job; returns ``(failed_rank, rc)`` — (None, 0) on
    clean completion, rank+code on the first failure (the job is torn
    down first), (-1, 1) on Ctrl-C.

    Two failure channels (the collective-era replacement for ps-lite's
    server heartbeat/recovery hooks, reference src/kvstore/
    kvstore_dist.h:59-62):

    - **exit**: a worker dying strands its peers inside a collective, so
      the launcher — not the survivors — detects the death and kills the
      job.
    - **heartbeat silence** (``--heartbeat-timeout`` > 0): a worker that
      *hangs* — wedged in native code under the GIL, swapped out, so
      even its in-process watchdog can't run — stops touching its
      per-rank heartbeat file (written by mxnet_tpu.watchdog inside the
      worker).  A stale mtime past the deadline is treated as a stall:
      the job is killed and the rank reported with the stall exit code
      (75), which classify_exit maps to retryable.  Workers that never
      wrote a heartbeat (non-mxnet commands) are not monitored.
    """
    try:
        while True:
            running = False
            for rank, p in enumerate(procs):
                rc = p.poll()
                if rc is None:
                    running = True
                elif rc != 0:
                    # one worker died — peers may be stranded in a
                    # collective; kill the job (politely first: peers
                    # flush telemetry postmortems on SIGTERM)
                    print("launch.py: %s %d exited with %d; "
                          "terminating remaining workers"
                          % (label, rank, rc), file=sys.stderr,
                          flush=True)
                    _escalate_kill(procs, signal.SIGTERM,
                                   args.kill_grace)
                    return rank, rc
            if not running:
                return None, 0
            if heartbeat_dir and args.heartbeat_timeout > 0:
                now = time.time()
                for rank, p in enumerate(procs):
                    if p.poll() is not None:
                        continue
                    hb = os.path.join(heartbeat_dir,
                                      "hb-%d.json" % rank)
                    try:
                        age = now - os.stat(hb).st_mtime
                    except OSError:
                        continue  # never wrote one: not monitored
                    if age > args.heartbeat_timeout:
                        print("launch.py: %s %d heartbeat silent for "
                              "%.1fs (deadline %.1fs) — declaring the "
                              "rank stalled and terminating the job"
                              % (label, rank, age,
                                 args.heartbeat_timeout),
                              file=sys.stderr, flush=True)
                        _escalate_kill(procs, signal.SIGTERM,
                                       args.kill_grace)
                        return rank, STALL_EXIT
            time.sleep(0.2)
    except KeyboardInterrupt:
        # bounded Ctrl-C teardown: SIGINT first (KeyboardInterrupt in
        # the worker → its finally blocks / atexit postmortems run),
        # then the escalation ladder — never an unbounded wait() on a
        # worker that swallows the signal
        print("launch.py: interrupt — stopping workers (SIGINT, then "
              "escalating after %.1fs grace)" % args.kill_grace,
              file=sys.stderr, flush=True)
        _escalate_kill(procs, signal.SIGINT, args.kill_grace)
        return -1, 1


def _worker_env(args, mem, world, rank, slot, attempt, prev_world):
    """The per-worker env contract for one attempt.  ``rank`` is the
    contiguous per-attempt index (what jax.distributed and DMLC_* see);
    ``slot`` is the launch-stable identity elastic eviction tracks —
    equal until a membership change re-packs the survivors."""
    env = {
        "MXTPU_NUM_WORKERS": str(world),
        "MXTPU_WORKER_RANK": str(rank),
        "MXTPU_WORKER_SLOT": str(slot),
        "MXTPU_RESTART_ATTEMPT": str(attempt),
        # lets a restarted worker count the cross-attempt world change
        # in its elastic.transitions telemetry (mxnet_tpu/elastic.py).
        # Always set — "" reads as unset — so a stale value inherited
        # from the launcher's own environment (nested launch, debug
        # shell reusing a worker env) can't fabricate a transition.
        "MXTPU_PREV_WORLD_SIZE":
            "" if prev_world is None else str(prev_world),
        # reference env contract (dmlc_tracker) for script compat
        "DMLC_ROLE": "worker",
        "DMLC_NUM_WORKER": str(world),
        "DMLC_NUM_SERVER": "0",
        "DMLC_WORKER_ID": str(rank),
    }
    env.update(_cache_env(args))
    env.update(_telemetry_env(args, slot))
    return env


def _run_local_once(args, cmd, attempt, mem, prev_world=None):
    """One local job attempt: spawn the active workers wired to a fresh
    coordinator port (``--port 0`` re-picks per attempt, so a port left
    wedged by the previous attempt is simply abandoned) plus a fresh
    heartbeat run dir, then monitor to completion or teardown."""
    port = args.port or _free_port()
    coordinator = "127.0.0.1:%d" % port
    hb_dir = tempfile.mkdtemp(prefix="mxtpu-hb-")
    world = mem.world_size
    mem.record(attempt, "attempt_start", port=port)
    procs = []
    try:
        for rank, slot in enumerate(mem.active):
            env = dict(os.environ)
            env.update(_worker_env(args, mem, world, rank, slot,
                                   attempt, prev_world))
            env.update({
                # JAX multi-process coordination
                "MXTPU_COORDINATOR": coordinator,
                # per-rank heartbeat files — exported even when
                # --heartbeat-timeout is 0: the files are the "where
                # was it" record on any kill, and the worker watchdog's
                # stall diagnostics fall back to this dir when
                # MXTPU_POSTMORTEM_DIR is unset (cost: one small write
                # per worker per second)
                "MXTPU_HEARTBEAT_DIR": hb_dir,
            })
            if args.cpu_fake_devices:
                env["JAX_PLATFORMS"] = "cpu"
            if args.local_device_count:
                flags = env.get("XLA_FLAGS", "")
                env["XLA_FLAGS"] = (
                    "%s --xla_force_host_platform_device_count"
                    "=%d" % (flags, args.local_device_count)).strip()
            procs.append(subprocess.Popen(cmd, env=env))
        return _monitor_procs(args, procs, heartbeat_dir=hb_dir)
    finally:
        # a stalled worker without MXTPU_POSTMORTEM_DIR falls back to
        # dumping its stack trace / postmortem HERE — deleting those
        # would erase the diagnosis the stall exit just promised
        try:
            diagnostics = [n for n in os.listdir(hb_dir)
                           if n.startswith(("stall-stacks-",
                                            "postmortem-"))]
        except OSError:
            diagnostics = []
        if diagnostics:
            print("launch.py: stall diagnostics preserved in %s (%s)"
                  % (hb_dir, ", ".join(sorted(diagnostics))),
                  file=sys.stderr, flush=True)
        else:
            shutil.rmtree(hb_dir, ignore_errors=True)


def classify_exit(rc):
    """Classify a failed worker's exit code →
    ('retryable'|'permanent'|'clean', reason).

    Restart attempts are a scarce budget; burning one on a failure that
    will repeat identically (CLI misuse exit 2, unresolvable/unrunnable
    command 126/127) just delays the terminal error.  Deaths by signal
    (rc < 0: OOM-killer SIGKILL, preemption SIGTERM, segfaults) and
    generic runtime failures (rc == 1: an uncaught exception
    mid-training) are exactly what checkpoint-restart exists for.  Note
    the interpreter exits 1 for uncaught ImportError too — exit codes
    cannot distinguish an import-time crash from a mid-training one, so
    those retry conservatively (bounded by the backoff schedule).

    Two dedicated retryable classes from the hang-defense layer
    (mxnet_tpu/watchdog.py): 75 (EX_TEMPFAIL) is a diagnosed stall —
    the worker's watchdog dumped stacks + postmortem and self-terminated,
    or this launcher declared heartbeat silence; 76 is a coordinator
    port bind failure — a restart with ``--port 0`` picks a fresh port.

    One CLEAN class: 80 is a graceful serving-replica drain
    (mxnet_tpu/serving/replica.py EXIT_SERVE_DRAIN) — planned, never
    blamed toward elastic eviction; the restart loop journals it as
    drain/replace transitions and spins the replacement without
    backoff."""
    if rc < 0:
        return "retryable", "killed by signal %d" % (-rc)
    if rc == STALL_EXIT:
        return "retryable", ("exit code 75: stall (watchdog/heartbeat "
                             "detected a hang; stacks + postmortem "
                             "dumped)")
    if rc == PORT_IN_USE_EXIT:
        return "retryable", ("exit code 76: coordinator port in use — "
                             "restart re-picks the port (--port 0)")
    if rc == WORKER_LOST_EXIT:
        return "retryable", ("exit code 77: worker lost (fault site "
                             "worker.lost — simulated permanent rank "
                             "death; --elastic evicts repeat offenders)")
    if rc == SERVE_DRAIN_EXIT:
        return "clean", ("exit code 80: graceful serving drain — the "
                         "replica finished its residents and released "
                         "its pages; never blamed toward eviction, the "
                         "restart spins an AOT-warm replacement")
    if rc == 2:
        return "permanent", ("exit code 2: usage/import-time error — "
                             "would fail identically on every attempt")
    if rc in (126, 127):
        return "permanent", "exit code %d: command not runnable" % rc
    return "retryable", "exit code %d: runtime failure" % rc


def _restart_loop(args, run_once, cmd):
    """The classify → (evict/readmit) → backoff → restart-from-
    checkpoints policy, shared by the local and ssh launchers.  With
    ``--elastic`` the membership for each attempt is recomputed here:
    a slot blamed for ``--evict-after`` consecutive failures (or one
    permanent exit) is dropped and the survivors re-ranked; evicted
    slots rejoin after sitting out ``--readmit-after`` attempts."""
    mem = _Membership(args)
    elastic = getattr(args, "elastic", False)
    prev_world = None
    for attempt in range(args.max_restarts + 1):
        if elastic and attempt:
            for slot in mem.readmit_due(attempt, args.readmit_after):
                if mem.world_size >= args.num_workers:
                    break  # never above the launch size
                mem.readmit(attempt, slot)
                print("launch.py: re-admitting recovered worker slot %d "
                      "for attempt %d (world size back up to %d)"
                      % (slot, attempt, mem.world_size),
                      file=sys.stderr, flush=True)
        world = mem.world_size
        failed_rank, rc = run_once(args, cmd, attempt, mem, prev_world)
        if failed_rank is None:
            mem.record(attempt, "complete")
            return 0
        if failed_rank == -1:
            mem.record(attempt, "interrupted")
            return rc or 1
        kind, reason = classify_exit(rc)
        if kind == "clean":
            # graceful serving drain (exit 80): planned, never blamed —
            # no failure note, no streak, no eviction, no backoff.  The
            # journal records drain/replace DISTINCTLY from training
            # failures; the next attempt is the replacement spin-up
            # (AOT-warm via the shared --aot-cache-dir).
            slot = mem.slot_of(failed_rank)
            mem.record(attempt, "drain", slot=slot, rank=failed_rank,
                       rc=rc, reason=reason)
            print("launch.py: attempt %d (world size %d): worker rank "
                  "%d (slot %d) drained gracefully (%s)"
                  % (attempt, world, failed_rank, slot, reason),
                  file=sys.stderr, flush=True)
            if attempt == args.max_restarts:
                # out of restart budget: the drain itself is a success
                mem.record(attempt, "complete", rc=rc)
                return 0
            mem.record(attempt, "replace", slot=slot)
            print("launch.py: spinning replacement for drained slot %d "
                  "(attempt %d/%d; no backoff — a drain is planned, "
                  "not a crash)" % (slot, attempt + 1,
                                    args.max_restarts),
                  file=sys.stderr, flush=True)
            prev_world = world
            continue
        slot = mem.note_failure(attempt, failed_rank, rc, kind, reason)
        print("launch.py: attempt %d (world size %d): worker rank %d "
              "(slot %d) failure classified %s (%s)"
              % (attempt, world, failed_rank, slot, kind, reason),
              file=sys.stderr, flush=True)
        if attempt == args.max_restarts:
            mem.record(attempt, "gave_up", rc=rc)
            return rc or 1
        evicted_now = []
        if elastic:
            # a PERMANENT exit evicts only once the job has proven it
            # can run at all (attempt >= 1): exit codes cannot tell a
            # bad HOST from a bad COMMAND, and a usage/import error hits
            # every rank identically on the very first attempt — evicting
            # healthy slots one per attempt would burn the whole restart
            # budget re-proving it, so attempt-0 permanent failures fail
            # fast below (and must not slip through the streak branch
            # either — with --evict-after 1 a streak of 1 would).  A
            # host that goes permanently bad mid-job still gets dropped
            # on any later attempt.
            if kind == "permanent":
                should_evict = attempt > 0
            else:
                should_evict = mem.streak >= args.evict_after
            if should_evict and slot in mem.active:
                if world - 1 >= max(1, args.min_workers):
                    why = ("exit classified permanent" if
                           kind == "permanent" else
                           "%d consecutive failures (--evict-after %d)"
                           % (mem.streak, args.evict_after))
                    mem.evict(attempt, slot, why)
                    evicted_now.append(slot)
                    print("launch.py: evicting worker slot %d (%s); "
                          "next attempt runs at world size %d"
                          % (slot, why, mem.world_size),
                          file=sys.stderr, flush=True)
                    # a permanent single-rank failure is survivable once
                    # the rank is out of the job
                    kind = "retryable"
                elif kind != "permanent":
                    print("launch.py: NOT evicting slot %d — world size "
                          "%d already at --min-workers %d floor"
                          % (slot, world, args.min_workers),
                          file=sys.stderr, flush=True)
        if kind == "permanent":
            print("launch.py: not restarting — failure is not retryable "
                  "(%d restart attempts preserved)"
                  % (args.max_restarts - attempt),
                  file=sys.stderr, flush=True)
            mem.record(attempt, "gave_up", rc=rc)
            return rc or 1
        # exponential backoff: crash loops (a flaky host, a wedged
        # coordinator port) get geometrically more breathing room
        delay = min(args.restart_backoff * (2 ** attempt),
                    args.restart_backoff_max)
        if delay > 0:
            print("launch.py: backing off %.2fs before restart" % delay,
                  file=sys.stderr, flush=True)
            time.sleep(delay)
        print("launch.py: restarting job from checkpoints "
              "(attempt %d/%d) after worker %d failure: world size "
              "%d -> %d, evicted now %s, sitting out %s"
              % (attempt + 1, args.max_restarts, failed_rank, world,
                 mem.world_size, evicted_now or "none",
                 sorted(mem.evicted_at) or "none"),
              file=sys.stderr, flush=True)
        prev_world = world
    return 1


def _serve_port_doc(run_dir, slot):
    """Read a slot's port file (bootstrap discovery: host/port plus the
    incarnation stamp the worker minted at boot).  Raises OSError /
    ValueError when the worker has not published yet."""
    path = os.path.join(run_dir, "serve-port-slot%d.json" % slot)
    with open(path) as f:
        return json.load(f)


def _serve_rpc(run_dir, slot, msg, timeout=2.0):
    """One length-framed JSON RPC to a serve worker, dependency-free.

    The supervisor must not import the framework to supervise it (a
    jax import in the launcher would cost seconds and a device lock),
    so this is a deliberate stdlib-only mirror of
    ``mxnet_tpu/serving/rpc.py``'s wire format: 4-byte big-endian
    length + UTF-8 JSON, one connection per call.  Returns
    ``(reply_doc, port_doc)``; raises OSError/ValueError on any
    transport or framing trouble — callers treat that as "no answer",
    never as death (confirmation needs an incarnation change or a
    kill-ack, and the supervisor IS the kill-ack authority)."""
    doc = _serve_port_doc(run_dir, slot)
    payload = json.dumps(msg).encode("utf-8")
    with socket.create_connection(
            (doc.get("host", "127.0.0.1"), int(doc["port"])),
            timeout=timeout) as s:
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        s.settimeout(timeout)
        s.sendall(struct.pack(">I", len(payload)) + payload)
        buf = b""
        while len(buf) < 4:
            chunk = s.recv(4 - len(buf))
            if not chunk:
                raise OSError("serve rpc: connection closed mid-frame")
            buf += chunk
        (n,) = struct.unpack(">I", buf)
        body = b""
        while len(body) < n:
            chunk = s.recv(n - len(body))
            if not chunk:
                raise OSError("serve rpc: connection closed mid-frame")
            body += chunk
    return json.loads(body.decode("utf-8")), doc


def _serve_stop_fleet(args, run_dir, state):
    """Stop the fleet the control-plane way: order each live worker to
    drain over an incarnation-authenticated ``drain`` RPC (the stamp
    comes from the slot's own port file, so a replacement that took
    the slot between discovery and the call refuses the stale order),
    wait for the exit-80s, then escalate SIGTERM→SIGKILL on anything
    that did not answer or did not die — which is exactly the
    ``serve.worker.zombie`` drill: a worker that swallows its drain
    RPC still leaves, it just leaves feet-first."""
    for slot, st in sorted(state.items()):
        if st["proc"] is None or st["down"]:
            continue
        try:
            doc = _serve_port_doc(run_dir, slot)
            inc = {"pid": doc.get("pid"),
                   "attempt": doc.get("attempt"),
                   "nonce": doc.get("nonce")}
            reply, _ = _serve_rpc(run_dir, slot,
                                  {"method": "drain",
                                   "incarnation": inc},
                                  timeout=2.0)
            acked = bool(reply.get("ok"))
        except (OSError, ValueError):
            acked = False
        if not acked:
            print("launch.py: serve slot %d did not ack its drain RPC "
                  "— will escalate with signals" % slot,
                  file=sys.stderr, flush=True)
    procs = [st["proc"] for st in state.values()
             if st["proc"] is not None]
    deadline = time.time() + max(args.kill_grace, 5.0)
    while time.time() < deadline and \
            any(p.poll() is None for p in procs):
        time.sleep(0.1)
    stragglers = [p for p in procs if p.poll() is None]
    if stragglers:
        print("launch.py: %d worker(s) still up after the drain RPCs "
              "— escalating" % len(stragglers),
              file=sys.stderr, flush=True)
        _escalate_kill(stragglers, signal.SIGTERM, args.kill_grace)


def _serve_hb_check(args, run_dir, hb_dir, slot, st, now):
    """Per-slot liveness via the heartbeat RPC (ISSUE 17).

    Before a worker's first successful heartbeat (engine still
    building, port file unpublished) the PR-4 heartbeat FILE covers
    the boot window — the watchdog thread touches it from process
    start, so a worker wedged before it can even serve RPCs is still
    caught.  From first contact on, only the RPC view counts: the
    slot is killed when heartbeats have been silent past
    ``--heartbeat-timeout`` AND the progress sequence (decode steps,
    weights epoch) has not advanced either — a worker that answers
    nothing but is provably decoding is partitioned, not wedged, and
    killing it is the router's fencing problem, not ours."""
    p = st["proc"]
    if st["hb_ok_at"] is None:
        # boot window: heartbeat-file mtime is the only signal
        hb = os.path.join(hb_dir, "hb-%d.json" % slot)
        try:
            age = now - os.stat(hb).st_mtime
        except OSError:
            age = None
        if age is not None and age > args.heartbeat_timeout:
            print("launch.py: serve slot %d heartbeat silent %.1fs "
                  "during boot — killing the wedged replica"
                  % (slot, age), file=sys.stderr, flush=True)
            _escalate_kill([p], signal.SIGTERM, args.kill_grace)
    if now >= st["next_hb_at"]:
        st["next_hb_at"] = now + min(1.0,
                                     args.heartbeat_timeout / 4.0)
        try:
            reply, _doc = _serve_rpc(
                run_dir, slot, {"method": "heartbeat"},
                timeout=min(2.0, args.heartbeat_timeout))
        except (OSError, ValueError):
            reply = None
        if reply is not None and reply.get("ok"):
            st["hb_ok_at"] = now
            prog = reply.get("progress") or {}
            seq = (prog.get("decode_steps"),
                   prog.get("weights_epoch"))
            if seq != st["progress_seq"]:
                st["progress_seq"] = seq
                st["progress_at"] = now
    ok_at = st["hb_ok_at"]
    if ok_at is None:
        return
    hb_gap = now - ok_at
    prog_gap = now - (st["progress_at"] if st["progress_at"]
                      is not None else ok_at)
    if hb_gap > args.heartbeat_timeout and \
            prog_gap > args.heartbeat_timeout:
        print("launch.py: serve slot %d heartbeat RPC silent %.1fs "
              "with no decode progress — killing the wedged replica"
              % (slot, hb_gap), file=sys.stderr, flush=True)
        _escalate_kill([p], signal.SIGTERM, args.kill_grace)


def _serve_telemetry_pull(args, run_dir, slot, st, now):
    """Collector half of the RPC telemetry plane (ISSUE 18): pull the
    slot's newly-drained telemetry over the ``telemetry_pull`` RPC and
    append each returned line to ``<telemetry-dir>/stream-slot<K>.
    jsonl`` — the exact layout the in-worker file emitter writes and
    serve_report/job_report/telemetry_report already read, but
    assembled over the wire (the multi-host seam: the supervisor needs
    no shared filesystem with its workers).  The cursor is
    supervisor-held; a worker replacement declares ``reset`` in-band
    (the line schema carries the new identity), a missed pull just
    resumes at the old cursor next interval, and the per-pull chunk
    loop is bounded so one firehose worker cannot wedge supervision.
    Lines land whole via single O_APPEND writes, so readers can apply
    the usual torn-tail skip-and-count discipline."""
    if now < st["next_tel_at"]:
        return
    st["next_tel_at"] = now + args.telemetry_pull_interval
    path = os.path.join(args.telemetry_dir,
                        "stream-slot%d.jsonl" % slot)
    try:
        for _ in range(8):
            msg = {"method": "telemetry_pull"}
            if st["tel_cursor"] is not None:
                msg["cursor"] = st["tel_cursor"]
            reply, _doc = _serve_rpc(run_dir, slot, msg, timeout=2.0)
            if not reply.get("ok"):
                return
            st["tel_cursor"] = reply.get("cursor")
            line = (json.dumps(reply["line"]) + "\n").encode("utf-8")
            fd = os.open(path,
                         os.O_WRONLY | os.O_APPEND | os.O_CREAT, 0o644)
            try:
                os.write(fd, line)
            finally:
                os.close(fd)
            if not reply.get("more"):
                return
    except (OSError, ValueError, KeyError):
        pass  # no answer is a missed interval, never a supervision event


def _serve_spawn(args, mem, run_dir, hb_dir, cmd, slot, attempt):
    """One serving-replica worker process for ``slot``: the training
    env contract (slot == rank — serving has no collective world to
    re-pack) plus the serve-plane exports: the slot's PORT FILE (the
    bootstrap-discovery channel carrying the worker's incarnation
    stamp) and the heartbeat dir (boot-window liveness only — once a
    worker answers its first heartbeat RPC, the supervisor watches
    the RPC view, not file mtimes)."""
    env = dict(os.environ)
    env.update(_worker_env(args, mem, mem.world_size, slot, slot,
                           attempt, None))
    env.update({
        "MXTPU_HEARTBEAT_DIR": hb_dir,
        "MXTPU_SERVE_PORT_FILE":
            os.path.join(run_dir, "serve-port-slot%d.json" % slot),
    })
    # orphan reclamation (ISSUE 19): a fleet-wide abandon window for
    # vanished streaming clients; operator-set env wins (ssh-env rule)
    if getattr(args, "serve_abandon_s", 0) and \
            "MXTPU_SERVE_ABANDON_S" not in os.environ:
        env["MXTPU_SERVE_ABANDON_S"] = str(args.serve_abandon_s)
    if args.cpu_fake_devices:
        env["JAX_PLATFORMS"] = "cpu"
    mem.record(attempt, "spawn", slot=slot)
    return subprocess.Popen(cmd, env=env)


def _serve_loop(args, cmd):
    """The ``--serve`` fleet supervisor: N serving-replica processes,
    each its own slot, supervised INDIVIDUALLY (serving has no
    collective — one replica dying must replace that replica, never
    tear the fleet down, which is the whole point of the
    out-of-process shape).

    Per-slot policy, journaled into ``membership.json`` like the
    elastic trainer:

    - exit 80 (graceful drain): ``drain`` + ``replace`` transitions,
      respawned immediately with no backoff and no blame;
    - retryable exits (SIGKILL, 75, 77, crashes): ``failure`` +
      ``replace``, respawned with per-slot exponential backoff; the
      respawn shares the launch's AOT cache so the replacement comes
      up warm (0 foreground compiles).  A slot blamed
      ``--evict-after`` consecutive times (or any permanent exit) is
      evicted — a crash-looping replica must not burn the budget
      forever;
    - ``--max-restarts`` bounds TOTAL failure-respawns across the
      fleet (drain respawns are planned and free);
    - liveness is the RPC view (ISSUE 17): the supervisor polls each
      worker's ``heartbeat`` RPC and kills (SIGTERM→SIGKILL) a slot
      whose heartbeats go silent past ``--heartbeat-timeout`` with no
      decode-progress advance; heartbeat FILES cover only the boot
      window before the worker publishes its port file.

    The fleet runs until ``<run-dir>/serve-stop`` appears (the
    operator/driver's shutdown handle — each worker is ordered to
    drain over an incarnation-authenticated RPC, exit 80, with
    SIGTERM escalation for non-responders) or every slot is down
    (exit 1)."""
    mem = _Membership(args)
    run_dir = args.run_dir
    hb_dir = os.path.join(run_dir, "hb")
    os.makedirs(hb_dir, exist_ok=True)
    stop_path = os.path.join(run_dir, "serve-stop")
    # a stop handle is a one-shot order to THIS fleet: a stale file
    # from the previous fleet in a reused run dir must not drain the
    # fresh one the moment it spawns
    try:
        os.unlink(stop_path)
    except OSError:
        pass
    state = {}
    for slot in list(mem.active):
        state[slot] = {"attempt": 0, "streak": 0, "down": False,
                       "next_spawn_at": None,
                       "hb_ok_at": None, "progress_seq": None,
                       "progress_at": None, "next_hb_at": 0.0,
                       "tel_cursor": None, "next_tel_at": 0.0,
                       "proc": _serve_spawn(args, mem, run_dir, hb_dir,
                                            cmd, slot, 0)}
    pull_telemetry = bool(args.telemetry_dir) and \
        args.telemetry_pull_interval > 0
    fail_respawns = 0
    try:
        while True:
            if os.path.exists(stop_path):
                print("launch.py: serve-stop requested — draining the "
                      "fleet over the control RPC", file=sys.stderr,
                      flush=True)
                mem.record(0, "stop")
                if pull_telemetry:
                    # last collection before the workers drain away:
                    # short runs must still leave a complete tree
                    now = time.time()
                    for slot, st in sorted(state.items()):
                        if st["proc"] is not None and not st["down"]:
                            st["next_tel_at"] = 0.0
                            _serve_telemetry_pull(args, run_dir, slot,
                                                  st, now)
                _serve_stop_fleet(args, run_dir, state)
                mem.record(0, "complete")
                return 0
            now = time.time()
            if all(st["down"] for st in state.values()):
                if all(st.get("clean") for st in state.values()):
                    mem.record(0, "complete")
                    return 0
                mem.record(0, "gave_up",
                           reason="every serving slot is down")
                print("launch.py: every serving slot is down — giving "
                      "up", file=sys.stderr, flush=True)
                return 1
            for slot, st in sorted(state.items()):
                if st["down"]:
                    continue
                p = st["proc"]
                if p is None:
                    if now >= st["next_spawn_at"]:
                        st["attempt"] += 1
                        # fresh incarnation: the RPC liveness clock
                        # restarts with it
                        st["hb_ok_at"] = None
                        st["progress_seq"] = None
                        st["progress_at"] = None
                        st["next_hb_at"] = 0.0
                        st["proc"] = _serve_spawn(
                            args, mem, run_dir, hb_dir, cmd, slot,
                            st["attempt"])
                    continue
                rc = p.poll()
                if rc is None:
                    if args.heartbeat_timeout > 0:
                        _serve_hb_check(args, run_dir, hb_dir, slot,
                                        st, now)
                    if pull_telemetry:
                        _serve_telemetry_pull(args, run_dir, slot, st,
                                              now)
                    continue
                if rc == 0:
                    # clean completion (e.g. a worker's own run-length
                    # backstop): the slot is done — not blamed, not
                    # respawned
                    mem.record(st["attempt"], "complete", slot=slot)
                    st["down"] = True
                    st["clean"] = True
                    st["proc"] = None
                    continue
                kind, reason = classify_exit(rc)
                if kind == "clean":
                    mem.record(st["attempt"], "drain", slot=slot,
                               rc=rc, reason=reason)
                    st["streak"] = 0
                    st["proc"] = None
                    st["next_spawn_at"] = now  # a drain is planned
                    mem.record(st["attempt"], "replace", slot=slot)
                    print("launch.py: serve slot %d drained "
                          "gracefully; spinning replacement (no "
                          "backoff)" % slot, file=sys.stderr,
                          flush=True)
                    continue
                st["streak"] += 1
                mem.record(st["attempt"], "failure", slot=slot, rc=rc,
                           kind=kind, reason=reason,
                           consecutive_failures=st["streak"])
                print("launch.py: serve slot %d (attempt %d) failed: "
                      "%s (%s)" % (slot, st["attempt"], kind, reason),
                      file=sys.stderr, flush=True)
                if kind == "permanent" or \
                        st["streak"] >= max(1, args.evict_after):
                    why = ("exit classified permanent"
                           if kind == "permanent" else
                           "%d consecutive failures (--evict-after "
                           "%d)" % (st["streak"], args.evict_after))
                    if slot in mem.active:
                        mem.evict(st["attempt"], slot, why)
                    st["down"] = True
                    st["proc"] = None
                    print("launch.py: serve slot %d evicted (%s)"
                          % (slot, why), file=sys.stderr, flush=True)
                    continue
                if fail_respawns >= args.max_restarts:
                    mem.record(st["attempt"], "gave_up", slot=slot,
                               rc=rc,
                               reason="--max-restarts %d exhausted"
                               % args.max_restarts)
                    st["down"] = True
                    st["proc"] = None
                    print("launch.py: serve slot %d down — restart "
                          "budget exhausted" % slot, file=sys.stderr,
                          flush=True)
                    continue
                fail_respawns += 1
                delay = min(args.restart_backoff
                            * (2 ** (st["streak"] - 1)),
                            args.restart_backoff_max)
                st["proc"] = None
                st["next_spawn_at"] = now + delay
                mem.record(st["attempt"], "replace", slot=slot,
                           backoff_s=delay)
                print("launch.py: respawning serve slot %d in %.2fs "
                      "(failure respawn %d/%d)"
                      % (slot, delay, fail_respawns,
                         args.max_restarts),
                      file=sys.stderr, flush=True)
            time.sleep(0.15)
    except KeyboardInterrupt:
        print("launch.py: interrupt — stopping the serve fleet",
              file=sys.stderr, flush=True)
        _escalate_kill([st["proc"] for st in state.values()
                        if st["proc"] is not None],
                       signal.SIGINT, args.kill_grace)
        mem.record(0, "interrupted")
        return 1


def launch_local(args, cmd):
    if args.dry_run:
        port = args.port or _free_port()
        for rank in range(args.num_workers):
            # the real per-worker contract, so a pasted line reproduces
            # what a launched worker actually sees
            env = _worker_env(args, None, args.num_workers, rank, rank,
                              0, None)
            env["MXTPU_COORDINATOR"] = "127.0.0.1:%d" % port
            envs = " ".join("%s=%s" % (k, shlex.quote(v))
                            for k, v in sorted(env.items()))
            print("%s %s" % (envs,
                             " ".join(shlex.quote(c) for c in cmd)))
        return 0
    return _restart_loop(args, _run_local_once, cmd)


def _ssh_commands(args, cmd, attempt=0, mem=None, prev_world=None):
    """→ [ssh argv per worker] — one worker per ACTIVE slot's hostfile
    entry (elastic mode drops an evicted slot's host from the attempt
    and readmits it later; the slot→host binding is stable)."""
    assert args.hostfile, "--launcher ssh requires -H hostfile"
    with open(args.hostfile) as f:
        hosts = [h.strip() for h in f if h.strip()]
    hosts = (hosts * args.num_workers)[:args.num_workers]
    slots = list(mem.active) if mem is not None \
        else list(range(args.num_workers))
    world = len(slots)
    port = args.port or _free_port()
    coordinator = "%s:%d" % (socket.gethostname(), port)
    if mem is not None:
        mem.record(attempt, "attempt_start", port=port)
    out = []
    for rank, slot in enumerate(slots):
        # _worker_env covers the cache exports too: warm-start caches
        # assume a shared filesystem across hosts (the usual pod setup);
        # a host-local path just cold-starts harmlessly
        env = _worker_env(args, mem, world, rank, slot, attempt,
                          prev_world)
        env["MXTPU_COORDINATOR"] = coordinator
        envs = " ".join("%s=%s" % (k, shlex.quote(v))
                        for k, v in sorted(env.items()))
        remote = "cd %s; %s %s" % (shlex.quote(os.getcwd()), envs,
                                   " ".join(shlex.quote(c) for c in cmd))
        # -tt forces a remote tty so the remote process group dies with
        # the ssh client when the monitor tears the job down — without
        # it one remote worker failing leaves the others running forever
        out.append(["ssh", "-tt", "-o", "StrictHostKeyChecking=no",
                    "-o", "BatchMode=yes", hosts[slot], remote])
    return out


def _run_ssh_once(args, cmd, attempt, mem, prev_world=None):
    """One ssh job attempt, monitored like the local launcher: the first
    remote worker failing (its ssh client exits nonzero) tears the whole
    job down and reports the failed rank, instead of the old
    wait-for-everyone loop that left surviving hosts running forever.
    No heartbeat files here — they are host-local; stall defense on ssh
    jobs is the in-process watchdog (exit 75 propagates through ssh)."""
    procs = [subprocess.Popen(argv)
             for argv in _ssh_commands(args, cmd, attempt, mem,
                                       prev_world)]
    return _monitor_procs(args, procs, label="ssh worker")


def launch_ssh(args, cmd):
    if args.dry_run:
        for argv in _ssh_commands(args, cmd):
            print(" ".join(shlex.quote(a) for a in argv))
        return 0
    return _restart_loop(args, _run_ssh_once, cmd)


def _mpi_command(args, cmd):
    """One mpirun invocation (Open MPI CLI: -x/--hostfile); ranks adopt
    their mpirun-assigned rank at startup (base.py maps
    OMPI_COMM_WORLD_RANK/PMI_RANK/... onto the worker-rank contract the
    same way the reference's dmlc_tracker mpi mode rode mpirun,
    reference tools/launch.py:70).

    The coordinator must live where rank 0 runs: the first hostfile
    host (mpirun fills hosts in order), else this host.  Pass --port
    to pin a port known open on that host; _free_port() only checks
    the launcher."""
    if args.hostfile:
        with open(args.hostfile) as f:
            hosts = [h.split()[0] for h in f if h.strip()]
        coord_host = hosts[0]
    else:
        coord_host = socket.gethostname()
    port = args.port or _free_port()
    coordinator = "%s:%d" % (coord_host, port)
    argv = ["mpirun", "-np", str(args.num_workers)]
    if args.hostfile:
        argv += ["--hostfile", args.hostfile]
    argv += ["-x", "MXTPU_COORDINATOR=%s" % coordinator,
             "-x", "MXTPU_NUM_WORKERS=%d" % args.num_workers,
             "-x", "MXTPU_RANK_FROM_MPI=1",
             "-x", "DMLC_ROLE=worker",
             "-x", "DMLC_NUM_WORKER=%d" % args.num_workers]
    for k, v in _cache_env(args).items():
        argv += ["-x", "%s=%s" % (k, v)]
    return argv + list(cmd)


def launch_mpi(args, cmd):
    argv = _mpi_command(args, cmd)
    if args.dry_run:
        print(" ".join(shlex.quote(a) for a in argv))
        return 0
    return subprocess.call(argv)


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="Launch a distributed job (reference tools/launch.py)")
    parser.add_argument("-n", "--num-workers", required=True, type=int,
                        help="number of worker processes")
    parser.add_argument("-s", "--num-servers", type=int, default=0,
                        help="ignored — no parameter servers in the "
                        "all-reduce design (kept for CLI compat)")
    parser.add_argument("-H", "--hostfile", default=None,
                        help="hostfile for ssh launcher")
    parser.add_argument("--dry-run", action="store_true",
                        help="print the launch commands/environment "
                        "without running anything")
    parser.add_argument("--launcher", default="local",
                        choices=["local", "ssh", "mpi"],
                        help="cluster type")
    parser.add_argument("--port", type=int, default=0,
                        help="coordinator port (0 = pick a free one)")
    parser.add_argument("--cpu-fake-devices", action="store_true",
                        help="force JAX_PLATFORMS=cpu in workers (local "
                        "fake-cluster testing)")
    parser.add_argument("--local-device-count", type=int, default=0,
                        help="virtual devices per worker process "
                        "(xla_force_host_platform_device_count; test "
                        "multi-chip-per-host jobs without hardware)")
    parser.add_argument("--serve", action="store_true",
                        help="serving-fleet mode (local launcher): run "
                        "the command as -n independent serving-replica "
                        "slots (tools/serve_worker.py), each "
                        "supervised INDIVIDUALLY — exit 80 journals "
                        "drain/replace and respawns immediately; "
                        "crashes/SIGKILL/stalls respawn with backoff "
                        "(AOT-warm via the shared --aot-cache-dir), "
                        "evicting a slot after --evict-after "
                        "consecutive failures; every transition lands "
                        "in <run-dir>/membership.json.  Each slot "
                        "publishes <run-dir>/serve-port-slot<K>.json "
                        "for router proxies "
                        "(mxnet_tpu.serving.rpc.fleet_proxies); stop "
                        "the fleet by creating <run-dir>/serve-stop")
    parser.add_argument("--elastic", action="store_true",
                        help="make world size a per-restart decision: a "
                        "worker slot blamed for --evict-after "
                        "consecutive failures (or one permanent exit) "
                        "is dropped from the next attempt — survivors "
                        "re-ranked contiguously, job resumes from "
                        "checkpoints at N-1 — and re-admitted after "
                        "sitting out --readmit-after attempts; "
                        "transitions recorded in <run-dir>/"
                        "membership.json")
    parser.add_argument("--min-workers", type=int, default=1,
                        help="elastic shrink floor: never evict below "
                        "this many workers (default 1)")
    parser.add_argument("--evict-after", type=int, default=2,
                        help="consecutive failures of the same worker "
                        "slot before elastic mode evicts it (default 2; "
                        "a permanent exit evicts immediately from "
                        "attempt 1 on — an attempt-0 permanent failure "
                        "still fails the job fast, since a usage/import "
                        "error hits every rank identically)")
    parser.add_argument("--readmit-after", type=int, default=1,
                        help="attempts an evicted slot sits out before "
                        "being re-admitted (default 1)")
    parser.add_argument("--run-dir", default=None,
                        help="job run dir holding membership.json (the "
                        "elastic transition journal; render with "
                        "tools/perf_probe/telemetry_report.py).  "
                        "Default: a per-launch temp dir when --elastic, "
                        "else none")
    parser.add_argument("--telemetry-dir", default=None,
                        help="job-scope telemetry tree: each worker "
                        "slot's JSON-lines stream (MXTPU_TELEMETRY, "
                        "append-only per slot), crash postmortems and "
                        "stall-stacks (MXTPU_POSTMORTEM_DIR) all land "
                        "here, next to membership.json — the input of "
                        "tools/perf_probe/job_report.py.  Default: "
                        "<run-dir>/telemetry when --run-dir is set "
                        "(incl. the --elastic auto run dir); pass 'off' "
                        "to disable.  Operator-set MXTPU_TELEMETRY / "
                        "MXTPU_POSTMORTEM_DIR env always wins")
    parser.add_argument("--telemetry-interval", type=float, default=10.0,
                        help="seconds between telemetry stream lines "
                        "per worker (the [:interval] half of the "
                        "MXTPU_TELEMETRY spec; default 10)")
    parser.add_argument("--max-restarts", type=int, default=0,
                        help="restart the whole job this many times when "
                        "a worker dies (workers resume from their own "
                        "checkpoints; MXTPU_RESTART_ATTEMPT tells them "
                        "which attempt is running); non-retryable "
                        "failures (e.g. exit code 2) stop immediately")
    parser.add_argument("--restart-backoff", type=float, default=1.0,
                        help="base seconds between restarts; doubles "
                        "each attempt (exponential backoff)")
    parser.add_argument("--restart-backoff-max", type=float, default=60.0,
                        help="backoff ceiling in seconds")
    parser.add_argument("--heartbeat-timeout", type=float, default=0.0,
                        help="kill + restart the job when a worker's "
                        "heartbeat file (touched by mxnet_tpu.watchdog "
                        "under MXTPU_HEARTBEAT_DIR) goes quiet for this "
                        "many seconds (0 = off); catches workers wedged "
                        "in native code that their in-process watchdog "
                        "cannot see")
    parser.add_argument("--kill-grace", type=float, default=5.0,
                        help="seconds to wait between teardown "
                        "escalation steps (SIGINT/SIGTERM → SIGKILL)")
    parser.add_argument("--telemetry-pull-interval", type=float,
                        default=2.0,
                        help="--serve only: seconds between "
                        "telemetry_pull RPC collections per slot "
                        "(appended to <telemetry-dir>/stream-slot<K>"
                        ".jsonl — fleet observability with no shared "
                        "filesystem reads; 0 disables the collector)")
    parser.add_argument("--serve-abandon-s", type=float, default=0.0,
                        help="--serve only: reclaim a streamed request "
                        "whose client stopped polling for this many "
                        "seconds (typed verdict 'abandoned', slot + KV "
                        "pages released — SERVING.md §10; exported to "
                        "workers as MXTPU_SERVE_ABANDON_S; 0 = off; "
                        "operator-set env wins)")
    parser.add_argument("--aot-cache-dir", default=None,
                        help="compiled-executable warm-start cache "
                        "exported to workers as MXTPU_AOT_CACHE_DIR (+ "
                        "JAX_COMPILATION_CACHE_DIR fallback); persists "
                        "across restart attempts so a restarted rank "
                        "deserializes the fused step instead of "
                        "recompiling it.  Default: $MXTPU_AOT_CACHE_DIR, "
                        "else mxtpu-aot/ under the jax cache dir; pass "
                        "'off' to disable")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="command for launching the program")
    args = parser.parse_args(argv)
    cmd = [c for c in args.command if c != "--"]
    assert cmd, "no command given"
    if args.serve and args.launcher != "local":
        print("launch.py: --serve is a local-launcher mode",
              file=sys.stderr, flush=True)
        return 2
    if args.serve and not args.run_dir:
        # the run dir is the fleet's rendezvous (port files, heartbeat
        # tree, membership journal, serve-stop handle) — it must exist
        args.run_dir = tempfile.mkdtemp(prefix="mxtpu-serve-")
    if args.elastic and args.launcher == "mpi":
        print("launch.py: --elastic is a local/ssh launcher feature "
              "(mpirun owns process placement; use your MPI runtime's "
              "fault tolerance there) — ignoring it", file=sys.stderr,
              flush=True)
        args.elastic = False
    if args.elastic and not args.run_dir:
        # the membership journal is the record of what the job looked
        # like over time — keep it after exit (unlike the heartbeat
        # dirs), and say where it lives
        args.run_dir = tempfile.mkdtemp(prefix="mxtpu-run-")
    if args.run_dir and args.launcher != "mpi":
        # (mpi bypasses _restart_loop/_Membership: no journal to announce)
        os.makedirs(args.run_dir, exist_ok=True)
        print("launch.py: membership journal at %s"
              % os.path.join(args.run_dir, "membership.json"),
              file=sys.stderr, flush=True)
    if args.telemetry_dir == "off":
        args.telemetry_dir = None
    elif not args.telemetry_dir and args.run_dir and \
            args.launcher != "mpi":
        args.telemetry_dir = os.path.join(args.run_dir, "telemetry")
    if args.telemetry_dir and args.launcher != "mpi":
        os.makedirs(args.telemetry_dir, exist_ok=True)
        print("launch.py: job telemetry tree at %s (render with "
              "tools/perf_probe/job_report.py)" % args.telemetry_dir,
              file=sys.stderr, flush=True)
    elif args.telemetry_dir:
        # mpi has no slot contract to key the per-worker streams by
        print("launch.py: --telemetry-dir is a local/ssh launcher "
              "feature — ignoring it under mpi", file=sys.stderr,
              flush=True)
        args.telemetry_dir = None
    if args.aot_cache_dir == "off":
        args.aot_cache_dir = None
    elif not args.aot_cache_dir:
        # shared by every restart attempt, and by the next launch — the
        # whole point is that attempt N+1 finds attempt N's compiled
        # executables (operator env wins when already set)
        args.aot_cache_dir = os.environ.get("MXTPU_AOT_CACHE_DIR") or \
            os.path.join(os.environ.get("JAX_COMPILATION_CACHE_DIR") or
                         DEFAULT_JAX_CACHE_DIR, "mxtpu-aot")
    if args.serve:
        if args.dry_run:
            # the real per-slot contract, so a pasted line
            # reproduces what a launched replica actually sees
            # (mem=None like launch_local's dry run: a DRY run
            # must not journal a 'launch' transition into a run
            # dir a live fleet may be using)
            for slot in range(args.num_workers):
                env = _worker_env(args, None, args.num_workers,
                                  slot, slot, 0, None)
                env.update({
                    "MXTPU_HEARTBEAT_DIR":
                        os.path.join(args.run_dir, "hb"),
                    "MXTPU_SERVE_PORT_FILE": os.path.join(
                        args.run_dir,
                        "serve-port-slot%d.json" % slot),
                })
                envs = " ".join(
                    "%s=%s" % (k, shlex.quote(v))
                    for k, v in sorted(env.items()))
                print("%s %s" % (envs, " ".join(
                    shlex.quote(c) for c in cmd)))
            return 0
        return _serve_loop(args, cmd)
    if args.launcher == "local":
        return launch_local(args, cmd)
    if args.launcher == "mpi":
        return launch_mpi(args, cmd)
    return launch_ssh(args, cmd)


if __name__ == "__main__":
    sys.exit(main())
