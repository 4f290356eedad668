"""Base utilities for the TPU-native framework.

Plays the role of the reference's ``python/mxnet/base.py`` (ctypes bridge,
handle types, ``check_call``) — but there is no C ABI to cross for the compute
path: ops lower to XLA via JAX.  What remains here is the shared error type,
string/registry helpers, and a few numeric aliases.

Reference: /root/reference/python/mxnet/base.py
"""
from __future__ import annotations

import numpy as _np

__all__ = ["MXNetError", "string_types", "numeric_types", "integer_types"]


class MXNetError(RuntimeError):
    """Error raised by the framework (reference: base.py:MXNetError)."""


string_types = (str,)
numeric_types = (float, int, _np.generic)
integer_types = (int, _np.integer)


def check_call(ret):
    """Kept for API compatibility; no C calls to check in the TPU build."""
    if ret:  # pragma: no cover - compatibility shim
        raise MXNetError(str(ret))


def _as_list(obj):
    """Return obj wrapped in a list if it is not already a list/tuple."""
    if isinstance(obj, (list, tuple)):
        return list(obj)
    return [obj]


def _distributed_initialized(jax):
    """Has jax.distributed already joined a mesh in this process?
    Getting this wrong is not cosmetic: re-running bring-up would make
    rank 0's port pre-probe see its OWN live coordination service and
    exit 76."""
    return jax.distributed.is_initialized()


def _membership_env_changed(jax):
    """Does the env membership contract disagree with the live mesh?
    An elastic restart re-exports MXTPU_NUM_WORKERS/MXTPU_WORKER_RANK
    for the re-ranked survivors; a process that joined under the OLD
    contract must not silently keep using it."""
    import os
    try:
        want_num = int(os.environ["MXTPU_NUM_WORKERS"])
        want_rank = int(os.environ["MXTPU_WORKER_RANK"])
    except (KeyError, ValueError):
        return False  # no/garbled contract: nothing to compare against
    try:
        return (jax.process_count() != want_num or
                jax.process_index() != want_rank)
    except Exception:
        return False  # backend not up yet; initialize() will see env


def _coordinator_port_free(coord):
    """Rank 0 pre-probe: can the coordinator port still be bound?  A
    restarted job can race a dying predecessor (or another tenant) for a
    pinned --port; probing with our own socket gives a deterministic
    "address in use" verdict instead of whatever message the JAX
    coordination service wraps the bind failure in."""
    import socket
    host, _, port = coord.rpartition(":")
    try:
        port = int(port)
    except ValueError:
        return True  # unparseable address: let initialize() report it
    import errno
    s = socket.socket()
    try:
        # SO_REUSEADDR to exactly match the grpc server's bind semantics:
        # TIME_WAIT debris from a killed predecessor job must not fail
        # the probe (it would not fail the real bind either) — only a
        # LIVE socket holding the port is a conflict
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind((host if host not in ("", "localhost") else "", port))
        return True
    except OSError as e:
        # ONLY a genuine address-in-use is this probe's verdict; any
        # other failure (unresolvable hostname, non-local address,
        # IPv6 literal this parse mangled) must fall through to the
        # real bind so the job surfaces a config error instead of
        # burning its restart budget on retryable exit 76s
        return getattr(e, "errno", None) != errno.EADDRINUSE
    finally:
        s.close()


def _maybe_init_distributed():
    """Join the process mesh from tools/launch.py's env contract
    (MXTPU_COORDINATOR / MXTPU_NUM_WORKERS / MXTPU_WORKER_RANK) — the
    TPU-era replacement for ps-lite's DMLC_PS_ROOT_URI bootstrap.

    Must run before any JAX backend initializes; mxnet_tpu/__init__ calls
    it at import time, and kvstore.create('dist_*') re-invokes it as a
    safety net.

    Bring-up is timeout-guarded (a worker pointed at a dead coordinator
    used to block in ``jax.distributed.initialize`` forever): non-zero
    ranks probe the coordinator over TCP with retry/backoff for a
    ``MXTPU_CONNECT_TIMEOUT × (MXTPU_CONNECT_RETRIES+1)`` window
    (defaults 60s × 3); expiry raises MXNetError naming the coordinator
    — an *exit*, which the launcher classifies retryable and answers
    with a job restart, instead of an eternal hang.  A rank-0
    coordinator-port bind failure exits ``EXIT_PORT_IN_USE`` (76) so the
    launcher can re-pick the port (``--port 0``) on restart."""
    import os
    coord = os.environ.get("MXTPU_COORDINATOR")
    if not coord:
        return
    import jax
    if _distributed_initialized(jax):
        # already joined — but an elastic restart may have re-exported
        # the membership env (tools/launch.py --elastic re-ranks the
        # survivors and changes MXTPU_NUM_WORKERS between attempts).
        # Each elastic attempt is a fresh PROCESS, so normally this path
        # never sees a mismatch.  When it does (a harness re-exporting
        # env inside one process), say so loudly and KEEP the old mesh:
        # jax pins the process topology for the process lifetime
        # (process_count/process_index are lru_cached over the frozen
        # backend), so a shutdown+re-initialize here would neither
        # update what jax reports nor ever clear the mismatch — it
        # would just re-run bring-up on every later call.  The only
        # supported way to change this process's membership is to exit
        # and let the launcher respawn it (retryable exits exist for
        # exactly that).
        if _membership_env_changed(jax):
            import logging
            logging.warning(
                "mxnet_tpu: membership env (MXTPU_NUM_WORKERS/"
                "MXTPU_WORKER_RANK=%s/%s) no longer matches the mesh "
                "this process joined (%d processes, rank %d); jax "
                "cannot re-join in-process — keeping the existing "
                "mesh. Exit the process and let tools/launch.py "
                "respawn it under the new membership.",
                os.environ.get("MXTPU_NUM_WORKERS"),
                os.environ.get("MXTPU_WORKER_RANK"),
                jax.process_count(), jax.process_index())
        return  # re-calls are no-ops
    if os.environ.get("MXTPU_RANK_FROM_MPI") == "1" and \
            "MXTPU_WORKER_RANK" not in os.environ:
        # mpi launcher (tools/launch.py --launcher mpi): adopt the rank
        # mpirun assigned this process (and fill the reference-compat
        # DMLC_WORKER_ID alongside, like the local/ssh launchers do)
        for var in ("OMPI_COMM_WORLD_RANK", "PMI_RANK", "PMIX_RANK",
                    "SLURM_PROCID"):
            if var in os.environ:
                os.environ["MXTPU_WORKER_RANK"] = os.environ[var]
                os.environ.setdefault("DMLC_WORKER_ID", os.environ[var])
                break
    try:
        num = int(os.environ["MXTPU_NUM_WORKERS"])
        rank = int(os.environ["MXTPU_WORKER_RANK"])
    except KeyError as e:
        # misconfigured env (coordinator without rank contract): the old
        # degrade-to-single-process behaviour, loudly
        import logging
        logging.warning(
            "mxnet_tpu: could not join the distributed mesh at %s (%s); "
            "this process runs single-process. Import mxnet_tpu (or "
            "create the dist kvstore) before touching any arrays.",
            coord, e)
        return
    import sys
    import time
    from .watchdog import EXIT_PORT_IN_USE, _env_float

    def _port_in_use_exit(detail):
        print("mxnet_tpu: coordinator port %s is already bound (%s); "
              "exiting %d so the launcher re-picks the port (--port 0) "
              "on restart" % (coord, detail, EXIT_PORT_IN_USE),
              file=sys.stderr, flush=True)
        raise SystemExit(EXIT_PORT_IN_USE)

    if rank == 0 and not _coordinator_port_free(coord):
        _port_in_use_exit("pre-bind probe failed")

    t = _env_float("MXTPU_CONNECT_TIMEOUT", 0.0)
    timeout = t if t > 0 else 60.0
    # 0 retries is a valid choice (fail fast after one window)
    retries = max(0, int(_env_float("MXTPU_CONNECT_RETRIES", 2.0)))
    if rank != 0:
        # dead-coordinator defense BEFORE touching jax.distributed: on
        # deadline expiry jax's own initialization_timeout hard-aborts
        # the process (LOG(FATAL) in the XLA coordination client, SIGABRT
        # — no Python exception to catch), so the bounded wait runs as a
        # plain TCP probe here, where failure can raise a diagnosable
        # MXNetError naming the coordinator
        _wait_for_coordinator(coord, timeout * (retries + 1))
    try:
        # belt only (the TCP probe above bounds the dead-coordinator
        # case): never BELOW jax's own 300s default — the connect
        # timeout is sized for "is the coordinator reachable", not for
        # a slow-but-healthy whole-cluster join (hosts can start
        # minutes apart on a real pod)
        jax.distributed.initialize(
            coordinator_address=coord, num_processes=num,
            process_id=rank,
            initialization_timeout=int(
                max(300, timeout * (retries + 1))))
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception as e:  # jax wraps grpc errors inconsistently
        msg = str(e).lower()
        if "should only be called once" in msg:
            # raced another in-process initializer: already joined —
            # still publish the membership (the race winner may have
            # been user code calling jax.distributed.initialize
            # directly, which records nothing)
            from . import elastic
            elastic.note_membership(num, rank)
            return
        if rank == 0 and ("address already in use" in msg or
                          "address in use" in msg or
                          "failed to bind" in msg):
            _port_in_use_exit(e)
        raise MXNetError(
            "could not join the distributed mesh at %s as rank %d/%d: "
            "%s. Exiting so the launcher can restart the job instead "
            "of hanging in bring-up forever." % (coord, rank, num, e)
        ) from e
    # joined: publish the membership this process runs under — feeds the
    # elastic.world_size gauge / elastic.transitions counter (a restart
    # at a different world size counts via MXTPU_PREV_WORLD_SIZE) and
    # the postmortem membership block
    from . import elastic
    elastic.note_membership(num, rank)


def _wait_for_coordinator(coord, deadline_s):
    """Bounded retry-with-backoff TCP probe of the coordinator: returns
    once it accepts a connection (rank 0 may start it at any point inside
    the window), raises MXNetError naming the address when the deadline
    expires — the worker *exits* (retryable, launch.py restarts the job)
    instead of blocking in bring-up forever."""
    import socket
    import time
    host, _, port = coord.rpartition(":")
    try:
        port = int(port)
    except ValueError:
        return  # unparseable address: let initialize() report it
    deadline = time.monotonic() + max(1.0, deadline_s)
    delay, last = 0.2, None
    while True:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            break
        try:
            s = socket.create_connection(
                (host or "127.0.0.1", port),
                timeout=min(5.0, max(0.5, remaining)))
            s.close()
            return
        except OSError as e:
            last = e
        time.sleep(min(delay, max(0.0, deadline - time.monotonic())))
        delay = min(delay * 1.6, 3.0)
    raise MXNetError(
        "could not join the distributed mesh: coordinator %s did not "
        "accept a connection within %.0fs (last error: %s). The "
        "coordinator is dead, unreachable, or never started; exiting "
        "so the launcher can restart the job instead of hanging in "
        "bring-up forever." % (coord, deadline_s, last))
