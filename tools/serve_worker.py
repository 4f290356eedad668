#!/usr/bin/env python
"""One out-of-process serving replica: engine + RPC front-end.

The process shape of ISSUE 14: each ``ServingReplica`` runs in its own
OS process behind the length-framed JSON RPC plane
(``mxnet_tpu/serving/rpc.py``).  The main loop single-threadedly
interleaves RPC handling with the decode loop — the engine is never
touched from two threads:

    accept/answer pending RPCs  →  replica.step() when non-idle
    →  drain-on-request (exit 80)  →  repeat

Spin-up publishes a PORT FILE (``MXTPU_SERVE_PORT_FILE`` or
``--port-file``) carrying host/port/pid/attempt/boot-nonce — the
incarnation stamp router proxies pin, so a replacement taking over
the slot reads as confirmed death to the old proxy, never a silent
redirect.  The file is BOOTSTRAP DISCOVERY only (ISSUE 17): liveness
rides the ``heartbeat`` RPC (incarnation + decode-progress sequence),
and drain orders arrive as incarnation-authenticated ``drain`` RPCs —
the worker trusts no shared filesystem once it is up.  With
``MXTPU_AOT_CACHE_DIR`` exported (the ``tools/launch.py --serve``
default) a replacement spins up AOT-warm: 0 foreground serving
compiles before its first token (the health RPC reports the count).

Exit codes (the tools/launch.py contract):

- 80 — graceful drain (an RPC ``drain`` request, or SIGTERM): finish
  residents + accepted queue, verify page conservation, exit clean
  (never blamed toward eviction; the launcher journals
  drain/replace and respawns AOT-warm);
- 77 — replica lost (the ``serve.replica.lost`` site fired in a
  standalone process): retryable;
- 75 — a wedged decode (the stall watchdog's exit, armed via
  MXTPU_STALL_TIMEOUT);
- SIGKILL — the ``serve.replica.sigkill`` site (or the OOM killer):
  no cleanup runs, which is exactly what the fleet drill drills.

The model is built DETERMINISTICALLY from CLI args (seed + dims), so
every replica of a fleet serves bit-identical greedy tokens — the
failover re-decode contract.  ``--checkpoint-prefix`` additionally
subscribes the replica to a CheckpointManager prefix for live weight
hot-swap (PR 11).

Usage (typically under ``tools/launch.py --serve``):

    python tools/serve_worker.py --port-file /run/serve-port-slot0.json
"""
from __future__ import annotations

import argparse
import os
import signal
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def build_net(args):
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import gpt

    np.random.seed(args.seed)
    mx.random.seed(args.seed)
    net = gpt.GPTLM(args.vocab, args.n_layer, args.d_model, args.n_head,
                    max_len=args.max_len)
    net.initialize()
    return net


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="out-of-process serving replica (ISSUE 14)")
    parser.add_argument("--port-file",
                        default=os.environ.get("MXTPU_SERVE_PORT_FILE"),
                        help="where to publish host/port/pid/attempt "
                        "(MXTPU_SERVE_PORT_FILE; required)")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=0,
                        help="0 = pick a free port (the port file is "
                        "the discovery channel)")
    # deterministic model build — every replica of a fleet must serve
    # bit-identical greedy tokens
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--vocab", type=int, default=256)
    parser.add_argument("--n-layer", type=int, default=2)
    parser.add_argument("--d-model", type=int, default=128)
    parser.add_argument("--n-head", type=int, default=4)
    parser.add_argument("--max-len", type=int, default=64)
    # engine shape
    parser.add_argument("--num-slots", type=int, default=8)
    parser.add_argument("--page-size", type=int, default=16)
    parser.add_argument("--max-prefill-len", type=int, default=32)
    parser.add_argument("--max-seq-len", type=int, default=48)
    parser.add_argument("--checkpoint-prefix", default=None,
                        help="subscribe to this CheckpointManager "
                        "prefix for live weight hot-swap")
    parser.add_argument("--idle-sleep", type=float, default=0.02,
                        help="idle RPC-poll timeout, seconds — the "
                        "only time the loop blocks (submit pickup "
                        "latency when idle; a serving loop polls "
                        "non-blocking)")
    parser.add_argument("--drain-linger", type=float, default=3.0,
                        help="seconds to keep answering status RPCs "
                        "after a drain completes, so router proxies "
                        "harvest the final request states before the "
                        "process exits 80")
    parser.add_argument("--max-seconds", type=float, default=0,
                        help="exit 0 after this long (test hygiene "
                        "backstop; 0 = run until drained/killed)")
    args = parser.parse_args(argv)
    if not args.port_file:
        parser.error("--port-file (or MXTPU_SERVE_PORT_FILE) required")

    # identity: under launch.py --serve the slot IS the rank (serving
    # has no collective world to re-pack)
    slot = os.environ.get("MXTPU_WORKER_SLOT",
                          os.environ.get("MXTPU_WORKER_RANK", "0"))
    attempt = int(os.environ.get("MXTPU_RESTART_ATTEMPT", "0") or 0)

    import jax
    jax.devices()   # backend up before the engine builds programs

    from mxnet_tpu import aot_cache, telemetry, watchdog
    aot_cache.enable_persistent_cache()
    from mxnet_tpu.serving import (CheckpointSubscriber, ReplicaLost,
                                   ServingEngine, ServingReplica)
    from mxnet_tpu.serving.rpc import RpcServer, write_port_file

    telemetry.install_crash_hooks()
    watchdog.start_heartbeat()      # no-op without MXTPU_HEARTBEAT_DIR
    watchdog.maybe_arm()            # no-op without MXTPU_STALL_TIMEOUT

    net = build_net(args)
    engine = ServingEngine(net, num_slots=args.num_slots,
                           page_size=args.page_size,
                           max_prefill_len=args.max_prefill_len,
                           max_seq_len=args.max_seq_len)
    subscriber = None
    if args.checkpoint_prefix:
        subscriber = CheckpointSubscriber(args.checkpoint_prefix, net)
    # the replica id names the INCARNATION: a replacement must not
    # inherit its corpse's tag, or serve_report's failover arcs would
    # read victim == survivor and the fleet view could never link a
    # re-decode that landed on the replaced slot (the proxy-side
    # successor naming, "slotK+attempt", matches this)
    rid = "slot%s" % slot if attempt == 0 else \
        "slot%s+%d" % (slot, attempt)
    replica = ServingReplica(engine, replica_id=rid,
                             subscriber=subscriber)
    # durable-before-discoverable: the engine's AOT variant stores run
    # in the background; a COLD worker must not publish its port file
    # (→ the fleet looks ready → a drill may kill a peer → the
    # launcher spawns a replacement) until its executables are on
    # disk, or the replacement races the store and pays a foreground
    # compile the warm-spin-up contract forbids
    aot_cache.drain(timeout=180)
    server = RpcServer(replica, host=args.host, port=args.port,
                       attempt=attempt)
    # the port file repeats the server's OWN boot nonce: discovery and
    # the heartbeat RPC describe the same incarnation, so a proxy can
    # cross-check either channel without false mismatches
    write_port_file(args.port_file, server.port, host=args.host,
                    attempt=attempt,
                    nonce=server.incarnation["nonce"])
    print("serve_worker: slot %s attempt %d serving on %s:%d (pid %d "
          "nonce %s)"
          % (slot, attempt, args.host, server.port, os.getpid(),
             server.incarnation["nonce"]),
          file=sys.stderr, flush=True)

    # SIGTERM = polite drain request (the launcher teardown path): the
    # loop below notices and runs the full drain protocol → exit 80
    def _on_term(_sig, _frm):
        server.drain_requested = True
    signal.signal(signal.SIGTERM, _on_term)

    t_end = (time.monotonic() + args.max_seconds
             if args.max_seconds > 0 else None)
    rc = 0
    next_alert_t = 0.0
    try:
        while True:
            if t_end is not None and time.monotonic() > t_end:
                print("serve_worker: --max-seconds reached; exiting",
                      file=sys.stderr, flush=True)
                break
            idle = replica.idle
            server.poll(timeout=args.idle_sleep if idle else 0.0)
            if server.drain_requested:
                rc = replica.drain()
                # linger answering STATUS RPCs so router proxies can
                # harvest the drained requests' final states — exiting
                # on the ack would make the completions unobservable
                # and strand every in-flight handle "running"
                t_linger = time.monotonic() + args.drain_linger
                while time.monotonic() < t_linger:
                    server.poll(timeout=0.05)
                print("serve_worker: drained clean; exiting %d" % rc,
                      file=sys.stderr, flush=True)
                break
            if not replica.idle:
                replica.step()
            else:
                if subscriber is not None:
                    # an idle replica still hot-swaps publications
                    replica.maybe_swap()
                # idle alert cadence (ISSUE 18): replica.step() runs
                # the rules while decoding; an idle worker must still
                # notice its own stall/breaker state between pulls
                now = time.monotonic()
                if now >= next_alert_t:
                    next_alert_t = now + 1.0
                    telemetry.check_alerts(now)
                    # an idle worker still ages out retained stream
                    # buffers (ISSUE 19): step() runs this sweep while
                    # decoding, but terminal buffers past their TTL
                    # must not pin memory on a quiet replica
                    engine.sweep_streams()
    except ReplicaLost as e:
        # a standalone replica dies retryable — the launcher respawns
        # the slot and the router's proxy confirms the death
        print("serve_worker: %s — exiting retryable" % e,
              file=sys.stderr, flush=True)
        rc = 77
    finally:
        server.close()
        telemetry.stop_emitter()
        watchdog.stop_heartbeat()
    return rc


if __name__ == "__main__":
    sys.exit(main())
