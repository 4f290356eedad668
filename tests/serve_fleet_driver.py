"""Router driver for the out-of-process fleet e2e (ISSUE 14), run as
a CLEAN subprocess (the serving_driver.py pattern) against a live
``tools/launch.py --serve`` fleet:

- builds the Router over :func:`mxnet_tpu.serving.rpc.fleet_proxies`
  (port-file discovery, heartbeat fusion);
- serves a seeded workload while slot 1's armed
  ``serve.replica.sigkill`` kills that replica mid-load (the launcher
  respawns it; the router's spawn callback adopts the successor);
- asserts the survivability contract: every accepted request completes
  EXACTLY ONCE (router journal audited: one ``complete`` line per
  rid), greedy tokens bit-identical to an in-process reference engine
  on the same seed/net, ≥1 journaled failover retry, and the
  replacement incarnation reports 0 foreground serving compiles over
  its health RPC (AOT-warm via the launch-shared cache);
- leaves its own telemetry stream + router journal in the run-dir
  tree, so the test can run ``serve_report`` over the REAL
  multi-process artifacts afterwards.

Usage: python serve_fleet_driver.py RUN_DIR
Prints SERVE_FLEET_OK on success; any assertion failure exits nonzero.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np  # noqa: E402

# identity for the driver's own stream lines (a pseudo-slot far from
# the replica slots) — set BEFORE the package import stamps identity
os.environ.setdefault("MXTPU_WORKER_SLOT", "9")
os.environ.setdefault("MXTPU_WORKER_RANK", "9")

import mxnet_tpu  # noqa: E402,F401
from mxnet_tpu import telemetry  # noqa: E402
from mxnet_tpu.serving import Router, ServingEngine  # noqa: E402
from mxnet_tpu.serving.rpc import fleet_proxies  # noqa: E402

SLOTS = [0, 1, 2]
ENGINE_KW = dict(num_slots=8, page_size=16, max_prefill_len=32,
                 max_seq_len=48)


def expected_tokens(prompts, new_tokens):
    """The unfaulted reference: one in-process engine on the same
    seeded net the workers build — greedy decode is placement-
    independent, so the fleet must reproduce these bit-for-bit."""
    import argparse
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    from serve_worker import build_net
    ns = argparse.Namespace(seed=0, vocab=256, n_layer=2, d_model=128,
                            n_head=4, max_len=64)
    eng = ServingEngine(build_net(ns), **ENGINE_KW)
    out = []
    for p, n in zip(prompts, new_tokens):
        out.append(eng.generate([p], n)[0])
    return out


def main(run_dir):
    tdir = os.path.join(run_dir, "telemetry")
    os.makedirs(tdir, exist_ok=True)
    telemetry.start_emitter(
        os.path.join(tdir, "stream-slot9.jsonl"), interval=0.25)
    journal_path = os.path.join(tdir, "router-journal-slot9.jsonl")

    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, 256, int(rng.randint(4, 20)))
               .astype(np.int32) for _ in range(9)]
    new_tokens = [int(rng.randint(4, 9)) for _ in range(9)]
    expect = expected_tokens(prompts, new_tokens)

    proxies = fleet_proxies(run_dir, SLOTS, timeout=180,
                            timeout_s=1.0)
    replaced = []

    def spawn():
        # the launcher already respawned the dead slot (or is about
        # to): adopt whichever dead proxy has no successor yet
        for p in proxies:
            if not p.alive and p not in replaced:
                replaced.append(p)
                fresh = p.successor(timeout=150)
                proxies.append(fresh)
                return fresh
        raise RuntimeError("spawn() called with no dead proxy")

    rt = Router(list(proxies), spawn=spawn, max_retries=2,
                journal_path=journal_path)
    rrs = [rt.submit(p, n) for p, n in zip(prompts, new_tokens)]

    deadline = time.time() + 240
    while not all(rr.done for rr in rrs) and time.time() < deadline:
        rt.step()
        time.sleep(0.01)

    states = [(rr.state, rr.verdict, rr.replica_id) for rr in rrs]
    assert all(rr.state == "completed" for rr in rrs), states
    got = [rr.tokens for rr in rrs]
    assert got == expect, "fleet tokens diverged from the unfaulted " \
        "reference decode (failover re-decode must be bit-identical)"

    # the kill really happened and was failed over
    assert rt.failovers == 1, rt.failovers
    retried = [rr for rr in rrs if rr.retries > 0]
    assert retried, "no request was failed over by the sigkill"
    assert replaced and replaced[0].replica_id == "slot1", replaced

    # exactly-once, from the durable audit record: one `complete` line
    # per rid, and every retry names the killed replica
    completes, retries = {}, []
    with open(journal_path) as f:
        for line in f:
            doc = json.loads(line)
            if doc["event"] == "complete":
                completes[doc["rid"]] = completes.get(doc["rid"], 0) + 1
            elif doc["event"] == "retry":
                retries.append(doc)
    assert sorted(completes) == sorted(rr.rid for rr in rrs)
    assert all(n == 1 for n in completes.values()), completes
    assert retries and all(d.get("from_replica") == "slot1"
                           for d in retries), retries

    # the replacement incarnation is AOT-warm: 0 foreground compiles
    successor = proxies[-1]
    health = successor.health()
    assert health.get("reachable"), health
    assert health["remote"].get("serve_compiles") == 0, health["remote"]
    assert health["remote"]["health"]["engine"]["decode_steps"] > 0, \
        "the replacement never actually served"

    # the operator's live matrix over the healed fleet, from status +
    # telemetry_pull alone: one complete row for every live worker
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "perf_probe"))
    import fleet_top
    rows = fleet_top.collect_matrix(
        fleet_top.discover_targets(run_dir), timeout_s=5.0)["rows"]
    assert sorted(r["replica"] for r in rows) == \
        ["slot%d" % s for s in SLOTS], rows
    for r in rows:
        assert r.get("up") and r.get("engine") and \
            r.get("hb_rtt_ms") is not None, r

    telemetry.stop_emitter()
    with open(os.path.join(run_dir, "driver-report.json"), "w") as f:
        json.dump({"completed": len(rrs), "failovers": rt.failovers,
                   "retried": len(retried),
                   "successor": successor.replica_id}, f)
    print("SERVE_FLEET_OK completed=%d failovers=%d retried=%d"
          % (len(rrs), rt.failovers, len(retried)), flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
