"""RPC-plane laws for the out-of-process serving fleet (ISSUE 14).

Everything here runs against STUB replicas behind a real
``RpcServer``/``RpcReplicaProxy`` pair over loopback sockets — the
transport, deadline, retry, idempotence and circuit-breaker laws are
socket-level properties and must not pay an XLA compile to be pinned.
Real-engine integration rides tests/test_serve_fleet.py (slow e2e via
``tools/launch.py --serve``).

Pinned laws:

- framing round-trips; oversized/corrupt frames fail fast;
- circuit breaker (INJECTED clock): trip at the consecutive-failure
  threshold, open blocks, cooldown → half-open admits exactly ONE
  probe, probe success closes, probe failure re-trips;
- ``rpc.conn.refused`` exercises bounded retry + backoff (the call
  succeeds once the site disarms, counters prove the retries);
- idempotent submit keys: a retry after a lost ACK (``rpc.drop``
  eating the reply) dedups into the ORIGINAL handle — the worker
  decodes the request exactly once;
- a replica that blackholes every RPC costs a request at most its
  remaining deadline (typed ``expired_rpc`` verdict), never an
  unbounded hang — and the breaker RECOVERS once the replica does;
- Router over proxies: completion harvest, refusal spread, and
  incarnation-change failover (a replacement rewriting the port file
  reads as confirmed death; victims re-decode on the successor);
- Router journal torn-tail replay: a journal truncated mid-line
  replays every complete entry, skips-and-counts the partial one, and
  preserves at-most-once for every completed rid;
- RPC-native liveness (ISSUE 17): heartbeat RPCs carry the incarnation
  stamp + progress sequence; ``rpc.heartbeat.drop`` raises suspicion
  but NEVER fails over (data plane alive); ``rpc.partition`` confirms
  via fence_expiry, fails over, and the zombie's late completion is
  REJECTED with the typed ``fenced`` journal line (non-terminal on
  replay); drain RPCs are authenticated by incarnation; a
  ``serve.worker.zombie`` swallows its drain order (supervisor
  escalation is the only cure); timed-out call bursts leak no fds;
- telemetry pull plane (ISSUE 18): per-consumer drain cursors deliver
  every event exactly once to EACH of two concurrent consumers with
  per-consumer eviction counts; the ``telemetry_pull`` RPC is
  non-destructive and idempotent under a client-held cursor; bounded
  chunks reassemble complete and duplicate-free; a cursor minted
  against a dead incarnation is a DECLARED reset, never silent
  loss/duplication; ``rpc.telemetry.drop`` parks only the
  observability plane and the re-pull recovers; alert rules fire into
  the same stream and window-suppress re-firings.
"""
import collections
import json
import os
import socket
import threading
import time

import numpy as np
import pytest

import mxnet_tpu  # noqa: F401 — package init (telemetry registry)
from mxnet_tpu import fault, telemetry
from mxnet_tpu.serving import (CircuitBreaker, ReplicaLost, Router,
                               RpcError, RpcReplicaProxy, RpcServer)
from mxnet_tpu.serving.replica import EXIT_SERVE_DRAIN
from mxnet_tpu.serving.rpc import (BREAKER_CLOSED, BREAKER_HALF_OPEN,
                                   BREAKER_OPEN, VERDICT_EXPIRED_RPC,
                                   collect_telemetry, pull_telemetry,
                                   recv_frame, rpc_call, send_frame,
                                   write_port_file)
from mxnet_tpu.serving.scheduler import FINISHED, SHED

pytestmark = pytest.mark.rpcfleet


# -- stub replica (the serving_surv stub, server-side flavored) ------------

class _StubReq:
    def __init__(self, rid, max_new, shed=False):
        self.rid = rid
        self.max_new = max_new
        self.state = SHED if shed else "running"
        self.verdict = "shed" if shed else None
        self.error = "stub shed" if shed else None
        self.tokens = []
        self.ttft_s = None
        self.queue_wait_s = 0.0
        self.tpot_s = None

    @property
    def done(self):
        return self.state not in ("queued", "running")


class _StubReplica:
    """Server-side replica duck-type: one deterministic token (rid*10
    + position) per step per request — completions are checkable
    without a model."""

    def __init__(self, rid="stub", shed=False, step_sleep=0.0):
        self.replica_id = rid
        self.alive = True
        self.draining = False
        self.shed_mode = shed
        self.step_sleep = step_sleep
        self.reqs = []
        self.submits = 0
        self._next = 0

    @property
    def load(self):
        return sum(1 for r in self.reqs if not r.done)

    @property
    def idle(self):
        return all(r.done for r in self.reqs)

    def submit(self, prompt, max_new, deadline_s=None, trace=None):
        self.submits += 1
        r = _StubReq(self._next, int(max_new), shed=self.shed_mode)
        self._next += 1
        if not self.shed_mode:
            self.reqs.append(r)
        return r

    def step(self):
        if self.step_sleep and any(not r.done for r in self.reqs):
            time.sleep(self.step_sleep)
        n = 0
        for r in self.reqs:
            if not r.done:
                r.tokens.append(r.rid * 10 + len(r.tokens))
                if r.ttft_s is None:
                    r.ttft_s = 0.001
                if len(r.tokens) >= r.max_new:
                    r.state = FINISHED
                    r.verdict = "completed"
                n += 1
        return n

    def drain(self):
        while not self.idle:
            self.step()
        self.draining = True
        self.alive = False
        return EXIT_SERVE_DRAIN

    def health(self):
        return {"replica_id": self.replica_id, "alive": self.alive}


class _WorkerLoop:
    """The serve_worker main loop, in a thread: poll RPCs, step the
    stub — so proxy calls in the test thread get answered."""

    def __init__(self, replica=None):
        self.replica = replica or _StubReplica()
        self.server = RpcServer(self.replica)
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    @property
    def addr(self):
        return (self.server.host, self.server.port)

    def _run(self):
        drained = False
        while not self._stop.is_set():
            self.server.poll(timeout=0.01)
            if self.server.drain_requested and not drained:
                drained = True
                self.replica.drain()   # then linger answering status
            elif not self.replica.idle and self.replica.alive:
                self.replica.step()

    def close(self):
        self._stop.set()
        self._t.join(timeout=5.0)
        self.server.close()


@pytest.fixture(autouse=True)
def _clean_faults():
    fault.reset()
    yield
    fault.reset()


# -- framing ---------------------------------------------------------------

def test_frame_roundtrip():
    a, b = socket.socketpair()
    try:
        doc = {"method": "x", "payload": list(range(100)),
               "s": "héllo"}
        send_frame(a, doc)
        assert recv_frame(b) == doc
    finally:
        a.close()
        b.close()


def test_frame_corrupt_length_fails_fast():
    a, b = socket.socketpair()
    try:
        a.sendall(b"\xff\xff\xff\xff")  # claims ~4 GiB
        with pytest.raises(RpcError):
            recv_frame(b, deadline_t=time.monotonic() + 1.0)
    finally:
        a.close()
        b.close()


def test_frame_truncated_payload_times_out():
    a, b = socket.socketpair()
    try:
        import struct
        a.sendall(struct.pack(">I", 100) + b"{")  # 99 bytes missing
        with pytest.raises((socket.timeout, RpcError)):
            recv_frame(b, deadline_t=time.monotonic() + 0.2)
    finally:
        a.close()
        b.close()


# -- circuit breaker laws (injected clock) ---------------------------------

def test_breaker_trips_at_threshold_and_resets_on_success():
    clk = [0.0]
    br = CircuitBreaker(threshold=3, cooldown_s=10.0,
                        clock=lambda: clk[0])
    assert br.state == BREAKER_CLOSED
    br.record_failure()
    br.record_failure()
    br.record_success()          # success resets the CONSECUTIVE count
    br.record_failure()
    br.record_failure()
    assert br.state == BREAKER_CLOSED
    br.record_failure()
    assert br.state == BREAKER_OPEN and br.trips == 1
    assert not br.allow()


def test_breaker_half_open_single_probe_then_close():
    clk = [0.0]
    br = CircuitBreaker(threshold=1, cooldown_s=5.0,
                        clock=lambda: clk[0])
    br.record_failure()
    assert br.state == BREAKER_OPEN
    clk[0] = 4.9
    assert not br.allow()
    clk[0] = 5.1
    assert br.allow()            # the ONE half-open probe
    assert br.state == BREAKER_HALF_OPEN
    assert not br.allow()        # second caller blocked while probing
    br.record_success()
    assert br.state == BREAKER_CLOSED
    assert br.allow()


def test_breaker_probe_failure_retrips_fresh_cooldown():
    clk = [0.0]
    br = CircuitBreaker(threshold=1, cooldown_s=5.0,
                        clock=lambda: clk[0])
    br.record_failure()
    clk[0] = 6.0
    assert br.allow()
    br.record_failure()          # probe failed
    assert br.state == BREAKER_OPEN and br.trips == 2
    clk[0] = 10.0                # 4s into the FRESH cooldown
    assert not br.allow()
    clk[0] = 11.1
    assert br.allow()


# -- retry / backoff -------------------------------------------------------

def test_conn_refused_retries_then_succeeds():
    w = _WorkerLoop()
    try:
        telemetry.reset()
        fault.configure("rpc.conn.refused:2")
        t0 = time.perf_counter()
        reply = rpc_call(w.addr, {"method": "health"}, 1.0, retries=3,
                         backoff_s=0.01, backoff_max_s=0.05)
        wall = time.perf_counter() - t0
        assert reply["ok"]
        assert telemetry.counter("rpc.retries").value == 2
        assert telemetry.counter("rpc.conn_errors").value == 2
        assert wall < 2.0        # bounded: two small backoffs, no hang
    finally:
        w.close()


def test_retries_exhausted_raises_rpc_error():
    fault.configure("rpc.conn.refused:10")
    with pytest.raises(RpcError):
        rpc_call(("127.0.0.1", 1), {"method": "health"}, 0.2,
                 retries=1, backoff_s=0.01)
    assert fault.fire_count("rpc.conn.refused") == 2  # 1 + 1 retry


def test_rpc_delay_is_bounded_not_fatal():
    w = _WorkerLoop()
    try:
        os.environ["MXTPU_FAULT_DELAY_SECS"] = "0.1"
        try:
            fault.configure("rpc.delay:1")
            t0 = time.perf_counter()
            reply = rpc_call(w.addr, {"method": "health"}, 2.0,
                             retries=0)
            wall = time.perf_counter() - t0
        finally:
            del os.environ["MXTPU_FAULT_DELAY_SECS"]
        assert reply["ok"] and wall >= 0.1
    finally:
        w.close()


# -- idempotent submit keys (the lost-ACK law) -----------------------------

def test_lost_ack_retry_dedups_never_double_decodes():
    w = _WorkerLoop()
    try:
        # first reply eaten by rpc.drop: the submit WAS processed and
        # journaled; the client retry must get the ORIGINAL handle
        fault.configure("rpc.drop:1")
        proxy = RpcReplicaProxy("a", addr=w.addr, timeout_s=0.3,
                                retries=2)
        m = proxy.submit(np.ones(3, np.int32), 2, trace="tr-1")
        assert w.replica.submits == 1          # exactly one decode
        for _ in range(50):
            proxy.step()
            if m.done:
                break
            time.sleep(0.01)
        assert m.state == FINISHED and len(m.tokens) == 2
    finally:
        w.close()


def test_duplicate_submit_key_returns_same_rid():
    w = _WorkerLoop()
    try:
        msg = {"method": "submit", "key": "K", "trace": "K",
               "prompt": [1, 2], "max_new": 1, "deadline_s": None}
        r1 = rpc_call(w.addr, msg, 1.0)
        r2 = rpc_call(w.addr, dict(msg), 1.0)
        assert r1["ok"] and r2["ok"]
        assert r2.get("dedup") is True
        assert r1["request"]["rid"] == r2["request"]["rid"]
        assert w.replica.submits == 1
    finally:
        w.close()


def test_shed_refusal_not_journaled():
    w = _WorkerLoop(_StubReplica(shed=True))
    try:
        msg = {"method": "submit", "key": "K2", "trace": "K2",
               "prompt": [1], "max_new": 1, "deadline_s": None}
        r1 = rpc_call(w.addr, msg, 1.0)
        assert r1["request"]["state"] == SHED
        r2 = rpc_call(w.addr, dict(msg), 1.0)
        # a refusal is not a decode: the retry gets a FRESH admission
        # attempt, not the dedup'd shed verdict
        assert r2.get("dedup") is None
        assert w.replica.submits == 2
    finally:
        w.close()


# -- blackhole: bounded cost + breaker recovery ----------------------------

def test_blackholed_replica_costs_at_most_the_deadline():
    w = _WorkerLoop()
    try:
        proxy = RpcReplicaProxy(
            "b", addr=w.addr, timeout_s=0.15, retries=0,
            breaker=CircuitBreaker(threshold=2, cooldown_s=0.2,
                                   name="b"))
        m = proxy.submit(np.ones(2, np.int32), 4, deadline_s=5.0,
                         trace="tr-bh")
        # now blackhole EVERY rpc (status polls included)
        fault.configure("rpc.drop:1000")
        m.deadline_t = proxy._clock() + 0.3   # 0.3s of budget left
        t0 = time.perf_counter()
        while not m.done and time.perf_counter() - t0 < 5.0:
            proxy.step()
            time.sleep(0.02)
        wall = time.perf_counter() - t0
        assert m.done, "blackholed request hung past its deadline"
        assert m.verdict == VERDICT_EXPIRED_RPC
        # budget (0.3) + one call timeout of grace (0.15) + slack —
        # NEVER the 5s hang ceiling
        assert wall < 2.0, wall
        assert telemetry.counter("rpc.expired_unreachable").value >= 1
        assert proxy.breaker.state == BREAKER_OPEN
        assert proxy.alive           # unreachable is NOT dead
        assert proxy.idle            # nothing left to wait on

        # the replica comes back: the breaker's half-open probe heals
        fault.reset()
        time.sleep(0.25)             # cooldown elapses
        proxy.step()                 # the probe
        assert proxy.breaker.state == BREAKER_CLOSED
        m2 = proxy.submit(np.ones(2, np.int32), 1, trace="tr-rec")
        for _ in range(50):
            proxy.step()
            if m2.done:
                break
            time.sleep(0.01)
        assert m2.state == FINISHED
    finally:
        w.close()


def test_breaker_open_submit_skips_without_socket():
    proxy = RpcReplicaProxy(
        "c", addr=("127.0.0.1", 1), timeout_s=0.1, retries=0,
        breaker=CircuitBreaker(threshold=1, cooldown_s=100.0,
                               name="c"))
    with pytest.raises(ReplicaLost):
        proxy.submit(np.ones(1, np.int32), 1, trace="t")  # trips it
    calls0 = telemetry.counter("rpc.calls").value
    errs0 = telemetry.counter("rpc.conn_errors").value
    with pytest.raises(ReplicaLost):
        proxy.submit(np.ones(1, np.int32), 1, trace="t2")
    # breaker-open: refused at the proxy, no socket burned
    assert telemetry.counter("rpc.calls").value == calls0
    assert telemetry.counter("rpc.conn_errors").value == errs0


# -- Router over proxies ---------------------------------------------------

def test_router_completes_over_rpc_proxies():
    wa, wb = _WorkerLoop(_StubReplica("a")), _WorkerLoop(_StubReplica("b"))
    try:
        pa = RpcReplicaProxy("a", addr=wa.addr, timeout_s=1.0)
        pb = RpcReplicaProxy("b", addr=wb.addr, timeout_s=1.0)
        rt = Router([pa, pb])
        rrs = [rt.submit(np.ones(2, np.int32), 3) for _ in range(4)]
        rt.run_until_idle(max_steps=2000)
        for _ in range(100):     # final harvest lag: one poll round
            rt.step()
            if all(rr.done for rr in rrs):
                break
            time.sleep(0.01)
        assert all(rr.state == "completed" for rr in rrs), \
            [(rr.state, rr.verdict) for rr in rrs]
        assert all(len(rr.tokens) == 3 for rr in rrs)
    finally:
        wa.close()
        wb.close()


def test_incarnation_change_fails_over_to_successor(tmp_path):
    """A replacement rewriting the slot's port file == confirmed death
    of the old incarnation: the Router prunes it, the spawn callback
    returns the successor proxy, victims re-decode there."""
    wa = _WorkerLoop(_StubReplica("a", step_sleep=0.02))  # doomed
    wc = _WorkerLoop(_StubReplica("c", step_sleep=0.001))  # successor
    try:
        pf = str(tmp_path / "serve-port-slot0.json")
        write_port_file(pf, wa.addr[1], attempt=0)
        pa = RpcReplicaProxy("slot0", port_file=pf, timeout_s=0.5)
        spawned = []

        def spawn():
            fresh = pa.successor(timeout=5.0)
            spawned.append(fresh)
            return fresh

        rt = Router([pa], spawn=spawn, max_retries=2)
        rr = rt.submit(np.ones(2, np.int32), 50)  # long enough to be
        rt.step()                                 # mid-flight
        assert rr.state == "accepted"
        # the launcher respawns slot 0: new pid/attempt, new port
        doc = {"host": "127.0.0.1", "port": wc.addr[1],
               "pid": os.getpid(), "attempt": 1, "t": time.time()}
        with open(pf, "w") as f:
            json.dump(doc, f)
        deadline = time.time() + 10.0
        while not rr.done and time.time() < deadline:
            rt.step()
            time.sleep(0.01)
        assert rt.failovers == 1 and spawned
        assert rr.state == "completed" and rr.retries == 1
        assert len(rr.tokens) == 50
        assert not pa.alive
        # the re-decode landed on the successor (replica c's stub)
        assert wc.replica.submits == 1
    finally:
        wa.close()
        wc.close()


def test_mute_connection_never_stalls_serving():
    """Slow-loris defense: a connection that sends NO frame (health
    probe, half-open socket, port scan) must cost the single-threaded
    worker loop nothing — frames assemble non-blocking, so real calls
    keep answering promptly while the mute socket just ages out."""
    w = _WorkerLoop(_StubReplica("a"))
    try:
        mutes = [socket.create_connection(w.addr) for _ in range(5)]
        time.sleep(0.05)               # the loop accepts them
        t0 = time.perf_counter()
        reply = rpc_call(w.addr, {"method": "health"}, 2.0, retries=0)
        dt = time.perf_counter() - t0
        assert reply["ok"] and dt < 0.5, dt
        proxy = RpcReplicaProxy("a", addr=w.addr, timeout_s=1.0)
        m = proxy.submit(np.ones(2, np.int32), 2, trace="t-mute")
        for _ in range(100):
            proxy.step()
            if m.done:
                break
            time.sleep(0.01)
        assert m.state == FINISHED
        for s in mutes:
            s.close()
    finally:
        w.close()


def test_router_drain_over_rpc_harvests_completions():
    """Router.drain harvests exactly once after the drains return: the
    proxy must observe every accepted request's FINAL state before
    returning, never strand them 'running' on the bare ack."""
    w = _WorkerLoop(_StubReplica("a", step_sleep=0.01))
    try:
        proxy = RpcReplicaProxy("a", addr=w.addr, timeout_s=1.0)
        rt = Router([proxy])
        rrs = [rt.submit(np.ones(2, np.int32), 10) for _ in range(3)]
        rt.step()
        out = rt.drain()
        assert out == [("a", EXIT_SERVE_DRAIN)]
        assert all(rr.state == "completed" and len(rr.tokens) == 10
                   for rr in rrs), [(rr.state, rr.verdict)
                                    for rr in rrs]
        assert not proxy.alive
    finally:
        w.close()


# -- router journal torn-tail replay ---------------------------------------

def test_journal_torn_tail_replay(tmp_path):
    journal = str(tmp_path / "router-journal-slot0.jsonl")
    w = _WorkerLoop()
    try:
        proxy = RpcReplicaProxy("a", addr=w.addr, timeout_s=1.0)
        rt = Router([proxy], journal_path=journal)
        rrs = [rt.submit(np.ones(2, np.int32), 2) for _ in range(3)]
        deadline = time.time() + 10.0
        while not all(rr.done for rr in rrs) and time.time() < deadline:
            rt.step()
            time.sleep(0.01)
        assert all(rr.state == "completed" for rr in rrs)
    finally:
        w.close()
    # crash simulation: the writer died mid-append — the tail is a
    # PARTIAL line (single-os.write discipline: earlier lines intact)
    with open(journal, "ab") as f:
        f.write(b'{"t": 1.0, "event": "accept", "rid": 99, "tr')
    rt2 = Router([], journal_path=journal)
    rep = rt2.replay_journal()
    assert rep["torn"] == 1
    assert rep["requests"] == 3
    for rr in rrs:
        replayed = rt2.request(rr.rid)
        assert replayed is not None
        assert replayed.state == "completed"      # at-most-once: never
        assert replayed.verdict == "completed"    # re-executed
        assert replayed.trace == rr.trace
    assert rt2._next_rid == 3                     # no rid collision
    # serve_report applies the same skip-and-count to the journal
    sys_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "perf_probe")
    import sys
    sys.path.insert(0, sys_path)
    try:
        import serve_report
        rep2 = serve_report.load_serve(str(tmp_path))
        assert len(rep2["journal"]) >= 3 * 2      # accept+complete each
        assert any("unparseable" in n for n in rep2["notes"])
    finally:
        sys.path.remove(sys_path)


def test_replay_journal_fenced_lines_are_non_terminal(tmp_path):
    """A journal mixing accept/retry/complete, a FENCED late completion
    (written AFTER the real complete — the zombie finished late), and a
    torn tail: fenced lines are counted and advance rids but never fold
    into the request's state; the torn line is skipped-and-counted."""
    journal = str(tmp_path / "router-journal-slot0.jsonl")
    lines = [
        {"t": 1.0, "event": "accept", "rid": 0, "trace": "tr-0",
         "replica": "slot0", "state": "accepted", "verdict": None,
         "retries": 0, "incarnation": [11, 0, "aa"], "fence_epoch": 0},
        {"t": 1.1, "event": "retry", "rid": 0, "trace": "tr-0",
         "replica": None, "state": "accepted", "verdict": None,
         "retries": 1, "from_replica": "slot0",
         "reason": "fence_expiry", "fence_epoch": 1},
        {"t": 1.2, "event": "accept", "rid": 0, "trace": "tr-0",
         "replica": "slot0+1", "state": "accepted", "verdict": None,
         "retries": 1, "incarnation": [12, 1, "bb"], "fence_epoch": 1},
        {"t": 1.3, "event": "complete", "rid": 0, "trace": "tr-0",
         "replica": "slot0+1", "state": "completed",
         "verdict": "completed", "retries": 1, "tokens": 4},
        {"t": 1.4, "event": "fenced", "rid": 0, "trace": "tr-0",
         "replica": "slot0", "state": "fenced", "verdict": "fenced",
         "retries": 1, "fence_epoch": 1, "tokens_rejected": 4},
        {"t": 1.5, "event": "accept", "rid": 1, "trace": "tr-1",
         "replica": "slot0+1", "state": "accepted", "verdict": None,
         "retries": 0},
    ]
    with open(journal, "w") as f:
        for doc in lines:
            f.write(json.dumps(doc) + "\n")
        f.write('{"t": 1.6, "event": "complete", "rid": 1, "tr')
    rt = Router([], journal_path=journal)
    rep = rt.replay_journal()
    assert rep["torn"] == 1
    assert rep["fenced"] == 1
    assert rep["entries"] == 6
    assert rep["requests"] == 2
    r0 = rt.request(0)
    # the fenced line came LAST but folded NOTHING: the request's own
    # story (completed on slot0+1) stands — at-most-once survives the
    # zombie's late completion across a router restart too
    assert r0.state == "completed" and r0.verdict == "completed"
    assert r0.replica_id == "slot0+1"
    assert r0.retries == 1
    r1 = rt.request(1)
    assert r1.state == "accepted"     # the torn complete never applied
    assert rt._next_rid == 2


# -- RPC-native liveness: heartbeats, suspicion, fencing (ISSUE 17) --------

def test_heartbeat_rpc_reports_incarnation_and_progress():
    w = _WorkerLoop()
    try:
        r = rpc_call(w.addr, {"method": "heartbeat"}, 1.0)
        assert r["ok"]
        inc = r["incarnation"]
        assert inc == w.server.incarnation
        assert inc["pid"] == os.getpid()
        assert set(r["progress"]) == {"decode_steps", "weights_epoch"}
        # the stub has no progress() duck-type: that reads as "no
        # progress signal", never as progress
        assert r["progress"]["decode_steps"] is None
        # two boots of the same pid/attempt still differ by nonce —
        # the component that survives pid recycling
        s2 = RpcServer(_StubReplica())
        try:
            assert s2.incarnation["nonce"] != inc["nonce"]
        finally:
            s2.close()
    finally:
        w.close()


def test_heartbeat_drop_raises_suspicion_never_failover():
    """``rpc.heartbeat.drop``: the liveness plane is blackholed while
    submits/status keep answering.  The fleet must record suspicion
    (counter + gauge + span) and keep serving — ZERO failovers, even
    with the tightest dead_after window — then clear the suspicion
    when the plane heals."""
    w = _WorkerLoop(_StubReplica("a", step_sleep=0.005))
    try:
        telemetry.reset()
        proxy = RpcReplicaProxy("a", addr=w.addr, timeout_s=0.5,
                                retries=0, heartbeat_s=0.02,
                                suspect_after_s=0.1, dead_after_s=0.3)
        rt = Router([proxy])
        rr = rt.submit(np.ones(2, np.int32), 20)
        rt.step()
        assert rr.state == "accepted"
        fault.configure("rpc.heartbeat.drop:100000")
        deadline = time.time() + 15.0
        while (not rr.done or not proxy.suspected) and \
                time.time() < deadline:
            rt.step()
            time.sleep(0.01)
        assert rr.state == "completed" and len(rr.tokens) == 20
        assert proxy.suspected
        assert telemetry.counter("rpc.suspicions").value >= 1
        assert rt.failovers == 0
        assert proxy.alive and proxy.confirmed_reason is None
        # the liveness plane heals: suspicion clears, nothing died
        fault.reset()
        while proxy.suspected and time.time() < deadline:
            rt.step()
            time.sleep(0.01)
        assert not proxy.suspected
        assert rt.failovers == 0
    finally:
        w.close()


def test_partition_fails_over_and_fences_the_zombie(tmp_path):
    """``rpc.partition``: the router's link to replica a is blackholed
    while a keeps decoding.  Confirmation types as ``fence_expiry``
    (suspicion sustained, zero observed progress), the victim re-places
    on the successor bit-identically, and the ZOMBIE's late completion
    — a never died — is observed and REJECTED with the typed ``fenced``
    journal line, which replays non-terminally."""
    journal = str(tmp_path / "router-journal-slot0.jsonl")
    wa = _WorkerLoop(_StubReplica("a", step_sleep=0.01))   # the zombie
    wb = _WorkerLoop(_StubReplica("b", step_sleep=0.001))  # successor
    try:
        telemetry.reset()
        pa = RpcReplicaProxy(
            "slot0", addr=wa.addr, timeout_s=0.2, retries=0,
            heartbeat_s=0.02, suspect_after_s=0.05, dead_after_s=0.3,
            breaker=CircuitBreaker(threshold=1, cooldown_s=100.0,
                                   name="slot0"))

        def spawn():
            # the partition heals the moment the replacement exists
            # (finite drills end); the zombie then becomes REACHABLE —
            # which is exactly what makes its late completion
            # observable instead of silently unread
            fault.reset()
            return RpcReplicaProxy("slot0+1", addr=wb.addr,
                                   timeout_s=1.0)

        rt = Router([pa], spawn=spawn, max_retries=2,
                    journal_path=journal)
        rr = rt.submit(np.ones(2, np.int32), 25)
        rt.step()
        assert rr.state == "accepted"
        fault.configure("rpc.partition:100000")
        deadline = time.time() + 20.0
        while rt.failovers == 0 and time.time() < deadline:
            rt.step()
            time.sleep(0.01)
        assert rt.failovers == 1
        assert pa.confirmed_reason == "fence_expiry"
        assert not pa.alive
        assert telemetry.counter(
            "rpc.confirmations.fence_expiry").value >= 1
        while not rr.done and time.time() < deadline:
            rt.step()
            time.sleep(0.01)
        # the re-decode completed exactly once, bit-identical to the
        # successor stub's deterministic stream
        assert rr.state == "completed" and rr.retries == 1
        assert rr.tokens == list(range(25))
        while telemetry.counter("rpc.fenced_results").value == 0 and \
                time.time() < deadline:
            rt.step()
            time.sleep(0.01)
        assert telemetry.counter("rpc.fenced_results").value >= 1
        # the default rules saw it: the confirmation and the rejected
        # write-back each fired an alert into the event stream
        telemetry.check_alerts()
        alerts = {e["args"]["rule"] for e in telemetry.request_events()
                  if e["event"] == "alert"}
        assert {"replica_fenced", "fenced_writeback"} <= alerts, alerts
        with open(journal) as f:
            lines = [json.loads(ln) for ln in f if ln.strip()]
        completes = [ln for ln in lines
                     if ln["event"] == "complete"
                     and ln["rid"] == rr.rid]
        fenced = [ln for ln in lines if ln["event"] == "fenced"]
        retries = [ln for ln in lines if ln["event"] == "retry"]
        assert len(completes) == 1          # at-most-once, audited
        assert fenced and fenced[0]["replica"] == "slot0"
        assert fenced[0]["fence_epoch"] == 1
        assert fenced[0]["tokens_rejected"] == 25
        assert retries and retries[0]["reason"] == "fence_expiry"
        rt2 = Router([], journal_path=journal)
        rep = rt2.replay_journal()
        assert rep["fenced"] == 1
        assert rt2.request(rr.rid).state == "completed"
        assert rt2.request(rr.rid).verdict == "completed"
    finally:
        wa.close()
        wb.close()


def test_drain_rpc_authenticated_by_incarnation():
    w = _WorkerLoop()
    try:
        wrong = {"pid": 1, "attempt": 99, "nonce": "deadbeef"}
        r = rpc_call(w.addr, {"method": "drain", "incarnation": wrong},
                     1.0)
        assert not r["ok"] and "incarnation" in r["error"]
        assert not w.server.drain_requested
        r2 = rpc_call(w.addr,
                      {"method": "drain",
                       "incarnation": dict(w.server.incarnation)}, 1.0)
        assert r2["ok"]
        assert w.server.drain_requested
    finally:
        w.close()


def test_zombie_swallows_drain_and_kill_ack_confirms():
    """``serve.worker.zombie``: the drain order is read and IGNORED —
    no ack, no drain flag; the caller's deadline is its only way out.
    The supervisor's escalation (kill + ack) is then the typed
    confirmation road for the proxy."""
    w = _WorkerLoop(_StubReplica("a"))
    try:
        fault.configure("serve.worker.zombie:2")
        proxy = RpcReplicaProxy("a", addr=w.addr, timeout_s=0.2,
                                retries=1)
        with pytest.raises(RpcError):
            proxy.drain(timeout=1.0)
        assert not w.server.drain_requested
        assert w.replica.alive
        # the site disarmed (count burnt): a fresh order lands — in the
        # real fleet this is the post-escalation REPLACEMENT accepting
        r = rpc_call(w.addr, {"method": "drain"}, 1.0)
        assert r["ok"] and w.server.drain_requested
        # kill-ack is confirmation evidence on its own: a proxy whose
        # supervisor reaped the corpse fails over on the next step
        dead = RpcReplicaProxy("d", addr=("127.0.0.1", 1),
                               timeout_s=0.1, retries=0)
        dead.note_kill_ack()
        with pytest.raises(ReplicaLost):
            dead.step()
        assert dead.confirmed_reason == "kill_ack"
    finally:
        w.close()


def test_inject_rpc_gated_by_env(monkeypatch):
    """The drill-plane ``inject`` method arms a fault site in a
    RUNNING worker (the partition drill needs to cut a link that
    already carries accepted work) — but ONLY when the worker was
    launched with MXTPU_RPC_ALLOW_INJECT=1; production workers take
    no fault orders over the wire."""
    w = _WorkerLoop(_StubReplica("a"))
    try:
        monkeypatch.delenv("MXTPU_RPC_ALLOW_INJECT", raising=False)
        r = rpc_call(w.addr, {"method": "inject",
                              "spec": "rpc.drop:1"}, 1.0)
        assert not r["ok"] and "MXTPU_RPC_ALLOW_INJECT" in r["error"]
        assert fault.fire_count("rpc.drop") == 0
        monkeypatch.setenv("MXTPU_RPC_ALLOW_INJECT", "1")
        r = rpc_call(w.addr, {"method": "inject",
                              "spec": "rpc.heartbeat.drop:1"}, 1.0)
        assert r["ok"] and r["armed"] == "rpc.heartbeat.drop:1"
        with pytest.raises(RpcError):   # the armed site fires
            rpc_call(w.addr, {"method": "heartbeat"}, 0.3, retries=0)
        # an empty spec disarms: the link heals
        r = rpc_call(w.addr, {"method": "inject", "spec": ""}, 1.0)
        assert r["ok"]
        assert rpc_call(w.addr, {"method": "heartbeat"}, 1.0)["ok"]
    finally:
        w.close()


# -- fd hygiene: the one-connection-per-call path --------------------------

@pytest.mark.skipif(not os.path.isdir("/proc/self/fd"),
                    reason="needs /proc/self/fd")
def test_timed_out_call_burst_does_not_leak_fds():
    """Every timeout/error branch of ``rpc_call`` must close its
    socket — a listener that never accepts (calls connect via the
    backlog, then time out waiting for the reply) is the worst case:
    25 timed-out calls, zero fd growth."""
    ls = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    try:
        ls.bind(("127.0.0.1", 0))
        ls.listen(64)
        addr = ls.getsockname()[:2]

        def fds():
            return len(os.listdir("/proc/self/fd"))

        with pytest.raises(RpcError):   # warm-up: lazy-import churn
            rpc_call(addr, {"method": "health"}, 0.02, retries=0)
        base = fds()
        for _ in range(25):
            with pytest.raises(RpcError):
                rpc_call(addr, {"method": "health"}, 0.02, retries=0)
        assert fds() <= base + 2, "timed-out rpc calls leaked fds"
    finally:
        ls.close()


# -- telemetry pull plane: cursor laws, chunking, drops, alerts (ISSUE 18) --

def _note_probe(tag, n):
    """Stamp ``n`` recognizable events; returns the probe's filter."""
    for i in range(n):
        telemetry.note_request_event("", "law_probe",
                                     args={"tag": tag, "i": i})

    def mine(evs):
        return [e for e in evs if e["event"] == "law_probe"
                and (e.get("args") or {}).get("tag") == tag]
    return mine


def test_two_consumers_each_see_every_event_exactly_once():
    """PR-13's exactly-once drain, now PER CONSUMER: the file emitter
    and a second drain cursor run against one ring and neither steals
    from the other — each consumer sees every event exactly once across
    its own consume calls."""
    telemetry.reset()
    mine = _note_probe("dual", 6)
    evs_a, drop_a = telemetry.consume_request_events("emitter")
    evs_b, drop_b = telemetry.consume_request_events("second")
    assert len(mine(evs_a)) == 6 and drop_a == 0
    assert len(mine(evs_b)) == 6 and drop_b == 0
    # consumed-for-A is NOT consumed-for-B: both cursors advanced past
    # the batch independently, and a re-consume delivers nothing twice
    assert mine(telemetry.consume_request_events("emitter")[0]) == []
    assert mine(telemetry.consume_request_events("second")[0]) == []
    mine2 = _note_probe("dual2", 3)
    assert len(mine2(telemetry.consume_request_events("second")[0])) == 3
    assert len(mine2(telemetry.consume_request_events("emitter")[0])) == 3


def test_slow_consumer_eviction_counted_per_consumer():
    """A consumer that drains slower than the ring turns over is the
    ONLY one whose record gains a gap — and the gap is declared on its
    own cursor (``dropped``), not smeared across every consumer."""
    telemetry.reset()
    ring = telemetry._req_ring
    telemetry._req_ring = collections.deque(maxlen=8)
    try:
        # register both cursors at seq 0, then let only "fast" keep up
        telemetry.consume_request_events("fast")
        telemetry.consume_request_events("slow")
        _note_probe("burst1", 6)
        evs, dropped = telemetry.consume_request_events("fast")
        assert len(evs) == 6 and dropped == 0
        # 12 more events through a ring of 8: everything before the
        # final 8 is evicted under "slow"'s still-parked cursor
        _note_probe("burst2", 12)
        evs, dropped = telemetry.consume_request_events("fast")
        assert dropped == 4          # 12 new - 8 surviving, fast's own
        assert len(evs) == 8
        evs, dropped = telemetry.consume_request_events("slow")
        assert dropped == 10         # 6 + 12 noted, only 8 survive
        assert len(evs) == 8
        # both recovered: the next batch is exactly-once again for each
        _note_probe("burst3", 2)
        assert telemetry.consume_request_events("fast")[1] == 0
        assert telemetry.consume_request_events("slow")[1] == 0
    finally:
        telemetry._req_ring = ring
        telemetry.reset()


def test_telemetry_pull_is_nondestructive_and_idempotent():
    """The ``telemetry_pull`` RPC serves a read-only slice under a
    CLIENT-held cursor: pulling never moves the emitter's cursor, and
    re-presenting an old cursor re-reads the same slice — a dropped
    reply costs nothing."""
    telemetry.reset()
    w = _WorkerLoop(_StubReplica("a"))
    try:
        mine = _note_probe("pull", 5)
        r1 = pull_telemetry(w.addr, timeout_s=2.0)
        assert r1["ok"] and not r1["reset"]
        assert r1["line"]["schema"] == "mxtpu-telemetry-2"
        got1 = mine(r1["line"].get("req_events") or [])
        assert len(got1) == 5
        # idempotent re-pull: the server held no per-client state, so
        # the same (None) cursor re-reads the very same events
        r1b = pull_telemetry(w.addr, timeout_s=2.0)
        assert ([e["seq"] for e in mine(r1b["line"].get("req_events")
                                        or [])]
                == [e["seq"] for e in got1])
        # ...and the pull stole nothing from the emitter's own cursor
        evs, dropped = telemetry.consume_request_events("emitter")
        assert len(mine(evs)) == 5 and dropped == 0
        # advancing the returned cursor is exact: only newer events
        mine2 = _note_probe("pull2", 3)
        r2 = pull_telemetry(w.addr, cursor=r1["cursor"], timeout_s=2.0)
        evs2 = r2["line"].get("req_events") or []
        assert len(mine2(evs2)) == 3 and not mine(evs2)
        assert not r2["reset"]
        assert telemetry.counter("rpc.telemetry.pulls").value >= 3
    finally:
        w.close()
        telemetry.reset()


def test_telemetry_pull_chunks_reassemble_complete():
    """Bounded chunks: ``max_events`` caps every reply and sets
    ``more``; walking the cursor reassembles the full record with no
    duplicate and no hole."""
    telemetry.reset()
    w = _WorkerLoop(_StubReplica("a"))
    try:
        mine = _note_probe("chunk", 10)
        seqs, cursor, pulls = [], None, 0
        while True:
            r = pull_telemetry(w.addr, cursor=cursor, max_events=3,
                               timeout_s=2.0)
            cursor = r["cursor"]
            evs = r["line"].get("req_events") or []
            assert len(evs) <= 3
            seqs += [e["seq"] for e in mine(evs)]
            pulls += 1
            if not r["more"]:
                break
            assert r["line"]["pull"]["more"]
        assert pulls > 1, "10 events in 3-event chunks must span pulls"
        assert len(seqs) == 10 and len(set(seqs)) == 10
        assert seqs == sorted(seqs)
    finally:
        w.close()
        telemetry.reset()


def test_telemetry_pull_incarnation_reset_declared_across_restart():
    """A cursor minted against a dead incarnation would index a
    different boot's seq space — honoring it silently drops or
    duplicates.  The successor DECLARES the discontinuity
    (``reset: True``) and restarts the slice from the oldest surviving
    record, so the collector re-reads rather than loses."""
    telemetry.reset()
    w1 = _WorkerLoop(_StubReplica("a"))
    addr1 = w1.addr
    try:
        _note_probe("before", 4)
        r1 = pull_telemetry(addr1, timeout_s=2.0)
        held = r1["cursor"]
        assert held["incarnation"]["nonce"]
    finally:
        w1.close()
    # events the old incarnation never shipped under the held cursor
    mine_after = _note_probe("after", 3)
    w2 = _WorkerLoop(_StubReplica("a2"))   # fresh boot nonce
    try:
        r2 = pull_telemetry(w2.addr, cursor=held, timeout_s=2.0)
        assert r2["reset"], "stale-incarnation cursor must be declared"
        assert (r2["incarnation"]["nonce"]
                != held["incarnation"]["nonce"])
        # the reset slice restarts from the oldest surviving event:
        # nothing after the held cursor is silently skipped
        evs = r2["line"].get("req_events") or []
        assert len(mine_after(evs)) == 3
        # and the NEW cursor advances cleanly on this incarnation
        r3 = pull_telemetry(w2.addr, cursor=r2["cursor"], timeout_s=2.0)
        assert not r3["reset"]
        assert not mine_after(r3["line"].get("req_events") or [])
    finally:
        w2.close()
        telemetry.reset()


def test_telemetry_drop_parks_reply_and_repull_recovers():
    """``rpc.telemetry.drop`` blackholes ONE pull reply — the
    observability plane only: the collector eats its deadline, the data
    plane never notices, and the client-held cursor makes the re-pull
    idempotent — the record comes through complete."""
    telemetry.reset()
    w = _WorkerLoop(_StubReplica("a"))
    try:
        mine = _note_probe("dropped", 4)
        fault.configure("rpc.telemetry.drop:1")
        with pytest.raises(RpcError):
            pull_telemetry(w.addr, timeout_s=0.3, retries=0)
        assert telemetry.counter(
            "rpc.telemetry.dropped_replies").value == 1
        # the data plane stayed up throughout the drill
        assert rpc_call(w.addr, {"method": "health"}, 1.0)["ok"]
        # re-pull with the same (absent) cursor: nothing was consumed
        # server-side, so the lost reply's events all arrive now
        r = pull_telemetry(w.addr, timeout_s=2.0)
        assert len(mine(r["line"].get("req_events") or [])) == 4
        assert not r["reset"]
    finally:
        w.close()
        telemetry.reset()


def test_collect_telemetry_appends_emitter_shaped_stream(tmp_path):
    """The collector primitive lands pulled lines in a stream file the
    existing readers parse unchanged, and a held cursor across collect
    calls keeps the file duplicate-free."""
    telemetry.reset()
    w = _WorkerLoop(_StubReplica("a"))
    path = str(tmp_path / "stream-pulled.jsonl")
    try:
        mine = _note_probe("collect", 4)
        out1 = collect_telemetry(path, w.addr, timeout_s=2.0)
        assert out1["lines"] >= 1 and out1["resets"] == 0
        mine2 = _note_probe("collect2", 2)
        out2 = collect_telemetry(path, w.addr, cursor=out1["cursor"],
                                 timeout_s=2.0)
        assert out2["lines"] >= 1
        docs = [json.loads(ln) for ln in
                open(path, encoding="utf-8") if ln.strip()]
        assert all(d["schema"] == "mxtpu-telemetry-2" for d in docs)
        evs = [e for d in docs for e in d.get("req_events") or []]
        assert len(mine(evs)) == 4 and len(mine2(evs)) == 2
        seqs = [e["seq"] for e in evs]
        assert len(seqs) == len(set(seqs)), "held cursor must dedup"
    finally:
        w.close()
        telemetry.reset()


def test_alert_rules_fire_into_stream_and_window_suppress():
    """A counter-delta rule fires once per window however bursty the
    counter, the firing rides the request-event stream every consumer
    already drains (including the RPC pull), and the counter
    ``telemetry.alerts`` counts every firing."""
    telemetry.reset()
    rules = telemetry.alert_rules()
    telemetry.clear_alert_rules()
    w = _WorkerLoop(_StubReplica("a"))
    try:
        telemetry.add_alert_rule("law_burst", "law.alert_probe",
                                 kind="counter_delta",
                                 severity="critical", window_s=30.0)
        telemetry.counter("law.alert_probe").inc(5)
        fired = telemetry.check_alerts(now=100.0)
        assert [f["rule"] for f in fired] == ["law_burst"]
        assert fired[0]["value"] == 5 and fired[0]["severity"] == \
            "critical"
        assert telemetry.counter("telemetry.alerts").value == 1
        # window suppression: a fresh burst inside the window is quiet
        telemetry.counter("law.alert_probe").inc(2)
        assert telemetry.check_alerts(now=110.0) == []
        # ...and re-alerts once the window elapses
        telemetry.counter("law.alert_probe").inc(1)
        refired = telemetry.check_alerts(now=131.0)
        assert [f["rule"] for f in refired] == ["law_burst"]
        # the firings ride the SAME stream the pull drains: trace-less
        # typed events, rendered by serve_report/fleet_top downstream
        r = pull_telemetry(w.addr, timeout_s=2.0)
        alerts = [e for e in r["line"].get("req_events") or []
                  if e["event"] == "alert"]
        assert [a["args"]["rule"] for a in alerts] == ["law_burst"] * 2
        assert alerts[0]["trace"] == ""
    finally:
        w.close()
        telemetry.clear_alert_rules()
        for r in rules:
            telemetry._alert_rules.append(r)
        telemetry.reset()


# -- streamed delivery: cursor laws, cancel, drop drill (ISSUE 19) ---------

class _StreamStub(_StubReplica):
    """The stub, delivery-plane flavored: requests carry a trace, and
    ``poll``/``cancel`` implement the engine's cursor contract (pure
    function of (request state, cursor); typed ``cancelled`` verdict)
    so the WIRE's laws are testable without a model."""

    def submit(self, prompt, max_new, deadline_s=None, trace=None,
               **kw):
        r = super().submit(prompt, max_new, deadline_s=deadline_s,
                           trace=trace)
        r.trace = trace if trace is not None else "stub-%d" % r.rid
        return r

    def _find(self, trace):
        for r in self.reqs:
            if getattr(r, "trace", None) == trace:
                return r
        return None

    def poll(self, trace, cursor=0, max_tokens=None):
        r = self._find(trace)
        if r is None:
            return None
        cursor = max(0, int(cursor))
        chunk = r.tokens[cursor:] if max_tokens is None else \
            r.tokens[cursor:cursor + max(1, int(max_tokens))]
        new = cursor + len(chunk)
        return {"trace": trace, "rid": r.rid, "cursor": new,
                "tokens": [int(t) for t in chunk],
                "more": (not r.done) or new < len(r.tokens),
                "state": r.state, "verdict": r.verdict,
                "error": r.error, "done": r.done}

    def cancel(self, trace):
        r = self._find(trace)
        if r is None:
            return None
        if not r.done:
            r.state = "cancelled"
            r.verdict = "cancelled"
        return {"trace": trace, "rid": r.rid, "state": r.state,
                "verdict": r.verdict, "done": r.done}


def test_poll_chunks_reassemble_and_repoll_is_idempotent():
    """Cursor laws 1+2 (SERVING.md §10) over the real wire: bounded
    chunks concatenate to the full token list, and re-polling the SAME
    cursor returns the SAME tokens — the recovery move for a dropped
    reply costs nothing and tears nothing."""
    w = _WorkerLoop(_StreamStub("a"))
    try:
        proxy = RpcReplicaProxy("a", addr=w.addr, timeout_s=1.0)
        m = proxy.submit(np.ones(2, np.int32), 6, trace="tr-s1")
        deadline = time.time() + 10.0
        while time.time() < deadline:
            reply = proxy.poll("tr-s1", cursor=0)
            if reply is not None and not reply["more"]:
                break
            time.sleep(0.01)
        # bounded-chunk walk: max_tokens=2 forces 3 chunks
        assembled, cursor = [], 0
        for _ in range(16):
            reply = proxy.poll("tr-s1", cursor=cursor, max_tokens=2)
            assert reply is not None and reply["known"]
            assert len(reply["tokens"]) <= 2
            assert reply["cursor"] == cursor + len(reply["tokens"])
            assembled += reply["tokens"]
            cursor = reply["cursor"]
            if not reply["more"]:
                break
        assert assembled == [0, 1, 2, 3, 4, 5]   # rid 0: 0*10 + pos
        assert reply["verdict"] == "completed" and reply["done"]
        # idempotence: the same cursor yields the same slice, twice
        a = proxy.poll("tr-s1", cursor=2, max_tokens=2)
        b = proxy.poll("tr-s1", cursor=2, max_tokens=2)
        assert a["tokens"] == b["tokens"] == [2, 3]
        assert m.key == "tr-s1"   # the wire key IS the trace
    finally:
        w.close()


def test_stream_drop_blackholes_reply_and_repoll_recovers():
    """The ``serve.stream.drop`` drill (delivery plane only): the poll
    reply is parked, the client's per-call deadline is the only way
    out, and the idempotent re-poll at the SAME cursor recovers
    exactly the tokens the dropped reply carried."""
    telemetry.reset()
    w = _WorkerLoop(_StreamStub("a"))
    try:
        proxy = RpcReplicaProxy("a", addr=w.addr, timeout_s=1.0)
        proxy.submit(np.ones(2, np.int32), 4, trace="tr-d1")
        deadline = time.time() + 10.0
        while time.time() < deadline:
            reply = proxy.poll("tr-d1", cursor=0)
            if reply is not None and not reply["more"]:
                break
            time.sleep(0.01)
        fault.configure("serve.stream.drop:1")
        t0 = time.monotonic()
        dropped = proxy.poll("tr-d1", cursor=1, timeout_s=0.3)
        waited = time.monotonic() - t0
        assert dropped is None           # blackholed, deadline paid
        assert waited < 2.0              # bounded by the call deadline
        assert telemetry.counter(
            "serving.stream.dropped_replies").value == 1
        recovered = proxy.poll("tr-d1", cursor=1)
        assert recovered is not None and recovered["known"]
        assert recovered["tokens"] == [1, 2, 3]   # no gap, no dup
        # the drill cut ONLY delivery: the data plane kept answering
        assert proxy.health().get("alive")
    finally:
        w.close()
        telemetry.reset()


def test_cancel_rpc_lands_typed_verdict_and_is_idempotent():
    """Cancel over the wire: the typed terminal ``cancelled`` verdict
    lands, a repeat cancel is a no-op answering the same terminal
    state, and a subsequent poll reports ``more=False`` with the
    verdict attached."""
    w = _WorkerLoop(_StreamStub("a", step_sleep=0.05))
    try:
        proxy = RpcReplicaProxy("a", addr=w.addr, timeout_s=1.0)
        proxy.submit(np.ones(2, np.int32), 1000, trace="tr-c1")
        reply = proxy.cancel("tr-c1")
        assert reply is not None and reply["known"]
        assert reply["verdict"] == "cancelled" and reply["done"]
        again = proxy.cancel("tr-c1")
        assert again["verdict"] == "cancelled" and again["done"]
        polled = proxy.poll("tr-c1", cursor=0)
        assert polled["more"] is False
        assert polled["verdict"] == "cancelled"
    finally:
        w.close()


def test_poll_unknown_trace_answers_known_false():
    """A trace the worker never saw (or aged out past the stream TTL)
    answers ``known=False`` — typed, never a hang or a crash."""
    w = _WorkerLoop(_StreamStub("a"))
    try:
        proxy = RpcReplicaProxy("a", addr=w.addr, timeout_s=1.0)
        reply = proxy.poll("tr-never", cursor=3)
        assert reply is not None
        assert reply["known"] is False and reply["more"] is False
        assert reply["state"] == "unknown"
        unknown_cancel = proxy.cancel("tr-never")
        assert unknown_cancel["known"] is False
    finally:
        w.close()


def test_poll_incarnation_mismatch_declares_reset():
    """Cursor law 4: a poll carrying a cursor minted against a
    DIFFERENT incarnation is answered with ``reset=True`` — the
    discontinuity is declared, never silent (the router maps the
    cursor onto the survivor's bit-identical re-decode)."""
    w = _WorkerLoop(_StreamStub("a"))
    try:
        mine = w.server.incarnation
        ok = rpc_call(w.addr, {
            "method": "poll", "trace": "tr-x", "cursor": 0,
            "incarnation": {"pid": mine["pid"],
                            "attempt": mine["attempt"],
                            "nonce": mine["nonce"]}}, 1.0)
        assert ok["ok"] and ok["reset"] is False
        stale = rpc_call(w.addr, {
            "method": "poll", "trace": "tr-x", "cursor": 0,
            "incarnation": {"pid": 1, "attempt": 99,
                            "nonce": "dead"}}, 1.0)
        assert stale["ok"] and stale["reset"] is True
    finally:
        w.close()


def test_replay_journal_cancelled_and_abandoned_are_terminal(tmp_path):
    """ISSUE 19 satellite: ``cancelled`` / ``abandoned`` journal lines
    replay TERMINAL — a restarted router never re-executes a request
    the client tore down or abandoned — while the torn-tail
    skip-and-count behavior is unchanged."""
    journal = str(tmp_path / "router-journal-slot0.jsonl")
    lines = [
        {"t": 1.0, "event": "accept", "rid": 0, "trace": "tr-0",
         "replica": "slot0", "state": "accepted", "verdict": None,
         "retries": 0},
        {"t": 1.1, "event": "fail", "rid": 0, "trace": "tr-0",
         "replica": "slot0", "state": "failed", "verdict": "cancelled",
         "retries": 0},
        {"t": 1.2, "event": "accept", "rid": 1, "trace": "tr-1",
         "replica": "slot0", "state": "accepted", "verdict": None,
         "retries": 0},
        {"t": 1.3, "event": "fail", "rid": 1, "trace": "tr-1",
         "replica": "slot0", "state": "failed", "verdict": "abandoned",
         "retries": 0},
        {"t": 1.4, "event": "accept", "rid": 2, "trace": "tr-2",
         "replica": "slot0", "state": "accepted", "verdict": None,
         "retries": 0},
    ]
    with open(journal, "w") as f:
        for doc in lines:
            f.write(json.dumps(doc) + "\n")
        f.write('{"t": 1.5, "event": "complete", "rid": 2, "tr')
    rt = Router([], journal_path=journal)
    rep = rt.replay_journal()
    assert rep["torn"] == 1
    assert rep["requests"] == 3
    r0, r1, r2 = rt.request(0), rt.request(1), rt.request(2)
    assert r0.done and r0.verdict == "cancelled"
    assert r1.done and r1.verdict == "abandoned"
    assert r2.state == "accepted"      # the torn complete never applied
    # polling a replayed terminal stream answers the verdict, not a
    # re-execution: no live mirror exists, more=False, no tokens
    doc = rt.poll(0, cursor=0)
    assert doc["done"] and doc["verdict"] == "cancelled"
    assert doc["more"] is False and doc["tokens"] == []
    assert rt._next_rid == 3
