"""Serving survivability plane (ISSUE 11): deadlines, SLO shedding,
replica drain/failover, live weight hot-swap under fault injection.

In-process: scheduler deadline/verdict laws, SLO hysteresis, allocator
conservation, router journal semantics over stub replicas, launcher
drain classification + membership journal.  Subprocess (clean-process
pallas pattern, tests/serving_surv_driver.py): engine/replica/router
drills with the real decode programs — fast sections in tier-1, the
combined e2e drill marked slow.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import PagedKVAllocator, SLOController
from mxnet_tpu.serving.kv_cache import SCRATCH_PAGE
from mxnet_tpu.serving.replica import ReplicaLost, EXIT_SERVE_DRAIN
from mxnet_tpu.serving.router import Router, VERDICT_RETRIES_EXHAUSTED
from mxnet_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                         FINISHED, SHED)

pytestmark = pytest.mark.serving

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- scheduler: deadlines + typed verdicts (pure host-side) -----------------

def _sched(num_pages=8, page_size=4, slots=2, max_seq=12):
    a = PagedKVAllocator(num_pages, page_size)
    return a, ContinuousBatchingScheduler(slots, a, 3, max_seq_len=max_seq)


def test_infeasible_reject_is_deterministic_and_reserves_nothing():
    a, s = _sched()
    for _ in range(16):   # mass rejection: no requeue loop, no leak
        with pytest.raises(ValueError, match="at most"):
            s.submit(np.ones(4, np.int32), 20)
    assert s.queued == 0
    a.assert_conservation()
    assert a.free_pages == 7
    # pool-bound rejection (fits max_seq_len but never the pool)
    a2, s2 = _sched(num_pages=3, max_seq=12)
    with pytest.raises(ValueError, match="usable"):
        s2.submit(np.ones(4, np.int32), 8)
    a2.assert_conservation()


def test_admit_rematches_prefix_after_eviction_drops_matched_nodes():
    """ISSUE 15 regression (review finding): admission pressure can
    evict the very prefix nodes the queue head just matched — the
    match must be RE-RUN after eviction, or the scheduler would retain
    a freed (possibly re-allocated) page as 'shared' while also
    handing it out as an owned write target."""
    from mxnet_tpu.serving import PrefixCache
    a = PagedKVAllocator(6, 4)            # 5 usable pages
    cache = PrefixCache(a)
    s = ContinuousBatchingScheduler(2, a, 5, max_seq_len=20,
                                    prefix_cache=cache)
    prompt = np.arange(8, dtype=np.int32)   # 2 full pages
    donor = a.allocate(2)
    cache.insert(prompt, donor)
    a.release(donor)                      # cache is now the only owner
    assert a.used_pages == 2 and a.free_pages == 3
    # head: same prompt, worst case 17 tokens = 5 pages.  The initial
    # match is 1 shared + a COW donor (capped at prompt-1), need 4 > 3
    # free -> evict_for drops the LRU leaf — the COW donor itself.
    req = s.submit(prompt, 9)
    placed = s.admit()
    assert placed == [req]
    # the stale match was discarded: after the eviction round the
    # re-match keeps only the surviving full page, no COW
    assert req.prefix_len == 4 and req.shared_count == 1
    assert req.cow_src is None
    row = s.block_tables[req.slot]
    live = [p for p in row if p != 0]
    assert len(live) == len(set(live)), \
        "a physical page appears twice in the block table"
    a.assert_conservation()
    cache.assert_consistent()
    s.finish(req)
    a.assert_conservation()


def test_queue_deadline_expiry_typed_verdict():
    a, s = _sched()
    q = s.submit(np.ones(3, np.int32), 2, deadline_s=1e-9)
    ok = s.submit(np.ones(3, np.int32), 2, deadline_s=60.0)
    time.sleep(0.002)
    expired = s.expire_queued()
    assert [e.rid for e in expired] == [q.rid]
    assert q.state == "expired" and q.verdict == "expired_queue"
    assert q.done and "deadline" in q.error
    assert s.queued == 1 and not ok.done
    a.assert_conservation()


def test_running_deadline_and_finish_verdicts():
    a, s = _sched()
    r = s.submit(np.ones(3, np.int32), 2, deadline_s=60.0)
    s.admit()
    assert r.state == "running" and not s.expired_running()
    r.deadline_t = time.perf_counter() - 1.0
    assert s.expired_running() == [r]
    s.finish(r, "expired", verdict="expired_decode", error="late")
    assert r.verdict == "expired_decode" and r.pages is None
    a.assert_conservation()
    assert a.used_pages == 0
    # plain completion stamps the completed verdict
    r2 = s.submit(np.ones(3, np.int32), 2)
    s.admit()
    s.finish(r2)
    assert r2.verdict == "completed" and r2.done


def test_shed_handle_is_terminal():
    _, s = _sched()
    r = s.shed(np.ones(3, np.int32), 2, error="over SLO")
    assert r.state == SHED and r.verdict == "shed" and r.done
    assert s.queued == 0 and r.pages is None


def test_allocator_conservation_catches_corruption():
    a = PagedKVAllocator(6, 2)
    a.assert_conservation()
    pages = a.allocate(2)
    a.assert_conservation()
    a._free.append(pages[0])        # simulate a double-accounted page
    with pytest.raises(MXNetError, match="both free and allocated"):
        a.assert_conservation()
    a._free.pop()
    a._refs.pop(pages[1])           # simulate a leaked page
    with pytest.raises(MXNetError, match="conservation"):
        a.assert_conservation()
    a._refs[pages[1]] = 0           # refcount corruption
    with pytest.raises(MXNetError, match="refcount"):
        a.assert_conservation()


# -- SLO controller hysteresis (pure host-side) -----------------------------

def test_slo_engage_release_hysteresis():
    c = SLOController(0.1, release_frac=0.5, window_s=10.0,
                      min_samples=3)
    t0 = 1000.0
    assert not c.should_shed(now=t0)
    for _ in range(5):
        c.observe(0.5, now=t0)
    assert c.should_shed(now=t0) and c.shedding
    # a good sample while the burst is still in-window: no flap
    c.observe(0.04, now=t0 + 1)
    assert c.should_shed(now=t0 + 1)
    # window rolls past the burst (only the 0.04 remains, below the
    # 0.05 release threshold) -> released
    assert not c.should_shed(now=t0 + 11)
    assert c.sheds == 1


def test_slo_head_wait_engages_without_samples():
    c = SLOController(0.1)
    assert c.should_shed(oldest_wait_s=0.5, now=10.0)
    assert not c.should_shed(oldest_wait_s=0.01, now=11.0)


def test_slo_from_env(monkeypatch):
    monkeypatch.delenv("MXTPU_SERVE_SLO_P99_S", raising=False)
    assert SLOController.from_env() is None
    monkeypatch.setenv("MXTPU_SERVE_SLO_P99_S", "0.25")
    monkeypatch.setenv("MXTPU_SERVE_SLO_RELEASE", "0.4")
    c = SLOController.from_env()
    assert c.target_p99_s == 0.25 and c.release_frac == 0.4


# -- router journal semantics over stub replicas ----------------------------

class _StubReq:
    def __init__(self, shed=False):
        self.state = SHED if shed else "queued"
        self.tokens = []
        self.verdict = "shed" if shed else None
        self.error = None


class _StubReplica:
    def __init__(self, rid, shed=False, tokens=3):
        self.replica_id = rid
        self.alive = True
        self.draining = False
        self.shed_mode = shed
        self.n_tokens = tokens
        self.reqs = []
        self.die_next = False
        self.last_deadline = None
        self.last_trace = None

    @property
    def load(self):
        return sum(1 for r in self.reqs if r.state != FINISHED)

    @property
    def idle(self):
        return all(r.state == FINISHED for r in self.reqs)

    def submit(self, prompt, max_new, deadline_s=None, trace=None):
        self.last_deadline = deadline_s
        self.last_trace = trace
        r = _StubReq(shed=self.shed_mode)
        if not self.shed_mode:
            self.reqs.append(r)
        return r

    def drain(self):
        for r in self.reqs:
            while len(r.tokens) < self.n_tokens:
                r.tokens.append(7)
            r.state = FINISHED
        self.alive = False
        return EXIT_SERVE_DRAIN

    def step(self):
        if self.die_next:
            self.alive = False
            raise ReplicaLost("stub died")
        n = 0
        for r in self.reqs:
            if r.state != FINISHED:
                r.tokens.append(7)
                if len(r.tokens) >= self.n_tokens:
                    r.state = FINISHED
                n += 1
        return n


def test_router_at_most_once_and_failover(tmp_path):
    journal = str(tmp_path / "journal.jsonl")
    a, b = _StubReplica("a"), _StubReplica("b")
    spawned = []

    def spawn():
        r = _StubReplica("c")
        spawned.append(r)
        return r

    rt = Router([a, b], spawn=spawn, max_retries=1,
                journal_path=journal)
    r1 = rt.submit(np.ones(2), 3)
    rt.run_until_idle()
    assert r1.state == "completed" and r1.tokens == [7, 7, 7]
    r2 = rt.submit(np.ones(2), 3)
    home = a if r2.replica_id == "a" else b
    home.die_next = True
    rt.step()
    assert rt.failovers == 1 and spawned
    assert r2.state == "accepted" and r2.replica_id != home.replica_id
    assert r2.retries == 1
    # at-most-once: the completed request was not re-executed
    assert r1.retries == 0 and r1.tokens == [7, 7, 7]
    rt.run_until_idle()
    assert r2.state == "completed"
    lines = [json.loads(ln) for ln in open(journal)]
    completes = [ln["rid"] for ln in lines if ln["event"] == "complete"]
    assert sorted(completes) == [r1.rid, r2.rid]   # exactly once each


def test_router_failover_matches_replica_identity_not_id(tmp_path):
    """Caller-supplied replica ids may collide (the default is 0):
    victims must be matched by replica OBJECT, or a failover would
    double-execute healthy requests on the surviving same-id replica."""
    a, b = _StubReplica("dup", tokens=5), _StubReplica("dup", tokens=5)
    rt = Router([a, b], max_retries=2)
    r1 = rt.submit(np.ones(2), 5)
    r2 = rt.submit(np.ones(2), 5)
    victim = r1._home
    healthy = b if victim is a else a
    healthy_rr = r1 if r1._home is healthy else r2
    victim.die_next = True
    rt.step()
    assert rt.failovers == 1
    # only the dead replica's request was retried
    dead_rr = r1 if healthy_rr is r2 else r2
    assert dead_rr.retries == 1 and healthy_rr.retries == 0
    assert healthy_rr._home is healthy
    rt.run_until_idle()
    assert r1.state == r2.state == "completed"
    # exactly 5 tokens each: the healthy one was never re-decoded
    assert healthy_rr.tokens == [7] * 5


def test_router_prunes_dead_replicas():
    a, b = _StubReplica("a"), _StubReplica("b")
    rt = Router([a, b], max_retries=1)
    rt.submit(np.ones(2), 3)
    rt.submit(np.ones(2), 3)
    a.die_next = True
    rt.step()
    assert a not in rt._replicas and b in rt._replicas
    rt.run_until_idle()
    assert all(rr.state == "completed" for rr in rt.requests)
    assert not rt._inflight


def test_router_retry_budget_exhausts_with_typed_verdict():
    a = _StubReplica("a")
    rt = Router([a], max_retries=0)
    r = rt.submit(np.ones(2), 3)
    a.die_next = True
    rt.step()
    assert r.state == "failed" and r.verdict == VERDICT_RETRIES_EXHAUSTED
    assert "retry budget" in r.error


def test_router_drain_harvests_completions():
    """Fleet drain must harvest: the drains finish every accepted
    request on dead replicas — no later step() will, so drain() itself
    moves the completions into the journal (handles go terminal)."""
    a = _StubReplica("a", tokens=2)
    rt = Router([a])
    rr = rt.submit(np.ones(2), 2)
    out = rt.drain()
    assert out == [("a", EXIT_SERVE_DRAIN)]
    assert rr.state == "completed" and rr.tokens == [7, 7] and rr.done


def test_router_failover_carries_remaining_deadline():
    """A failover re-placement passes the REMAINING budget relative to
    the original submission — retries must not multiply the caller's
    end-to-end deadline."""
    a, b = _StubReplica("a"), _StubReplica("b")
    rt = Router([a, b], max_retries=1)
    rr = rt.submit(np.ones(2), 3, deadline_s=5.0)
    home = a if rr._home is a else b
    assert abs(home.last_deadline - 5.0) < 0.5
    time.sleep(0.05)
    home.die_next = True
    other = b if home is a else a
    rt.step()
    assert rr._home is other
    assert other.last_deadline < 5.0 - 0.04, other.last_deadline


def test_router_journal_retention_bounds_memory():
    """Terminal entries are evicted past the retention cap (amortized
    at 2x); in-flight entries are never evicted."""
    a = _StubReplica("a", tokens=1)
    rt = Router([a], journal_retention=10)
    for _ in range(25):
        rt.submit(np.ones(2), 1)
        rt.run_until_idle()
    assert len(rt._journal) <= 20    # bounded at < 2x cap
    assert not rt._inflight
    # the newest entries survive (rids are monotonic)
    assert max(rt._journal) == 24


def test_router_typed_refusals_spread_then_propagate():
    rt = Router([_StubReplica("x", shed=True),
                 _StubReplica("y", shed=True)])
    r = rt.submit(np.ones(2), 2)
    assert r.state == "refused" and r.verdict == "shed"
    empty = Router([])
    r2 = empty.submit(np.ones(2), 2)
    assert r2.state == "refused" and r2.verdict == "no_live_replicas"


# -- launcher: drain classification + membership journal --------------------

def test_classify_exit_drain_is_clean():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import launch
    finally:
        sys.path.pop(0)
    kind, reason = launch.classify_exit(EXIT_SERVE_DRAIN)
    assert kind == "clean" and "drain" in reason
    assert launch.SERVE_DRAIN_EXIT == EXIT_SERVE_DRAIN == 80
    # the neighboring contracts are untouched
    assert launch.classify_exit(75)[0] == "retryable"
    assert launch.classify_exit(77)[0] == "retryable"
    assert launch.classify_exit(2)[0] == "permanent"


def test_launch_drain_journals_replace_and_never_blames(tmp_path):
    """A worker exiting 80 (graceful drain) restarts WITHOUT a failure
    note: membership.json records drain + replace events (distinct from
    training failures/evictions), and the job ends 0."""
    run_dir = str(tmp_path / "run")
    code = ("import os,sys;"
            "sys.exit(80 if os.environ.get('MXTPU_RESTART_ATTEMPT')"
            "=='0' else 0)")
    r = subprocess.run(
        ["timeout", "-k", "5", "120", sys.executable,
         os.path.join(REPO, "tools", "launch.py"), "-n", "1",
         "--max-restarts", "2", "--restart-backoff", "0",
         "--run-dir", run_dir, "--aot-cache-dir", "off",
         sys.executable, "-c", code],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "drained gracefully" in r.stderr
    doc = json.load(open(os.path.join(run_dir, "membership.json")))
    events = [t["event"] for t in doc["transitions"]]
    assert "drain" in events and "replace" in events
    assert "failure" not in events and "evict" not in events
    drain = next(t for t in doc["transitions"] if t["event"] == "drain")
    assert drain["slot"] == 0 and drain["rc"] == 80
    assert events[-1] == "complete"


def test_launch_drain_at_budget_end_is_success(tmp_path):
    """Drain on the LAST attempt: no budget for a replacement, but the
    drain itself is a success — exit 0, journaled complete."""
    run_dir = str(tmp_path / "run")
    r = subprocess.run(
        ["timeout", "-k", "5", "60", sys.executable,
         os.path.join(REPO, "tools", "launch.py"), "-n", "1",
         "--max-restarts", "0", "--run-dir", run_dir,
         "--aot-cache-dir", "off",
         sys.executable, "-c", "import sys; sys.exit(80)"],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    doc = json.load(open(os.path.join(run_dir, "membership.json")))
    events = [t["event"] for t in doc["transitions"]]
    assert "drain" in events and "failure" not in events


# -- subprocess drills (clean process, real decode programs) ----------------

def _driver_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    return env


def _run_driver(section, env=None, timeout=420, check=True):
    r = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tests", "serving_surv_driver.py"), section],
        env=env or _driver_env(), capture_output=True, timeout=timeout)
    out = r.stdout.decode() + r.stderr.decode()
    if check:
        assert r.returncode == 0, out[-3000:]
    return r.returncode, out


def test_surv_fast_sections():
    """Deadline verdicts (expired-in-queue vs expired-mid-decode), shed
    engage/release hysteresis at engine level, prefill-error typed
    verdict + page release, graceful drain (exit 80, zero dropped
    accepted), router failover with at-most-once journal + AOT-warm
    replacement, live hot-swap (invisible to residents, takes effect,
    torn swap rolls back), the per-request sampling determinism law
    (same seed/params -> identical tokens across batch compositions, a
    join/leave, and a router failover re-decode), the ISSUE-16
    speculative-decoding determinism laws under the same churn (greedy
    spec-on == dense chain in any batch composition; sampled spec
    streams reproduce across churn, an identical-weights hot-swap, and
    a failover re-decode; spec page marks never survive a step or a
    drain), and the serve.prefix.evict drill (victim falls back to a
    full prefill with correct tokens) — one clean process."""
    _, out = _run_driver("fast")
    for marker in ("SERVING_LIFECYCLE_OK", "SERVING_ROUTER_OK",
                   "SERVING_SWAP_OK", "SERVING_SAMPLING_OK",
                   "SERVING_SPEC_OK", "SERVING_PREFIX_EVICT_OK"):
        assert marker in out, out[-3000:]


def test_surv_decode_stall_watchdog(tmp_path):
    """serve.decode.stall wedges the decode loop: the serve_step lease
    expires, the replica dies 75 (retryable to the launcher), and the
    postmortem carries the serving snapshot."""
    pm = str(tmp_path / "pm")
    os.makedirs(pm)
    env = _driver_env()
    env.update({
        "MXTPU_FAULT_STALL_SECS": "60",
        "MXTPU_STALL_TIMEOUT": "2",
        "MXTPU_STARTUP_GRACE": "120",
        "MXTPU_POSTMORTEM_DIR": pm,
    })
    rc, out = _run_driver("stall", env=env, timeout=300, check=False)
    assert rc == 75, (rc, out[-3000:])
    assert "SERVING_STALL_NOT_DETECTED" not in out
    pms = [f for f in os.listdir(pm) if f.startswith("postmortem-")]
    assert pms, os.listdir(pm)
    doc = json.load(open(os.path.join(pm, pms[0])))
    assert "serve_step" in doc["reason"]
    assert doc["fault_fires"].get("serve.decode.stall") == 1
    snap = doc["serving"][0]
    assert snap["occupancy"] == 1 and snap["resident_rids"] == [0]
    assert snap["used_pages"] > 0 and "queued" in snap


@pytest.mark.slow
def test_surv_e2e_drill():
    """The combined drill: replica killed mid-load under a decode-stall
    hiccup with every accepted request completing exactly once
    (bit-identical greedy tokens), overload sheds instead of queuing
    unboundedly (serving.shed > 0, queue-wait p99 bounded), the
    replacement spins up AOT-warm with 0 foreground compiles, and a
    mid-run checkpoint hot-swap lands between decode steps with
    rollback verified on an injected torn swap."""
    _, out = _run_driver("e2e", timeout=480)
    for marker in ("SERVING_E2E_FAILOVER_OK", "SERVING_E2E_SHED_OK",
                   "SERVING_E2E_SWAP_OK"):
        assert marker in out, out[-3000:]
