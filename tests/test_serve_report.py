"""Request-scope serving observability (ISSUE 13): the telemetry
request-trace plane, the Router journal's single-write audit
discipline, and serve_report's fleet reconstruction — in-process on
synthetic artifacts (no jax).  The lifecycle laws against REAL engines
run in the clean-subprocess driver (serving_surv_driver.py ``trace``
section, test at the bottom)."""
import collections
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from mxnet_tpu import telemetry

pytestmark = pytest.mark.servescope

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools", "perf_probe"))
import serve_report  # noqa: E402
import telemetry_report  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_registry():
    telemetry.reset()
    yield
    telemetry.reset()


# -- the telemetry request-event plane --------------------------------------

def test_mint_trace_unique_and_stable_prefix():
    ids = {telemetry.mint_trace() for _ in range(100)}
    assert len(ids) == 100
    assert len({i.rsplit("-", 1)[0] for i in ids}) == 1  # one process


def test_request_events_order_and_reset():
    tr = telemetry.mint_trace()
    telemetry.note_request_event(tr, "submit", args={"prompt_len": 3})
    telemetry.note_request_event(tr, "admit", args={"slot": 0})
    telemetry.note_request_event("", "tokens", args={"traces": [tr]})
    telemetry.note_request_event(tr, "verdict",
                                 args={"verdict": "completed",
                                       "final": True})
    evs = telemetry.request_events()
    assert [e["event"] for e in evs] == ["submit", "admit", "tokens",
                                         "verdict"]
    assert [e["seq"] for e in evs] == [0, 1, 2, 3]
    assert all(e["t"] > 0 for e in evs)
    telemetry.reset()
    assert telemetry.request_events() == []


def test_consume_cursor_ships_each_event_exactly_once():
    tr = telemetry.mint_trace()
    telemetry.note_request_event(tr, "submit")
    first, dropped = telemetry.consume_request_events()
    assert [e["event"] for e in first] == ["submit"] and dropped == 0
    telemetry.note_request_event(tr, "verdict",
                                 args={"final": True,
                                       "verdict": "shed"})
    second, dropped = telemetry.consume_request_events()
    assert [e["event"] for e in second] == ["verdict"] and dropped == 0
    assert telemetry.consume_request_events() == ([], 0)
    # the full ring stays readable (postmortem view) after consuming
    assert len(telemetry.request_events()) == 2


def test_ring_eviction_of_unemitted_events_is_counted():
    small = collections.deque(maxlen=4)
    old = telemetry._req_ring
    telemetry._req_ring = small
    try:
        for i in range(10):
            telemetry.note_request_event("t", "token")
        evs, dropped = telemetry.consume_request_events()
        # 4 survive in the ring, 6 were evicted before any line
        assert len(evs) == 4 and dropped == 6
        assert telemetry.counter("serving.trace_dropped").value == 6
        # emitted events evicted later are NOT re-counted
        for i in range(4):
            telemetry.note_request_event("t", "token")
        _, dropped = telemetry.consume_request_events()
        assert dropped == 0
    finally:
        telemetry._req_ring = old


def test_emitter_lines_carry_incremental_req_events(tmp_path):
    path = str(tmp_path / "stream.jsonl")
    tr = telemetry.mint_trace()
    telemetry.note_request_event(tr, "submit")
    telemetry.start_emitter(path, interval=30)   # only the final line
    telemetry.note_request_event(tr, "verdict",
                                 args={"final": True,
                                       "verdict": "completed"})
    telemetry.stop_emitter()
    lines = [json.loads(ln) for ln in open(path)]
    assert lines and lines[-1].get("final")
    shipped = [e for ln in lines for e in ln.get("req_events", [])]
    assert [e["event"] for e in shipped] == ["submit", "verdict"]
    # exactly once: seqs unique across the whole stream
    assert len({e["seq"] for e in shipped}) == len(shipped)


def test_emit_failure_rolls_back_the_consume_cursor(tmp_path):
    """A failed line write must not swallow its events: the consume
    cursor rolls back so the NEXT successful line (or a reader) still
    carries them — never a silent exactly-once violation."""
    tr = telemetry.mint_trace()
    telemetry.note_request_event(tr, "submit")
    bad = tmp_path / "is-a-dir.jsonl"
    bad.mkdir()
    telemetry._emit_line(str(bad))          # os.open fails -> rollback
    evs, dropped = telemetry.consume_request_events()
    assert [e["event"] for e in evs] == ["submit"] and dropped == 0


def test_load_serve_prefers_at_death_postmortem_counters(tmp_path):
    """A crashed replica's postmortem counters are newer than its last
    periodic stream line (monotonic: max-merge wins) — a stale stream
    line must not fabricate a traced-vs-counter mismatch."""
    tree = _synthetic_tree(tmp_path, torn_journal=False)
    pm = {"schema": "mxtpu-postmortem-2", "pid": 77,
          "identity": {"pid": 77}, "reason": "crash",
          "counters": {"serving.tokens": 9, "serving.stalls": 1},
          "request_trace": []}
    with open(os.path.join(tree, "telemetry", "postmortem-77.json"),
              "w") as f:
        json.dump(pm, f)
    data = serve_report.load_serve(tree)
    (pkey,) = data["counters"]       # (slot, attempt, pid) per process
    assert pkey[-1] == 77
    assert data["counters"][pkey]["serving.tokens"] == 9  # at-death
    assert data["counters"][pkey]["serving.goodput"] == 5  # stream kept
    assert data["counters"][pkey]["serving.stalls"] == 1   # pm-only


def test_load_serve_distinguishes_processes_beyond_pid(tmp_path):
    """Containerized replicas can share a pid (and restarts recycle
    them): the event dedup keys on the full (slot, attempt, pid)
    identity, so two same-pid processes with overlapping seqs never
    swallow each other's lifecycle records."""
    tdir = tmp_path / "telemetry"
    tdir.mkdir(parents=True)
    for slot in (0, 1):
        line = {
            "schema": "mxtpu-telemetry-2", "time_unix": 101.0,
            "pid": 7,
            "identity": {"pid": 7, "slot": slot, "attempt": 0},
            "req_events": [
                _ev(0, 100.0 + slot, "S%d" % slot, "submit",
                    prompt_len=1, max_new=1),
                _ev(1, 100.1 + slot, "S%d" % slot, "verdict",
                    verdict="shed", final=True, tokens=0),
            ],
        }
        with open(tdir / ("stream-slot%d.jsonl" % slot), "w") as f:
            f.write(json.dumps(line) + "\n")
    rep = serve_report.analyze(str(tmp_path))
    assert set(rep["requests"]) == {"S0", "S1"}
    assert rep["lifecycle"]["ok"], rep["lifecycle"]


def test_postmortem_carries_request_trace(tmp_path):
    tr = telemetry.mint_trace()
    telemetry.note_request_event(tr, "submit")
    telemetry.note_request_event(tr, "verdict",
                                 args={"final": True, "verdict": "shed"})
    path = str(tmp_path / "pm.json")
    telemetry.dump_postmortem("test", path=path)
    doc = json.load(open(path))
    assert [e["event"] for e in doc["request_trace"]] == ["submit",
                                                          "verdict"]


def test_flight_records_carry_where():
    import time
    t0 = time.perf_counter_ns()
    telemetry.note_train_step(t0, t0 + 1000, t0 + 2000,
                              where="serve_step")
    recs = telemetry.flight_records()
    assert recs[-1]["where"] == "serve_step"


# -- synthetic fleet artifacts ---------------------------------------------

def _ev(seq, t, trace, event, **args):
    return {"seq": seq, "t": t, "trace": trace, "event": event,
            "args": args}


def _synthetic_tree(tmp_path, torn_journal=True):
    """A two-replica fleet with: T1 completed on a (with a swap pause),
    T2 failed over a -> b (retry spans), T3 expired in queue
    (queue-dominated blame).  Counters reconcile with the traced
    tokens.  The journal carries a torn line when asked."""
    tdir = tmp_path / "telemetry"
    tdir.mkdir(parents=True)
    evs = [
        _ev(0, 100.0, "T1", "submit", prompt_len=4, max_new=3,
            router=True, rid=1,
            sampling={"temperature": 0.8, "top_k": 20, "top_p": 0.0,
                      "seed": 7}),
        _ev(1, 100.0, "T1", "place", replica="a"),
        _ev(2, 100.1, "T1", "admit", replica="a", slot=0,
            queue_wait_s=0.1, pages=1, prefix_hit=True, prefix_len=3,
            shared_pages=1),
        _ev(3, 100.1, "T1", "prefill", dispatch_s=0.02, sync_s=0.01),
        _ev(4, 100.13, "T1", "token"),
        _ev(5, 100.2, "", "swap", replica="a", ok=True, epoch=7,
            dur_s=0.05, traces=["T1"]),
        _ev(6, 100.3, "", "tokens", replica="a", step=1,
            traces=["T1"]),
        _ev(7, 100.4, "", "tokens", replica="a", step=2,
            traces=["T1", "T2"]),
        _ev(8, 100.41, "T1", "verdict", verdict="completed",
            final=False, replica="a", tokens=3, ttft_s=0.13,
            queue_wait_s=0.1, tpot_s=0.135),
        _ev(9, 100.41, "T1", "verdict", verdict="completed",
            final=True, router=True, rid=1, tokens=3, ttft_s=0.13,
            queue_wait_s=0.1),
        # T2: admitted on a, one token, a dies, re-decodes on b
        _ev(10, 100.05, "T2", "submit", prompt_len=4, max_new=2,
            router=True, rid=2),
        _ev(11, 100.05, "T2", "place", replica="a"),
        _ev(12, 100.35, "T2", "admit", replica="a", slot=1,
            queue_wait_s=0.3, pages=1, prefix_hit=False, prefix_len=0,
            shared_pages=0),
        _ev(13, 100.35, "T2", "prefill", dispatch_s=0.01, sync_s=0.0),
        # (T2's first token rides the step-7 batch above)
        _ev(14, 100.5, "T2", "retry", **{"from": "a", "retries": 1,
                                         "rid": 2,
                                         "reason": "fence_expiry"}),
        _ev(15, 100.6, "T2", "place", replica="b"),
        _ev(16, 100.6, "T2", "admit", replica="b", slot=0,
            queue_wait_s=0.0, pages=1, prefix_hit=True, prefix_len=4,
            shared_pages=1),
        _ev(17, 100.6, "T2", "prefill", dispatch_s=0.01, sync_s=0.0),
        _ev(18, 100.7, "T2", "token"),
        _ev(19, 100.8, "", "tokens", replica="b", step=1,
            traces=["T2"]),
        _ev(20, 100.81, "T2", "verdict", verdict="completed",
            final=False, replica="b", tokens=2, ttft_s=0.3),
        _ev(21, 100.81, "T2", "verdict", verdict="completed",
            final=True, router=True, rid=2, tokens=2, ttft_s=0.3,
            queue_wait_s=0.3),
        # T3: never admitted — expires in queue (queue-dominated)
        _ev(22, 100.0, "T3", "submit", prompt_len=3, max_new=2,
            router=True, rid=3, deadline_s=0.5),
        _ev(23, 100.0, "T3", "place", replica="a"),
        _ev(24, 100.55, "T3", "verdict", verdict="expired_queue",
            final=False, replica="a", tokens=0),
        _ev(25, 100.56, "T3", "verdict", verdict="expired_queue",
            final=True, router=True, rid=3, tokens=0),
        # trace-less liveness news about replica a (ISSUE 17): one
        # wobble that clears, then the real death (fence expiry) and
        # a fenced late completion rejected by the router
        _ev(26, 100.45, "", "suspect", replica="a", gap_s=0.12),
        _ev(27, 100.48, "", "suspect_clear", replica="a", gap_s=0.05),
        _ev(28, 100.49, "", "suspect", replica="a", gap_s=0.31),
        _ev(29, 100.5, "", "confirm", replica="a",
            reason="fence_expiry", gap_s=0.31),
        {"seq": 30, "t": 100.85, "trace": "", "event": "fenced",
         "args": {"replica": "a", "trace": "T2", "rid": 2,
                  "fence_epoch": 1, "tokens": 2}},
    ]
    # token math: T1 = 1 prefill + steps 6,7 = 3; T2 = step 7 + 1
    # prefill(b) + step 19 = 3 (one re-decoded); T3 = 0 -> traced 6
    line = {
        "schema": "mxtpu-telemetry-2", "time_unix": 101.0, "pid": 77,
        "identity": {"pid": 77},
        "counters": {"serving.tokens": 6, "serving.goodput": 5,
                     "serving.requests": 3},
        "serving": [{"replica": "a", "decode_steps": 2, "prefills": 2}],
        "req_events": evs,
        "final": True,
        "last_steps": [{"step": 0, "t_unix": 100.3, "dispatch_s": 0.01,
                        "sync_s": 0.001, "dispatch_delta": 1,
                        "compile_delta": 0, "skipped": False,
                        "loss": None, "faults": [],
                        "where": "serve_step"}],
    }
    with open(tdir / "stream-slot0.jsonl", "w") as f:
        f.write(json.dumps(line) + "\n")
    journal = [
        {"t": 100.0, "event": "accept", "rid": 1, "trace": "T1",
         "replica": "a", "state": "accepted", "verdict": None,
         "retries": 0},
        {"t": 100.5, "event": "retry", "rid": 2, "trace": "T2",
         "replica": "a", "state": "accepted", "verdict": None,
         "retries": 1, "from_replica": "a"},
        {"t": 100.81, "event": "complete", "rid": 2, "trace": "T2",
         "replica": "b", "state": "completed", "verdict": "completed",
         "retries": 1, "tokens": 2},
    ]
    with open(tdir / "router-journal-slot0.jsonl", "w") as f:
        for ln in journal:
            f.write(json.dumps(ln) + "\n")
        if torn_journal:
            f.write('{"t": 100.9, "event": "compl')   # torn mid-write
    return str(tmp_path)


def test_discover_classifies_router_journals(tmp_path):
    _synthetic_tree(tmp_path)
    found = telemetry_report.discover_run_dir(str(tmp_path))
    assert len(found["router_journals"]) == 1
    assert all("router-journal" not in p for p in found["streams"])
    assert len(found["streams"]) == 1


def test_serve_report_reconstructs_lifecycles(tmp_path):
    rep = serve_report.analyze(_synthetic_tree(tmp_path))
    assert rep["lifecycle"]["ok"], rep["lifecycle"]
    reqs = rep["requests"]
    assert set(reqs) == {"T1", "T2", "T3"}
    assert len(reqs["T1"]["token_ts"]) == 3
    assert len(reqs["T2"]["token_ts"]) == 3   # incl. the re-decode
    assert reqs["T2"]["retries"][0]["from"] == "a"
    # torn journal line skipped AND counted
    assert any("torn" in n for n in rep["data"]["notes"])
    assert len(rep["data"]["journal"]) == 3


def test_serve_report_prefix_class_split(tmp_path):
    """ISSUE 15: TTFT/queue-wait percentiles split by prefix hit/miss
    class.  The class is the FIRST admission's (T2 missed on replica a;
    its failover re-admission hitting on b must not flip it), and
    never-admitted requests (T3) have no class."""
    rep = serve_report.analyze(_synthetic_tree(tmp_path))
    split = rep["prefix"]
    assert set(split) == {"hit", "miss"}
    assert split["hit"]["n"] == 1 and split["miss"]["n"] == 1
    assert split["hit"]["mean_prefix_len"] == 3       # T1, not T2's b
    assert split["hit"]["ttft_p50"] == 0.13
    assert split["miss"]["ttft_p50"] == 0.3
    assert split["miss"]["queue_p50"] == 0.3
    assert split["hit"]["sampled"] == 1               # T1 sampled
    assert split["miss"]["sampled"] == 0
    reqs = rep["requests"]
    assert reqs["T3"]["prefix_hit"] is None
    assert reqs["T2"]["prefix_hit"] is False
    assert reqs["T1"]["sampling"]["seed"] == 7
    # the rendered report carries the table
    import io
    buf = io.StringIO()
    serve_report.render(rep, out=buf)
    assert "latency by prefix class" in buf.getvalue()


def test_serve_report_arcs_and_blame(tmp_path):
    rep = serve_report.analyze(_synthetic_tree(tmp_path))
    assert rep["linked_arcs"] == 1
    (arc,) = rep["arcs"]
    assert arc["victims"] == ["a"] and arc["survivor"] == "b"
    by_trace = {b["trace"]: b for b in rep["blame"]}
    # T2 was failed over: the victim replica is named
    assert by_trace["T2"]["replica"] == "a"
    assert "lost" in by_trace["T2"]["why"]
    # T2 failover window: retry at 100.5, 1 pre-loss token, regained
    # at overall token 2 (t=100.7) -> 0.2s charged to failover
    assert by_trace["T2"]["phases"]["failover_s"] == \
        pytest.approx(0.2, abs=1e-6)
    # T3 never held a slot: its whole budget is queue wait, and the
    # blame says so (never "decode" for a request that never decoded)
    assert by_trace["T3"]["dominant"] == "queue"
    # T1 completed un-retried and within any SLO: not blamed
    assert "T1" not in by_trace
    # swap pause charged to exactly the resident trace
    assert rep["requests"]["T1"]["swap_s"] == pytest.approx(0.05)


def test_serve_report_liveness_lane_and_confirmed_arcs(tmp_path):
    """ISSUE 17: the per-replica liveness lane rebuilds suspicion
    spans, the worst heartbeat gap, the typed confirmation reason, and
    fenced-rejection counts from the TRACE-LESS liveness events — and
    the failover arc names the confirmation reason the proxy fired
    on."""
    rep = serve_report.analyze(_synthetic_tree(tmp_path))
    lanes = rep["liveness"]
    assert set(lanes) == {"a"}
    ln = lanes["a"]
    # two suspicions: one cleared wobble, one that confirmed
    assert ln["suspicions"] == 2
    assert len(ln["spans"]) == 2
    assert ln["spans"][0]["cleared"] is True
    assert ln["spans"][0]["dur_s"] == pytest.approx(0.03)
    assert ln["spans"][1]["cleared"] is False
    assert ln["open_suspect_t"] is None
    assert ln["max_gap_s"] == pytest.approx(0.31)
    assert ln["confirmed"] == {"t": 100.5, "reason": "fence_expiry"}
    assert ln["fenced"] == 1 and ln["fenced_tokens"] == 2
    # the healthy survivor has no lane — no news is good news
    assert "b" not in lanes
    # the retry record and the linked arc both carry the reason
    assert rep["requests"]["T2"]["retries"][0]["reason"] == \
        "fence_expiry"
    (arc,) = rep["arcs"]
    assert arc["reasons"] == ["fence_expiry"]
    # liveness events are replica news, never request lifecycle hops
    assert rep["lifecycle"]["ok"], rep["lifecycle"]
    import io
    buf = io.StringIO()
    serve_report.render(rep, out=buf)
    text = buf.getvalue()
    assert "per-replica liveness lane" in text
    assert "confirmed fence_expiry" in text
    assert "fence_expiry" in text


def test_failover_phase_charges_nothing_for_tokenless_victims():
    """A replica killed while a request was accepted-but-queued (or
    pre-first-token) lost no progress: failover_s must be 0 — the
    survivor's full decode is useful decode, and the re-queue wait is
    queue time — never 'the whole survivor run charged to failover'."""
    evs = [
        _ev(0, 10.0, "Q", "submit", prompt_len=2, max_new=2,
            router=True, rid=1),
        _ev(1, 10.0, "Q", "place", replica="a"),
        # killed on a before any token
        _ev(2, 10.5, "Q", "retry", **{"from": "a", "retries": 1}),
        _ev(3, 10.6, "Q", "place", replica="b"),
        _ev(4, 10.7, "Q", "admit", replica="b", slot=0,
            queue_wait_s=0.1, pages=1),
        _ev(5, 10.7, "Q", "token"),
        _ev(6, 10.9, "", "tokens", replica="b", traces=["Q"]),
        _ev(7, 10.91, "Q", "verdict", verdict="completed", final=True,
            router=True, rid=1, tokens=2),
    ]
    reqs = serve_report.build_requests(evs)
    p = reqs["Q"]["phases"]
    assert p["failover_s"] == 0.0
    assert p["decode_s"] > 0
    assert reqs["Q"]["dominant"] != "failover"


def test_failover_phase_nets_out_duplicates_on_second_retry():
    """Second failover: the regain target is the NET progress, not 2x
    the raw token count (raw counts include the first failover's
    re-decoded duplicates)."""
    evs = [
        _ev(0, 10.0, "R", "submit", prompt_len=2, max_new=3,
            router=True, rid=1),
        _ev(1, 10.0, "R", "admit", replica="a", slot=0,
            queue_wait_s=0.0, pages=1),
        _ev(2, 10.1, "R", "token"),                    # 1 real
        _ev(3, 10.2, "R", "retry", **{"from": "a", "retries": 1}),
        _ev(4, 10.3, "R", "admit", replica="b", slot=0,
            queue_wait_s=0.0, pages=1),
        _ev(5, 10.4, "R", "token"),                    # re-decode of 1
        _ev(6, 10.5, "R", "token"),                    # 2nd real
        _ev(7, 10.6, "R", "retry", **{"from": "b", "retries": 2}),
        _ev(8, 10.7, "R", "admit", replica="c", slot=0,
            queue_wait_s=0.0, pages=1),
        _ev(9, 10.8, "R", "token"),                    # re-decode of 1
        _ev(10, 10.9, "R", "token"),                   # re-decode of 2
        _ev(11, 11.0, "R", "token"),                   # 3rd real
        _ev(12, 11.01, "R", "verdict", verdict="completed",
            final=True, router=True, rid=1, tokens=3),
    ]
    reqs = serve_report.build_requests(evs)
    p = reqs["R"]["phases"]
    # retry 1: 1 net token, regained at overall token 2 (t=10.4):
    # 0.2s.  retry 2: raw k=3 but 1 duplicate -> net 2, regained at
    # overall token 5 (t=10.9): 0.3s.  A raw-2k rule would wait for
    # overall token 6 (t=11.0) and overcharge.
    assert p["failover_s"] == pytest.approx(0.5, abs=1e-6)


def _poll(seq, t, trace, cursor):
    """A trace-less delivery-plane poll event (the event's own trace
    field is empty like tokens/swap; the polled trace rides in args)."""
    return {"seq": seq, "t": t, "trace": "", "event": "poll",
            "args": {"replica": "a", "trace": trace, "cursor": cursor}}


def test_delivery_phase_charges_poll_gaps_not_decode():
    """ISSUE 19: a streamed token nobody has pulled yet is the CLIENT's
    latency — the emit -> first-covering-poll window is delivery_s, not
    decode_s.  And a tail re-poll AFTER the final verdict is lawful
    (idempotent re-polls are the whole point), never an
    'events after final verdict' lifecycle violation."""
    evs = [
        _ev(0, 10.0, "S", "submit", prompt_len=2, max_new=2,
            router=True, rid=1),
        _ev(1, 10.0, "S", "admit", replica="a", slot=0,
            queue_wait_s=0.0, pages=1),
        _ev(2, 10.1, "S", "token"),
        # cursor=1: token 0 delivered 0.05s after emit
        _poll(3, 10.15, "S", 1),
        _ev(4, 10.2, "S", "token"),
        # cursor=2: token 1 delivered 0.3s after emit
        _poll(5, 10.5, "S", 2),
        _ev(6, 10.55, "S", "verdict", verdict="completed", final=True,
            router=True, rid=1, tokens=2),
        # tail re-poll after the verdict (client confirming the end)
        _poll(7, 10.6, "S", 2),
    ]
    reqs = serve_report.build_requests(evs)
    p = reqs["S"]["phases"]
    assert p["delivery_s"] == pytest.approx(0.35, abs=1e-6)
    assert p["decode_s"] == pytest.approx(0.2, abs=1e-6)
    assert reqs["S"]["dominant"] == "delivery"
    violations, open_traces = serve_report.lifecycle_check(reqs)
    assert violations == [] and open_traces == []


def test_delivery_phase_merges_overlapping_poll_windows():
    """One slow poll covering two emits is ONE gap, not two: the
    per-token windows overlap and must be union-merged, else a single
    lazy poller double-charges delivery past wall time."""
    evs = [
        _ev(0, 10.0, "M", "submit", prompt_len=2, max_new=2,
            router=True, rid=1),
        _ev(1, 10.0, "M", "admit", replica="a", slot=0,
            queue_wait_s=0.0, pages=1),
        _ev(2, 10.1, "M", "token"),
        _ev(3, 10.2, "M", "token"),
        # one poll covers both tokens: windows (10.1,10.5)+(10.2,10.5)
        # merge to 0.4s, NOT 0.7s
        _poll(4, 10.5, "M", 2),
        _ev(5, 10.55, "M", "verdict", verdict="completed", final=True,
            router=True, rid=1, tokens=2),
    ]
    p = serve_report.build_requests(evs)["M"]["phases"]
    assert p["delivery_s"] == pytest.approx(0.4, abs=1e-6)


def test_stream_latency_split_and_unpolled_completed_delivery():
    """stream_latency_split classes a trace by whether any poll named
    it: the streamed TTFT clock is submit -> first DELIVERING poll
    (cursor past 0), the unary clock is the engine ttft_s stamp plus
    the full-reply completion time.  A never-polled COMPLETED request
    charges its last-token -> verdict window (the unary reply riding
    back) to delivery, not decode."""
    evs = [
        _ev(0, 10.0, "S", "submit", prompt_len=2, max_new=1,
            router=True, rid=1),
        _ev(1, 10.0, "S", "admit", replica="a", slot=0,
            queue_wait_s=0.0, pages=1),
        _ev(2, 10.1, "S", "token"),
        _poll(3, 10.15, "S", 1),
        _ev(4, 10.2, "S", "verdict", verdict="completed", final=True,
            router=True, rid=1, tokens=1),
        _ev(5, 10.0, "U", "submit", prompt_len=2, max_new=2,
            router=True, rid=2),
        _ev(6, 10.0, "U", "admit", replica="a", slot=1,
            queue_wait_s=0.0, pages=1),
        _ev(7, 10.1, "U", "token"),
        _ev(8, 10.2, "U", "token"),
        _ev(9, 10.4, "U", "verdict", verdict="completed", final=True,
            router=True, rid=2, tokens=2, ttft_s=0.1),
    ]
    reqs = serve_report.build_requests(evs)
    st = serve_report.stream_latency_split(reqs)
    assert st["streamed"]["n"] == 1
    assert st["streamed"]["ttft_p50"] == pytest.approx(0.15, abs=1e-6)
    assert st["unary"]["n"] == 1
    assert st["unary"]["ttft_p50"] == pytest.approx(0.1, abs=1e-6)
    assert st["unary"]["completion_p50"] == pytest.approx(0.4, abs=1e-6)
    # the never-polled completed request's ride-back window is delivery
    pu = reqs["U"]["phases"]
    assert pu["delivery_s"] == pytest.approx(0.2, abs=1e-6)
    assert pu["decode_s"] == pytest.approx(0.2, abs=1e-6)


def test_serve_report_accounting_and_latency_split(tmp_path):
    rep = serve_report.analyze(_synthetic_tree(tmp_path))
    acc = rep["accounting"]
    assert acc["tokens"] == 6 and acc["traced_tokens"] == 6
    assert acc["tokens_match"]
    assert acc["goodput"] == 5
    lat = rep["latency"]
    assert lat["completed"]["n"] == 2
    assert lat["expired_queue"]["n"] == 1
    assert lat["completed"]["ttft_p99"] == pytest.approx(0.3)


def test_serve_report_merged_trace_loads_as_one_file(tmp_path):
    rep = serve_report.analyze(_synthetic_tree(tmp_path))
    doc, t0 = serve_report.merged_trace(rep["data"], rep["requests"])
    path = tmp_path / "trace.json"
    with open(path, "w") as f:
        json.dump(doc, f)
    loaded = json.load(open(path))
    evs = loaded["traceEvents"]
    names = {e["args"].get("name") for e in evs if e["ph"] == "M"}
    assert "replica a" in names and "replica b" in names
    # the failover arc renders as a flow arrow pair crossing tracks
    starts = [e for e in evs if e["ph"] == "s"]
    ends = [e for e in evs if e["ph"] == "f"]
    assert len(starts) == 1 and len(ends) == 1
    assert starts[0]["pid"] != ends[0]["pid"]
    # residency segments as spans; decode steps on the process track
    assert any(e["ph"] == "X" and e.get("cat") == "request"
               for e in evs)
    assert any(e["ph"] == "X" and e["name"] == "serve_step.dispatch"
               for e in evs)


def test_serve_report_dedups_postmortem_ring_against_stream(tmp_path):
    tree = _synthetic_tree(tmp_path, torn_journal=False)
    # a postmortem from the SAME pid re-carries ring events (the crash
    # path dumps what the stream already shipped) plus one newer event
    pm = {
        "schema": "mxtpu-postmortem-2", "pid": 77,
        "identity": {"pid": 77}, "reason": "test",
        "request_trace": [
            _ev(25, 100.56, "T3", "verdict", verdict="expired_queue",
                final=True, router=True, rid=3, tokens=0),
            _ev(31, 100.9, "T9", "submit", prompt_len=1, max_new=1),
            _ev(32, 100.91, "T9", "verdict", verdict="shed",
                final=True, tokens=0),
        ],
    }
    with open(os.path.join(tree, "telemetry", "postmortem-77.json"),
              "w") as f:
        json.dump(pm, f)
    rep = serve_report.analyze(tree)
    # seq 25 deduped by (pid, seq); T9 appears once with its verdict
    t3_finals = [v for v in rep["requests"]["T3"]["verdicts"]
                 if v["args"].get("final")]
    assert len(t3_finals) == 1
    assert "T9" in rep["requests"]
    assert rep["lifecycle"]["ok"]


def test_telemetry_report_renders_serving_plane_and_journal(tmp_path):
    import io
    tree = _synthetic_tree(tmp_path)
    out = io.StringIO()
    telemetry_report.render_run_dir(tree, out)
    text = out.getvalue()
    assert "serving plane:" in text
    assert "goodput=5" in text
    assert "ROUTER JOURNAL" in text
    assert "failover: rid 2 trace T2 off replica a" in text
    assert "serve_report.py" in text   # the cross-ref line
    assert "torn" in text              # journal torn line counted


# -- router journal write discipline ---------------------------------------

def test_router_journal_single_write_append_discipline(tmp_path):
    """Journal lines are single os.write O_APPEND appends (opened per
    line — no fd pinned for the router's lifetime): every line is
    whole, trace ids ride along, and a pre-existing file is appended
    to, never truncated."""
    from mxnet_tpu.serving.router import Router
    path = str(tmp_path / "router-journal.jsonl")
    with open(path, "w") as f:
        f.write('{"t": 0, "event": "accept", "rid": 999, '
                '"trace": "old"}\n')

    class _Req:
        state, tokens, verdict, error = "queued", [], None, None

        def __init__(self):
            self.ttft_s = self.queue_wait_s = self.tpot_s = None

    class _Rep:
        replica_id, alive, draining = "r", True, False
        load, idle = 0, True

        def submit(self, prompt, max_new, deadline_s=None, trace=None):
            r = _Req()
            r.trace = trace
            return r

        def step(self):
            for r in self.reqs:
                r.state = "finished"
            return 0

    rep = _Rep()
    rt = Router([rep], journal_path=path)
    rr = rt.submit(np.ones(2), 1)
    assert rr.trace
    lines = [json.loads(ln) for ln in open(path)]
    assert lines[0]["rid"] == 999          # prior content intact
    assert lines[-1]["event"] == "accept"
    assert lines[-1]["trace"] == rr.trace  # the audit line carries it


def test_router_journal_env_default(tmp_path, monkeypatch):
    from mxnet_tpu.serving.router import Router
    path = str(tmp_path / "router-journal-slot0.jsonl")
    monkeypatch.setenv("MXTPU_SERVE_JOURNAL", path)
    rt = Router([])
    rt.submit(np.ones(2), 1)               # refused: no replicas
    assert os.path.exists(path)
    (line,) = [json.loads(ln) for ln in open(path)]
    assert line["event"] == "refuse"
    assert line["verdict"] == "no_live_replicas"


# -- the lifecycle laws against real engines (clean subprocess) -------------

@pytest.mark.serving
def test_trace_lifecycle_laws_real_engines():
    """Satellite laws end-to-end: exactly one terminal verdict per
    submitted request (completed/shed/expired-queue/expired-decode/
    prefill-error/infeasible all covered), trace id survives failover
    with a linking retry span, shed/expired traces close, traced token
    count == serving.tokens delta bit-exactly, and serve_report
    reconstructs the real artifact tree (blame + loadable merged
    trace)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    r = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tests", "serving_surv_driver.py"),
         "trace"],
        env=env, capture_output=True, timeout=420)
    out = r.stdout.decode() + r.stderr.decode()
    assert r.returncode == 0, out[-3000:]
    assert "SERVING_TRACE_OK" in out, out[-3000:]
