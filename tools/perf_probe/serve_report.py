"""Fleet serving report: request lifecycles, failover arcs, SLO blame.

The serving twin of ``job_report.py`` (ISSUE 13, OBSERVABILITY.md §12).
A serving fleet leaves three artifact kinds behind in one run-dir tree
(``tools/launch.py --run-dir`` / ``MXTPU_SERVE_JOURNAL`` layout): the
Router's audit journal (``router-journal*.jsonl``), each replica
process's telemetry stream (``stream-slot*.jsonl`` — every line carries
the request-trace events recorded since the previous line, plus the
periodic serving status block), and crash postmortems (which carry the
request-event ring).  ``telemetry_report.py`` renders each artifact
faithfully; THIS tool answers the fleet-level questions none can alone:

- **what did each request experience** — per-trace lifecycle
  reconstruction (submit → admit → prefill → every decode token → one
  terminal verdict), across replicas: a failed-over request's victim
  and survivor segments are ONE trace linked by the Router's ``retry``
  event, so the arc reads as a single story;
- **who served what, and how well** — a per-replica request matrix
  (admits, tokens, verdicts, retries-out) and TTFT / TPOT / queue-wait
  percentiles SPLIT BY VERDICT CLASS (a p99 that mixes completed and
  shed requests describes nothing);
- **who was suspected, who was confirmed dead, and why** — a
  per-replica liveness lane (ISSUE 17): suspicion spans from the RPC
  heartbeat view, the worst observed heartbeat gap, the typed
  confirmation reason (incarnation / kill_ack / fence_expiry) named on
  each failover arc, and fenced late-completion rejections;
- **SLO breach blame** — every deadline-missed / shed / failed-over
  (and, with ``--slo-ttft``, p99-breaching) request decomposed into its
  phase budget: queue wait, prefill, decode, hot-swap pauses, failover
  re-decode — with the dominant phase and the responsible replica
  named.  "Replica a died and its victims spent 60% of their budget
  re-decoding on b" is a sentence this tool prints, not a forensic
  project.  Streamed requests (ISSUE 19) add a **delivery** phase:
  the poll-gap windows between each token's emit and the first
  successful poll that covered it — a slow poller is the client's
  latency, never blamed on the replica's decode;
- **streamed vs unary TTFT** (ISSUE 19) — first-token percentiles
  split by delivery mode: the streamed class measures submit → first
  token DELIVERED through ``poll``, the unary class measures the
  engine's emit stamp and its completion (the whole point of
  streaming is that the first number beats the last one);
- **goodput** — ``serving.goodput`` (tokens on requests that completed
  within deadline) vs raw ``serving.tokens``, and traced token events
  reconciled with the counter;
- **one merged chrome trace** (``--trace-out``) — pid = replica,
  tid = decode slot, one span per residency segment, token instants,
  flow arrows linking failover arcs across replicas, hot-swap pauses,
  and each process's recent decode-step spans — loadable as ONE file
  in Perfetto.

Torn artifact lines (a process killed mid-append) are skipped and
counted, and request events evicted before any stream line could carry
them are declared per line (``req_dropped``) — no silent caps anywhere.

Usage:
    python tools/perf_probe/serve_report.py RUN_DIR \
        [--trace-out serve-trace.json] [--slo-ttft SECONDS]

``discover_run_dir`` / ``parse_artifact`` are shared with
``telemetry_report.py`` (one input contract, not two copies).
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import telemetry_report as _tr  # noqa: E402 (sibling module)

#: verdicts that are refusals (the request never held a slot here)
REFUSAL_VERDICTS = ("shed", "draining", "no_live_replicas")
#: trace pid for per-process decode-step tracks (real replica pids are
#: small ordinals; keep the synthetic ones far away)
PROC_TRACK_BASE = 900
SWAP_TID = 9990


def _pct(sorted_vals, q):
    if not sorted_vals:
        return None
    i = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[i]


# -- loading ---------------------------------------------------------------

def load_serve(run_dir):
    """Parse the run dir into the fleet structure: request events (from
    every stream line's ``req_events`` and every postmortem's
    ``request_trace``, deduplicated by (process, seq) — a crashed
    replica leaves the SAME ring twice), the router journal, the last
    serving status block and counter snapshot per process, and each
    process's flight records (decode-step spans)."""
    found = _tr.discover_run_dir(run_dir)
    notes = []
    events = {}          # (proc key, seq) -> event dict (+"_pid")
    counters = {}        # proc key -> merged counters dict
    status = {}          # (proc key, engine tag) -> engine snapshot
    flights = {}         # proc key -> {step: flight rec}
    req_dropped = 0
    journal = []

    def _proc_key(doc):
        """One key per fleet PROCESS: identity slot + attempt + pid.
        The dedup's job is to match a process's stream lines against
        its own postmortem ring — but pid ALONE collides across
        containerized replicas (every container's service can be pid
        7) and across restart attempts that recycle a pid, and a
        collision would silently discard a whole replica's lifecycle
        record.  Slot/attempt (the elastic identity the transport
        stamps on every line) disambiguate both."""
        ident = doc.get("identity") or {}
        return (ident.get("slot"), ident.get("attempt"),
                ident.get("pid") or doc.get("pid"))

    def _fold(doc, recs):
        pkey = _proc_key(doc)
        pid = pkey[-1]
        for e in recs:
            events.setdefault((pkey, e.get("seq")), dict(e, _pid=pid))
        return pkey

    def _merge_counters(pkey, new):
        # counters are monotonic, so max-merge per process keeps
        # whichever artifact saw more — a process can leave SEVERAL
        # views of the same registry (its own emitter stream, the
        # ISSUE-18 pulled stream on the collector host, a postmortem),
        # and whichever file parses last must not roll the totals back
        cur = counters.setdefault(pkey, new)
        if cur is not new:
            for k, v in new.items():
                old = cur.get(k)
                if isinstance(v, (int, float)) and \
                        isinstance(old, (int, float)):
                    cur[k] = max(old, v)
                elif k not in cur:
                    cur[k] = v

    def _fold_flights(pkey, recs):
        # dedup by (process, step): pulled lines carry INCREMENTAL
        # flight slices, so one process's records arrive spread over
        # many lines (and possibly twice, via its own final line too)
        by_step = flights.setdefault(pkey, {})
        for rec in recs:
            by_step.setdefault(rec.get("step"), rec)

    for path in found["streams"]:
        for doc in _tr.parse_artifact(path, notes):
            pkey = _fold(doc, doc.get("req_events") or [])
            req_dropped += doc.get("req_dropped", 0)
            if doc.get("counters"):
                _merge_counters(pkey, doc["counters"])
            for snap in doc.get("serving") or []:
                status[(pkey, snap.get("replica"))] = snap
            if doc.get("last_steps"):
                _fold_flights(pkey, doc["last_steps"])
    for path in found["postmortems"]:
        docs = _tr.parse_artifact(path, notes)
        if docs:
            doc = docs[-1]
            pkey = _fold(doc, doc.get("request_trace") or [])
            _merge_counters(pkey, doc.get("counters") or {})
            for snap in doc.get("serving") or []:
                key = (pkey, snap.get("replica"))
                old = status.get(key)
                if old is None or (snap.get("decode_steps") or 0) >= \
                        (old.get("decode_steps") or 0):
                    status[key] = snap
    for path in found["router_journals"]:
        for doc in _tr.parse_artifact(path, notes):
            if "rid" in doc and "event" in doc:
                journal.append(doc)
    evs = sorted(events.values(),
                 key=lambda e: (e.get("t", 0), e.get("seq", 0)))
    flight_list = [(pk, [by_step[s] for s in sorted(
                        by_step, key=lambda s: (s is None, s))])
                   for pk, by_step in flights.items()]
    return {"run_dir": run_dir, "events": evs, "journal": journal,
            "counters": counters, "status": status,
            "flights": flight_list,
            "req_dropped": req_dropped, "notes": notes}


# -- lifecycle reconstruction ----------------------------------------------

def build_requests(events):
    """Per-trace lifecycle records from the merged event list.

    The batched ``tokens`` events (one per decode step, naming every
    advanced trace) are len-expanded here: each named trace gets one
    token at the step's stamp.  Engine-scope ``swap`` events are charged
    to the traces they name.  Returns ``{trace: record}`` where a record
    holds the ordered events, per-segment residency (a new segment per
    ``admit`` — a failover arc has one per replica), token timestamps,
    retries, swap pauses, and the final verdict.

    Ordering uses the merged-list POSITION (the (t, seq) sort of
    ``load_serve``), never raw ``seq``: seq counters are per-process,
    and a trace spanning a router process and a remote replica process
    would compare apples to oranges."""
    reqs = {}

    def rec(trace):
        r = reqs.get(trace)
        if r is None:
            r = reqs[trace] = {
                "trace": trace, "events": [], "segments": [],
                "token_ts": [], "retries": [], "swap_s": 0.0,
                "swap_count": 0, "verdicts": [], "final": None,
                "submit_t": None, "rid": None, "router": False,
                "prompt_len": None, "max_new": None,
                "deadline_s": None, "last_pos": -1,
                "prefix_hit": None, "prefix_len": None,
                "sampling": None, "poll_ts": [],
            }
        return r

    for pos, e in enumerate(events):
        ev, tr = e.get("event"), e.get("trace")
        args = e.get("args") or {}
        if ev == "tokens":
            for t in args.get("traces") or []:
                r = rec(t)
                r["token_ts"].append(e.get("t"))
                if r["segments"]:
                    r["segments"][-1]["tokens"] += 1
                r["last_pos"] = pos
            continue
        if ev == "swap":
            for t in args.get("traces") or []:
                r = rec(t)
                r["swap_s"] += args.get("dur_s") or 0.0
                r["swap_count"] += 1
            continue
        if ev == "poll":
            # delivery-plane event (ISSUE 19): trace-less like tokens/
            # swap — it feeds the delivery phase and the streamed-TTFT
            # split but NEVER the lifecycle record (a tail re-poll
            # after the final verdict is lawful, not a violation)
            t = args.get("trace")
            if t:
                rec(t)["poll_ts"].append((e.get("t"),
                                          args.get("cursor") or 0))
            continue
        if not tr:
            continue
        r = rec(tr)
        r["events"].append(e)
        r["last_pos"] = pos
        if ev == "submit":
            if r["submit_t"] is None:
                r["submit_t"] = e.get("t")
            r["router"] = r["router"] or bool(args.get("router"))
            for k in ("prompt_len", "max_new", "deadline_s"):
                if r[k] is None:
                    r[k] = args.get(k)
            if args.get("rid") is not None and r["router"]:
                r["rid"] = args.get("rid")
            if r["sampling"] is None:
                r["sampling"] = args.get("sampling")
        elif ev == "admit":
            r["segments"].append({
                "replica": args.get("replica"), "t": e.get("t"),
                "slot": args.get("slot"),
                "queue_wait_s": args.get("queue_wait_s") or 0.0,
                "prefill_s": 0.0, "tokens": 0, "end": None,
                "prefix_hit": args.get("prefix_hit"),
                "shared_pages": args.get("shared_pages"),
            })
            # the request's prefix class is its FIRST admission's (a
            # failover re-admission may hit where the original missed —
            # the class the caller FELT is the first one)
            if r["prefix_hit"] is None and \
                    args.get("prefix_hit") is not None:
                r["prefix_hit"] = bool(args.get("prefix_hit"))
                r["prefix_len"] = args.get("prefix_len")
        elif ev == "prefill":
            if r["segments"]:
                r["segments"][-1]["prefill_s"] += (
                    (args.get("dispatch_s") or 0.0)
                    + (args.get("sync_s") or 0.0))
        elif ev == "token":
            r["token_ts"].append(e.get("t"))
            if r["segments"]:
                r["segments"][-1]["tokens"] += 1
        elif ev == "retry":
            r["retries"].append({"t": e.get("t"),
                                 "from": args.get("from"),
                                 "reason": args.get("reason")})
            if r["segments"]:
                r["segments"][-1]["end"] = e.get("t")
        elif ev == "verdict":
            r["verdicts"].append(dict(e, _pos=pos))
            if args.get("final"):
                r["final"] = r["verdicts"][-1]
            if r["segments"] and r["segments"][-1]["end"] is None:
                r["segments"][-1]["end"] = e.get("t")
            if r["rid"] is None and args.get("rid") is not None:
                r["rid"] = args.get("rid")
    for r in reqs.values():
        _phase_budget(r)
    return reqs


def _phase_budget(r):
    """Decompose one request's wall time into its phase budget (the
    blame decomposition).  ``failover_s`` is the window from each
    ``retry`` until the survivor REGAINED the victim's progress (the
    k tokens produced before the loss exist again at overall token
    2k — greedy re-decode reproduces them bit-identically), so the
    re-decode is charged to the failover, not to useful decode.  The
    phases partition total wall time exactly: ``decode_s`` is the
    remainder, never double-counted."""
    final = r["final"] or (r["verdicts"][-1] if r["verdicts"] else None)
    t0 = r["submit_t"]
    t1 = final["t"] if final is not None else (
        r["token_ts"][-1] if r["token_ts"] else t0)
    if t0 is None or t1 is None:
        r["phases"] = None
        return
    total = max(0.0, t1 - t0)
    # a request that never reached a slot (expired in queue, shed,
    # refused) spent its WHOLE budget waiting — that is queue time,
    # not decode time
    queue = (sum(s["queue_wait_s"] for s in r["segments"])
             if r["segments"] else total)
    prefill = sum(s["prefill_s"] for s in r["segments"])
    swap = r["swap_s"]
    failover = 0.0
    ts = r["token_ts"]
    dup = 0   # tokens already re-produced by earlier failovers
    for ret in sorted(r["retries"], key=lambda x: x["t"] or 0):
        k = sum(1 for t in ts if t <= ret["t"])
        unique = k - dup      # the victim's NET progress to re-produce
        if unique <= 0:
            # killed while queued / pre-first-token: nothing to regain,
            # and the survivor's queue wait is already in queue_s —
            # charging a window here would double-count it
            continue
        target = k + unique   # overall token count at regained progress
        regained = ts[target - 1] if len(ts) >= target else t1
        failover += max(0.0, regained - ret["t"])
        dup = k
    # delivery (ISSUE 19): the poll-gap windows — for each token, the
    # wall time between its EMIT and the first successful poll whose
    # cursor covers it.  A streamed token nobody has pulled yet is the
    # CLIENT's latency, not the engine's: charging it to decode would
    # blame the replica for a slow poller.  Overlapping windows are
    # merged (one slow poll covering 10 emits is one gap, not ten).
    delivery = 0.0
    polls = sorted((p for p in r["poll_ts"] if p[0] is not None),
                   key=lambda p: p[0])
    if polls:
        intervals = []
        for i, emit in enumerate(ts):
            # first poll whose cursor is PAST token i delivered it; a
            # never-covered token (the client vanished mid-stream)
            # stays undelivered to the end of the record
            cover = next((p[0] for p in polls
                          if p[1] > i and p[0] >= emit), t1)
            lo = max(t0, emit)
            hi = min(t1, max(cover, emit))
            if hi > lo:
                intervals.append((lo, hi))
        intervals.sort()
        cur_lo = cur_hi = None
        for lo, hi in intervals:
            if cur_hi is None or lo > cur_hi:
                if cur_hi is not None:
                    delivery += cur_hi - cur_lo
                cur_lo, cur_hi = lo, hi
            else:
                cur_hi = max(cur_hi, hi)
        if cur_hi is not None:
            delivery += cur_hi - cur_lo
    elif ts and final is not None and \
            (final.get("args") or {}).get("verdict") == "completed":
        # never-polled COMPLETED request: the budget between its last
        # token and its final verdict is the unary reply riding back —
        # delivery, not decode
        delivery = max(0.0, t1 - ts[-1])
    used = queue + prefill + swap + failover + delivery
    decode = max(0.0, total - used)
    r["phases"] = {"total_s": total, "queue_s": queue,
                   "prefill_s": prefill, "decode_s": decode,
                   "swap_s": swap, "failover_s": failover,
                   "delivery_s": delivery}
    r["dominant"] = max(
        ("queue_s", "prefill_s", "decode_s", "swap_s", "failover_s",
         "delivery_s"),
        key=lambda k: r["phases"][k])[:-2]


def lifecycle_check(reqs):
    """The trace laws (pinned by tests/test_serve_report.py):
    every trace closes with EXACTLY ONE final verdict event, and that
    verdict is the trace's last event.  Returns the violation list
    (empty == lawful) and the set of open traces."""
    violations, open_traces = [], []
    for tr, r in sorted(reqs.items()):
        finals = [v for v in r["verdicts"]
                  if (v.get("args") or {}).get("final")]
        if not finals:
            open_traces.append(tr)
            continue
        if len(finals) > 1:
            violations.append(
                "trace %s has %d final verdicts (law: exactly one)"
                % (tr, len(finals)))
        if finals[-1]["_pos"] < r["last_pos"]:
            violations.append(
                "trace %s has events after its final verdict" % tr)
    return violations, open_traces


# -- fleet views -----------------------------------------------------------

def replica_matrix(reqs):
    """{replica: {admits, tokens, retries_out, verdict counts}} — the
    per-replica request matrix."""
    m = {}

    def row(tag):
        return m.setdefault(tag, {"admits": 0, "tokens": 0,
                                  "retries_out": 0, "verdicts": {}})

    for r in reqs.values():
        for seg in r["segments"]:
            rr = row(seg["replica"])
            rr["admits"] += 1
            rr["tokens"] += seg["tokens"]
        for ret in r["retries"]:
            row(ret["from"])["retries_out"] += 1
        final = r["final"]
        if final is not None:
            tag = ((final.get("args") or {}).get("replica")
                   or (r["segments"][-1]["replica"] if r["segments"]
                       else "-"))
            v = (final.get("args") or {}).get("verdict")
            vr = row(tag)["verdicts"]
            vr[v] = vr.get(v, 0) + 1
    return m


def verdict_latency_split(reqs):
    """{verdict: {n, ttft p50/p99, tpot p50/p99, queue p50/p99}} from
    the final verdict events' latency stamps."""
    groups = {}
    for r in reqs.values():
        if r["final"] is None:
            continue
        args = r["final"].get("args") or {}
        g = groups.setdefault(args.get("verdict"),
                              {"n": 0, "ttft": [], "tpot": [],
                               "queue": []})
        g["n"] += 1
        for key, field in (("ttft", "ttft_s"), ("tpot", "tpot_s"),
                           ("queue", "queue_wait_s")):
            if args.get(field) is not None:
                g[key].append(args[field])
    out = {}
    for v, g in groups.items():
        row = {"n": g["n"]}
        for key in ("ttft", "tpot", "queue"):
            vals = sorted(g[key])
            row[key + "_p50"] = _pct(vals, 0.5)
            row[key + "_p99"] = _pct(vals, 0.99)
        out[v] = row
    return out


def stream_latency_split(reqs):
    """TTFT percentiles split streamed-vs-unary (ISSUE 19).  A request
    is *streamed* iff at least one ``poll`` event named its trace.  The
    two classes measure DIFFERENT clocks on purpose: the streamed TTFT
    is submit → the first poll that DELIVERED a token (cursor past 0 —
    what a streaming client actually waits), while the unary TTFT is
    the engine's emit-side ``ttft_s`` stamp plus nothing (the whole
    reply rides back with the verdict, so first-token latency IS
    completion latency for that class).  The acceptance bar — streamed
    p50 well under the unary COMPLETION p50 — is what streaming buys."""
    streamed, unary, unary_total = [], [], []
    for r in reqs.values():
        polls = sorted((p for p in r["poll_ts"] if p[0] is not None),
                       key=lambda p: p[0])
        if polls:
            if r["submit_t"] is None:
                continue
            first = next((p[0] for p in polls if p[1] > 0), None)
            if first is not None:
                streamed.append(max(0.0, first - r["submit_t"]))
            continue
        if r["final"] is None:
            continue
        args = r["final"].get("args") or {}
        if args.get("ttft_s") is not None:
            unary.append(args["ttft_s"])
        if r["submit_t"] is not None:
            unary_total.append(max(0.0, r["final"]["t"] - r["submit_t"]))
    streamed.sort(), unary.sort(), unary_total.sort()
    return {
        "streamed": {"n": len(streamed),
                     "ttft_p50": _pct(streamed, 0.5),
                     "ttft_p99": _pct(streamed, 0.99)},
        "unary": {"n": len(unary),
                  "ttft_p50": _pct(unary, 0.5),
                  "ttft_p99": _pct(unary, 0.99),
                  "completion_p50": _pct(unary_total, 0.5),
                  "completion_p99": _pct(unary_total, 0.99)},
    }


def prefix_latency_split(reqs):
    """TTFT / queue-wait percentiles split by prefix-cache class
    (ISSUE 15): a ``hit`` request mapped shared pages and prefilled
    only its suffix, a ``miss`` paid the full prefill.  The cache's
    effect is thereby blameable per request like everything else in
    the §12 plane.  Read the TTFT split with the hardware in mind: on
    accelerators a hit skips the cached prefix's quadratic attention
    and should beat the miss class; on the CPU interpret path the
    static-pad suffix window plus the prefix gather make hit wall time
    >= miss — there the cache's measurable wins are the queue-wait
    split (admission capacity) and ``serving.prefill_tokens``.
    Requests that were never admitted (shed, expired-in-queue) have no
    class and are excluded."""
    groups = {}
    for r in reqs.values():
        if r["prefix_hit"] is None or r["final"] is None:
            continue
        args = r["final"].get("args") or {}
        g = groups.setdefault("hit" if r["prefix_hit"] else "miss",
                              {"n": 0, "ttft": [], "queue": [],
                               "prefix_len": [], "sampled": 0})
        g["n"] += 1
        if args.get("ttft_s") is not None:
            g["ttft"].append(args["ttft_s"])
        if args.get("queue_wait_s") is not None:
            g["queue"].append(args["queue_wait_s"])
        if r["prefix_len"]:
            g["prefix_len"].append(r["prefix_len"])
        if r["sampling"]:
            g["sampled"] += 1
    out = {}
    for cls, g in groups.items():
        ttft, queue = sorted(g["ttft"]), sorted(g["queue"])
        out[cls] = {
            "n": g["n"], "sampled": g["sampled"],
            "ttft_p50": _pct(ttft, 0.5), "ttft_p99": _pct(ttft, 0.99),
            "queue_p50": _pct(queue, 0.5),
            "queue_p99": _pct(queue, 0.99),
            "mean_prefix_len": (sum(g["prefix_len"])
                                / len(g["prefix_len"])
                                if g["prefix_len"] else 0),
        }
    return out


def failover_arcs(reqs):
    """Failed-over requests as linked arcs: one per retried trace —
    victim replica, survivor replica, tokens lost/regained, whether
    the arc completed, and the CONFIRMATION REASON the liveness
    machine typed on each hop (ISSUE 17: incarnation / kill_ack /
    fence_expiry; None for in-process ReplicaLost)."""
    arcs = []
    for tr, r in sorted(reqs.items()):
        if not r["retries"]:
            continue
        hops = [s["replica"] for s in r["segments"]]
        arcs.append({
            "trace": tr, "rid": r["rid"],
            "victims": [ret["from"] for ret in r["retries"]],
            "reasons": [ret.get("reason") for ret in r["retries"]],
            "path": hops,
            "survivor": hops[-1] if hops else None,
            "verdict": ((r["final"] or {}).get("args") or {})
            .get("verdict"),
            "failover_s": (r["phases"] or {}).get("failover_s"),
        })
    return arcs


def liveness_lanes(events):
    """Per-replica liveness lane (ISSUE 17), rebuilt from the
    trace-less liveness events the RPC proxies and the Router emit:
    suspicion spans (``suspect`` → ``suspect_clear`` or ``confirm``),
    the worst observed heartbeat gap, the confirmed death (typed
    reason), and fenced late-completion rejections.  These events
    carry no trace id by design — they are replica news, not request
    lifecycle hops — so they never appear in ``build_requests``;
    this lane is their home."""
    lanes = {}

    def lane(tag):
        return lanes.setdefault(tag, {
            "replica": tag, "suspicions": 0, "spans": [],
            "open_suspect_t": None, "max_gap_s": 0.0,
            "confirmed": None, "fenced": 0, "fenced_tokens": 0})

    for e in events:
        ev = e.get("event")
        if ev not in ("suspect", "suspect_clear", "confirm", "fenced"):
            continue
        args = e.get("args") or {}
        tag = args.get("replica")
        if tag is None:
            continue
        ln = lane(tag)
        t = e.get("t")
        if ev == "suspect":
            ln["suspicions"] += 1
            ln["open_suspect_t"] = t
            ln["max_gap_s"] = max(ln["max_gap_s"],
                                  args.get("gap_s") or 0.0)
        elif ev == "suspect_clear":
            if ln["open_suspect_t"] is not None and t is not None:
                ln["spans"].append(
                    {"t": ln["open_suspect_t"],
                     "dur_s": max(0.0, t - ln["open_suspect_t"]),
                     "cleared": True})
            ln["open_suspect_t"] = None
            ln["max_gap_s"] = max(ln["max_gap_s"],
                                  args.get("gap_s") or 0.0)
        elif ev == "confirm":
            if ln["open_suspect_t"] is not None and t is not None:
                ln["spans"].append(
                    {"t": ln["open_suspect_t"],
                     "dur_s": max(0.0, t - ln["open_suspect_t"]),
                     "cleared": False})
                ln["open_suspect_t"] = None
            ln["confirmed"] = {"t": t, "reason": args.get("reason")}
        elif ev == "fenced":
            ln["fenced"] += 1
            ln["fenced_tokens"] += args.get("tokens") or 0
    return lanes


def alert_lanes(events):
    """Fired alert-rule events (ISSUE 18), in fleet time order.  Like
    liveness events these are trace-less replica news — invisible to
    ``build_requests`` — so the alerts lane is their only rendering;
    each row names the rule, severity, the metric that tripped it, the
    observed value, and the pid that fired it."""
    out = []
    for e in events:
        if e.get("event") != "alert":
            continue
        args = e.get("args") or {}
        out.append({"t": e.get("t"), "pid": e.get("_pid"),
                    "rule": args.get("rule"),
                    "severity": args.get("severity"),
                    "metric": args.get("metric"),
                    "value": args.get("value")})
    out.sort(key=lambda a: a["t"] or 0)
    return out


def blame(reqs, slo_ttft=None):
    """The SLO breach blame list: every request whose terminal verdict
    is not ``completed``, every failed-over request, and (with
    ``slo_ttft``) every completed request whose TTFT breached it —
    each decomposed into its phase budget with the dominant phase and
    the responsible replica named."""
    out = []
    for tr, r in sorted(reqs.items()):
        final = r["final"]
        if final is None:
            continue
        args = final.get("args") or {}
        verdict = args.get("verdict")
        breach = None
        if verdict != "completed":
            breach = verdict
        elif r["retries"]:
            breach = "failed_over"
        elif slo_ttft is not None and \
                (args.get("ttft_s") or 0.0) > slo_ttft:
            breach = "ttft_over_slo"
        if breach is None:
            continue
        phases = r["phases"] or {}
        dominant = r.get("dominant")
        if r["retries"]:
            blamed = r["retries"][-1]["from"]
            why = "replica %s lost mid-decode" % blamed
        elif verdict in REFUSAL_VERDICTS:
            blamed = ((r["verdicts"][0].get("args") or {})
                      .get("replica") if r["verdicts"] else None)
            why = "intake refused (%s)" % verdict
        elif dominant == "queue":
            blamed = (r["segments"][0]["replica"] if r["segments"]
                      else args.get("replica"))
            why = "queue wait dominated"
        else:
            blamed = (r["segments"][-1]["replica"] if r["segments"]
                      else args.get("replica"))
            why = "%s phase dominated" % (dominant or "?")
        out.append({"trace": tr, "rid": r["rid"], "breach": breach,
                    "verdict": verdict, "phases": phases,
                    "dominant": dominant, "replica": blamed,
                    "why": why})
    out.sort(key=lambda b: -(b["phases"].get("total_s") or 0.0)
             if b["phases"] else 0.0)
    return out


def accounting(data, reqs):
    """Goodput vs raw tokens and the traced-vs-counter
    reconciliation."""
    tokens = goodput = requests = dropped = scale_repairs = 0
    spec = {"draft_tokens": 0, "accepted": 0, "rejected": 0,
            "rollbacks": 0}
    for c in data["counters"].values():
        tokens += c.get("serving.tokens", 0)
        goodput += c.get("serving.goodput", 0)
        requests += c.get("serving.requests", 0)
        dropped += c.get("serving.trace_dropped", 0)
        scale_repairs += c.get("serving.kv.scale_repairs", 0)
        for key in spec:
            spec[key] += c.get("serving.spec." + key, 0)
    traced = sum(len(r["token_ts"]) for r in reqs.values())
    # fleet tokens-per-dispatch (ISSUE 16): decode tokens over decode
    # dispatches — 1.0 without speculation, > 1 when accepted drafts
    # multiply what each donated dispatch commits
    decode_steps = sum(s.get("decode_steps") or 0
                       for s in data["status"].values())
    prefills = sum(s.get("prefills") or 0
                   for s in data["status"].values())
    return {
        "tokens": tokens, "goodput": goodput, "requests": requests,
        "traced_tokens": traced,
        "tokens_match": traced == tokens and not dropped
        and not data["req_dropped"],
        "trace_dropped": dropped + data["req_dropped"],
        "goodput_fraction": (goodput / tokens) if tokens else None,
        "kv_scale_repairs": scale_repairs,
        "spec": spec if spec["draft_tokens"] else None,
        "acceptance_rate": (spec["accepted"] / spec["draft_tokens"]
                            if spec["draft_tokens"] else None),
        "tokens_per_dispatch": ((tokens - prefills) / decode_steps
                                if decode_steps else None),
    }


# -- merged chrome trace ---------------------------------------------------

def merged_trace(data, reqs):
    """One chrome-tracing document for the fleet: pid = replica (tid =
    decode slot; residency segments as spans, tokens as thread-scoped
    instants, failover arcs as flow arrows crossing replica tracks,
    hot-swap pauses on a dedicated row), plus each process's recent
    decode-step spans (the flight ring) on per-process tracks.  Returns
    ``(doc, t0_unix)``."""
    tags = sorted({s["replica"] for r in reqs.values()
                   for s in r["segments"] if s["replica"] is not None})
    pid_of = {tag: i + 1 for i, tag in enumerate(tags)}
    stamps = [r["submit_t"] for r in reqs.values()
              if r["submit_t"] is not None]
    stamps += [rec["t_unix"] for _, recs in data["flights"]
               for rec in recs if rec.get("t_unix")]
    t0 = min(stamps) if stamps else 0.0

    def us(t):
        return (t - t0) * 1e6

    events = []
    for tag, pid in pid_of.items():
        events.append({"ph": "M", "name": "process_name", "pid": pid,
                       "args": {"name": "replica %s" % tag}})
    flow_id = 0
    for tr, r in sorted(reqs.items()):
        label = "req %s" % (r["rid"] if r["rid"] is not None else tr)
        final_args = (r["final"] or {}).get("args") or {}
        prev_end = None
        for i, seg in enumerate(r["segments"]):
            pid = pid_of.get(seg["replica"], 0)
            tid = seg["slot"] if seg["slot"] is not None else 0
            end = seg["end"]
            if end is None:
                seg_ts = [t for t in r["token_ts"] if t >= seg["t"]]
                end = seg_ts[-1] if seg_ts else seg["t"]
            events.append({
                "name": label, "cat": "request", "ph": "X",
                "pid": pid, "tid": tid, "ts": us(seg["t"]),
                "dur": max(1.0, (end - seg["t"]) * 1e6),
                "args": {"trace": tr, "segment": i,
                         "tokens": seg["tokens"],
                         "verdict": final_args.get("verdict")}})
            if prev_end is not None:
                # the failover arc: an arrow from the victim segment's
                # end to the survivor's admit
                flow_id += 1
                events.append({"name": "failover", "cat": "request",
                               "ph": "s", "id": flow_id, "pid":
                               prev_end[0], "tid": prev_end[1],
                               "ts": us(prev_end[2])})
                events.append({"name": "failover", "cat": "request",
                               "ph": "f", "bp": "e", "id": flow_id,
                               "pid": pid, "tid": tid,
                               "ts": us(seg["t"])})
            prev_end = (pid, tid, end)
        for t in r["token_ts"]:
            seg = next((s for s in reversed(r["segments"])
                        if s["t"] <= t), None)
            if seg is None:
                continue
            events.append({"name": "token", "cat": "token", "ph": "i",
                           "s": "t",
                           "pid": pid_of.get(seg["replica"], 0),
                           "tid": seg["slot"] or 0, "ts": us(t),
                           "args": {"trace": tr}})
    for e in (e for e in data["events"] if e.get("event") == "swap"):
        args = e.get("args") or {}
        pid = pid_of.get(args.get("replica"), 0)
        events.append({"name": "swap epoch %s%s"
                       % (args.get("epoch"),
                          "" if args.get("ok") else " (ROLLBACK)"),
                       "cat": "swap", "ph": "X", "pid": pid,
                       "tid": SWAP_TID, "ts": us(e.get("t", t0)),
                       "dur": max(1.0, (args.get("dur_s") or 0.0)
                                  * 1e6),
                       "args": {"traces": args.get("traces")}})
    for i, (proc, recs) in enumerate(data["flights"]):
        pid = PROC_TRACK_BASE + i
        slot, attempt, ppid = proc
        label = "pid %s" % ppid if slot is None else \
            "slot %s attempt %s pid %s" % (slot, attempt, ppid)
        events.append({"ph": "M", "name": "process_name", "pid": pid,
                       "args": {"name": "process %s (decode steps)"
                                % label}})
        for rec in recs:
            where = rec.get("where") or "step"
            ts = us(rec.get("t_unix", t0))
            dur = (rec.get("dispatch_s") or 0.0) * 1e6
            events.append({"name": where + ".dispatch", "cat": "step",
                           "ph": "X", "pid": pid, "tid": 0, "ts": ts,
                           "dur": dur,
                           "args": {"step": rec.get("step")}})
            if rec.get("sync_s") is not None:
                events.append({"name": where + ".sync", "cat": "step",
                               "ph": "X", "pid": pid, "tid": 0,
                               "ts": ts + dur,
                               "dur": rec["sync_s"] * 1e6,
                               "args": {"step": rec.get("step")}})
    return {"traceEvents": events, "displayTimeUnit": "ms"}, t0


# -- the report ------------------------------------------------------------

def analyze(run_dir, slo_ttft=None):
    """Load + reconstruct + judge: the structured fleet report
    (``render`` prints it; tests/test_serve_report.py and
    tests/serving_surv_driver.py ``section_trace`` assert on it)."""
    data = load_serve(run_dir)
    reqs = build_requests(data["events"])
    violations, open_traces = lifecycle_check(reqs)
    arcs = failover_arcs(reqs)
    journal_retries = [d for d in data["journal"]
                       if d.get("event") == "retry"]
    # an arc is LINKED when the same trace names both a victim and a
    # different survivor — a victim killed while still queued (no
    # residency segment on the dead replica) links exactly the same way
    linked_arcs = sum(
        1 for a in arcs
        if a["victims"] and a["survivor"] is not None
        and a["survivor"] not in a["victims"])
    return {
        "data": data, "requests": reqs,
        "lifecycle": {"violations": violations,
                      "open_traces": open_traces,
                      "ok": not violations and not open_traces},
        "matrix": replica_matrix(reqs),
        "latency": verdict_latency_split(reqs),
        "stream": stream_latency_split(reqs),
        "prefix": prefix_latency_split(reqs),
        "arcs": arcs, "linked_arcs": linked_arcs,
        "journal_retries": journal_retries,
        "liveness": liveness_lanes(data["events"]),
        "alerts": alert_lanes(data["events"]),
        "blame": blame(reqs, slo_ttft),
        "accounting": accounting(data, reqs),
    }


def render(rep, out=sys.stdout):
    data = rep["data"]
    reqs = rep["requests"]
    out.write("== SERVE REPORT %s ==\n" % data["run_dir"])
    out.write("  %d trace(s), %d journal line(s), %d replica stream "
              "process(es)\n"
              % (len(reqs), len(data["journal"]),
                 len(data["counters"])))
    for note in data["notes"]:
        out.write("  %s\n" % note)
    if data["req_dropped"]:
        out.write("  WARNING: %d request event(s) evicted before any "
                  "stream line carried them — lifecycles may have "
                  "gaps\n" % data["req_dropped"])
    lc = rep["lifecycle"]
    if lc["ok"]:
        out.write("  lifecycle laws: every trace closed with exactly "
                  "one final verdict\n")
    else:
        for v in lc["violations"]:
            out.write("  LIFECYCLE VIOLATION: %s\n" % v)
        for tr in lc["open_traces"]:
            out.write("  OPEN TRACE (no final verdict): %s\n" % tr)

    out.write("\n-- per-replica request matrix --\n")
    # per-replica dispatch accounting from the status snapshots: the
    # tokens-per-dispatch column (ISSUE 16) reads 1.00 on a
    # non-speculative replica and > 1 where accepted drafts multiplied
    # what each donated decode dispatch committed
    snaps = {}
    for snap in data["status"].values():
        if snap.get("replica"):
            snaps[snap["replica"]] = snap
    rows = []
    for tag in sorted(rep["matrix"]):
        m = rep["matrix"][tag]
        snap = snaps.get(tag) or {}
        steps = snap.get("decode_steps") or 0
        pre = snap.get("prefills") or 0
        tpd = ("%.2f" % ((m["tokens"] - pre) / steps)) if steps else "-"
        kv_bpt = snap.get("kv_bytes_per_token")
        rows.append((tag, m["admits"], m["tokens"], tpd,
                     snap.get("kv_dtype") or "-",
                     "%.0f" % kv_bpt if kv_bpt is not None else "-",
                     m["retries_out"],
                     "  ".join("%s=%d" % kv
                               for kv in sorted(m["verdicts"].items()))
                     or "-"))
    _tr._table(("replica", "admits", "tokens", "tok/disp", "kv",
                "kvB/tok", "lost", "verdicts"), rows, out)

    out.write("\n-- latency by verdict class --\n")
    rows = []
    for v in sorted(rep["latency"]):
        g = rep["latency"][v]
        rows.append((v, g["n"], _tr._fmt_s(g["ttft_p50"]),
                     _tr._fmt_s(g["ttft_p99"]),
                     _tr._fmt_s(g["tpot_p50"]),
                     _tr._fmt_s(g["queue_p50"]),
                     _tr._fmt_s(g["queue_p99"])))
    _tr._table(("verdict", "n", "ttft_p50", "ttft_p99", "tpot_p50",
                "queue_p50", "queue_p99"), rows, out)

    st = rep.get("stream") or {}
    if (st.get("streamed") or {}).get("n"):
        out.write("\n-- TTFT: streamed vs unary (ISSUE 19) --\n")
        s, u = st["streamed"], st["unary"]
        rows = [("streamed", s["n"], _tr._fmt_s(s["ttft_p50"]),
                 _tr._fmt_s(s["ttft_p99"]), "-", "-"),
                ("unary", u["n"], _tr._fmt_s(u["ttft_p50"]),
                 _tr._fmt_s(u["ttft_p99"]),
                 _tr._fmt_s(u["completion_p50"]),
                 _tr._fmt_s(u["completion_p99"]))]
        _tr._table(("class", "n", "ttft_p50", "ttft_p99",
                    "compl_p50", "compl_p99"), rows, out)
        out.write("  (streamed TTFT = submit -> first poll that "
                  "delivered a token; a unary reply only lands with "
                  "its verdict, so its first-token latency is its "
                  "completion latency)\n")

    if rep["prefix"]:
        out.write("\n-- latency by prefix class (ISSUE 15) --\n")
        rows = []
        for cls in sorted(rep["prefix"]):
            g = rep["prefix"][cls]
            rows.append((cls, g["n"], g["sampled"],
                         "%.1f" % g["mean_prefix_len"],
                         _tr._fmt_s(g["ttft_p50"]),
                         _tr._fmt_s(g["ttft_p99"]),
                         _tr._fmt_s(g["queue_p50"]),
                         _tr._fmt_s(g["queue_p99"])))
        _tr._table(("prefix", "n", "sampled", "avg_len", "ttft_p50",
                    "ttft_p99", "queue_p50", "queue_p99"), rows, out)
        c = {}
        for cc in data["counters"].values():
            for key in ("serving.prefix.hits", "serving.prefix.miss",
                        "serving.prefix.shared_pages",
                        "serving.prefix.cow_copies",
                        "serving.prefix.evictions",
                        "serving.prefill_tokens",
                        "serving.sampling.requests"):
                if cc.get(key):
                    c[key] = c.get(key, 0) + cc[key]
        if c:
            out.write("  " + "  ".join(
                "%s=%d" % kv for kv in sorted(c.items())) + "\n")

    if rep["liveness"]:
        out.write("\n-- per-replica liveness lane (ISSUE 17) --\n")
        rows = []
        for tag in sorted(rep["liveness"]):
            ln = rep["liveness"][tag]
            conf = ln["confirmed"]
            spans = len(ln["spans"]) + (
                1 if ln["open_suspect_t"] is not None else 0)
            rows.append((tag, ln["suspicions"], spans,
                         _tr._fmt_s(ln["max_gap_s"]),
                         conf["reason"] if conf else "-",
                         ln["fenced"], ln["fenced_tokens"]))
        _tr._table(("replica", "suspicions", "spans", "max_hb_gap",
                    "confirmed", "fenced", "fenced_tok"), rows, out)

    if rep.get("alerts"):
        out.write("\n-- fired alerts (ISSUE 18) --\n")
        t0 = min((a["t"] for a in rep["alerts"]
                  if a["t"] is not None), default=None)
        rows = []
        for a in rep["alerts"]:
            rows.append((
                _tr._fmt_s(a["t"] - t0) if a["t"] is not None
                and t0 is not None else "-",
                a["severity"] or "-", a["rule"] or "?",
                a["metric"] or "-",
                a["value"] if a["value"] is not None else "-",
                a["pid"] if a["pid"] is not None else "-"))
        _tr._table(("t+", "severity", "rule", "metric", "value",
                    "pid"), rows, out)

    if rep["arcs"]:
        out.write("\n-- failover arcs (linked by trace id) --\n")
        for a in rep["arcs"]:
            reason = ", ".join(x for x in a.get("reasons") or [] if x)
            out.write("  req %s [%s]: %s -> %s (%s, failover cost %s"
                      "%s)\n"
                      % (a["rid"] if a["rid"] is not None
                         else a["trace"],
                         a["trace"], " + ".join(a["victims"]),
                         a["survivor"], a["verdict"],
                         _tr._fmt_s(a["failover_s"]),
                         ", confirmed %s" % reason if reason else ""))

    if rep["blame"]:
        out.write("\n-- SLO breach blame --\n")
        for b in rep["blame"]:
            p = b["phases"] or {}
            out.write("  req %s (%s): %s — dominant %s; %s\n"
                      % (b["rid"] if b["rid"] is not None
                         else b["trace"], b["breach"],
                         "  ".join("%s %s" % (k[:-2],
                                              _tr._fmt_s(p.get(k)))
                                   for k in ("queue_s", "prefill_s",
                                             "decode_s", "swap_s",
                                             "failover_s",
                                             "delivery_s")
                                   if p.get(k)),
                         b["dominant"], b["why"]))
        blamed = {}
        for b in rep["blame"]:
            if b["replica"] is not None:
                blamed[b["replica"]] = blamed.get(b["replica"], 0) + 1
        if blamed:
            out.write("  blame by replica: " + "  ".join(
                "%s=%d" % kv for kv in sorted(blamed.items())) + "\n")
    else:
        out.write("\n  no SLO breaches: every request completed "
                  "without failover\n")

    acc = rep["accounting"]
    out.write("\n-- goodput --\n")
    out.write("  tokens=%d goodput=%d (%.1f%%)  traced=%d (%s)\n"
              % (acc["tokens"], acc["goodput"],
                 100.0 * (acc["goodput_fraction"] or 0.0),
                 acc["traced_tokens"],
                 "bit-exact" if acc["tokens_match"]
                 else "MISMATCH vs serving.tokens"))
    if acc.get("kv_scale_repairs"):
        out.write("  kv quantization: %d scale-poison repair(s) — "
                  "victims re-prefilled on the finite guard (ISSUE "
                  "20)\n" % acc["kv_scale_repairs"])
    if acc.get("spec"):
        sp = acc["spec"]
        out.write("  spec decode: drafted=%d accepted=%d rejected=%d "
                  "rollbacks=%d  acceptance=%.1f%%  "
                  "tokens/dispatch=%s\n"
                  % (sp["draft_tokens"], sp["accepted"],
                     sp["rejected"], sp["rollbacks"],
                     100.0 * (acc["acceptance_rate"] or 0.0),
                     "%.2f" % acc["tokens_per_dispatch"]
                     if acc["tokens_per_dispatch"] is not None
                     else "-"))


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Merge a serving fleet's artifacts (router journal "
        "+ replica streams + postmortems) into one report: request "
        "lifecycles, failover arcs, SLO breach blame, goodput, "
        "merged chrome trace")
    ap.add_argument("run_dir", help="run dir holding the telemetry "
                    "tree (stream-slot*.jsonl, router-journal*.jsonl, "
                    "postmortems)")
    ap.add_argument("--slo-ttft", type=float, default=None,
                    help="also blame COMPLETED requests whose TTFT "
                    "exceeded this many seconds")
    ap.add_argument("--trace-out", default=None,
                    help="write the merged fleet chrome trace "
                    "(Perfetto-loadable) to this path")
    args = ap.parse_args(argv)
    if not os.path.isdir(args.run_dir):
        sys.stderr.write("serve_report.py: %s is not a run dir\n"
                         % args.run_dir)
        return 2
    rep = analyze(args.run_dir, slo_ttft=args.slo_ttft)
    if not rep["requests"]:
        sys.stderr.write("serve_report.py: no request traces under %s "
                         "(serve with telemetry enabled? "
                         "MXTPU_TELEMETRY / --telemetry-dir)\n"
                         % args.run_dir)
        return 1
    render(rep)
    if args.trace_out:
        doc, t0 = merged_trace(rep["data"], rep["requests"])
        with open(args.trace_out, "w") as f:
            json.dump(doc, f)
        spans = sum(1 for e in doc["traceEvents"] if e["ph"] == "X")
        sys.stdout.write("\n  merged trace: %s (%d span(s) across %d "
                         "replica track(s), t0=%.3f)\n"
                         % (args.trace_out, spans, sum(
                             1 for e in doc["traceEvents"]
                             if e["ph"] == "M"
                             and str(e["args"].get("name", ""))
                             .startswith("replica")), t0))
    return 0


if __name__ == "__main__":
    sys.exit(main())
