"""Render telemetry artifacts for humans.

Each positional argument is a file OR a run directory.  Files are
sniffed per artifact type:

- a JSON-lines timeline written by the periodic emitter
  (``MXTPU_TELEMETRY=path[:interval]``) — one ``report()`` object per
  line (schema ``mxtpu-telemetry-2``; ``-1`` lines from older runs still
  render); the summary covers the LAST line (cumulative totals) and
  notes the line count / wall span, or
- a crash postmortem (schema ``mxtpu-postmortem-2`` / ``-1``) dumped by
  the flight recorder into ``MXTPU_POSTMORTEM_DIR`` — rendered as the
  crash reason, step_stats, fault firings, and the last-K per-step
  table, or
- an elastic membership journal (schema ``mxtpu-membership-1``) written
  by ``tools/launch.py`` into ``<run-dir>/membership.json`` — rendered
  as the world-size transition timeline (attempt starts, failures with
  blamed slot/exit, evictions, re-admissions), or
- a Router audit journal (``router-journal*.jsonl``, schema-less JSON
  lines keyed by request id) — rendered as event/verdict counts and
  failover arcs.  Serving replicas' streams additionally render a
  "serving plane" digest (periodic status line: occupancy, pages, SLO
  state, weights epoch); ``serve_report.py`` merges the fleet.

A **run directory** (``tools/launch.py --run-dir``) renders everything
it holds together — the membership journal, every rank's stream, every
postmortem, and a stall-stacks inventory — so one command digests a
whole job.  ``job_report.py`` (same directory) goes further: it MERGES
the rank streams into one job timeline with straggler blame and a
cross-rank chrome trace; this tool renders each artifact faithfully,
one at a time.

Usage:
    python tools/perf_probe/telemetry_report.py RUN_DIR_OR_FILE ...

See OBSERVABILITY.md for the metric-name and schema contract.
"""
import json
import os
import sys


def _fmt_s(v):
    if v is None:
        return "-"
    if v >= 1.0:
        return "%.2fs" % v
    if v >= 1e-3:
        return "%.2fms" % (v * 1e3)
    return "%.1fus" % (v * 1e6)


def _fmt_n(v):
    return "-" if v is None else ("%.0f" % v)


def _hist_rows(hists):
    rows = []
    for name, h in sorted(hists.items(), key=lambda kv: -kv[1]["sum"]):
        if not h["count"]:
            continue
        # size histograms (ckpt.write_bytes...) render as plain numbers,
        # duration histograms as scaled seconds
        fmt = _fmt_n if "bytes" in name else _fmt_s
        rows.append((name, h["count"], fmt(h["sum"] / h["count"]),
                     fmt(h["p50"]), fmt(h["p90"]), fmt(h["p99"]),
                     fmt(h["max"]), fmt(h["sum"])))
    return rows


def _table(header, rows, out):
    if not rows:
        return
    widths = [max(len(str(r[i])) for r in [header] + rows)
              for i in range(len(header))]
    for r in [header] + rows:
        out.write("  " + "  ".join(
            str(c).ljust(w) for c, w in zip(r, widths)).rstrip() + "\n")


def _identity_line(doc):
    """`` [rank 1/3 slot 2 attempt 0]`` from a schema-2 identity block
    (empty for schema-1 artifacts / standalone runs)."""
    ident = doc.get("identity") or {}
    if ident.get("rank") is None:
        return ""
    if (ident.get("world_size") or 1) <= 1 and not ident.get("attempt"):
        return ""  # standalone process: no job context to show
    return " [rank %s/%s slot %s attempt %s]" % (
        ident.get("rank"), ident.get("world_size"), ident.get("slot"),
        ident.get("attempt"))


def render_report(doc, out, context=""):
    """Phase-time breakdown + histogram percentiles of one report()."""
    out.write("== telemetry report%s%s ==\n"
              % (_identity_line(doc), context))
    ss = doc.get("step_stats") or {}
    out.write("  steps %s  dispatches %s  compiles %s  skipped %s  "
              "step_ema %s\n" % (
                  ss.get("steps"), ss.get("dispatch_count"),
                  ss.get("compile_count"), ss.get("skipped_steps"),
                  _fmt_s(ss.get("step_time_ema_s"))))
    phases = doc.get("phases") or {}
    total = sum(h["sum"] for h in phases.values())
    # NB: nested spans (ckpt.write encloses ckpt.fsync/rename, etc.)
    # overlap, so the sum exceeds wall time and shares are of the SUM of
    # span time, not of the run
    out.write("\n  phase-time breakdown (summed span time %s; nested "
              "spans overlap):\n" % _fmt_s(total))
    rows = []
    for (name, count, mean, p50, p90, p99, mx, tot) in \
            _hist_rows(phases):
        share = phases[name]["sum"] / total * 100 if total else 0.0
        rows.append((name, count, mean, p50, p99, tot,
                     "%.1f%%" % share))
    _table(("phase", "count", "mean", "p50", "p99", "total", "of-sum"),
           rows, out)
    hists = doc.get("histograms") or {}
    if any(h["count"] for h in hists.values()):
        out.write("\n  histograms:\n")
        _table(("name", "count", "mean", "p50", "p90", "p99", "max",
                "sum"), _hist_rows(hists), out)
    counters = {k: v for k, v in (doc.get("counters") or {}).items() if v}
    if counters:
        out.write("\n  counters: " + "  ".join(
            "%s=%s" % kv for kv in sorted(counters.items())) + "\n")
    gauges = {k: v for k, v in (doc.get("gauges") or {}).items()
              if v is not None}
    if gauges:
        out.write("  gauges: " + "  ".join(
            "%s=%s" % kv for kv in sorted(gauges.items())) + "\n")
    _render_ckpt_pipeline(doc, out)
    _render_io_pipeline(doc, out)
    _render_serving_plane(doc, out)


# phases the step loop actually blocks on under async checkpointing vs
# the work the writer thread absorbs — the split telemetry_report exists
# to make visible (PERF.md §6)
_CKPT_HOT = ("ckpt.save", "ckpt.snapshot", "ckpt.async_wait")
_CKPT_BG = ("ckpt.async_write", "ckpt.write", "ckpt.fsync", "ckpt.rename")


def _render_ckpt_pipeline(doc, out):
    """Checkpoint-pipeline digest: queue depth, save counts, and the
    step-visible stall (hot-path spans) vs background write time.  Note
    ``ckpt.save`` encloses snapshot+enqueue under async but the whole
    write under sync — the per-span rows tell the two apart."""
    c = doc.get("counters") or {}
    phases = doc.get("phases") or {}
    saves = c.get("ckpt.saves", 0)
    if not saves and not any(
            (phases.get(k) or {}).get("count") for k in _CKPT_HOT):
        return
    g = doc.get("gauges") or {}
    out.write("\n  checkpoint pipeline: saves=%d async=%d errors=%d "
              "io_retries=%d queue_depth=%s\n"
              % (saves, c.get("ckpt.async_saves", 0),
                 c.get("ckpt.async_errors", 0),
                 c.get("ckpt.io_retries", 0),
                 g.get("ckpt.queue_depth", "-")))
    rows = []
    for group, names in (("step-visible", _CKPT_HOT),
                         ("background", _CKPT_BG)):
        for name in names:
            h = phases.get(name)
            if not h or not h["count"]:
                continue
            rows.append((name, group, h["count"],
                         _fmt_s(h["sum"] / h["count"]), _fmt_s(h["p50"]),
                         _fmt_s(h["p99"]), _fmt_s(h["max"])))
    _table(("span", "where", "count", "mean", "p50", "p99", "max"),
           rows, out)


# the streaming input plane's phase names (mxnet_tpu/stream/,
# OBSERVABILITY.md §11): worker-side decode/open phases folded consumer-
# side, plus the two starvation signals a training rank actually blocks
# on — io.queue_wait (consumer starved on the decode result queue) and
# data.prefetch_wait (consumer starved on the device prefetcher)
_IO_PHASES = ("io.queue_wait", "io.decode", "io.shard_open",
              "data.prefetch_wait")


def _render_io_pipeline(doc, out):
    """Streaming-input digest: record/byte/torn counters, open-shard
    gauge, and the io.* phase table — so "is the input plane keeping
    up, and what is it costing" reads off one report the way the
    checkpoint pipeline does."""
    c = doc.get("counters") or {}
    phases = doc.get("phases") or {}
    records = c.get("io.records", 0)
    if not records and not any(
            (phases.get(k) or {}).get("count") for k in _IO_PHASES[:3]):
        return
    g = doc.get("gauges") or {}
    out.write("\n  stream input plane: records=%d bytes=%d torn=%d "
              "batches=%d shards_open=%s\n"
              % (records, c.get("io.bytes", 0),
                 c.get("io.torn_records", 0), c.get("data.batches", 0),
                 g.get("io.shards_open", "-")))
    rows = []
    for name in _IO_PHASES:
        h = phases.get(name)
        if not h or not h["count"]:
            continue
        rows.append((name, h["count"], _fmt_s(h["sum"] / h["count"]),
                     _fmt_s(h["p50"]), _fmt_s(h["p99"]),
                     _fmt_s(h["max"]), _fmt_s(h["sum"])))
    _table(("span", "count", "mean", "p50", "p99", "max", "total"),
           rows, out)


def _render_serving_plane(doc, out):
    """Serving-scope digest (OBSERVABILITY.md §12): the request/token/
    goodput counters and — when the line carries the periodic serving
    status block — one row per live engine (occupancy, pages, SLO
    controller state, weights epoch).  ``serve_report.py`` (same
    directory) merges the whole fleet; this renders one process's
    view faithfully."""
    c = doc.get("counters") or {}
    serving = doc.get("serving") or []
    requests = c.get("serving.requests", 0)
    if not requests and not serving and not c.get("router.requests"):
        return
    tokens = c.get("serving.tokens", 0)
    goodput = c.get("serving.goodput", 0)
    out.write("\n  serving plane: requests=%d tokens=%d goodput=%d "
              "(%.1f%%) shed=%d expired=%d+%d swaps=%d rollbacks=%d "
              "trace_dropped=%d\n"
              % (requests, tokens, goodput,
                 100.0 * goodput / tokens if tokens else 100.0,
                 c.get("serving.shed", 0),
                 c.get("serving.expired_queue", 0),
                 c.get("serving.expired_decode", 0),
                 c.get("serving.swaps", 0),
                 c.get("serving.swap_rollbacks", 0),
                 c.get("serving.trace_dropped", 0)))
    rows = []
    for s in serving:
        slo = s.get("slo") or {}
        rows.append((s.get("replica"), "%s/%s" % (s.get("occupancy"),
                                                  s.get("num_slots")),
                     s.get("queued"),
                     "%s/%s" % (s.get("free_pages"), s.get("num_pages")),
                     s.get("decode_steps"),
                     "drain" if s.get("draining") else
                     ("shed" if s.get("shedding") else "ok"),
                     ("-" if slo.get("windowed_p99_s") is None
                      else _fmt_s(slo.get("windowed_p99_s"))),
                     s.get("weights_epoch")
                     if s.get("weights_epoch") is not None else "-"))
    if rows:
        _table(("engine", "occ", "queued", "pages_free", "steps",
                "state", "slo_p99", "epoch"), rows, out)


def render_router_journal(docs, out, path=""):
    """Summarize a Router audit journal (one JSON line per lifecycle
    transition): event counts, failover arcs, terminal verdicts — the
    faithful single-artifact view; ``serve_report.py`` joins it with
    the replica streams for blame."""
    events = {}
    verdicts = {}
    retries = [d for d in docs if d.get("event") == "retry"]
    for d in docs:
        events[d.get("event", "?")] = events.get(d.get("event", "?"),
                                                 0) + 1
        if d.get("event") in ("complete", "fail", "refuse", "drop",
                              "reject") and d.get("verdict"):
            verdicts[d["verdict"]] = verdicts.get(d["verdict"], 0) + 1
    out.write("== ROUTER JOURNAL%s: %d line(s), %d request(s) ==\n"
              % ((" " + path) if path else "", len(docs),
                 len({d.get("rid") for d in docs})))
    out.write("  events: " + "  ".join(
        "%s=%d" % kv for kv in sorted(events.items())) + "\n")
    if verdicts:
        out.write("  terminal verdicts: " + "  ".join(
            "%s=%d" % kv for kv in sorted(verdicts.items())) + "\n")
    for d in retries:
        out.write("  failover: rid %s trace %s off replica %s "
                  "(retry %s)\n"
                  % (d.get("rid"), d.get("trace"), d.get("from_replica"),
                     d.get("retries")))


def render_membership(doc, out):
    """The elastic membership journal as a timeline: one row per
    transition, so "what did the job's world look like over time" reads
    straight down (the launcher-side sibling of the in-worker
    ``elastic.*`` metrics)."""
    trans = doc.get("transitions") or []
    n_evict = sum(1 for t in trans if t.get("event") == "evict")
    n_readmit = sum(1 for t in trans if t.get("event") == "readmit")
    out.write("== MEMBERSHIP: %d slot(s), %d transition(s), %d "
              "eviction(s), %d re-admission(s) ==\n"
              % (doc.get("total_slots", 0), len(trans), n_evict,
                 n_readmit))
    t0 = trans[0].get("time", 0) if trans else 0
    rows = []
    for t in trans:
        event = t.get("event", "?")
        detail = ""
        if event == "failure":
            detail = "slot %s rank %s rc=%s %s" % (
                t.get("slot"), t.get("rank"), t.get("rc"),
                t.get("kind", ""))
        elif event in ("evict", "readmit"):
            detail = "slot %s%s" % (
                t.get("slot"),
                (": " + t["reason"]) if t.get("reason") else "")
        elif event == "attempt_start":
            detail = "port %s" % t.get("port")
        rows.append(("+" + _fmt_s(t.get("time", 0) - t0),
                     t.get("attempt"), event, t.get("world_size"),
                     ",".join(str(s) for s in
                              t.get("active_slots", [])) or "-",
                     ",".join(str(s) for s in
                              t.get("evicted_slots", [])) or "-",
                     detail))
    _table(("when", "attempt", "event", "world", "active", "evicted",
            "detail"), rows, out)


def render_postmortem(doc, out):
    """Pretty-print a flight-recorder crash postmortem."""
    out.write("== POSTMORTEM (pid %s)%s ==\n"
              % (doc.get("pid"), _identity_line(doc)))
    out.write("  reason: %s\n" % doc.get("reason"))
    mem = doc.get("membership") or {}
    if mem.get("coordinator") or (mem.get("world_size") or 1) > 1 or \
            mem.get("transitions"):
        out.write("  membership: world_size=%s rank=%s slot=%s "
                  "attempt=%s transitions=%s\n"
                  % (mem.get("world_size"), mem.get("rank"),
                     mem.get("slot"), mem.get("attempt"),
                     mem.get("transitions")))
    ss = doc.get("step_stats") or {}
    out.write("  step_stats: %s\n" % json.dumps(ss))
    wd = doc.get("watchdog") or {}
    if wd.get("leases") or str(doc.get("reason", "")).startswith("stall"):
        prog = wd.get("progress") or {}
        out.write("  watchdog: armed=%s timeout=%ss grace=%ss "
                  "last-progress step=%s phase=%s\n"
                  % (wd.get("armed"), wd.get("timeout"), wd.get("grace"),
                     prog.get("step"), prog.get("phase")))
        rows = [(name, _fmt_s(lease.get("age_s")),
                 _fmt_s(lease.get("timeout_s")), lease.get("step"))
                for name, lease in sorted((wd.get("leases") or {}).items())]
        _table(("lease", "age", "timeout", "step"), rows, out)
    fires = doc.get("fault_fires") or {}
    if fires:
        out.write("  fault firings: " + "  ".join(
            "%s x%d" % kv for kv in sorted(fires.items())) + "\n")
    steps = doc.get("last_steps") or []
    out.write("\n  last %d step records (flight recorder, ring %s):\n"
              % (len(steps), (doc.get("flight") or {}).get("maxlen")))
    rows = []
    for r in steps[-20:]:
        rows.append((r["step"],
                     _fmt_s(r["dispatch_s"]), _fmt_s(r["sync_s"]),
                     r["dispatch_delta"], r["compile_delta"],
                     "SKIP" if r["skipped"] else
                     ("?" if r["skipped"] is None else "ok"),
                     "-" if r["loss"] is None else "%.4g" % r["loss"],
                     ",".join(r["faults"]) or "-"))
    _table(("step", "dispatch", "sync", "disp+", "comp+", "guard",
            "loss", "faults"), rows, out)
    if len(steps) > 20:
        out.write("  (%d older records omitted)\n" % (len(steps) - 20))
    render_report(doc, out, context=" (at crash)")


def parse_artifact(path, notes=None):
    """Parse one telemetry artifact file → list of JSON docs (one for a
    postmortem/journal, one per line for an emitter stream).  Torn lines
    (a process killed mid-append — the exact crash this tooling serves)
    are skipped and counted into ``notes`` (a list of strings)."""
    with open(path) as f:
        text = f.read()
    if not text.strip():
        return []
    try:
        # a postmortem is one (indented, multi-line) JSON document
        return [json.loads(text)]
    except ValueError:
        docs, skipped = [], 0
        for ln in text.splitlines():
            if not ln.strip():
                continue
            try:
                docs.append(json.loads(ln))
            except ValueError:
                skipped += 1
        if skipped and notes is not None:
            notes.append("(%d unparseable line(s) skipped in %s — torn "
                         "mid-append write)" % (skipped, path))
        return docs


def render_file(path, out=sys.stdout):
    notes = []
    docs = parse_artifact(path, notes)
    for note in notes:
        out.write("  %s\n" % note)
    if not docs:
        out.write("%s: %s\n" % (path, "empty" if not notes
                                else "no parseable JSON"))
        return
    last = docs[-1]
    schema = str(last.get("schema") or "")
    if schema.startswith("mxtpu-postmortem-"):
        render_postmortem(last, out)
        return
    if schema.startswith("mxtpu-membership-"):
        render_membership(last, out)
        return
    if not schema and "rid" in last and "event" in last:
        # a Router audit journal: schema-less JSON lines keyed by
        # request id + lifecycle event
        render_router_journal(docs, out)
        return
    ctx = ""
    if len(docs) > 1:
        span = last.get("time_unix", 0) - docs[0].get("time_unix", 0)
        ctx = " (%d samples over %s)" % (len(docs), _fmt_s(span))
    _render_watchdog_timeline(docs, out)
    _render_alert_timeline(docs, out)
    render_report(last, out, context=ctx)


def discover_run_dir(run_dir):
    """Inventory a launch.py run dir: the membership journal, every
    per-slot stream, every router journal (the serving fleet's audit
    record — ``router-journal*.jsonl``, the ``MXTPU_SERVE_JOURNAL``
    layout), every postmortem, every stall-stacks dump — looking both at
    the top level and under ``telemetry/`` (the launcher's default
    tree).  Returns ``{"membership": path|None, "streams": [...],
    "router_journals": [...], "postmortems": [...],
    "stall_stacks": [...]}`` with sorted lists.  Shared with
    job_report.py and serve_report.py (their input contract)."""
    roots = [run_dir, os.path.join(run_dir, "telemetry")]
    found = {"membership": None, "streams": [], "router_journals": [],
             "postmortems": [], "stall_stacks": []}
    for root in roots:
        try:
            names = sorted(os.listdir(root))
        except OSError:
            continue
        for name in names:
            path = os.path.join(root, name)
            if not os.path.isfile(path):
                continue
            if name == "membership.json":
                found["membership"] = found["membership"] or path
            elif name.startswith("router-journal") and \
                    name.endswith(".jsonl"):
                found["router_journals"].append(path)
            elif name.endswith(".jsonl"):
                found["streams"].append(path)
            elif name.startswith("postmortem-") and \
                    name.endswith(".json"):
                found["postmortems"].append(path)
            elif name.startswith("stall-stacks-"):
                found["stall_stacks"].append(path)
    return found


def render_run_dir(run_dir, out=sys.stdout):
    """Render every artifact of one run dir, membership journal first
    (the job's shape over time), then each rank stream, then each
    postmortem, with a stall-stacks inventory line at the end."""
    found = discover_run_dir(run_dir)
    if not (found["membership"] or found["streams"]
            or found["router_journals"] or found["postmortems"]):
        out.write("%s: no telemetry artifacts (membership.json, "
                  "*.jsonl, postmortem-*.json)\n" % run_dir)
        return
    out.write("== RUN DIR %s ==\n" % run_dir)
    first = True
    for path in ([found["membership"]] if found["membership"] else []) \
            + found["streams"] + found["router_journals"] \
            + found["postmortems"]:
        if not first:
            out.write("\n")
        first = False
        out.write("-- %s --\n" % os.path.relpath(path, run_dir))
        render_file(path, out)
    if found["stall_stacks"]:
        out.write("\n  stall-stacks dumps: %s\n" % ", ".join(
            os.path.relpath(p, run_dir) for p in found["stall_stacks"]))
    if found["router_journals"]:
        out.write("\n  serving artifacts present: serve_report.py "
                  "(same directory) merges the router journal with the "
                  "replica streams into the fleet view (request "
                  "lifecycles, failover arcs, SLO breach blame)\n")


def _render_watchdog_timeline(docs, out):
    """Call out hang-defense events across an emitter timeline: the
    samples where ``watchdog.stalls`` incremented (with the worst lease
    age the sample carried), so a soak run's stalls are visible without
    diffing counters by hand."""
    t0 = docs[0].get("time_unix", 0)
    prev = 0
    events = []
    for doc in docs:
        v = (doc.get("counters") or {}).get("watchdog.stalls", 0) or 0
        if v > prev:
            events.append((doc.get("time_unix", 0) - t0, v - prev,
                           (doc.get("gauges") or {})
                           .get("watchdog.lease_age")))
        prev = v
    if not events:
        return
    out.write("== WATCHDOG: %d stall(s) in this timeline ==\n"
              % sum(n for _, n, _ in events))
    for t, n, age in events:
        out.write("  +%s: %d stall(s) detected (lease_age %s)\n"
                  % (_fmt_s(t), n, _fmt_s(age) if age is not None
                     else "-"))


def _render_alert_timeline(docs, out):
    """Call out the alert-rule firings (ISSUE 18) riding a stream as
    trace-less ``alert`` request events, so a timeline's rule verdicts
    (breaker opened, watchdog stalled, goodput collapsed, ...) read at
    the top without grepping req_events by hand."""
    t0 = docs[0].get("time_unix", 0)
    fired = []
    for doc in docs:
        for e in doc.get("req_events") or []:
            if e.get("event") == "alert":
                fired.append((e.get("t", 0) - t0, e.get("args") or {}))
    if not fired:
        return
    out.write("== ALERTS: %d rule firing(s) in this timeline ==\n"
              % len(fired))
    for t, a in sorted(fired):
        out.write("  +%s: [%s] %s (%s=%s)\n"
                  % (_fmt_s(max(0.0, t)), a.get("severity", "?"),
                     a.get("rule", "?"), a.get("metric", "?"),
                     a.get("value", "-")))


def main(argv):
    if not argv:
        sys.stderr.write(__doc__)
        return 2
    for i, path in enumerate(argv):
        if i:
            sys.stdout.write("\n")
        if os.path.isdir(path):
            render_run_dir(path)
        else:
            render_file(path)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
