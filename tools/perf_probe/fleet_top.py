#!/usr/bin/env python
"""Live fleet matrix over the RPC telemetry plane (ISSUE 18).

``serve_report`` answers fleet questions post-hoc from the run-dir
tree; THIS tool asks a *running* fleet directly — one ``heartbeat`` +
one ``telemetry_pull`` per replica per refresh, no shared filesystem,
no run-dir reads beyond bootstrap port-file discovery.  Per replica it
renders what an operator triaging "slot 2 is suspected" needs in one
row (SERVING.md §9):

- engine state: occupancy / decode slots, queue depth, free KV pages,
  shed + drain + SLO state, installed weights epoch, decode steps;
- efficiency: prefix-cache hit rate, speculative acceptance rate, and
  goodput tok/s (counter deltas between refreshes — the first
  snapshot shows cumulative totals);
- delivery (ISSUE 19): live streams (``strm``), waiting pollers
  (``wait``), and reclaimed-orphan count (``orph``) from the engine
  snapshot's stream block — a rising ``orph`` says clients are
  vanishing mid-stream (the ``orphan_reclaim`` alert fires on the
  same counter);
- liveness: heartbeat round-trip + incarnation stamp, and — when run
  inside the router process via :func:`collect_matrix` — the local
  suspicion / breaker / fence gauges the proxies maintain (a
  standalone fleet_top has no proxy state and prints ``-``);
- the newest ``alert`` events the replica's rules fired, straight off
  the pulled stream.

Modes: ``--once`` prints one matrix and exits (``--json`` emits the
raw rows — the drill/cron contract; tests/serve_fleet_driver.py reads
``collect_matrix`` over a live fleet);
default is a watch loop every ``--interval`` seconds.  Cursors are
held client-side, so watching costs each worker only its newly-drained
events per refresh and never steals from the supervisor's collector.

Usage:

    python tools/perf_probe/fleet_top.py --run-dir /run/fleet --once
    python tools/perf_probe/fleet_top.py --addr 10.0.0.2:7001 \
        --addr 10.0.0.3:7001 --interval 2
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", ".."))

from mxnet_tpu import telemetry as _telemetry           # noqa: E402
from mxnet_tpu.serving import rpc as _rpc               # noqa: E402

#: how many of a replica's newest alert events ride each row
ALERT_TAIL = 4


def discover_targets(run_dir):
    """``[(name, addr), ...]`` from a ``launch.py --serve`` run dir's
    port files (bootstrap discovery only — everything after this rides
    the RPC plane)."""
    out = []
    for path in sorted(glob.glob(
            os.path.join(run_dir, "serve-port-slot*.json"))):
        m = re.search(r"slot(\d+)\.json$", path)
        name = "slot%s" % (m.group(1) if m else "?")
        try:
            doc = _rpc.read_port_file(path)
            out.append((name,
                        (doc.get("host", "127.0.0.1"),
                         int(doc["port"]))))
        except (OSError, ValueError, KeyError, TypeError):
            out.append((name, None))  # not up yet: rendered as down
    return out


def _rate(num, den):
    return (num / den) if den else None


def _local_liveness(name):
    """Suspicion / breaker / fence state for ``name`` from THIS
    process's registry — meaningful only where the router's proxies
    live.  ``None`` fields mean 'no local evidence', rendered ``-``."""
    suspect = _telemetry.gauge("rpc.suspect.%s" % name).value
    breaker = _telemetry.gauge("rpc.breaker.%s" % name).value
    breaker_s = {0: "closed", 1: "half-open", 2: "open"}.get(breaker)
    confirms = {}
    for n, v in (_telemetry.report().get("counters") or {}).items():
        if n.startswith("rpc.confirmations.") and v:
            confirms[n.rpartition(".")[2]] = v
    return {"suspect": suspect,
            "breaker": breaker_s,
            "confirmations": confirms or None,
            "fenced_results":
                _telemetry.counter("rpc.fenced_results").value or None}


def collect_row(name, addr, cursor=None, timeout_s=2.0,
                local_liveness=True):
    """One fleet-matrix row: pull + heartbeat one replica.  Returns the
    row dict (``up=False`` rows carry only the error) and the advanced
    pull cursor."""
    if addr is None:
        return {"replica": name, "up": False,
                "error": "no port published"}, cursor
    row = {"replica": name, "up": True,
           "addr": "%s:%s" % (addr[0], addr[1])}
    t0 = time.perf_counter()
    try:
        hb = _rpc.rpc_call(addr, {"method": "heartbeat"}, timeout_s,
                           retries=0)
        row["hb_rtt_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 2)
        row["incarnation"] = hb.get("incarnation")
        row["draining"] = hb.get("draining")
        prog = hb.get("progress") or {}
        row["decode_steps"] = prog.get("decode_steps")
        row["weights_epoch"] = prog.get("weights_epoch")
    except (_rpc.RpcError, OSError) as e:
        row["up"] = False
        row["error"] = "heartbeat: %s" % e
        return row, cursor
    try:
        reply = _rpc.pull_telemetry(addr, cursor=cursor,
                                    timeout_s=timeout_s)
    except (_rpc.RpcError, OSError) as e:
        row["error"] = "telemetry_pull: %s" % e
        return row, cursor
    cursor = reply["cursor"]
    row["cursor_reset"] = bool(reply.get("reset"))
    line = reply.get("line") or {}
    ctr = line.get("counters") or {}
    row["counters"] = ctr
    row["time_unix"] = line.get("time_unix")
    for snap in line.get("serving") or []:
        row["engine"] = {
            "occupancy": snap.get("occupancy"),
            "num_slots": snap.get("num_slots"),
            "queued": snap.get("queued"),
            "free_pages": snap.get("free_pages"),
            "num_pages": snap.get("num_pages"),
            "shedding": snap.get("shedding"),
            "draining": snap.get("draining"),
            "decode_steps": snap.get("decode_steps"),
            "weights_epoch": snap.get("weights_epoch"),
            "slo": snap.get("slo"),
            "stream": snap.get("stream"),
            "kv_dtype": snap.get("kv_dtype"),
            "kv_bytes_per_token": snap.get("kv_bytes_per_token"),
        }
        break  # one engine per worker process in the fleet layout
    row["prefix_hit_rate"] = _rate(
        ctr.get("serving.prefix.hits", 0),
        ctr.get("serving.prefix.hits", 0)
        + ctr.get("serving.prefix.miss", 0))
    row["spec_accept_rate"] = _rate(
        ctr.get("serving.spec.accepted", 0),
        ctr.get("serving.spec.draft_tokens", 0))
    row["tokens"] = ctr.get("serving.tokens", 0)
    row["goodput_tokens"] = ctr.get("serving.goodput", 0)
    row["alerts"] = [e.get("args") or {}
                     for e in line.get("req_events") or []
                     if e.get("event") == "alert"][-ALERT_TAIL:]
    if local_liveness:
        row["liveness"] = _local_liveness(name)
    return row, cursor


def collect_matrix(targets, cursors=None, prev=None, timeout_s=2.0,
                   local_liveness=True):
    """Rows for every ``(name, addr)`` target; ``cursors`` (mutated in
    place when given) holds per-name pull cursors across refreshes, and
    ``prev`` (the previous call's result) turns cumulative token
    counters into tok/s rates.  This is the in-process entry point the
    partition drill and the router host use — the CLI below is a thin
    loop over it."""
    cursors = {} if cursors is None else cursors
    prev_rows = {r["replica"]: r for r in (prev or {}).get("rows", [])}
    rows = []
    for name, addr in targets:
        row, cursors[name] = collect_row(
            name, addr, cursor=cursors.get(name), timeout_s=timeout_s,
            local_liveness=local_liveness)
        p = prev_rows.get(name)
        if p and row.get("up") and p.get("up") and \
                row.get("time_unix") and p.get("time_unix"):
            dt = row["time_unix"] - p["time_unix"]
            if dt > 0:
                row["tok_s"] = round(
                    (row["tokens"] - p.get("tokens", 0)) / dt, 2)
                row["goodput_tok_s"] = round(
                    (row["goodput_tokens"]
                     - p.get("goodput_tokens", 0)) / dt, 2)
        rows.append(row)
    return {"t": time.time(), "rows": rows}


# -- rendering ---------------------------------------------------------------

def _fmt(v, pct=False):
    if v is None:
        return "-"
    if pct:
        return "%d%%" % round(v * 100)
    return str(v)


def render_matrix(matrix, out=sys.stdout):
    cols = ("replica", "state", "occ", "queue", "free_pg", "kv",
            "prefix", "spec", "tok/s", "strm", "wait", "orph", "hb_ms",
            "susp", "breaker", "epoch")
    rows = []
    for r in matrix["rows"]:
        if not r.get("up"):
            rows.append((r["replica"], "DOWN", "-", "-", "-", "-", "-",
                         "-", "-", "-", "-", "-", "-", "-", "-",
                         r.get("error", "")[:24]))
            continue
        eng = r.get("engine") or {}
        state = "shed" if eng.get("shedding") else (
            "drain" if (eng.get("draining") or r.get("draining"))
            else "ok")
        if r.get("cursor_reset"):
            state += "*"   # cursor discontinuity declared this refresh
        live = r.get("liveness") or {}
        occ = "-"
        if eng.get("num_slots"):
            occ = "%s/%s" % (eng.get("occupancy"), eng.get("num_slots"))
        strm = eng.get("stream") or {}
        rows.append((
            r["replica"], state, occ, _fmt(eng.get("queued")),
            _fmt(eng.get("free_pages")),
            _fmt(eng.get("kv_dtype")),
            _fmt(r.get("prefix_hit_rate"), pct=True),
            _fmt(r.get("spec_accept_rate"), pct=True),
            _fmt(r.get("tok_s", r.get("tokens"))),
            _fmt(strm.get("live")), _fmt(strm.get("waiting")),
            _fmt(strm.get("abandoned")),
            _fmt(r.get("hb_rtt_ms")),
            {1: "SUSPECT", 0: "-"}.get(live.get("suspect"), "-"),
            live.get("breaker") or "-",
            _fmt(r.get("weights_epoch"))))
    widths = [max(len(str(c)),
                  max((len(str(row[i])) for row in rows), default=0))
              for i, c in enumerate(cols)]
    line = "  ".join(c.ljust(w) for c, w in zip(cols, widths))
    out.write(line + "\n" + "-" * len(line) + "\n")
    for row in rows:
        out.write("  ".join(str(v).ljust(w)
                            for v, w in zip(row, widths)) + "\n")
    alerts = [(r["replica"], a) for r in matrix["rows"]
              for a in r.get("alerts") or []]
    if alerts:
        out.write("alerts:\n")
        for name, a in alerts:
            out.write("  [%s] %s %s (%s=%s)\n"
                      % (a.get("severity", "?"), name,
                         a.get("rule", "?"), a.get("metric", "?"),
                         a.get("value", "-")))
    out.flush()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("run_dir_pos", nargs="?", default=None,
                    metavar="RUN_DIR",
                    help="launch.py --serve run dir (same as "
                         "--run-dir)")
    ap.add_argument("--run-dir", default=None,
                    help="launch.py --serve run dir (port-file "
                         "discovery)")
    ap.add_argument("--addr", action="append", default=[],
                    help="host:port of a worker (repeatable; "
                         "bypasses --run-dir discovery)")
    ap.add_argument("--once", action="store_true",
                    help="one refresh, then exit")
    ap.add_argument("--json", action="store_true",
                    help="emit raw row dicts instead of the table")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="watch-mode refresh seconds")
    ap.add_argument("--timeout", type=float, default=2.0,
                    help="per-RPC deadline seconds")
    args = ap.parse_args(argv)
    run_dir = args.run_dir or args.run_dir_pos
    targets = []
    for a in args.addr:
        host, _, port = a.rpartition(":")
        targets.append((a, (host or "127.0.0.1", int(port))))
    if run_dir:
        targets.extend(discover_targets(run_dir))
    if not targets:
        ap.error("no targets: pass --run-dir and/or --addr")
    cursors, prev = {}, None
    while True:
        matrix = collect_matrix(targets, cursors=cursors, prev=prev,
                                timeout_s=args.timeout)
        if args.json:
            json.dump(matrix, sys.stdout, default=str)
            sys.stdout.write("\n")
            sys.stdout.flush()
        else:
            if not args.once:
                sys.stdout.write("\x1b[2J\x1b[H")  # clear, home
            render_matrix(matrix)
        if args.once:
            return 0
        prev = matrix
        time.sleep(max(0.1, args.interval))


if __name__ == "__main__":
    sys.exit(main())
