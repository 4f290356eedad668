"""Reader ``device_scopes``: the traced slice's device time by program and
scope.

A program of ``mxnet_tpu`` enters ``telemetry.device_scope(name)`` around
each stretch of a layer (``attn.proj``, ``moe.scatter``, ``kv_write``,
...; OBSERVABILITY.md section 2), which puts the name into the
``op_name`` of every instruction compiled from it.  A device event of the
trace holds its instruction WITHOUT that metadata but starts with the
instruction's own name (``%fusion.1608 = ...``), and
``telemetry.program_scopes()`` gives the scope path of every instruction
name of every live compiled program.  This reader joins the two, over
the same window as the reduction (first to last whole benchmark span) on
the first device:

- a RUN of a program is one event of line "XLA Modules"; an "XLA Ops"
  event belongs to the run that holds its start;
- time goes to the INNERMOST event that covers it (a ``while``'s event
  covers its body's events: they get their own time, the ``while`` keeps
  what none of them covers), so the parts of a run sum to its busy time;
- an event's scope path is looked up by its instruction name in the table
  of its run's module.  Where several live programs share a module name
  (an engine a prefill length), a run's table is the one that holds every
  instruction name of the run; a run that fits none or several counts
  whole as unattributed, and so does an instruction name its table does
  not hold.

args ``{"what": ..., "program": regex on the module name, "scope": regex
on the scope path}`` (``moe/moe.scatter``; outermost first):
- ``ms_per_run``: device time inside the matching scopes per run of the
  matching programs, over the runs that lie WHOLE inside the window, ms;
- ``unattributed_pct``: busy time of events with an empty scope path, of
  runs without a table and outside any run, as a share of the window's
  busy time: what this reading does not cover (takes no ``scope``).
A program without ``telemetry.program_scopes`` (the parent of the PR that
added it), a run without a trace, a slice without a whole benchmark span
or without a whole matching run reads as nothing.

It leaves ``perfbench_out/<cell>/device_scopes.json`` (seconds and runs
by program and scope path, the ten longest unattributed instructions by
their ``trace_reduce.short_name``, what the reading cost) and, beside
it, the table it read by (``program_scopes.json``), so that

    python3 perfbench/readers/device_scopes.py <file.xplane.pb | slice.json.gz> <program_scopes.json>

prints the same breakdown by hand, from any ``jax.profiler`` trace of a
process that dumped its ``telemetry.program_scopes()``.
"""
import bisect
import collections
import functools
import json
import os
import re
import sys
import time

if __name__ == "__main__":        # by hand: perfbench/ is not on the path
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

import common
import trace_reduce
from readers import program_spans

_INSTRUCTION = re.compile(r"^%(\S+) = ")
#: a run nothing is known of: ops outside every "XLA Modules" event
NO_PROGRAM = "(no program)"


def tables_by_module(tables):
    """``{module: [scopes, ...]}`` from ``telemetry.program_scopes()``'s
    list, equal tables once."""
    out = collections.defaultdict(list)
    for t in tables:
        if t["scopes"] not in out[t["module"]]:
            out[t["module"]].append(t["scopes"])
    return out


def _table_of(names, candidates):
    """A run's table: the module's only one, else the one of
    ``candidates`` that holds every name of the run; nothing where none
    or several do."""
    if len(candidates) > 1:
        candidates = [t for t in candidates if all(n in t for n in names)]
    return candidates[0] if len(candidates) == 1 else None


def breakdown(doc, tables):
    """The slice's device time by program and scope path, as the module
    docstring has it; ``None`` without a whole benchmark span or a
    device plane."""
    _, bench = trace_reduce.host_spans(doc)
    planes = sorted(
        (int(trace_reduce.DEVICE_PLANE.match(p["name"]).group(1)), i)
        for i, p in enumerate(doc["planes"])
        if trace_reduce.DEVICE_PLANE.match(p["name"]))
    if not bench or not planes:
        return None
    lo = min(s for _, s, _ in bench)
    hi = max(s + d for _, s, d in bench)
    ops, runs = [], []
    for line in doc["planes"][planes[0][1]]["lines"]:
        if line["name"] == trace_reduce.OPS_LINE:
            ops = trace_reduce._clip(line["events"], lo, hi)
        elif line["name"] == trace_reduce.MODULES_LINE:
            runs = sorted((s, s + d, name.split("(", 1)[0])
                          for name, s, d in line["events"]
                          if s + d > lo and s < hi)
    # the innermost event over every piece of busy time, by its place in
    # ``ops``; then by run: {instruction name: ns}
    pieces = trace_reduce.innermost_timeline(
        [(str(i), a, b - a) for i, (_, a, b) in enumerate(ops)])
    starts = [r[0] for r in runs]
    per_run = [collections.Counter() for _ in runs]
    text_of = {}
    loose = collections.Counter()
    for a, b, i in pieces:
        text = ops[int(i)][0]
        m = _INSTRUCTION.match(text)
        name = m.group(1) if m else text
        k = bisect.bisect_right(starts, a) - 1
        if k >= 0 and a < runs[k][1]:
            per_run[k][name] += b - a
            text_of.setdefault((runs[k][2], name), text)
        else:
            loose[text] += b - a

    by_module = tables_by_module(tables)
    programs = collections.defaultdict(lambda: {
        "runs": 0, "whole_runs": 0, "whole_run_s": 0.0,
        "scopes": collections.Counter(),
        "whole_scopes": collections.Counter()})
    unattributed = collections.Counter(loose)
    for (s, e, module), names in zip(runs, per_run):
        prog = programs[module]
        whole = s >= lo and e <= hi
        prog["runs"] += 1
        table = _table_of(names, by_module.get(module, ()))
        if whole:
            prog["whole_runs"] += 1
            prog["whole_run_s"] += (e - s) * 1e-9
        for name, ns in names.items():
            path = table.get(name, "") if table else ""
            prog["scopes"][path] += ns * 1e-9
            if whole:
                prog["whole_scopes"][path] += ns * 1e-9
            if not path:
                unattributed[text_of[module, name]] += ns
    if loose:
        programs[NO_PROGRAM]["scopes"][""] = sum(loose.values()) * 1e-9
    busy = sum(sum(p["scopes"].values()) for p in programs.values())
    short = collections.Counter()
    for text, ns in unattributed.items():
        short[trace_reduce.short_name(text)] += ns * 1e-9
    return {
        "window_s": (hi - lo) * 1e-9, "busy_s": busy,
        "unattributed_s": sum(unattributed.values()) * 1e-9,
        "unattributed": [[k, v] for k, v in short.most_common(10)],
        "programs": {
            module: dict(p, scopes=dict(p["scopes"].most_common()),
                         whole_scopes=dict(p["whole_scopes"].most_common()))
            for module, p in programs.items()}}


def quantity(red, args):
    """One number of a :func:`breakdown`, by the metric file's args."""
    what = args["what"]
    if not red or not red["busy_s"]:
        return None
    if what == "unattributed_pct":
        return 100.0 * red["unattributed_s"] / red["busy_s"]
    if what != "ms_per_run":
        raise ValueError("device_scopes: unknown quantity %r" % what)
    program, scope = re.compile(args["program"]), re.compile(args["scope"])
    hit = [p for module, p in red["programs"].items()
           if program.search(module)]
    runs = sum(p["whole_runs"] for p in hit)
    if not runs:
        return None
    return 1e3 * sum(s for p in hit for path, s in p["whole_scopes"].items()
                     if scope.search(path)) / runs


@functools.lru_cache(maxsize=1)
def reading(path):
    """The breakdown of the run's own trace file, read once a process;
    written beside the trace's directory with the table it was read by."""
    from mxnet_tpu import telemetry
    t0 = time.perf_counter()
    tables = telemetry.program_scopes()
    t1 = time.perf_counter()
    red = breakdown(trace_reduce.load(path), tables)
    cost = {"program_scopes_s": t1 - t0,
            "reader_s": time.perf_counter() - t1}
    common.say("device_scopes", programs=len(tables), **cost)
    if red is not None:
        out_dir = path.split(os.sep + "trace" + os.sep)[0]
        with open(os.path.join(out_dir, "device_scopes.json"), "w") as f:
            json.dump(dict(red, cost=cost), f, indent=1)
        with open(os.path.join(out_dir, "program_scopes.json"), "w") as f:
            json.dump(tables, f, separators=(",", ":"))
    return red


def value(rec, args):
    if not rec.get("trace"):
        return None
    from mxnet_tpu import telemetry
    path = program_spans.newest_trace()
    if path is None or not hasattr(telemetry, "program_scopes"):
        return None
    return quantity(reading(path), args)


def main(argv):
    with open(argv[1]) as f:
        red = breakdown(trace_reduce.read_doc(argv[0]), json.load(f))
    if not red:
        raise SystemExit("no benchmark span or no device in the trace")
    print("window %.4f s, busy %.4f s, unattributed %.2f%%"
          % (red["window_s"], red["busy_s"],
             100.0 * red["unattributed_s"] / max(red["busy_s"], 1e-30)))
    for module, p in sorted(red["programs"].items(),
                            key=lambda kv: -sum(kv[1]["scopes"].values())):
        n = p["whole_runs"]
        print("%s: %d runs, %d whole of %.3f ms" % (
            module, p["runs"], n, 1e3 * p["whole_run_s"] / n if n else 0.0))
        print("  %-34s %10s %8s %12s" % ("scope", "s", "% busy",
                                         "ms/whole run"))
        for path, s in p["scopes"].items():
            print("  %-34s %10.6f %8.2f %12.4f" % (
                path or "(none)", s, 100.0 * s / red["busy_s"],
                1e3 * p["whole_scopes"].get(path, 0.0) / n if n else 0.0))
    print("longest unattributed:")
    for name, s in red["unattributed"]:
        print("  %10.6f s  %s" % (s, name))


if __name__ == "__main__":
    main(sys.argv[1:])
