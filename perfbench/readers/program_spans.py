"""Reader ``program_spans``: the program's own spans in the traced slice.

``mxnet_tpu.telemetry.span`` enters a ``jax.profiler.TraceAnnotation``
while a trace is taken, so the phases of ``ServingEngine.step`` and
``Module.fit_step`` lie on the benchmark's thread in the run's own
``.xplane.pb``, over the device ops they waited for.  ``run.py`` keeps
that file until the readers have run; this reader finds it (the newest
under ``perfbench_out/*/trace``) and reads it with ``trace_reduce``'s own
functions, over the same window as the reduction (first to last whole
benchmark span).

args ``{"what": ..., "match": regex on the span's name}``:
- ``idle_ms_per_span``: time inside the matching spans during which no
  operation ran on the first device, per span, ms: what the host adds
  inside that phase;
- ``wall_ms_per_span``: their mean wall time, ms.
A program without such a span (the parent of the PR that added them), a
run without a trace file, or a slice holding none reads as nothing.

    python3 perfbench/readers/program_spans.py <file.xplane.pb | slice.json.gz> [regex]

prints, for every span of the benchmark's thread whose name matches
(default: the program's ``serve*`` and ``fit_step*`` spans), how many
lie in the window, their mean wall time and the device-idle time inside
them: the breakdown of a step's host time, by hand.
"""
import bisect
import collections
import functools
import glob
import os
import re
import sys

if __name__ == "__main__":        # by hand: perfbench/ is not on the path
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

import common
import trace_reduce


def newest_trace():
    found = glob.glob(os.path.join(
        common.ROOT, "perfbench_out", "*", "trace", "plugins", "profile",
        "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


@functools.lru_cache(maxsize=1)
def slice_of(path):
    """``(thread events, lo, hi, busy)``: the benchmark's thread, the
    reduction's window and the merged busy intervals of the first
    device inside it, all in ns."""
    return slice_of_doc(trace_reduce.load(path))


def slice_of_doc(doc):
    thread, bench = trace_reduce.host_spans(doc)
    if not bench:
        return None
    lo = min(s for _, s, _ in bench)
    hi = max(s + d for _, s, d in bench)
    planes = sorted(
        (int(trace_reduce.DEVICE_PLANE.match(p["name"]).group(1)), i)
        for i, p in enumerate(doc["planes"])
        if trace_reduce.DEVICE_PLANE.match(p["name"]))
    busy = []
    if planes:
        for line in doc["planes"][planes[0][1]]["lines"]:
            if line["name"] == trace_reduce.OPS_LINE:
                busy += [[a, b] for _, a, b in
                         trace_reduce._clip(line["events"], lo, hi)]
    return thread, lo, hi, trace_reduce._union(busy)


def spans_matching(sl, match):
    """``[(name, start, end, idle_ns)]`` of the thread's spans whose name
    matches and that lie whole inside the window."""
    thread, lo, hi, busy = sl
    pat = re.compile(match)
    starts = [b[0] for b in busy]
    out = []
    for name, s, d in thread:
        name = trace_reduce._span_name(name)
        if s < lo or s + d > hi or not pat.search(name):
            continue
        covered = 0.0
        k = max(0, bisect.bisect_right(starts, s) - 1)
        while k < len(busy) and busy[k][0] < s + d:
            covered += max(0.0, min(busy[k][1], s + d) - max(busy[k][0], s))
            k += 1
        out.append((name, s, s + d, d - covered))
    return out


def value(rec, args):
    if not rec.get("trace"):
        return None
    path = newest_trace()
    sl = slice_of(path) if path else None
    if not sl:
        return None
    spans = spans_matching(sl, args["match"])
    if not spans:
        return None
    what = args["what"]
    if what == "idle_ms_per_span":
        return 1e-6 * sum(s[3] for s in spans) / len(spans)
    if what == "wall_ms_per_span":
        return 1e-6 * sum(s[2] - s[1] for s in spans) / len(spans)
    raise ValueError("program_spans: unknown quantity %r" % what)


def main(argv):
    sl = slice_of_doc(trace_reduce.read_doc(argv[0]))
    if not sl:
        raise SystemExit("no benchmark span in the trace")
    by = collections.defaultdict(list)
    for span in spans_matching(
            sl, argv[1] if len(argv) > 1 else r"^(serve[._]|fit_step)"):
        by[span[0]].append(span)
    print("%-26s %6s %12s %12s %12s" % ("span", "n", "wall ms/span",
                                        "idle ms/span", "idle ms"))
    for name, spans in sorted(by.items(),
                              key=lambda kv: -sum(s[3] for s in kv[1])):
        idle = sum(s[3] for s in spans)
        print("%-26s %6d %12.3f %12.3f %12.3f" % (
            name, len(spans),
            1e-6 * sum(s[2] - s[1] for s in spans) / len(spans),
            1e-6 * idle / len(spans), 1e-6 * idle))


if __name__ == "__main__":
    main(sys.argv[1:])
