"""From a profiler trace (``.xplane.pb``) to numbers.

``load`` turns the file into plain lists (``jax.profiler.ProfileData``
reads it, nothing else is needed); ``reduce`` works on those lists alone,
so that its check runs on a small recorded slice kept as JSON
(``tests/data``).  What it gives, for the traced slice:

- ``window_s``: from the start of the first whole benchmark span
  (``step`` / ``train_step``, written by the runners) to the end of the
  last one: whole steps only, so that busy time and steps match;
- ``busy_s``: the union of the intervals in which an operation ran on a
  device (line "XLA Ops" of each ``/device:TPU:n`` plane), clipped to the
  window and averaged over the chips used;
- ``device_ops``: ``[name, seconds]``, longest first.  On a TPU the trace
  names an operation by its whole HLO instruction (kilobytes of operand
  shapes); ``short_name`` keeps what tells operations apart: opcode, the
  instruction's name without its number, the result's shape without its
  layout, and a custom call's target (``tpu_custom_call`` is a Mosaic
  kernel).  Instructions that agree in all of these are summed;
- ``modules``: ``[name, seconds, runs]`` for whole programs (line "XLA
  Modules", e.g. ``jit_decode(...)`` with its fingerprint cut off);
- ``idle_gaps``: the idle time inside the window, summed by what the host
  was doing meanwhile (the innermost span of the benchmark's thread that
  covers each piece of a gap), longest first.

    python3 perfbench/trace_reduce.py <file.xplane.pb> [--export out.slice.json.gz --spans first:last]

prints what the trace holds (planes, lines, most frequent names): look at
one trace by hand before trusting a reduction of it.
"""
import collections
import json
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
BENCH_SPANS = ("step", "train_step")


_HLO = re.compile(r"^%(\S+) = (\(|[^\s{]+).*?[})] ([\w\-]+)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(text):
    """``%copy.1608 = bf16[5711,16,16,64]{3,2,1,0:T(8,128)(2,1)}
    copy(...)`` -> ``copy copy bf16[5711,16,16,64]``."""
    m = _HLO.match(text)
    if not m:
        return text[:80]
    base = re.sub(r"\.\d+", "", m.group(1))
    shape = m.group(2)
    if shape == "(":                     # a tuple: its first element
        first = re.match(r"\w+\[[\d,]*\]", text[m.end(2):])
        shape = "(%s,...)" % (first.group(0) if first else "")
    out = "%s %s %s" % (m.group(3), base, shape)
    target = _TARGET.search(text)
    return out + " " + target.group(1) if target else out


def load(path):
    """``{"planes": [{"name", "lines": [{"name", "events": [[name,
    start_ns, dur_ns], ...]}]}]}``"""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(intervals):
    """Sorted, merged ``[start, end]`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events, lo, hi):
    """Events cut to [lo, hi]; those outside vanish."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b))
    return out


def _span_name(name):
    """``TraceAnnotation`` writes keyword arguments into the name after a
    ``#``; the span's own name is what precedes it."""
    return name.split("#", 1)[0]


def host_spans(doc):
    """Events of the host thread that wrote the benchmark's spans:
    ``(thread events, benchmark spans)``."""
    best, spans = [], []
    for plane in doc["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            mine = [e for e in line["events"]
                    if _span_name(e[0]) in BENCH_SPANS]
            if len(mine) > len(spans):
                best, spans = line["events"], mine
    return best, spans


def innermost_timeline(events):
    """One thread's spans flattened: sorted ``(start, end, name)`` pieces,
    each named by the innermost span that covers it."""
    out, stack, cursor = [], [], 0.0

    def emit(to):
        nonlocal cursor
        if to > cursor:
            out.append((cursor, to, stack[-1][1]))
            cursor = to

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        if stack:
            emit(s)
        cursor = max(cursor, s) if stack else s
        stack.append((s + d, _span_name(name)))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def reduce(doc, chips=1, steps=None):
    """The numbers named in the module docstring, as a dict of plain
    values.  ``steps``: the runner's own record of each benchmark span in
    the slice, in order (what it dispatched), joined to the spans by
    position."""
    thread, spans = host_spans(doc)
    devices = sorted(
        (int(DEVICE_PLANE.match(p["name"]).group(1)), p)
        for p in doc["planes"] if DEVICE_PLANE.match(p["name"]))
    devices = [p for _, p in devices][:chips]
    if spans:
        lo = min(s for _, s, _ in spans)
        hi = max(s + d for _, s, d in spans)
    else:
        every = [e for p in devices for l in p["lines"]
                 for e in l["events"]]
        lo = min((e[1] for e in every), default=0.0)
        hi = max((e[1] + e[2] for e in every), default=0.0)
    window = hi - lo

    ops = collections.Counter()
    modules = collections.Counter()
    module_counts = collections.Counter()
    busy_total, busy0 = 0.0, []
    for i, plane in enumerate(devices):
        intervals = []
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                for name, a, b in _clip(line["events"], lo, hi):
                    name = short_name(name)
                    ops[name] += b - a
                    intervals.append([a, b])
            elif line["name"] == MODULES_LINE:
                for name, a, b in _clip(line["events"], lo, hi):
                    name = name.split("(", 1)[0]
                    modules[name] += b - a
                    module_counts[name] += 1
        merged = _union(intervals)
        busy_total += sum(e - s for s, e in merged)
        if i == 0:
            busy0 = merged
    n = max(1, len(devices))

    # idle gaps on the first device, by what the host did meanwhile
    gaps, edge = [], lo
    for s, e in busy0:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    if devices and hi > edge:
        gaps.append((edge, hi))
    idle = collections.Counter()
    timeline = innermost_timeline(thread)
    k = 0
    for a, b in gaps:                     # both lists are sorted
        while k < len(timeline) and timeline[k][1] <= a:
            k += 1
        j, covered = k, 0.0
        while j < len(timeline) and timeline[j][0] < b:
            s0, s1, name = timeline[j]
            part = min(b, s1) - max(a, s0)
            if part > 0:
                idle[name] += part
                covered += part
            j += 1
        if b - a > covered:
            idle["(no host span)"] += b - a - covered

    # busy time inside each benchmark span, for the per-step readers
    per_span = []
    for name, s, d in sorted(spans, key=lambda e: e[1]):
        inside = sum(min(e, s + d) - max(b, s) for b, e in busy0
                     if min(e, s + d) > max(b, s))
        per_span.append({"name": _span_name(name), "wall_s": d * 1e-9,
                         "busy_s": inside * 1e-9})
    if steps is not None and len(steps) == len(per_span):
        for rec, st in zip(per_span, steps):
            rec.update(st)

    def ranked(counter, counts=None):
        rows = sorted(counter.items(), key=lambda kv: -kv[1])
        if counts is None:
            return [[k, v * 1e-9 / n] for k, v in rows]
        return [[k, v * 1e-9 / n, counts[k] // n] for k, v in rows]

    return {
        "window_s": window * 1e-9,
        "busy_s": busy_total * 1e-9 / n,
        "chips_traced": len(devices),
        "device_ops": ranked(ops),
        "modules": ranked(modules, module_counts),
        "idle_gaps": [[k, v * 1e-9] for k, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])],
        "idle_s": sum(idle.values()) * 1e-9,
        "spans": per_span,
    }


def reduce_file(path, chips=1, steps=None):
    return reduce(load(path), chips, steps)


def describe(doc, top=25):
    """What a trace holds, for reading by hand."""
    for plane in doc["planes"]:
        print("plane %r: %d lines" % (plane["name"], len(plane["lines"])))
        for line in plane["lines"]:
            ev = line["events"]
            total = sum(e[2] for e in ev) * 1e-9
            print("  line %r: %d events, %.4f s summed"
                  % (line["name"], len(ev), total))
            by = collections.Counter()
            cnt = collections.Counter()
            for name, _, d in ev:
                by[name] += d
                cnt[name] += 1
            for name, d in by.most_common(top):
                print("      %10.6f s  x%-6d %s"
                      % (d * 1e-9, cnt[name], name[:150]))


def export_slice(doc, first, last):
    """The events that overlap benchmark spans ``first`` .. ``last`` (by
    position), times counted from the first of them: the same plain
    structure, small enough to keep (the recorded slice the reduction's
    check runs on)."""
    _, spans = host_spans(doc)
    spans = sorted(spans, key=lambda e: e[1])[first:last + 1]
    lo, hi = spans[0][1], spans[-1][1] + spans[-1][2]
    planes = []
    for plane in doc["planes"]:
        lines = []
        for line in plane["lines"]:
            ev = [[n, s - lo, d] for n, s, d in line["events"]
                  if s + d >= lo and s <= hi]
            if ev:
                lines.append({"name": line["name"], "events": ev})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


def read_doc(path):
    """A trace file, or a slice that ``--export`` wrote."""
    if path.endswith(".json.gz"):
        import gzip
        with gzip.open(path, "rt") as f:
            return json.load(f)
    return load(path)


def main(argv):
    doc = read_doc(argv[0])
    if "--export" in argv:
        import gzip
        out = argv[argv.index("--export") + 1]
        first, last = (int(x) for x in
                       argv[argv.index("--spans") + 1].split(":"))
        with gzip.open(out, "wt") as f:
            json.dump(export_slice(doc, first, last), f,
                      separators=(",", ":"))
        return
    describe(doc)
    red = reduce(doc)
    print(json.dumps({k: (v[:15] if isinstance(v, list) else v)
                      for k, v in red.items() if k != "spans"}, indent=1))


if __name__ == "__main__":
    main(sys.argv[1:])
