"""Reader ``trace_busy``: device busy and idle time of the traced slice.

args ``{"what": ...}``:
- ``idle_pct``: 100 x (1 - busy / window) over the slice;
- ``busy_ms_per_span``: device busy per benchmark span (a train step), ms;
- ``host_ms_per_span``: span wall time minus device busy inside it, ms,
  averaged over the spans: what the host adds to a step;
- ``module_ms_per_run``: device time of one run of the program whose
  name (line "XLA Modules" of the trace, e.g. ``jit_decode``) matches
  ``match``.
"""
import re


def value(rec, args):
    tr = rec.get("trace")
    if not tr or not tr["window_s"] or not tr["chips_traced"]:
        return None
    what = args["what"]
    if what == "idle_pct":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    if what == "module_ms_per_run":
        hit = [(s, n) for name, s, n in tr["modules"]
               if re.search(args["match"], name)]
        runs = sum(n for _, n in hit)
        return 1e3 * sum(s for s, _ in hit) / runs if runs else None
    spans = tr["spans"]
    if not spans:
        return None
    if what == "busy_ms_per_span":
        return 1e3 * sum(s["busy_s"] for s in spans) / len(spans)
    if what == "host_ms_per_span":
        return 1e3 * sum(s["wall_s"] - s["busy_s"] for s in spans) \
            / len(spans)
    raise ValueError("trace_busy: unknown quantity %r" % what)
