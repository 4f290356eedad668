"""Reader ``counters``: a number the runner counted, or the ratio of two.

args: ``{"value": key}`` or ``{"num": key, "den": key, "scale": x}``;
keys name entries of the run record's ``counters``.  A key the runner
did not count, or a zero denominator, reads as nothing.
"""


def value(rec, args):
    c = rec["counters"]
    if "value" in args:
        return c.get(args["value"])
    num, den = c.get(args["num"]), c.get(args["den"])
    if num is None or not den:
        return None
    return args.get("scale", 1.0) * num / den
