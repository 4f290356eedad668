"""Reader ``request_events``: the program's own request-lifecycle events
(``mxnet_tpu.telemetry.request_events()``: the ring the engine writes
``admit`` / ``prefill`` / ``token`` / ``verdict`` events into, on the
host's clock).

args ``{"event": name, "field": key or [keys], "scale": x}``: the mean,
over the events of that name in the last ``counters["window_s"]`` seconds
of the ring (counted back from its newest event: the window closes on
the engine's last step), of the sum of the named ``args`` fields, times
``scale`` (default 1000: the fields are seconds, the metrics ms).  No
such event in the ring reads as nothing.
"""


def value(rec, args):
    from mxnet_tpu import telemetry
    events = telemetry.request_events()
    if not events:
        return None
    fields = args["field"]
    if isinstance(fields, str):
        fields = [fields]
    since = events[-1]["t"] - rec["counters"]["window_s"]
    got = [sum(e["args"][f] for f in fields) for e in events
           if e["event"] == args["event"] and e["t"] >= since
           and all(f in e["args"] for f in fields)]
    if not got:
        return None
    return args.get("scale", 1e3) * sum(got) / len(got)
