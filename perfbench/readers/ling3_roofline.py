"""Reader ``ling3_roofline``: a kernel's (or the decode program's)
share of its HBM roofline in the traced slice, for the cells of the
Ling-3.0-flash-VL share.

args ``{"what": ..., "match": regex}``: least time to move the bytes the
traced work needs (``shapes_ling3``, HBM peak) over the device time of
the operations whose trace name matches ``match``:

- ``moe_gmm``: the held experts that got a token, each expert's weights
  once (decode steps at the window's mean count of hit experts a step,
  prefills at theirs);
- ``kda_step``: every live slot's state read once and written once;
- ``mla_decode``: every live latent row once;
- ``decode_hbm``: all of a decode step's needed bytes over the device
  time of the decode program (``match`` names the program on the trace
  line "XLA Modules").
A program without such a kernel, or a run whose counters lack the
counts (the parent of the PR that added them), reads as nothing.
"""
import re

import shapes
import shapes_ling3


def value(rec, args):
    tr = rec.get("trace")
    peaks, cfg, c = rec.get("peaks"), rec["config"], rec["counters"]
    if not tr or not tr["spans"] or not peaks:
        return None
    if "moe_experts_hit_per_decode_step" not in c or \
            any("context_tokens" not in s for s in tr["spans"]):
        return None
    what = args["what"]
    pat = re.compile(args["match"])
    steps = sum(s["decode_steps"] for s in tr["spans"])
    prefills = sum(s["prefills"] for s in tr["spans"])
    live = sum((s["tokens"] - s["prefills"]) for s in tr["spans"])
    context = sum(s["context_tokens"] * s["decode_steps"]
                  for s in tr["spans"])
    hit = steps * c["moe_experts_hit_per_decode_step"]
    if what == "decode_hbm":
        secs = sum(s for name, s, _ in tr["modules"] if pat.search(name))
        nbytes = shapes_ling3.decode_bytes(cfg, steps, hit, live, context)
    else:
        secs = sum(s for name, s in tr["device_ops"] if pat.search(name))
        if what == "moe_gmm":
            nbytes = shapes_ling3.moe_gmm_bytes(
                cfg, hit + prefills * c["moe_experts_hit_per_prefill"])
        elif what == "kda_step":
            nbytes = shapes_ling3.kda_state_bytes(cfg, live)
        elif what == "mla_decode":
            nbytes = shapes_ling3.latent_bytes(cfg, context)
        else:
            raise ValueError("ling3_roofline: unknown quantity %r" % what)
    if not secs or not nbytes:
        return None
    least, _ = shapes.roofline_seconds(0, nbytes, peaks)
    return 100.0 * least / secs
