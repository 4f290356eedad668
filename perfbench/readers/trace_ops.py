"""Reader ``trace_ops``: device time of the operations whose trace name
matches ``match`` (a regular expression), over the traced slice.

args ``{"match": ..., "what": ...}``:
- ``ms_per_span``: their device time per benchmark span, ms;
- ``share_pct``: their share of the slice's device-busy time;
- ``flash_roofline_pct``: least time for the slice's forward + backward
  attention FLOPs (``shapes.flash_fwd_bwd_flops``, bf16 peak: the kernel
  is compute-bound) over their device time, as a percentage;
- ``gpt2_step_mfu_pct``: model FLOPs of the traced train steps
  (``shapes.gpt2_train_step_flops``, no recompute) at the bf16 peak over
  ALL device-busy time of those steps (takes no ``match``);
- ``paged_roofline_pct``: least time to read the live K and V of every
  traced decode step once (``shapes.paged_attention_bytes``, HBM peak:
  the kernel is memory-bound) over their device time, as a percentage.
Nothing matched reads as nothing.
"""
import re

import shapes


def value(rec, args):
    tr = rec.get("trace")
    if not tr or not tr["spans"]:
        return None
    what = args["what"]
    peaks, cfg, c = rec.get("peaks"), rec["config"], rec["counters"]
    if what == "gpt2_step_mfu_pct":
        # model FLOPs of the traced steps over ALL their device-busy time
        if not peaks:
            return None
        flops = len(tr["spans"]) * shapes.gpt2_train_step_flops(
            cfg, c["batch"], c["seq"])
        least, _ = shapes.roofline_seconds(flops, 0, peaks)
        return 100.0 * least / sum(s["busy_s"] for s in tr["spans"])
    pat = re.compile(args["match"])
    secs = sum(s for name, s in tr["device_ops"] if pat.search(name))
    if not secs:
        return None
    if what == "ms_per_span":
        return 1e3 * secs / len(tr["spans"])
    if what == "share_pct":
        return 100.0 * secs / tr["busy_s"]
    if not peaks:
        return None
    if what == "flash_roofline_pct":
        flops = len(tr["spans"]) * cfg["n_layer"] * \
            shapes.flash_fwd_bwd_flops(c["batch"], cfg["n_head"], c["seq"],
                                       cfg["n_embd"] // cfg["n_head"])
        least, _ = shapes.roofline_seconds(flops, 0, peaks)
    elif what == "paged_roofline_pct":
        if any("context_tokens" not in s for s in tr["spans"]):
            return None
        nbytes = sum(shapes.paged_attention_bytes(
            [s["context_tokens"]], c["kv_heads"], c["head_dim"],
            c["kv_itemsize"], c["n_layers"]) * s["decode_steps"]
            for s in tr["spans"])
        least, _ = shapes.roofline_seconds(0, nbytes, peaks)
    else:
        raise ValueError("trace_ops: unknown quantity %r" % what)
    return 100.0 * least / secs
