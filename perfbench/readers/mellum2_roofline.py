"""Reader ``mellum2_roofline``: a kernel's share of its roofline, the
window layers' and the decode program's shares of their HBM roofline and
the whole step's share of the bf16 peak, in the traced slice of the cell
of the Mellum 2 stage (``kexaone_roofline``'s quantities and one more).

The traced slice holds ``decode_steps`` runs of the decode program (the
benchmark's span records) and the runs of the prefill program the trace
line "XLA Modules" counts; the work of a run is the WINDOW's mean (the
engine's own counts: ``kv.rows_read``, ``kv.rows_full``,
``kv.window_rows_read``, the hit experts, the chunk events), as
``dsv32_roofline`` takes it.

args ``{"what": ..., "match": regex}``: least time for what the traced
work needs (``shapes_mellum2``; the larger of operations at the bf16 peak
and bytes at the HBM peak) over the device time of the operations (or,
``decode_hbm``, of the program on the trace line "XLA Modules") whose
name matches ``match``:

- ``paged_attn``: every decoding slot's K and V of the full layers read
  once a step (a step's ``kv.rows_full`` over the number of layers);
- ``window_hbm``: the ring rows a decode run's sliding layers read (the
  window's mean ``kv.window_rows_read`` a step, K and V of 4 x 128
  values a row) at the HBM peak, over the device time in scope
  ``attn.window`` of a whole decode run (``device_scopes``; takes no
  ``match``);
- ``moe_gmm``: the held experts that got a token, weights read once;
- ``decode_hbm``: every matrix outside the routed experts and the head
  once a run, the hit experts, the K and V rows the queries see;
- ``step_mfu``: all operations the slice's decoded and prefilled tokens
  need at the bf16 peak over the slice's device-BUSY time (takes no
  ``match``);
- ``rows_read_pct``: rows of K/V read over what every layer full would
  have read, summed over the window's decode steps (a counter, not a
  time).
A run whose counters lack the counts (the parent of the PR that added
them) reads as nothing.
"""
import re

import shapes
import shapes_mellum2 as shp


def _slice_work(tr, c):
    """The traced slice's decode steps and chunk runs, and the window's
    mean work of one of each."""
    steps = sum(s["decode_steps"] for s in tr["spans"])
    decoded = sum(s["tokens"] - s["prefills"] for s in tr["spans"])
    chunks = sum(n for name, _, n in tr["modules"]
                 if re.search("^jit_prefill", name))
    per_step = steps / max(1, c["decode_steps"])
    per_chunk = chunks / max(1, c["prefill_chunks"])
    return {
        "steps": steps, "decoded": decoded, "chunks": chunks,
        "dec_read": per_step * c["kv_rows_read"],
        "dec_full": per_step * c["kv_rows_full"],
        "dec_local": per_step * c["moe_local_assignments"],
        "dec_hit": per_step * c["moe_experts_hit"],
        "pre_rows": per_chunk * c["chunk_rows"],
        "pre_read": per_chunk * c["kv_prefill_rows_read"],
        "pre_local": per_chunk * c["moe_prefill_local_assignments"],
        "pre_hit": per_chunk * c["moe_prefill_experts_hit"]}


def _window_hbm(rec, c):
    """The ring rows of a mean decode run at the HBM peak over the
    device time in scope ``attn.window`` of a whole decode run."""
    from readers import device_scopes
    ms = device_scopes.value(rec, {
        "what": "ms_per_run", "program": "^jit_decode",
        "scope": r"(^|/)attn\.window(/|$)"})
    peaks = rec.get("peaks")
    if not ms or not peaks or not c["decode_steps"]:
        return None
    nbytes = shp.kv_bytes(
        rec["config"], c["kv_window_rows_read"] / c["decode_steps"])
    return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / (ms / 1e3)


def value(rec, args):
    c = rec["counters"]
    what = args["what"]
    if "kv_window_rows_read" not in c:
        return None
    if what == "window_hbm":
        return _window_hbm(rec, c)
    if what == "rows_read_pct":
        if not c["kv_rows_full"]:
            return None
        return 100.0 * c["kv_rows_read"] / c["kv_rows_full"]
    tr, peaks, cfg = rec.get("trace"), rec.get("peaks"), rec["config"]
    if not tr or not tr["spans"] or not peaks:
        return None
    w = _slice_work(tr, c)
    if what == "step_mfu":
        flops = shp.step_flops(
            cfg, w["decoded"] + w["pre_rows"], w["decoded"] + w["chunks"],
            w["dec_local"] + w["pre_local"], w["dec_read"] + w["pre_read"])
        busy = tr["busy_s"]
        return 100.0 * flops / peaks["bf16_flops_per_s"] / busy \
            if busy else None
    pat = re.compile(args["match"])
    if what == "decode_hbm":
        secs = sum(s for name, s, _ in tr["modules"] if pat.search(name))
        flops = 0
        nbytes = shp.decode_bytes(cfg, w["steps"], w["dec_hit"],
                                  w["dec_read"])
    else:
        secs = sum(s for name, s in tr["device_ops"] if pat.search(name))
        if what == "paged_attn":
            rows = w["dec_full"] / len(cfg["layers_kept"]) \
                * shp.layer_kinds(cfg).count(shp.FULL)
            flops = shp.attention_flops(cfg, rows)
            nbytes = shp.kv_bytes(cfg, rows)
        elif what == "moe_gmm":
            flops = shp.expert_flops(cfg, w["dec_local"] + w["pre_local"])
            nbytes = shp.moe_gmm_bytes(cfg, w["dec_hit"] + w["pre_hit"])
        else:
            raise ValueError("mellum2_roofline: unknown quantity %r" % what)
    if not secs or not (flops or nbytes):
        return None
    least, _ = shapes.roofline_seconds(flops, nbytes, peaks)
    return 100.0 * least / secs
