"""Reader ``dsv32_roofline``: a kernel's share of its roofline, and the
whole step's share of the bf16 peak, in the traced slice of the cell of
the DeepSeek-V3.2 share.

The traced slice holds ``decode_steps`` runs of the decode program
(the benchmark's span records) and the runs of the prefill program the
trace line "XLA Modules" counts; the work of a run is the WINDOW's mean
(the engine's own counts: ``dsa.rows_in_context``, ``dsa.rows_attended``,
the hit experts, the chunk events), as ``ling3_roofline`` takes it.

args ``{"what": ..., "match": regex}``: least time for what the traced
work needs (``shapes_dsv32``; the larger of operations at the bf16 peak
and bytes at the HBM peak) over the device time of the operations whose
trace name matches ``match``:

- ``dsa_index``: every query scores each indexer key in its context
  once; a slot's keys (decode) and a chunk's context (prefill) read once;
- ``mla_sparse``: every query attends over its selected rows once, each
  selected row read once a query;
- ``moe_gmm``: the held experts that got a token, weights read once;
- ``step_mfu``: all operations the slice's decoded and prefilled tokens
  need at the bf16 peak over the slice's device-BUSY time (takes no
  ``match``);
- ``rows_attended_pct``: rows attended over rows in context, summed
  over the window's decode steps (a counter, not a time).
A run whose counters lack the counts (the parent of the PR that added
them) reads as nothing.
"""
import re

import shapes
import shapes_dsv32


def _slice_work(tr, c):
    """The traced slice's decode steps and chunk runs, and the window's
    mean work of one of each."""
    steps = sum(s["decode_steps"] for s in tr["spans"])
    decoded = sum(s["tokens"] - s["prefills"] for s in tr["spans"])
    chunks = sum(n for name, _, n in tr["modules"]
                 if re.search("^jit_prefill", name))
    per_step = 1.0 / max(1, c["decode_steps"])
    per_chunk = 1.0 / max(1, c["prefill_chunks"])
    return {
        "steps": steps, "decoded": decoded, "chunks": chunks,
        "dec_ctx": steps * per_step * c["dsa_rows_in_context"],
        "dec_att": steps * per_step * c["dsa_rows_attended"],
        "dec_local": steps * per_step * c["moe_local_assignments"],
        "dec_hit": steps * per_step * c["moe_experts_hit"],
        "pre_rows": chunks * per_chunk * c["chunk_rows"],
        "pre_keys": chunks * per_chunk * c["chunk_context_rows"],
        "pre_ctx": chunks * per_chunk * c["dsa_prefill_rows_in_context"],
        "pre_att": chunks * per_chunk * c["dsa_prefill_rows_attended"],
        "pre_local": chunks * per_chunk
        * c["moe_prefill_local_assignments"],
        "pre_hit": chunks * per_chunk * c["moe_prefill_experts_hit"]}


def value(rec, args):
    c = rec["counters"]
    what = args["what"]
    if "dsa_rows_in_context" not in c:
        return None
    if what == "rows_attended_pct":
        if not c["dsa_rows_in_context"]:
            return None
        return 100.0 * c["dsa_rows_attended"] / c["dsa_rows_in_context"]
    tr, peaks, cfg = rec.get("trace"), rec.get("peaks"), rec["config"]
    if not tr or not tr["spans"] or not peaks:
        return None
    w = _slice_work(tr, c)
    n_layers = len(cfg["layers_kept"])
    if what == "step_mfu":
        flops = shapes_dsv32.step_flops(
            cfg, w["decoded"] + w["pre_rows"], w["decoded"] + w["chunks"],
            w["dec_local"] + w["pre_local"], w["dec_ctx"] + w["pre_ctx"],
            w["dec_att"] + w["pre_att"])
        busy = tr["busy_s"]
        return 100.0 * flops / peaks["bf16_flops_per_s"] / busy \
            if busy else None
    pat = re.compile(args["match"])
    secs = sum(s for name, s in tr["device_ops"] if pat.search(name))
    if what == "dsa_index":
        flops = shapes_dsv32.index_flops(cfg, w["dec_ctx"] + w["pre_ctx"])
        # decode: a slot's keys once a layer; prefill: a chunk's context
        nbytes = shapes_dsv32.index_bytes(
            cfg, w["dec_ctx"] + n_layers * w["pre_keys"])
    elif what == "mla_sparse":
        att = w["dec_att"] + w["pre_att"]
        flops = shapes_dsv32.sparse_attention_flops(
            cfg, att, (w["decoded"] + w["pre_rows"]) * n_layers)
        nbytes = shapes_dsv32.sparse_attention_bytes(cfg, att)
    elif what == "moe_gmm":
        flops = shapes_dsv32.expert_flops(
            cfg, w["dec_local"] + w["pre_local"])
        nbytes = shapes_dsv32.moe_gmm_bytes(cfg, w["dec_hit"] + w["pre_hit"])
    else:
        raise ValueError("dsv32_roofline: unknown quantity %r" % what)
    if not secs or not (flops or nbytes):
        return None
    least, _ = shapes.roofline_seconds(flops, nbytes, peaks)
    return 100.0 * least / secs
