"""Reader ``gigachat_roofline``: a kernel's share of its roofline, the
decode program's share of its HBM roofline and the whole step's share of
the bf16 peak, in the traced slice of the cell of the GigaChat3.5 share.

The traced slice holds ``decode_steps`` runs of the decode program (the
benchmark's span records) and the runs of the prefill program the trace
line "XLA Modules" counts; the work of a run is the WINDOW's mean (the
engine's own counts: ``latent.rows_read``, the hit experts, the chunk
events), as ``kexaone_roofline`` takes it.  A live slot's decode step is
one decoded token of the slice.

args ``{"what": ..., "match": regex}``: least time for what the traced
work needs (``shapes_gigachat``; the larger of operations at the bf16
peak and bytes at the HBM peak) over the device time of the operations
(or, ``decode_hbm``, of the program on the trace line "XLA Modules")
whose name matches ``match``:

- ``kda_step``: every live slot's recurrent state read and written once a
  decode step, every delta-rule layer (bytes only: the kernel's
  arithmetic is on the VPU);
- ``mla_decode``: every latent row a decoding query reads, once, at its
  576 values (bytes only);
- ``moe_gmm``: the held experts' choices at the bf16 peak, the hit
  experts' weights read once at the HBM peak;
- ``decode_hbm``: every matrix outside the routed experts and the head
  once a run, the hit experts, the states, the latent rows;
- ``step_mfu``: all operations the slice's decoded and prefilled tokens
  need at the bf16 peak over the slice's device-BUSY time (takes no
  ``match``).
A run whose counters lack the counts (a program without
``latent.rows_read``) reads as nothing.
"""
import re

import shapes
import shapes_gigachat as shp


def _slice_work(tr, c):
    """The traced slice's decode steps, decoded tokens and chunk runs,
    and the window's mean work of one step and one chunk run."""
    steps = sum(s["decode_steps"] for s in tr["spans"])
    decoded = sum(s["tokens"] - s["prefills"] for s in tr["spans"])
    chunks = sum(n for name, _, n in tr["modules"]
                 if re.search("^jit_prefill", name))
    per_step = steps / max(1, c["decode_steps"])
    per_chunk = chunks / max(1, c["prefill_chunks"])
    return {
        "steps": steps, "decoded": decoded, "chunks": chunks,
        "dec_read": per_step * c["latent_rows_read"],
        "dec_local": per_step * c["moe_local_assignments"],
        "dec_hit": per_step * c["moe_experts_hit"],
        "pre_rows": per_chunk * c["chunk_rows"],
        "pre_read": per_chunk * c["latent_prefill_rows_read"],
        "pre_local": per_chunk * c["moe_prefill_local_assignments"],
        "pre_hit": per_chunk * c["moe_prefill_experts_hit"]}


def value(rec, args):
    c = rec["counters"]
    what = args["what"]
    if "latent_rows_read" not in c:
        return None
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
    flops = 0
    if what == "decode_hbm":
        secs = sum(s for name, s, _ in tr["modules"] if pat.search(name))
        nbytes = shp.decode_bytes(cfg, w["steps"], w["dec_hit"],
                                  w["decoded"], w["dec_read"])
    else:
        secs = sum(s for name, s in tr["device_ops"] if pat.search(name))
        if what == "kda_step":
            nbytes = shp.state_bytes(cfg, w["decoded"])
        elif what == "mla_decode":
            nbytes = shp.latent_bytes(cfg, w["dec_read"])
        elif what == "moe_gmm":
            flops = shp.expert_flops(cfg, w["dec_local"] + w["pre_local"])
            nbytes = shp.moe_gmm_bytes(cfg, w["dec_hit"] + w["pre_hit"])
        else:
            raise ValueError("gigachat_roofline: unknown quantity %r"
                             % what)
    if not secs or not (flops or nbytes):
        return None
    least, _ = shapes.roofline_seconds(flops, nbytes, peaks)
    return 100.0 * least / secs
