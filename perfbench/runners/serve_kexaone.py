"""Runner ``serve_kexaone``: ``runners/serve.py``'s window (its ``drive``)
over K-EXAONE's language model held as one chip's share
(``mxnet_tpu.gluon.model_zoo.exaone_moe``), with this model's own net,
vocabulary slice and correctness probes.

``correct`` is decided by TWO probe requests through the live, timed
engine, each against the plain reference's full forward pass over all
of its positions (``reference/kexaone.py``), on logits and never on
tokens:

- **(a)** before the window, alone in the engine: a prompt of two whole
  chunks and a ragged third (the chunked prefill runs at an offset,
  against the slot's own pages and the rings the chunk before left;
  every ring wraps 36 times) and a few new tokens;
- **(b)** after the window, on the engine as the window left it: what
  waits in the queue and what still prefills is cancelled, so that the
  probe's chunk runs go next, and the other slots go on decoding beside
  it.  One request at the mix's longest prompt (pages (a) never
  reached, the last chunk run at the largest offset the cell has) and a
  few new tokens;

each held to the same limits:

- **routing**: the engine reports, for every position, the experts it
  chose (``eng.last_prefill`` after each chunk run, ``eng.last_decode``
  after each decode step that gave the probe a token).  The reference
  adopts a differing choice only where every expert of the difference
  lies within ``route_delta`` of its own 8th selection score; any other
  difference fails the run;
- **logits**: at each generated position the engine's logits lie within
  ``tol_logit`` of the reference's, and the reference's logit of the
  engine's token within ``tol_gap`` of its maximum;

plus 0 compiles in the window and no failed request.
"""
import gc
import time

import numpy as np

import common
from reference import kexaone as reference
from runners.serve import drive, stats_ms
from runners.serve_dsv32 import warm_steps


def model_cfg(cfg):
    """The model's own configuration from the file's keys: the router
    keeps its published width (the file's ``num_experts`` is the count
    held here), the layer pattern its published length (read at
    ``layers_kept``)."""
    out = {k: v for k, v in cfg.items() if isinstance(v, (int, float))
           and not isinstance(v, bool)}
    out.update(num_experts=cfg["published"]["num_experts"],
               rope_theta=cfg["rope_parameters"]["rope_theta"],
               layer_types=list(cfg["layer_types"]),
               experts_held=list(cfg["experts_held"]),
               layers=list(cfg["layers_kept"]))
    return out


def build_net(cfg, seed):
    from mxnet_tpu.gluon.model_zoo import exaone_moe
    net = getattr(exaone_moe, cfg["model"]["factory"])(model_cfg(cfg))
    net.init_seeded(common.seed_key(seed))
    return net


def probe(eng, net, check, seed, compute_as=None):
    """One request through the live engine against the reference (the
    module docstring has the rules).  Other slots may be live: a step's
    news is the probe's where its own request moved."""
    cfg = net.cfg
    rng = np.random.default_rng([int(seed), 0x9C0BE, check["prompt_len"]])
    prompt = rng.integers(0, cfg["vocab_size"], check["prompt_len"]) \
        .astype(np.int32)
    req = eng.submit(prompt, check["max_new"])
    rows, experts = [], []
    slot = None
    chunks = -(-prompt.size // eng.max_prefill_len)
    for _ in range(20 * (check["max_new"] + chunks + eng.num_slots)):
        if req.done:
            break
        sent, had = req.prefilled, len(req.tokens)
        eng.step()
        if req.slot is not None:
            slot = req.slot
        if req.prefilled > sent:
            # its chunk run went out: the choices at the chunk's real rows
            logits, aux = eng.last_prefill
            experts.append(
                np.asarray(aux["experts"])[:, :req.prefilled - sent])
            if req.prefilled == prompt.size:
                rows.append(np.asarray(logits))
        # a token past the prefill's own came from the one decode
        # dispatch this step read
        if len(req.tokens) > max(had, 1):
            logits, aux = eng.last_decode
            rows.append(np.asarray(logits[slot]))
            experts.append(np.asarray(aux["experts"])[:, slot][:, None])
    if not req.done or len(req.tokens) != check["max_new"] \
            or len(rows) != check["max_new"]:
        return False, {"probe": "engine gave %d tokens and %d rows, "
                       "state %s" % (len(req.tokens), len(rows), req.state)}
    seq = np.concatenate([prompt, np.asarray(req.tokens, np.int32)])[:-1]
    sys_experts = np.concatenate(experts, axis=1)[:, :seq.size]
    want, routing = reference.forward(
        eng._p, seq, cfg, sys_experts=list(sys_experts),
        route_delta=check["route_delta"],
        rows=np.arange(prompt.size - 1, seq.size), compute_as=compute_as)
    want = np.asarray(want)
    got = np.stack(rows)
    toks = np.asarray(req.tokens)
    gaps = want.max(-1) - want[np.arange(len(toks)), toks]

    def total(key):
        return int(sum(np.asarray(d[key]).sum() for d in routing))

    err = float(np.abs(got - want).max())
    doc = {"probe_logit_err": err, "tol_logit": check["tol_logit"],
           "probe_max_gap": float(gaps.max()), "tol_gap": check["tol_gap"],
           "probe_argmax_agree": int((gaps == 0).sum()),
           "probe_tokens": len(toks), "probe_positions": int(seq.size),
           "route_delta": check["route_delta"],
           "route_adopted": total("adopted"),
           "route_mismatch": total("mismatch"),
           "route_delta_needed": float(max(
               (np.asarray(d["need"]).max() for d in routing),
               default=0.0))}
    del want, routing
    gc.collect()
    ok = doc["route_mismatch"] == 0 and err <= check["tol_logit"] \
        and gaps.max() <= check["tol_gap"]
    return bool(ok), doc


def probe_after(eng, net, check, seed, compute_as=None):
    """Probe (b): clear the way to a slot (the queue, the prefilling
    slots, and one decoding slot if none is free), then :func:`probe`
    at ``check["after"]``'s sizes beside the slots that go on decoding."""
    from mxnet_tpu.serving.scheduler import QUEUED
    cleared = 0
    for req in list(eng._streams.values()):
        if req.state == QUEUED or (not req.done and req.prefilling):
            eng.cancel(req.trace)
            cleared += 1
    if eng.sched.occupancy == eng.num_slots:
        eng.cancel(max(eng.sched.running,
                       key=lambda r: r.max_new - len(r.tokens)).trace)
        cleared += 1
    live = eng.sched.occupancy
    ok, doc = probe(eng, net, dict(check, **check["after"]), seed,
                    compute_as)
    return ok, dict({"after_" + k: v for k, v in doc.items()},
                    after_cleared=cleared, after_live_slots=live)


def build_engine(ctx):
    from mxnet_tpu.serving import ServingEngine

    watch = ctx.watch
    t0 = time.perf_counter()
    net = build_net(ctx.config, ctx.seed)
    watch.on_device([p.data()._data
                     for p in net.collect_params().values()],
                    "serving weights")
    common.say("weights", seconds=time.perf_counter() - t0,
               memory=watch.memory())
    t0 = time.perf_counter()
    eng = ServingEngine(net, record_logits=False, **ctx.cell["engine"])
    common.say("engine_built", seconds=time.perf_counter() - t0,
               num_pages=eng.alloc.num_pages,
               kv_bytes_per_token=eng.kv_bytes_per_token,
               state_bytes_per_slot=eng.state_bytes_per_slot,
               compile_cache=dict(watch.cache), memory=watch.memory())
    watch.on_device(eng._kv, "caches")
    programs = []
    for prog in (eng._decode, eng._prefill):
        assert hasattr(prog.__wrapped__, "as_text"), \
            "a serving program fell back to lazy jit: %r" % prog
        programs.append(common.program_memory(prog.__wrapped__))
    if watch.want == "tpu":
        assert common.has_kernel(eng._decode.__wrapped__), \
            "no Mosaic call in the decode program"
    return eng, net, programs


def run(ctx):
    from mxnet_tpu import telemetry

    cell = ctx.cell
    eng, net, programs = build_engine(ctx)
    t0 = time.perf_counter()
    ok_probe, probe_doc = probe(eng, net, cell["correct"], ctx.seed)
    # the compared numbers beside their limits
    common.say("probe", ok=ok_probe, seconds=time.perf_counter() - t0,
               **probe_doc)
    gc.collect()
    gc.freeze()
    at_open = {}

    def opened(t_open):
        at_open.update(decode=dict(eng.stat_totals["decode"]),
                       prefill=dict(eng.stat_totals["prefill"]),
                       chunks=eng.prefill_chunks,
                       prefilling=len(eng.sched.prefilling))
        ctx.opened(t_open)

    # the window opens on the event the schedule defines: the whole
    # starting population decodes.  Its end falls where it falls
    params = dict(cell["runner_params"], warm_decode_steps=warm_steps(
        ctx.traffic, eng, net.cfg["vocab_size"]))
    w = drive(eng, ctx.traffic, params, ctx.seed, ctx.seconds,
              net.cfg["vocab_size"], ctx.spans, ctx.slice, opened)
    compiles = ctx.watch.compiles - ctx.compiles_at_open
    gaps, _ = w.pop("gaps"), w.pop("ttfts")
    e2e = {"serve_tok_s": w["tokens"] / w["window_s"]}

    def delta(program, name):
        return eng.stat_totals[program].get(name, 0) \
            - at_open[program].get(name, 0)

    # the window's chunk runs, from the engine's own request events (the
    # newest in the ring: one a run read, in the order they were read)
    chunks = eng.prefill_chunks - at_open["chunks"]
    events = [e["args"] for e in telemetry.request_events()
              if e["event"] == "prefill_chunk"][-chunks:] if chunks else []
    counters = dict(
        w, prefill_chunks=chunks, chunk_len=eng.max_prefill_len,
        chunk_rows=sum(e["rows"] for e in events),
        chunk_events=len(events),
        moe_local_assignments=delta("decode", "local_assignments"),
        moe_assignments=delta("decode", "assignments"),
        moe_experts_hit=delta("decode", "experts_hit"),
        moe_prefill_local_assignments=delta("prefill",
                                            "local_assignments"),
        moe_prefill_experts_hit=delta("prefill", "experts_hit"),
        kv_rows_read=delta("decode", "kv.rows_read"),
        kv_rows_full=delta("decode", "kv.rows_full"),
        kv_prefill_rows_read=delta("prefill", "kv.rows_read"),
        kv_prefill_rows_full=delta("prefill", "kv.rows_full"),
        kv_bytes_per_token=eng.kv_bytes_per_token,
        state_bytes_per_slot=eng.state_bytes_per_slot)
    records = counters.pop("span_records")
    common.say("window_model", itl_ms=stats_ms(gaps),
               warm_decode_steps=params["warm_decode_steps"],
               prefilling_at_open=at_open["prefilling"],
               **{k: counters[k] for k in counters
                  if k.startswith(("moe_", "kv_", "chunk_", "prefill_"))})
    # the window's counts are taken: the engine may step on
    t0 = time.perf_counter()
    ok_after, after_doc = probe_after(eng, net, cell["correct"], ctx.seed)
    common.say("probe_after", ok=ok_after, seconds=time.perf_counter() - t0,
               **after_doc)
    probe_doc.update(after_doc)
    correct = bool(ok_probe and ok_after and compiles == 0
                   and w["failed"] == 0)
    # the runner's last free line: the compared numbers beside their
    # limits (the harness's own ``setup`` line follows it)
    common.say("compared", ok=ok_probe and ok_after,
               compiles_in_window=compiles, failed=w["failed"], **probe_doc)
    return {"correct": correct, "attempted": w["attempted"],
            "failed": w["failed"], "end_to_end": e2e, "counters": counters,
            "programs": programs, "span_records": records,
            "why_not_correct": None if correct else dict(
                probe_doc, compiles_in_window=compiles,
                failed=w["failed"])}
