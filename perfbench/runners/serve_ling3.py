"""Runner ``serve_ling3``: ``runners/serve.py``'s window (its ``drive``)
over the language model of Ling-3.0-flash-VL held as one chip's share
(``mxnet_tpu.gluon.model_zoo.ling3``), with this model's own net,
vocabulary slice and correctness probe.

``correct`` is decided by ONE probe request through the live, timed
engine before the window (a 512-token prompt and 16 new tokens, so the
chunked KDA prefill, the plain MLA prefill and both decode kernels run
at the timed sizes) against the plain reference's full forward pass
(``reference/ling3.py``):

- **routing**: the engine reports the experts it chose at every
  position (``eng.last_prefill`` / ``eng.last_decode``).  Where the
  reference's own router margin is above ``route_delta`` the choices
  must agree; where they differ only by experts within ``route_delta``
  of the boundary the reference adopts the engine's choice (bfloat16
  activations can flip the 8th and 9th expert); any other difference
  fails the run.  The reference never takes a choice unchecked;
- **logits**: at each of the generated positions the engine's logits
  (the prefill's row and the decode steps' rows of the probe's slot)
  lie within ``tol_logit`` of the reference's, and the reference's
  logit of the engine's token within ``tol_gap`` of its maximum;

plus 0 compiles in the window and no failed request.
"""
import gc
import time

import numpy as np

import common
from reference import ling3 as reference
from runners.serve import drive, stats_ms


def model_cfg(cfg):
    """The model's own configuration from the file's keys: the router
    keeps its published width, ``num_experts`` in the file is the count
    held here."""
    out = {k: v for k, v in cfg.items() if isinstance(v, (int, float))
           and not isinstance(v, bool)}
    out.update(num_experts=cfg["published"]["num_experts"],
               experts_held=list(cfg["experts_held"]),
               layers=list(cfg["layers_kept"]))
    return out


def build_net(cfg, seed):
    from mxnet_tpu.gluon.model_zoo import ling3
    net = getattr(ling3, cfg["model"]["factory"])(model_cfg(cfg))
    net.init_seeded(common.seed_key(seed))
    return net


def probe(eng, net, check, seed):
    """One request through the live engine against the reference (the
    module docstring has the rules)."""
    cfg = net.cfg
    rng = np.random.default_rng([int(seed), 0x9C0BE])
    prompt = rng.integers(0, cfg["vocab_size"], check["prompt_len"]) \
        .astype(np.int32)
    req = eng.submit(prompt, check["max_new"])
    rows, experts, slot = [], [], None
    for _ in range(10 * check["max_new"]):
        if req.done:
            break
        n_before = len(req.tokens)
        eng.step()
        if slot is None:
            # the admission: the prefill's row, its choices at every
            # prompt position, and the slot the decode rows are read at
            slot = req.slot
            logits, aux = eng.last_prefill
            rows.append(np.asarray(logits))
            experts.append(np.asarray(aux["experts"])[:, :prompt.size])
            n_before += 1
        if len(req.tokens) > n_before:
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
        delta=check["route_delta"],
        rows=np.arange(prompt.size - 1, seq.size))
    want = np.asarray(want)
    got = np.stack(rows)
    toks = np.asarray(req.tokens)
    gaps = want.max(-1) - want[np.arange(len(toks)), toks]
    adopted = int(sum(np.asarray(r["adopted"]).sum() for r in routing))
    mismatch = int(sum(np.asarray(r["mismatch"]).sum() for r in routing))
    need = float(max(np.asarray(r["need"]).max() for r in routing))
    err = float(np.abs(got - want).max())
    del want, routing
    gc.collect()
    doc = {"probe_logit_err": err, "tol_logit": check["tol_logit"],
           "probe_max_gap": float(gaps.max()), "tol_gap": check["tol_gap"],
           "probe_argmax_agree": int((gaps == 0).sum()),
           "probe_tokens": len(toks), "route_delta": check["route_delta"],
           "route_adopted": adopted, "route_mismatch": mismatch,
           "route_delta_needed": need,
           "route_positions": int(sys_experts.shape[0] * seq.size)}
    ok = mismatch == 0 and err <= check["tol_logit"] \
        and gaps.max() <= check["tol_gap"]
    return bool(ok), doc


GAP_EDGES_MS = (0, 23.5, 24.5, 26, 30, 60, 88, 92, 100, 150, 1e9)


def step_seconds(gaps, slots):
    """Where the window's time went, by the length of a step: every slot
    sees a step as one gap, so the gaps of one bucket add up to ``slots``
    times the seconds spent in steps of that length.  ``{"lo-hi": [steps,
    seconds]}``, for the free lines."""
    ms = 1e3 * np.asarray(gaps)
    which = np.digitize(ms, GAP_EDGES_MS[1:-1])
    return {"%g-%g" % (GAP_EDGES_MS[i], GAP_EDGES_MS[i + 1]):
            [round(float((which == i).sum()) / slots, 1),
             round(float(ms[which == i].sum()) / slots / 1e3, 4)]
            for i in range(len(GAP_EDGES_MS) - 1) if (which == i).any()}


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
               state_bytes_per_slot=eng.state_bytes_per_slot,
               kv_bytes_per_token=eng.kv_bytes_per_token,
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
    cell = ctx.cell
    eng, net, programs = build_engine(ctx)
    t0 = time.perf_counter()
    ok_probe, probe_doc = probe(eng, net, cell["correct"], ctx.seed)
    common.say("probe", ok=ok_probe, seconds=time.perf_counter() - t0,
               **probe_doc)
    gc.collect()
    gc.freeze()
    at_open = {}

    def opened(t_open):
        at_open.update(decode=dict(eng.stat_totals["decode"]),
                       prefill=dict(eng.stat_totals["prefill"]),
                       prefills=eng.prefills)
        ctx.opened(t_open)

    w = drive(eng, ctx.traffic, cell["runner_params"], ctx.seed,
              ctx.seconds, net.cfg["vocab_size"], ctx.spans, ctx.slice,
              opened)
    compiles = ctx.watch.compiles - ctx.compiles_at_open
    gaps, _ = w.pop("gaps"), w.pop("ttfts")
    e2e = {"serve_tok_s": w["tokens"] / w["window_s"]}

    def delta(program, name):
        return eng.stat_totals[program].get(name, 0) \
            - at_open[program].get(name, 0)

    counters = dict(
        w, moe_local_assignments=delta("decode", "local_assignments"),
        moe_assignments=delta("decode", "assignments"),
        moe_experts_hit_per_decode_step=delta("decode", "experts_hit")
        / max(1, w["decode_steps"]),
        moe_experts_hit_per_prefill=delta("prefill", "experts_hit")
        / max(1, eng.prefills - at_open["prefills"]),
        moe_max_tokens_per_expert=delta("decode", "max_tokens_per_expert")
        / max(1, delta("decode", "expert_layers")),
        state_bytes_per_slot=eng.state_bytes_per_slot,
        kv_bytes_per_token=eng.kv_bytes_per_token)
    records = counters.pop("span_records")
    common.say("window_model", itl_ms=stats_ms(gaps),
               step_seconds_by_gap_ms=step_seconds(gaps, eng.num_slots),
               **{k: counters[k] for k in counters if k.startswith("moe_")})
    correct = bool(ok_probe and compiles == 0 and w["failed"] == 0)
    return {"correct": correct, "attempted": w["attempted"],
            "failed": w["failed"], "end_to_end": e2e, "counters": counters,
            "programs": programs, "span_records": records,
            "why_not_correct": None if correct else dict(
                probe_doc, compiles_in_window=compiles,
                failed=w["failed"])}
