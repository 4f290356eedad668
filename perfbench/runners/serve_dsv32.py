"""Runner ``serve_dsv32``: ``runners/serve.py``'s window (its ``drive``)
over DeepSeek-V3.2's language model held as one chip's share
(``mxnet_tpu.gluon.model_zoo.deepseek_v32``), with this model's own net,
vocabulary slice and correctness probe.

``correct`` is decided by ONE probe request through the live, timed
engine before the window: a prompt of two whole chunks and a ragged
third (so the chunked prefill runs at an offset, against the slot's own
pages, past ``index_topk`` positions) and a few new tokens, against the
plain reference's full forward pass over all positions
(``reference/dsv32.py``):

- **routing** and **selection**: the engine reports, for every
  position, the experts it chose and the rows it selected
  (``eng.last_prefill`` after each chunk run, ``eng.last_decode`` after
  each decode step).  The reference adopts a differing choice only
  where every entry of the difference lies within ``route_delta`` of
  its own 8th selection score, or within ``select_delta`` of its own
  ``index_topk``-th index score; any other difference fails the run;
- **logits**: at each generated position the engine's logits lie within
  ``tol_logit`` of the reference's, and the reference's logit of the
  engine's token within ``tol_gap`` of its maximum;

plus 0 compiles in the window and no failed request.

The probe's context is a few thousand tokens with one slot live.  So
AFTER the window, on the engine as the window left it (every slot live,
contexts of the traffic's own lengths), :func:`timed_selection` holds
the FIRST layer's selection to a ``select_delta`` of its own (that
layer's scores are smaller than a deeper layer's): its input is the
embedding, so the reference's index scores need a slot's tokens alone
(``reference.first_layer_index_scores``).  It compares the
rows the engine reports for a few query rows of one late chunk run and
for every decoding slot's row of one decode step.  Deeper layers'
selection and the attention over the selected rows are compared at the
probe's size only.
"""
import gc
import time

import numpy as np

import common
import trafficgen
from reference import dsv32 as reference
from runners.serve import drive, stats_ms


def model_cfg(cfg):
    """The model's own configuration from the file's keys: the router
    keeps its published width (the file's ``n_routed_experts`` is the
    count held here)."""
    out = {k: v for k, v in cfg.items() if isinstance(v, (int, float))
           and not isinstance(v, bool)}
    out.update(num_experts=cfg["published"]["n_routed_experts"],
               rope_scaling=dict(cfg["rope_scaling"]),
               experts_held=list(cfg["experts_held"]),
               layers=list(cfg["layers_kept"]))
    return out


def build_net(cfg, seed):
    from mxnet_tpu.gluon.model_zoo import deepseek_v32
    net = getattr(deepseek_v32, cfg["model"]["factory"])(model_cfg(cfg))
    net.init_seeded(common.seed_key(seed))
    return net


def probe(eng, net, check, seed, compute_as=None):
    """One request through the live engine against the reference (the
    module docstring has the rules)."""
    cfg = net.cfg
    rng = np.random.default_rng([int(seed), 0x9C0BE])
    prompt = rng.integers(0, cfg["vocab_size"], check["prompt_len"]) \
        .astype(np.int32)
    req = eng.submit(prompt, check["max_new"])
    rows, experts, selected = [], [], []
    slot = None
    for _ in range(10 * (check["max_new"] + prompt.size
                         // eng.max_prefill_len + 1)):
        if req.done:
            break
        sent, steps = req.prefilled, eng.decode_steps
        eng.step()
        if req.prefilled > sent:
            # a chunk run went out: its choices at the chunk's real rows
            slot = req.slot
            logits, aux = eng.last_prefill
            n = req.prefilled - sent
            experts.append(np.asarray(aux["experts"])[:, :n])
            selected.append(np.asarray(aux["selected"])[:, :n])
            if req.prefilled == prompt.size:
                rows.append(np.asarray(logits))
        if eng.decode_steps > steps:
            logits, aux = eng.last_decode
            rows.append(np.asarray(logits[slot]))
            experts.append(np.asarray(aux["experts"])[:, slot][:, None])
            selected.append(np.asarray(aux["selected"])[:, slot][:, None])
    if not req.done or len(req.tokens) != check["max_new"] \
            or len(rows) != check["max_new"]:
        return False, {"probe": "engine gave %d tokens and %d rows, "
                       "state %s" % (len(req.tokens), len(rows), req.state)}
    seq = np.concatenate([prompt, np.asarray(req.tokens, np.int32)])[:-1]
    sys_experts = np.concatenate(experts, axis=1)[:, :seq.size]
    sys_selected = np.concatenate(selected, axis=1)[:, :seq.size]
    want, routing, selection = reference.forward(
        eng._p, seq, cfg, sys_experts=list(sys_experts),
        sys_selected=list(sys_selected),
        route_delta=check["route_delta"],
        select_delta=check["select_delta"],
        rows=np.arange(prompt.size - 1, seq.size), compute_as=compute_as)
    want = np.asarray(want)
    got = np.stack(rows)
    toks = np.asarray(req.tokens)
    gaps = want.max(-1) - want[np.arange(len(toks)), toks]

    def total(docs, key):
        return int(sum(np.asarray(d[key]).sum() for d in docs))

    def worst(docs):
        return float(max((np.asarray(d["need"]).max() for d in docs),
                         default=0.0))

    err = float(np.abs(got - want).max())
    doc = {"probe_logit_err": err, "tol_logit": check["tol_logit"],
           "probe_max_gap": float(gaps.max()), "tol_gap": check["tol_gap"],
           "probe_argmax_agree": int((gaps == 0).sum()),
           "probe_tokens": len(toks), "probe_positions": int(seq.size),
           "route_delta": check["route_delta"],
           "route_adopted": total(routing, "adopted"),
           "route_mismatch": total(routing, "mismatch"),
           "route_delta_needed": worst(routing),
           "select_delta": check["select_delta"],
           "select_adopted": total(selection, "adopted"),
           "select_mismatch": total(selection, "mismatch"),
           "select_delta_needed": worst(selection)}
    del want, routing, selection
    gc.collect()
    ok = doc["route_mismatch"] == 0 and doc["select_mismatch"] == 0 \
        and err <= check["tol_logit"] and gaps.max() <= check["tol_gap"]
    return bool(ok), doc


def timed_selection(eng, cfg, check, compute_as=None):
    """The first layer's selection at the window's own sizes (module
    docstring): engine steps until a chunk run at an offset of
    ``chunk_offset_min`` or more went out (``max_steps`` at most: then
    the deepest seen), ``chunk_rows`` of its query rows, and the last
    decode step's row of every slot that decodes.  ``compute_as``: the
    reference's precision, for the limit's second reading."""
    chunk = None                  # (offset, request, end, rows, selected)
    for _ in range(check["max_steps"]):
        req = next(iter(eng.sched.prefilling), None)
        sent = req.prefilled if req is not None else 0
        eng.step()
        if req is None or req.prefilled <= sent:
            continue
        if chunk is None or sent > chunk[0]:
            rows = np.unique(np.linspace(0, req.prefilled - sent - 1,
                                         check["chunk_rows"]).astype(int))
            chunk = (sent, req, req.prefilled, rows,
                     np.asarray(eng.last_prefill[1]["selected"][0][rows]))
        if chunk[0] >= check["chunk_offset_min"]:
            break
    totals = np.zeros(3, np.int64)        # rows, adopted, mismatch
    needs, contexts = [0.0], []

    def held(seq, queries, sys_rows):
        """The near-tie rule on the selection of positions ``queries``."""
        scores = reference.first_layer_index_scores(
            eng._p, seq, cfg, queries, pad_to=eng.max_seq_len,
            compute_as=compute_as)
        _, doc = reference.select(scores, cfg["index_topk"],
                                  np.asarray(sys_rows, np.int32),
                                  check["select_delta"], positions=queries)
        totals[:] += (len(queries), int(np.asarray(doc["adopted"]).sum()),
                      int(np.asarray(doc["mismatch"]).sum()))
        needs.append(float(np.asarray(doc["need"]).max()))
        contexts.append(int(seq.size))

    if chunk is not None:
        offset, req, end, rows, selected = chunk
        held(req.prompt[:end], offset + rows, selected)
    # the last decode read (one a step): a resident request with a
    # token past its prefill's took its newest from it (one that sat it
    # out has none, or left when an earlier read gave it its last), so
    # it fed the token before that, at the position before
    _, aux = eng.last_decode
    selected = np.asarray(aux["selected"][0])
    live = np.asarray(aux["n_selected"]) > 0
    decode_rows = 0
    for req in eng.sched.running:
        if req.prefilling or len(req.tokens) < 2 or not live[req.slot]:
            continue
        seq = np.concatenate([req.prompt,
                              np.asarray(req.tokens[:-1], np.int32)])
        held(seq, np.asarray([seq.size - 1]), selected[req.slot][None])
        decode_rows += 1
    doc = {"timed_select_rows": int(totals[0]),
           "timed_select_adopted": int(totals[1]),
           "timed_select_mismatch": int(totals[2]),
           "timed_select_delta_needed": max(needs),
           "timed_select_delta": check["select_delta"],
           "timed_chunk_offset": None if chunk is None else int(chunk[0]),
           "timed_decode_rows": decode_rows,
           "timed_context_min": min(contexts, default=None),
           "timed_context_max": max(contexts, default=None)}
    return bool(totals[0] > 0 and totals[2] == 0), doc


def warm_steps(mix, eng, vocab):
    """Engine steps from the starting population's admission until the
    whole of it decodes: the chunk runs its prompts need, by the
    schedule itself (one chunk run an engine step, the slot admitted
    first).  ``drive``'s warm phase waits for admission only, and a
    window opened there lies inside the starting population's prefill."""
    stream = trafficgen.requests(mix, 0, vocab, eng.page_size,
                                 stagger=eng.num_slots)
    return sum(-(-next(stream)[1].size // eng.max_prefill_len)
               for _ in range(eng.num_slots))


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
        # the probe was request 0: the starting population is 1..slots
        prefilling = eng.sched.prefilling
        at_open.update(decode=dict(eng.stat_totals["decode"]),
                       prefill=dict(eng.stat_totals["prefill"]),
                       chunks=eng.prefill_chunks,
                       prefilling=len(prefilling),
                       starting_prefilling=sum(
                           r.rid <= eng.num_slots for r in prefilling))
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
        chunk_context_rows=sum(e["offset"] + e["rows"] for e in events),
        chunk_events=len(events),
        moe_local_assignments=delta("decode", "local_assignments"),
        moe_assignments=delta("decode", "assignments"),
        moe_experts_hit=delta("decode", "experts_hit"),
        moe_prefill_local_assignments=delta("prefill",
                                            "local_assignments"),
        moe_prefill_experts_hit=delta("prefill", "experts_hit"),
        dsa_rows_attended=delta("decode", "dsa.rows_attended"),
        dsa_rows_in_context=delta("decode", "dsa.rows_in_context"),
        dsa_prefill_rows_attended=delta("prefill", "dsa.rows_attended"),
        dsa_prefill_rows_in_context=delta("prefill",
                                          "dsa.rows_in_context"),
        kv_bytes_per_token=eng.kv_bytes_per_token)
    records = counters.pop("span_records")
    common.say("window_model", itl_ms=stats_ms(gaps),
               warm_decode_steps=params["warm_decode_steps"],
               prefilling_at_open=at_open["prefilling"],
               starting_prefilling_at_open=at_open["starting_prefilling"],
               **{k: counters[k] for k in counters
                  if k.startswith(("moe_", "dsa_", "chunk_", "prefill_"))})
    # the window's counts are taken: the engine may step on
    t0 = time.perf_counter()
    ok_timed, timed_doc = timed_selection(
        eng, net.cfg, cell["correct"]["timed_selection"])
    common.say("timed_selection", ok=ok_timed,
               seconds=time.perf_counter() - t0, **timed_doc)
    probe_doc.update(timed_doc)
    correct = bool(ok_probe and ok_timed and compiles == 0
                   and w["failed"] == 0)
    # the runner's last free line: the compared numbers beside their
    # limits (the harness's own ``setup`` line follows it)
    common.say("compared", ok=ok_probe and ok_timed,
               compiles_in_window=compiles,
               failed=w["failed"], **probe_doc)
    return {"correct": correct, "attempted": w["attempted"],
            "failed": w["failed"], "end_to_end": e2e, "counters": counters,
            "programs": programs, "span_records": records,
            "why_not_correct": None if correct else dict(
                probe_doc, compiles_in_window=compiles,
                failed=w["failed"])}
