"""Runner ``serve``: one ``ServingEngine`` on one chip under a request
stream, for ``--seconds`` of steady state.

The driving code is ``chip_smoke.py``'s ``gpt_net`` / ``run_engine``
(PR 21, proven on the chip) with the handful of requests replaced by a
timed window: one process, one thread, the loop a server's own would be
(submit what is due, ``eng.step()``, repeat).  Nothing is drained: the
window closes at ``--seconds``.
"""
import gc
import time

import numpy as np

import common
import trafficgen
from reference import gpt2 as reference


def build_net(cfg, seed):
    from mxnet_tpu.gluon.model_zoo import gpt
    net = getattr(gpt, cfg["model"]["factory"])(
        max_len=cfg["n_positions"], vocab_size=cfg["vocab_size"])
    common.seeded_gpt_weights(net, seed, keep_grads=False)
    return net


def probe(eng, net, cfg, check, seed):
    """One request through the live engine against the plain reference:
    at each generated position the reference's logit of the engine's
    token lies within ``tol`` of the reference's maximum."""
    import jax
    rng = np.random.default_rng([int(seed), 0x9C0BE])
    prompt = rng.integers(0, cfg["vocab_real"], check["prompt_len"]) \
        .astype(np.int32)
    req = eng.submit(prompt, check["max_new"])
    for _ in range(10 * check["max_new"]):
        if req.done:
            break
        eng.step()
    if not req.done or len(req.tokens) != check["max_new"]:
        return False, {"probe": "engine gave %d tokens, state %s"
                       % (len(req.tokens), req.state)}
    w, n_head = reference.weights_from_net(net)
    seq = np.concatenate([prompt, np.asarray(req.tokens, np.int32)])
    logits = np.asarray(jax.jit(reference.forward, static_argnums=2)(
        w, seq[None, :-1], n_head))[0]
    rows = logits[prompt.size - 1:]
    gaps = rows.max(-1) - rows[np.arange(len(req.tokens)), req.tokens]
    del w, logits
    gc.collect()
    doc = {"probe_max_gap": float(gaps.max()), "tol": check["tol"],
           "probe_argmax_agree": int((gaps == 0).sum()),
           "probe_tokens": len(req.tokens)}
    return bool(gaps.max() <= check["tol"]), doc


def stats_ms(values):
    """Mean and a few percentiles, in ms, for the free lines."""
    if not len(values):
        return None
    doc = {"mean": 1e3 * float(np.mean(values))}
    for q in (50, 90, 95, 99):
        doc["p%d" % q] = 1e3 * common.percentile(values, q)
    return doc


def build_engine(ctx):
    """The net with seeded weights, the engine over it, and what the
    compiler says its two programs hold."""
    from mxnet_tpu.serving import ServingEngine

    watch = ctx.watch
    net = build_net(ctx.config, ctx.seed)
    watch.on_device([p.data()._data
                     for p in net.collect_params().values()],
                    "serving weights")
    t0 = time.perf_counter()
    eng = ServingEngine(net, record_logits=False, **ctx.cell["engine"])
    common.say("engine_built", seconds=time.perf_counter() - t0,
               num_pages=eng.alloc.num_pages,
               compile_cache=dict(watch.cache), memory=watch.memory())
    watch.on_device(eng._kv, "KV pools")
    programs = []
    for prog in (eng._decode, eng._prefill):
        assert hasattr(prog.__wrapped__, "as_text"), \
            "a serving program fell back to lazy jit: %r" % prog
        programs.append(common.program_memory(prog.__wrapped__))
    if watch.want == "tpu":
        assert common.has_kernel(eng._decode.__wrapped__), \
            "no Mosaic call in the decode program"
    return eng, net, programs


def drive(eng, mix, params, seed, seconds, vocab, spans, slice_, opened):
    """Offer ``mix`` to ``eng``: the starting population joins and a few
    decode steps run (warm phase), then the window of ``seconds`` opens.
    Returns what the window held, as plain numbers and lists."""
    from mxnet_tpu.serving.scheduler import FINISHED, QUEUED, RUNNING

    slots = eng.num_slots
    backlog = mix["arrivals"]["process"] == "backlog"
    first = slots if backlog else int(mix["initial_population"])
    depth = int(params.get("queue_depth_x_slots", 2)) * slots
    stream = trafficgen.requests(mix, seed, vocab, eng.page_size,
                                 stagger=first)
    reqs, dues = [], []
    pending = next(stream)
    t_stream = time.perf_counter()

    def feed(now):
        """Submit what is due (backlog: keep the queue at ``depth``)."""
        nonlocal pending
        while (eng.sched.queued < depth) if backlog \
                else (t_stream + pending[0] <= now):
            due, prompt, max_new = pending
            with spans("submit"):
                reqs.append(eng.submit(prompt, max_new))
            dues.append(t_stream + due)
            pending = next(stream)

    def step():
        feed(time.perf_counter())
        return eng.step()

    # warm phase: the starting population joins (slot fill), then a few
    # decode steps; the window opens on a steady engine
    step()
    while any(r.admit_t is None and not r.done for r in reqs[:first]):
        step()
    for _ in range(int(params.get("warm_decode_steps", 3))):
        step()
    common.say("warm", fill_s=time.perf_counter() - t_stream,
               occupancy=eng.sched.occupancy, queued=eng.sched.queued,
               prefills=eng.prefills, decode_steps=eng.decode_steps)

    # -- the window ------------------------------------------------------
    # one row a step: t0, t1, prefills, decode steps, tokens, traced,
    # live context tokens, queue length
    steps = []
    t_open = time.perf_counter()
    opened(t_open)
    n_open = len(reqs)
    while True:
        now = time.perf_counter()
        if now - t_open >= seconds:
            break
        slice_.tick(now - t_open)
        feed(now)
        p0, d0 = eng.prefills, eng.decode_steps
        with spans("step"):
            made = eng.step()
        t1 = time.perf_counter()
        steps.append((now, t1, eng.prefills - p0, eng.decode_steps - d0,
                      made, spans.on,
                      sum(r.prompt.size + len(r.tokens)
                          for r in eng.sched.running),
                      eng.sched.queued))
        if made == 0 and not backlog:
            time.sleep(max(0.0, min(0.002, t_stream + pending[0] - t1)))
    t_close = time.perf_counter()
    slice_.stop()
    window_s = t_close - t_open

    # -- what the window holds -------------------------------------------
    tokens = 0
    gaps, ttfts, late = [], [], []
    ttft_cut = t_close - float(params.get("ttft_tail_s", 10))
    attempted = failed = 0
    hit_tokens = prompt_tokens = 0
    for i, (r, due) in enumerate(zip(reqs, dues)):
        tt = np.asarray(r.token_times)
        inside = (tt >= t_open) & (tt <= t_close)
        tokens += int(inside.sum())
        if tt.size > 1:
            gaps.append(np.diff(tt)[inside[1:]])
        late.append(r.submit_t - due)
        admitted = r.admit_t is not None and r.admit_t >= t_open
        if admitted:
            hit_tokens += r.prefix_len
            prompt_tokens += r.prompt.size
        in_ttft = (not backlog) and i >= first and t_open <= due <= ttft_cut
        if not (in_ttft or admitted):
            continue
        attempted += 1
        bad = r.state not in (FINISHED, QUEUED, RUNNING) or \
            (r.state == FINISHED and len(r.tokens) != r.max_new)
        if in_ttft:
            if r.first_token_t is None:
                bad = True
            else:
                ttfts.append(r.first_token_t - due)
        failed += bool(bad)
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    decode_steps = sum(s[3] for s in steps)
    mid = steps[len(steps) // 2][7] if steps else 0
    out = {
        "window_s": window_s, "tokens": tokens, "attempted": attempted,
        "failed": failed, "gaps": gaps, "ttfts": ttfts,
        "decode_steps": decode_steps,
        "prefills": sum(s[2] for s in steps),
        "decode_tokens": sum(s[4] - s[2] for s in steps),
        "decode_slot_steps": decode_steps * slots,
        "prefix_hit_tokens": hit_tokens,
        "prompt_tokens_admitted": prompt_tokens,
        "queued_mid": mid, "queued_close": eng.sched.queued,
        "span_records": [
            {"prefills": s[2], "decode_steps": s[3], "tokens": s[4],
             "context_tokens": s[6]} for s in steps if s[5]]}
    common.say("window", seconds=window_s, tokens=tokens,
               tok_s=tokens / window_s,
               requests_submitted_in_window=len(reqs) - n_open,
               attempted=attempted, failed=failed,
               itl_samples=int(gaps.size), ttft_samples=len(ttfts),
               itl_ms=stats_ms(gaps), ttft_ms=stats_ms(ttfts),
               generator_late_s_max=None if backlog else float(max(late)),
               generator_late_s_mean=None if backlog
               else float(np.mean(late)),
               decode_steps=decode_steps, prefills=out["prefills"],
               queued_mid=mid, queued_close=eng.sched.queued,
               occupancy_close=eng.sched.occupancy,
               mean_output_len=stream.mean_output())
    return out


def run(ctx):
    cell = ctx.cell
    ctx.config = dict(ctx.config, vocab_real=ctx.config["vocab_size"]
                      if ctx.tiny else 50257)
    eng, net, programs = build_engine(ctx)
    ok_probe, probe_doc = probe(eng, net, ctx.config, cell["correct"],
                                ctx.seed)
    common.say("probe", ok=ok_probe, **probe_doc)
    gc.collect()
    gc.freeze()
    w = drive(eng, ctx.traffic, cell["runner_params"], ctx.seed,
              ctx.seconds, ctx.config["vocab_real"], ctx.spans, ctx.slice,
              ctx.opened)
    compiles = ctx.watch.compiles - ctx.compiles_at_open
    gaps, ttfts = w.pop("gaps"), w.pop("ttfts")
    # the means are end-to-end metrics; the 95th percentiles are read per
    # layer only: they rest on a step of whole prefill runs and on ~3
    # requests (PERF.md section 2)
    e2e = {"serve_tok_s": w["tokens"] / w["window_s"]}
    tails = {}
    if gaps.size:
        e2e["itl_mean_ms"] = 1e3 * float(gaps.mean())
        tails["itl_p95_ms"] = 1e3 * common.percentile(gaps, 95)
    if ttfts:
        e2e["ttft_mean_ms"] = 1e3 * float(np.mean(ttfts))
        tails["ttft_p95_ms"] = 1e3 * common.percentile(ttfts, 95)
    counters = dict(w, **tails, kv_heads=eng.kv_heads,
                    head_dim=eng._head_dim,
                    kv_itemsize=eng.alloc.kv_itemsize,
                    n_layers=eng._n_layers)
    records = counters.pop("span_records")
    correct = bool(ok_probe and compiles == 0 and w["failed"] == 0)
    return {"correct": correct, "attempted": w["attempted"],
            "failed": w["failed"], "end_to_end": e2e, "counters": counters,
            "programs": programs, "span_records": records,
            "why_not_correct": None if correct else dict(
                probe_doc, compiles_in_window=compiles,
                failed=w["failed"])}
