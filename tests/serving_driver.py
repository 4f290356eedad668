"""Standalone serving-runtime checks (paged-attention kernel + engine);
run in a process of its own by tests/test_serving.py.

Usage: python serving_driver.py [kernel|engine|capacity|spec_sweep]
Prints SERVING_<SECTION>_OK markers on success.
"""
import contextlib
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.gluon.model_zoo import gpt  # noqa: E402

VOCAB, UNITS, HEADS, MAX_LEN = 128, 64, 2, 48
ENGINE_KW = dict(num_slots=3, page_size=8, max_prefill_len=16,
                 max_seq_len=32)


def _engine(net, **over):
    from mxnet_tpu.serving import ServingEngine
    kw = dict(ENGINE_KW)
    kw.update(over)
    return ServingEngine(net, **kw)


def _idle_pages_ok(eng):
    """Idle-engine page accounting: no leaks beyond the prefix index's
    own pins (one page per cached entry), conservation intact."""
    eng.alloc.assert_conservation()
    cached = 0 if eng._prefix is None else eng._prefix.cached_pages
    assert eng.alloc.used_pages == cached, \
        (eng.alloc.used_pages, cached)
    if eng._prefix is not None:
        eng._prefix.assert_consistent()


@contextlib.contextmanager
def _one_program_a_step(eng):
    """Inside the block every decode step and every prefill of ``eng``
    is ONE dispatch, nothing else dispatches and nothing compiles."""
    from mxnet_tpu import profiler
    profiler.reset_step_stats()
    d0, f0 = eng.decode_steps, eng.prefills
    yield
    stats = profiler.step_stats()
    assert stats["compile_count"] == 0, stats
    assert stats["dispatch_count"] == \
        (eng.decode_steps - d0) + (eng.prefills - f0), stats


def _net():
    np.random.seed(0)
    mx.random.seed(0)
    n = gpt.GPTLM(VOCAB, 2, UNITS, HEADS, max_len=MAX_LEN)
    n.initialize()
    return n


def _ref(net, prompt, max_new):
    return list(gpt.generate(net, prompt[None], max_new)[0, len(prompt):])


# -- kernel section --------------------------------------------------------

def _pool(rng, n_pages, page, kv, d):
    """One page pool as the engine stores it: a token's ``kv`` heads
    of ``d`` side by side, ``[num_pages, page, K_kv * D]``."""
    return rng.randn(n_pages, page, kv * d).astype(np.float32)


def _paged_setup(rng, s, h, d, page, n_pages, mp, ctx_lens):
    q = rng.randn(s, h, d).astype(np.float32)
    kp = _pool(rng, n_pages, page, h, d)
    vp = _pool(rng, n_pages, page, h, d)
    # distinct physical pages per slot, deliberately non-contiguous
    perm = rng.permutation(n_pages - 1) + 1
    bt = np.zeros((s, mp), np.int32)
    k = 0
    for i in range(s):
        need = -(-max(1, ctx_lens[i]) // page)
        bt[i, :need] = perm[k:k + need]
        k += need
    return q, kp, vp, bt, np.asarray(ctx_lens, np.int32)


def check_kernel_vs_reference_mixed_lengths():
    from mxnet_tpu.ops.pallas.paged_attention import (
        paged_attention, paged_attention_reference)
    rng = np.random.RandomState(0)
    q, kp, vp, bt, ctx = _paged_setup(rng, s=4, h=3, d=16, page=8,
                                      n_pages=16, mp=3,
                                      ctx_lens=[20, 5, 24, 1])
    out = np.asarray(paged_attention(q, kp, vp, bt, ctx))
    ref = np.asarray(paged_attention_reference(q, kp, vp, bt, ctx))
    err = np.abs(out - ref).max()
    assert err < 1e-5, ("kernel vs reference", err)


def check_kernel_empty_slot_zero():
    from mxnet_tpu.ops.pallas.paged_attention import paged_attention
    rng = np.random.RandomState(1)
    q, kp, vp, bt, ctx = _paged_setup(rng, s=3, h=2, d=8, page=4,
                                      n_pages=8, mp=2,
                                      ctx_lens=[7, 0, 3])
    out = np.asarray(paged_attention(q, kp, vp, bt, ctx))
    assert np.all(out[1] == 0.0), "empty slot must emit zeros"
    assert np.all(np.isfinite(out))


def check_kernel_vs_dense_flash():
    """The kernel over scattered pages == flash_attention over the same
    history laid out dense — mixed lengths, one launch."""
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention
    from mxnet_tpu.ops.pallas.paged_attention import paged_attention
    import jax.numpy as jnp
    rng = np.random.RandomState(2)
    s, h, d, page, mp = 3, 2, 16, 8, 3
    ctx_lens = [17, 9, 24]
    q, kp, vp, bt, ctx = _paged_setup(rng, s, h, d, page, 16, mp,
                                      ctx_lens)
    out = np.asarray(paged_attention(q, kp, vp, bt, ctx))
    for i, L in enumerate(ctx_lens):
        ks = np.concatenate([kp[p] for p in bt[i]],
                            axis=0)[:L].reshape(L, h, d)
        vs = np.concatenate([vp[p] for p in bt[i]],
                            axis=0)[:L].reshape(L, h, d)
        kd = jnp.asarray(ks.transpose(1, 0, 2)[None])
        vd = jnp.asarray(vs.transpose(1, 0, 2)[None])
        qd = jnp.asarray(q[i][None, :, None, :])        # [1, H, 1, D]
        # single-query non-causal attention over the full history is
        # exactly the decode step's semantics
        ref = np.asarray(flash_attention(qd, kd, vd, causal=False,
                                         block_q=8, block_k=8))
        err = np.abs(out[i] - ref[0, :, 0, :]).max()
        assert err < 1e-4, ("kernel vs dense flash", i, err)


def check_kernel_multi_vs_reference():
    """ISSUE 16 verify kernel: n_q query positions per slot, each with
    its OWN per-position context (the causal mask of batched draft
    verification) — vs the jnp oracle at mixed lengths, including rows
    past a slot's draft length (ctx 0 -> zeros) and an inactive slot.
    G == 1 must reproduce the single-query kernel BIT-identically (the
    spec-off cost/math baseline)."""
    from mxnet_tpu.ops.pallas.paged_attention import (
        paged_attention, paged_attention_multi,
        paged_attention_multi_reference)
    rng = np.random.RandomState(14)
    for s, h, kv, d, page, n_pages, mp, n_q, ctx_rows in (
            # per-position causal ramps; slot 1 has a short draft (two
            # dead rows), slot 2 is inactive (all rows masked)
            (3, 4, 2, 16, 8, 16, 3, 4,
             [[17, 18, 19, 20], [5, 6, 0, 0], [0, 0, 0, 0]]),
            # MQA, ragged page counts, ctx crossing page boundaries
            (2, 4, 1, 8, 4, 12, 4, 3,
             [[7, 8, 9], [15, 16, 0]])):
        q = rng.randn(s, n_q, h, d).astype(np.float32)
        kp = _pool(rng, n_pages, page, kv, d)
        vp = _pool(rng, n_pages, page, kv, d)
        perm = rng.permutation(n_pages - 1) + 1
        bt = np.zeros((s, mp), np.int32)
        k = 0
        for i in range(s):
            need = -(-max(1, max(ctx_rows[i])) // page)
            bt[i, :need] = perm[k:k + need]
            k += need
        ctx = np.asarray(ctx_rows, np.int32)
        out = np.asarray(paged_attention_multi(q, kp, vp, bt, ctx))
        ref = np.asarray(paged_attention_multi_reference(
            q, kp, vp, bt, ctx))
        err = np.abs(out - ref).max()
        assert err < 1e-5, ("multi kernel vs reference", err)
        assert np.all(np.isfinite(out))
        dead = ctx == 0
        assert np.all(out[dead] == 0.0), "masked rows must emit zeros"
        # G = 1 degenerates to the single-query kernel's exact op order
        ctx1 = ctx[:, :1]
        out1 = np.asarray(paged_attention_multi(
            q[:, :1], kp, vp, bt, ctx1))
        base = np.asarray(paged_attention(q[:, 0], kp, vp, bt,
                                          ctx1[:, 0]))
        assert out1[:, 0].tobytes() == base.tobytes(), \
            "G=1 verify kernel is not bit-identical to the decode kernel"


# -- engine section --------------------------------------------------------

def check_engine_matches_dense_generate(net):
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, VOCAB, (l,)).astype(np.int32)
               for l in (5, 11, 3)]
    eng = _engine(net)
    outs = eng.generate(prompts, max_new=7)
    for p, got in zip(prompts, outs):
        ref = list(gpt.generate(net, p[None], 7)[0, len(p):])
        assert got == ref, (got, ref)


def check_eos_and_slot_reuse(net):
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, VOCAB, (6,)).astype(np.int32)
    free_run = _engine(net).generate([prompt], max_new=8)[0]
    eos = free_run[2]           # stop at this token's FIRST occurrence
    eng = _engine(net, eos_id=int(eos))
    out = eng.generate([prompt], max_new=8)[0]
    want = free_run[:free_run.index(eos) + 1]
    assert out == want, (out, free_run)
    assert eng.sched.occupancy == 0
    _idle_pages_ok(eng)
    # slot reuse must leak no stale KV: same probe before/after churn
    probe = rng.randint(0, VOCAB, (4,)).astype(np.int32)
    eng2 = _engine(net)
    first = eng2.generate([probe], max_new=5)[0]
    for _ in range(2):
        eng2.generate([rng.randint(0, VOCAB, (rng.randint(2, 12),))
                       .astype(np.int32) for _ in range(3)], max_new=6)
    again = eng2.generate([probe], max_new=5)[0]
    assert first == again, "stale KV leaked across slot reuse"


def check_join_leave_bitexact(net):
    """THE continuous-batching invariant, bit-checked: a resident
    request's per-token logits are IDENTICAL whether it runs alone or
    with other requests joining and leaving mid-decode."""
    rng = np.random.RandomState(3)
    prompt_a = rng.randint(0, VOCAB, (6,)).astype(np.int32)
    others = [rng.randint(0, VOCAB, (l,)).astype(np.int32)
              for l in (9, 2, 13)]

    solo = _engine(net, record_logits=True)
    ra = solo.submit(prompt_a, 8)
    solo.run_until_idle()

    churn = _engine(net, record_logits=True)
    rb = churn.submit(prompt_a, 8)
    churn.step()                     # A prefilled + first decode alone
    churn.submit(others[0], 3)       # B joins mid-decode
    churn.step()
    churn.submit(others[1], 2)       # C joins; B leaves two steps later
    churn.step()
    churn.submit(others[2], 6)
    churn.run_until_idle()

    assert ra.tokens == rb.tokens, (ra.tokens, rb.tokens)
    assert len(ra.logits_trace) == len(rb.logits_trace) == 8
    for i, (la, lb) in enumerate(zip(ra.logits_trace, rb.logits_trace)):
        assert la.tobytes() == lb.tobytes(), \
            "logits for token %d differ bitwise under slot churn" % i


def check_oom_admission(net):
    """A pool too small for everyone: admission holds requests in the
    queue (never evicts a resident) and admits them as pages free up."""
    # one worst-case request needs (16 prompt + 8 new) / 8 = 3 pages;
    # a pool of 7 usable pages fits TWO residents, not three
    eng = _engine(net, num_pages=8)
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, VOCAB, (16,)).astype(np.int32)
               for _ in range(3)]
    reqs = [eng.submit(p, 8) for p in prompts]
    eng.step()
    assert eng.sched.occupancy == 2, eng.sched.occupancy
    assert eng.sched.queued == 1
    assert reqs[2].state == "queued"
    eng.run_until_idle()
    assert [r.state for r in reqs] == ["finished"] * 3
    for p, r in zip(prompts, reqs):
        ref = list(gpt.generate(net, p[None], 8)[0, len(p):])
        assert r.tokens == ref
    _idle_pages_ok(eng)
    # requests that can NEVER fit are rejected up front
    try:
        eng.submit(np.zeros(16, np.int32), 32)
        raise AssertionError("oversized request was accepted")
    except ValueError as e:
        assert "at most" in str(e)
    try:
        eng.submit(np.zeros(20, np.int32), 4)
        raise AssertionError("over-long prompt was accepted")
    except ValueError as e:
        assert "max_prefill_len" in str(e)


def check_dispatch_contract_and_telemetry(net):
    """dispatches == decode_steps + prefills exactly, 0 steady-state
    compiles across churn, with a collector's ``telemetry.pull_snapshot``
    after every engine step (a pull never forces a dispatch or a
    compile); serving telemetry populated, and on this unfaulted run
    goodput == tokens == traced token events == tokens produced."""
    from mxnet_tpu import profiler, telemetry
    eng = _engine(net)
    rng = np.random.RandomState(5)
    eng.generate([rng.randint(0, VOCAB, (4,)).astype(np.int32)], 2)
    telemetry.reset()
    profiler.reset_step_stats()
    d0, p0 = eng.decode_steps, eng.prefills
    cursor = {}

    def step_and_pull():
        nonlocal cursor
        eng.step()
        more = True
        while more:             # chunked tail, as a collector loops
            _doc, cursor, more = telemetry.pull_snapshot(
                cursor.get("req_seq"), cursor.get("step_seq"))

    eng.submit(rng.randint(0, VOCAB, (7,)).astype(np.int32), 6)
    step_and_pull()
    eng.submit(rng.randint(0, VOCAB, (12,)).astype(np.int32), 3)
    eng.submit(rng.randint(0, VOCAB, (2,)).astype(np.int32), 9)
    while not eng.sched.idle:
        step_and_pull()
    stats = profiler.step_stats()
    decode_steps = eng.decode_steps - d0
    prefills = eng.prefills - p0
    assert prefills == 3
    assert stats["dispatch_count"] == decode_steps + prefills, stats
    assert stats["compile_count"] == 0, stats
    rep = telemetry.report()
    c = rep["counters"]
    assert c["serving.requests"] == 3
    assert c["serving.prefills"] == 3
    assert c["serving.tokens"] == 6 + 3 + 9
    assert c["serving.goodput"] == 18       # nothing expired or failed
    assert telemetry.count_token_events(telemetry.request_events()) == 18
    assert rep["gauges"]["serving.batch_occupancy"] == 0  # drained
    assert rep["gauges"]["serving.kv_pages_free"] == eng.alloc.free_pages
    # the last decode step held one request at key position 9: two live
    # 8-token pages of the one 4-page block the paged kernel entered
    assert eng._pages_per_block == 4
    assert rep["gauges"]["serving.paged.block_fill"] == 0.5
    hists = rep["histograms"]
    assert hists["serving.ttft"]["count"] == 3
    assert hists["serving.tpot"]["count"] == 18 - 3
    assert hists["serving.queue_wait"]["count"] == 3
    phases = rep["phases"]
    assert phases["serve_step.dispatch"]["count"] == decode_steps
    assert phases["serve_prefill.dispatch"]["count"] == prefills
    # flight recorder carries per-decode-step records (postmortems show
    # a crashed replica's recent decode cadence)
    assert len(telemetry.flight_records()) >= decode_steps


# -- GQA: grouped-query attention in the paged kernel (ISSUE 15) -----------

def check_kernel_gqa_vs_reference():
    """K_kv < H: each KV head's page row feeds its whole query group —
    kernel vs the jnp oracle at mixed lengths, for GQA (H/2) and MQA
    (1)."""
    from mxnet_tpu.ops.pallas.paged_attention import (
        paged_attention, paged_attention_reference)
    rng = np.random.RandomState(7)
    for s, h, kv, d, page, n_pages, mp, ctx_lens in (
            (4, 4, 2, 16, 8, 16, 3, [20, 5, 24, 1]),
            (3, 4, 1, 8, 4, 12, 4, [13, 0, 16]),
            (2, 6, 3, 16, 8, 10, 2, [9, 16])):
        q = rng.randn(s, h, d).astype(np.float32)
        kp = _pool(rng, n_pages, page, kv, d)
        vp = _pool(rng, n_pages, page, kv, d)
        perm = rng.permutation(n_pages - 1) + 1
        bt = np.zeros((s, mp), np.int32)
        k = 0
        for i in range(s):
            need = -(-max(1, ctx_lens[i]) // page)
            bt[i, :need] = perm[k:k + need]
            k += need
        ctx = np.asarray(ctx_lens, np.int32)
        out = np.asarray(paged_attention(q, kp, vp, bt, ctx))
        ref = np.asarray(paged_attention_reference(q, kp, vp, bt, ctx))
        err = np.abs(out - ref).max()
        assert err < 1e-5, ("gqa kernel vs reference", h, kv, err)
        assert np.all(np.isfinite(out))


def check_gqa_engine_self_consistent(net):
    """The engine-level GQA invariants: a kv_heads-reduced engine keeps
    the join/leave bit-exactness contract (occupancy is still a mask),
    EOS leave releases pages, and its pools really are K_kv-shaped."""
    rng = np.random.RandomState(8)
    prompt_a = rng.randint(0, VOCAB, (6,)).astype(np.int32)
    others = [rng.randint(0, VOCAB, (l,)).astype(np.int32)
              for l in (9, 2, 13)]
    solo = _engine(net, kv_heads=1, record_logits=True)
    assert solo._kv[0][0].shape[2] == solo._head_dim      # K_kv 1
    ra = solo.submit(prompt_a, 8)
    solo.run_until_idle()
    churn = _engine(net, kv_heads=1, record_logits=True)
    rb = churn.submit(prompt_a, 8)
    churn.step()
    churn.submit(others[0], 3)
    churn.step()
    churn.submit(others[1], 2)
    churn.step()
    churn.submit(others[2], 6)
    churn.run_until_idle()
    assert ra.tokens == rb.tokens, (ra.tokens, rb.tokens)
    for i, (la, lb) in enumerate(zip(ra.logits_trace, rb.logits_trace)):
        assert la.tobytes() == lb.tobytes(), \
            "GQA logits for token %d differ bitwise under churn" % i
    _idle_pages_ok(churn)


def check_gqa_capacity_multiplier(net):
    """THE capacity acceptance: at K_kv = H/2 the same page-pool BYTES
    hold >= 1.5x the resident sequences.  Bytes per page scale with
    K_kv, so the same budget buys 2x pages; identical worst-case
    requests then admit ~2x residents (prefix cache off — capacity of
    UNIQUE prompts is the honest baseline)."""
    rng = np.random.RandomState(9)
    n_heads = net.blocks._children[0].attn._num_heads
    assert n_heads % 2 == 0
    pool_pages = 7              # usable pages at K_kv = H
    kw = dict(num_slots=8, page_size=8, max_prefill_len=16,
              max_seq_len=32, prefix_cache=False)
    eng_mha = _engine(net, num_pages=pool_pages, kv_heads=n_heads, **kw)
    # same bytes at half the KV heads: every page is half the size, so
    # ~2x the pages fit the identical pool-byte budget
    eng_gqa = _engine(net, num_pages=2 * pool_pages - 1,
                      kv_heads=n_heads // 2, **kw)
    assert eng_gqa._kv[0][0].nbytes <= eng_mha._kv[0][0].nbytes, \
        (eng_gqa._kv[0][0].nbytes, eng_mha._kv[0][0].nbytes)

    def residents(eng):
        # identical worst-case requests: 16 prompt + 8 new = 3 pages
        for _ in range(8):
            eng.submit(rng.randint(0, VOCAB, (16,)).astype(np.int32), 8)
        eng.step()
        occ = eng.sched.occupancy
        eng.run_until_idle()
        return occ

    occ_mha = residents(eng_mha)
    occ_gqa = residents(eng_gqa)
    assert occ_gqa >= 1.5 * occ_mha, (occ_mha, occ_gqa)
    assert occ_mha == 2 and occ_gqa == 4, (occ_mha, occ_gqa)


# -- prefix caching (ISSUE 15) ----------------------------------------------

def check_prefix_sharing_and_cow(net):
    """Shared-system-prompt admissions: page-aligned prefix hits map
    shared pages (refcounted) and prefill only the suffix; a prompt
    that diverges or ends mid-page copy-on-writes the boundary page.
    Tokens stay correct vs the dense reference in every case, and page
    conservation (with refcounts) holds after churn.  Uses the
    ENGINE_KW shapes, so inside the ``engine`` section the programs
    come off the in-process AOT memo (tier-1 compile budget)."""
    from mxnet_tpu import telemetry
    rng = np.random.RandomState(10)
    eng = _engine(net)                    # page_size 8, prefill pad 16
    assert eng._prefix is not None
    sysp = rng.randint(0, VOCAB, (8,)).astype(np.int32)  # 1 full page
    # pa is 16 tokens = 2 FULL pages: both cache after its prefill
    pa = np.concatenate([sysp, rng.randint(0, VOCAB, (8,))
                         .astype(np.int32)])
    pb = np.concatenate([sysp, rng.randint(0, VOCAB, (5,))
                         .astype(np.int32)])
    pt0 = telemetry.counter("serving.prefill_tokens").value
    ra = eng.generate([pa], 4)[0]
    pt_a = telemetry.counter("serving.prefill_tokens").value - pt0
    assert pt_a == pa.size                       # miss: full prefill
    rb_req = eng.submit(pb, 4)
    eng.run_until_idle()
    rb = rb_req.tokens
    assert rb_req.prefix_len == 8 and rb_req.shared_count == 1
    assert rb_req.cow_src is None               # aligned hit: no COW
    pt_b = telemetry.counter("serving.prefill_tokens").value - pt0 - pt_a
    assert pt_b == pb.size - 8                   # only the suffix
    assert ra == list(gpt.generate(net, pa[None], 4)[0, len(pa):])
    assert rb == list(gpt.generate(net, pb[None], 4)[0, len(pb):])

    # mid-page divergence: shares 1 full page + COWs the second
    pc = np.concatenate([pa[:11], rng.randint(0, VOCAB, (2,))
                         .astype(np.int32)])
    rc = eng.submit(pc, 4)
    eng.run_until_idle()
    assert rc.cow_src is not None and rc.cow_dst is not None
    assert rc.prefix_len == 11, rc.prefix_len
    assert rc.tokens == list(gpt.generate(net, pc[None], 4)
                             [0, len(pc):])
    # page-aligned FULL-prompt hit: capped at prompt-1 -> COW again
    pd = pa[:8].copy()
    rd = eng.submit(pd, 4)
    eng.run_until_idle()
    assert rd.prefix_len == 7 and rd.cow_src is not None
    assert rd.tokens == list(gpt.generate(net, pd[None], 4)
                             [0, len(pd):])
    _idle_pages_ok(eng)
    c = telemetry.report()["counters"]
    assert c["serving.prefix.hits"] >= 3
    assert c["serving.prefix.cow_copies"] >= 2
    assert c["serving.prefix.shared_pages"] >= 2


def check_prefix_cache_off_token_identity(net):
    """Cache-off and cache-on engines emit IDENTICAL tokens on a
    shared-prefix workload, greedy and sampled alike (the cache changes
    capacity and prefill cost, never tokens), and the cache-off engine
    leaves zero pages behind."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.serving import SamplingParams
    rng = np.random.RandomState(11)
    sysp = rng.randint(0, VOCAB, (8,)).astype(np.int32)
    prompts = [np.concatenate([sysp, rng.randint(0, VOCAB, (l,))
                               .astype(np.int32)]) for l in (3, 5, 2)]
    on = _engine(net, max_prefill_len=16, max_seq_len=32)
    off = _engine(net, max_prefill_len=16, max_seq_len=32,
                  prefix_cache=False)
    assert off._prefix is None
    toks_on = on.generate(prompts, 6)
    toks_off = off.generate(prompts, 6)
    assert toks_on == toks_off, (toks_on, toks_off)
    # the same prompts again, every other one sampled: these admissions
    # HIT the pages the first round cached
    samp = [None, SamplingParams(temperature=0.8, top_k=24, top_p=0.95,
                                 seed=4001), None]
    hits0 = telemetry.counter("serving.prefix.hits").value
    samp_on = on.generate(prompts, 6, sampling=samp)
    assert telemetry.counter("serving.prefix.hits").value >= hits0 + 3
    samp_off = off.generate(prompts, 6, sampling=samp)
    assert samp_on == samp_off, (samp_on, samp_off)
    assert samp_on[1] != toks_on[1], "the sampled request ran greedy"
    assert off.alloc.used_pages == 0
    _idle_pages_ok(on)


def check_prefix_eviction_under_pressure(net):
    """A pool mostly pinned by cached prefixes must still admit new
    (non-matching) requests: admission evicts LRU cache entries instead
    of queueing forever, and conservation holds throughout."""
    rng = np.random.RandomState(12)
    # 9 usable pages; each 16-token prompt caches 2 pages after its
    # 3-page reservation frees
    eng = _engine(net, page_size=8, max_prefill_len=16, max_seq_len=32,
                  num_pages=10, num_slots=2)
    for i in range(3):
        p = rng.randint(0, VOCAB, (16,)).astype(np.int32)
        out = eng.generate([p], 4)[0]
        assert len(out) == 4
        eng.alloc.assert_conservation()
    # the cache now pins 6 of 9 pages; a fresh request needs 3
    p = rng.randint(0, VOCAB, (16,)).astype(np.int32)
    r = eng.submit(p, 8)
    eng.run_until_idle()
    assert r.verdict == "completed"
    assert r.tokens == list(gpt.generate(net, p[None], 8)[0, len(p):])
    _idle_pages_ok(eng)


# -- per-request sampling (ISSUE 15) ----------------------------------------

def check_sampling_laws(net):
    """Sampling-decode laws at the engine level: seeded reproducibility,
    greedy-equals-argmax (temp 0 and top_k 1), and per-request isolation
    (a greedy resident's tokens are untouched by sampled neighbors);
    a request's sampling parameters are program INPUTS: with the prefix
    cache on, new parameters cost no compile and no extra dispatch."""
    from mxnet_tpu.serving import SamplingParams
    rng = np.random.RandomState(13)
    p0 = rng.randint(0, VOCAB, (6,)).astype(np.int32)
    p1 = rng.randint(0, VOCAB, (9,)).astype(np.int32)
    eng = _engine(net)
    assert eng._prefix is not None
    sp = SamplingParams(temperature=0.9, top_k=16, top_p=0.95, seed=3)
    a = eng.generate([p0], 6, sampling=sp)[0]
    b = eng.generate([p0], 6, sampling=sp)[0]
    assert a == b, "same seed+params must reproduce exactly"
    with _one_program_a_step(eng):
        c = eng.generate([p0], 6, sampling=SamplingParams(
            temperature=0.9, top_k=16, top_p=0.95, seed=4))[0]
    assert a != c, "different seeds produced identical 6-token runs"
    # top_k=1 at any temperature is argmax — equals the greedy engine
    greedy = eng.generate([p0], 6)[0]
    k1 = eng.generate([p0], 6,
                      sampling=SamplingParams(temperature=1.7, top_k=1,
                                              seed=9))[0]
    assert k1 == greedy, (k1, greedy)
    # greedy resident untouched by a sampled neighbor (per-slot params)
    both = _engine(net)
    rg = both.submit(p1, 6)
    both.step()
    both.submit(p0, 6, sampling=sp)
    both.run_until_idle()
    assert rg.tokens == _ref(net, p1, 6), (rg.tokens)
    _idle_pages_ok(both)


# -- speculative decoding (ISSUE 16) ----------------------------------------

def _periodic(rng, n, period=3):
    """A prompt whose greedy continuation the n-gram drafter can hit:
    small random-weight GPTs continue periodic contexts periodically,
    so these prompts make the spec checks non-vacuous (drafts actually
    get accepted) without depending on any particular weight draw for
    CORRECTNESS — the laws below hold for arbitrary acceptance."""
    return np.resize(rng.randint(0, VOCAB, (period,)).astype(np.int32),
                     n)


def _draftable_probe(net, rng, n_new, spec_k=4, tries=16):
    """A solo prompt on which the n-gram drafter provably lands a hit:
    replay the drafter along the dense reference stream and keep the
    first periodic prompt where some draft's head equals the model's
    next greedy token early enough for the accepted token to count.
    "Small GPTs continue periodic contexts periodically" is a tendency
    of one weight draw on one toolchain, not a law — a probe the
    drafter never hits makes the steps comparison below vacuous."""
    from mxnet_tpu.serving.engine import ngram_draft
    for _ in range(tries):
        probe = _periodic(rng, 10)
        ref = _ref(net, probe, n_new)
        for i in range(1, n_new - 1):
            ctx = np.concatenate([probe, np.asarray(ref[:i], np.int32)])
            if ngram_draft(ctx, spec_k)[:1] == [int(ref[i])]:
                return probe, ref
    raise AssertionError("no draftable probe in %d tries" % tries)


def check_spec_greedy_laws(net):
    """THE spec-decode determinism law, fast tier: a spec-on engine's
    greedy stream is BIT-identical to the dense reference (== spec-off)
    at mixed ragged lengths under staggered joins/leaves; drafting is
    non-vacuous (accepted > 0) and cuts decode steps on a draftable
    prompt; speculative page marks never outlive a step.  One spec
    config (spec_k=4) so this whole block pays a single extra
    compile set; later spec checks reuse the engine via the in-process
    AOT memo."""
    from mxnet_tpu import telemetry
    rng = np.random.RandomState(16)
    # ctor validation: draft positions must fit the wpe table
    try:
        _engine(net, spec_k=MAX_LEN - ENGINE_KW["max_seq_len"] + 1)
        raise AssertionError("oversized spec_k accepted")
    except ValueError as e:
        assert "spec_k" in str(e)

    on = _engine(net, spec_k=4)
    prompts = [_periodic(rng, 12), rng.randint(0, VOCAB, (5,))
               .astype(np.int32), _periodic(rng, 7)]
    news = (8, 6, 7)
    dt0 = telemetry.counter("serving.spec.draft_tokens").value
    ac0 = telemetry.counter("serving.spec.accepted").value
    rj0 = telemetry.counter("serving.spec.rejected").value
    handles = []
    for p, n in zip(prompts, news):
        handles.append(on.submit(p, n))
        on.step()                    # staggered joins; finishers leave
    on.run_until_idle()
    for h, p, n in zip(handles, prompts, news):
        assert h.tokens == _ref(net, p, n), (h.tokens, _ref(net, p, n))
    drafted = telemetry.counter("serving.spec.draft_tokens").value - dt0
    accepted = telemetry.counter("serving.spec.accepted").value - ac0
    rejected = telemetry.counter("serving.spec.rejected").value - rj0
    assert drafted > 0 and accepted > 0, (drafted, accepted)
    # the counters reconcile: every draft is accepted or rejected, and
    # every decode token is a slot's own step or an accepted draft the
    # host did not have to drop
    assert drafted == accepted + rejected, (drafted, accepted, rejected)
    assert sum(news) - on.prefills == \
        on.spec_slot_steps + accepted - on.spec_discarded, \
        (news, on.prefills, on.spec_slot_steps, accepted,
         on.spec_discarded)
    _idle_pages_ok(on)
    assert on.alloc.speculative_pages == 0

    # never more decode steps than spec-off for the same tokens, and
    # more than one token per decode dispatch where the drafter hits
    # (the whole point): solo draftable prompt, spec-off takes one step
    # per token after the prefill's first
    probe, ref = _draftable_probe(net, rng, 10)
    off = _engine(net)
    d_on0, d_off0 = on.decode_steps, off.decode_steps
    # draft, verify and accept ride the ONE program: a draft length is
    # a mask, never a shape
    with _one_program_a_step(on):
        t_on = on.generate([probe], 10)[0]
    t_off = off.generate([probe], 10)[0]
    assert t_on == t_off == ref
    steps_on = on.decode_steps - d_on0
    steps_off = off.decode_steps - d_off0
    assert steps_on <= steps_off, (steps_on, steps_off)
    assert (len(t_on) - 1) / steps_on > 1.0, (len(t_on), steps_on)

    # per-request override: spec_k=0 rides the SAME spec program with
    # an empty draft — no drafting for this request, same tokens
    dt1 = telemetry.counter("serving.spec.draft_tokens").value
    r = on.submit(probe, 5, spec_k=0)
    on.run_until_idle()
    assert r.tokens == _ref(net, probe, 5)
    assert telemetry.counter("serving.spec.draft_tokens").value == dt1
    return on


def check_spec_poison_drill(net, on):
    """The serve.spec.poison drill: every draft corrupted between draft
    and verify — verification must reject the poison and the emitted
    stream stay EXACTLY the non-speculative greedy chain
    (self-correction is the safety property, not draft quality)."""
    from mxnet_tpu import fault, telemetry
    rng = np.random.RandomState(17)
    prompt = _periodic(rng, 11)
    rej0 = telemetry.counter("serving.spec.rejected").value
    fault.configure("serve.spec.poison:999")
    try:
        out = on.generate([prompt], 8)[0]
        fired = fault.fire_count("serve.spec.poison")
    finally:
        fault.reset()
    assert fired >= 1, "the poison site never fired (drill vacuous)"
    assert out == _ref(net, prompt, 8), \
        "poisoned drafts leaked into the emitted stream"
    assert telemetry.counter("serving.spec.rejected").value > rej0
    _idle_pages_ok(on)
    assert on.alloc.speculative_pages == 0


def check_spec_k_sweep(net):
    """Exhaustive spec_k sweep (slow tier: every k compiles its own
    decode program): greedy bit-identity, sampled seeded
    reproducibility, and page accounting at k = 1, 2, 8 and 16 — 16 is
    the wpe boundary (max_seq_len + k == the net's max_len)."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.serving import SamplingParams
    rng = np.random.RandomState(18)
    prompts = [_periodic(rng, 11), rng.randint(0, VOCAB, (4,))
               .astype(np.int32), _periodic(rng, 6, period=2)]
    refs = [_ref(net, p, 8) for p in prompts]
    ac0 = telemetry.counter("serving.spec.accepted").value
    for k in (1, 2, 8, 16):
        eng = _engine(net, spec_k=k)
        handles = []
        for p in prompts:
            handles.append(eng.submit(p, 8))
            eng.step()
        eng.run_until_idle()
        for h, ref in zip(handles, refs):
            assert h.tokens == ref, (k, h.tokens, ref)
        sp = SamplingParams(temperature=0.8, top_k=24, seed=7)
        a = eng.generate([prompts[0]], 6, sampling=sp)[0]
        b = eng.generate([prompts[0]], 6, sampling=sp)[0]
        assert a == b, "sampled spec stream failed to reproduce at k=%d" % k
        _idle_pages_ok(eng)
        assert eng.alloc.speculative_pages == 0
    assert telemetry.counter("serving.spec.accepted").value > ac0


# -- streamed delivery (ISSUE 19; rides the engine section's AOT memo) -----

def check_stream_cursor_laws(net):
    """Cursor laws at the engine: chunks reassemble to the unary
    stream, re-polling a cursor is idempotent, ``more=False`` carries
    the terminal verdict, and polling never dispatches or recompiles
    (it reads a host-side buffer)."""
    from mxnet_tpu import profiler
    rng = np.random.RandomState(19)
    prompt = rng.randint(0, VOCAB, (6,)).astype(np.int32)
    ref = _ref(net, prompt, 8)
    eng = _engine(net)
    eng.generate([prompt[:4]], max_new=2)        # warm (AOT memo)
    profiler.reset_step_stats()
    req = eng.submit(prompt, 8)
    assembled = []
    while not req.done:
        eng.step()
        reply = eng.poll(req.trace, cursor=len(assembled))
        assert reply["cursor"] == len(assembled) + len(reply["tokens"])
        assembled += reply["tokens"]
    tail = eng.poll(req.trace, cursor=len(assembled))
    assembled += tail["tokens"]
    assert assembled == ref == req.tokens, (assembled, ref)
    assert tail["more"] is False and tail["verdict"] == "completed"
    # idempotence + bounded chunks: same cursor, same slice, twice
    a = eng.poll(req.trace, cursor=2, max_tokens=3)
    b = eng.poll(req.trace, cursor=2, max_tokens=3)
    assert a["tokens"] == b["tokens"] == ref[2:5]
    assert a["more"] is True               # terminal but not drained
    stats = profiler.step_stats()
    assert stats.get("compile_count", 0) == 0, \
        "polling recompiled: %s" % stats
    assert eng.decode_steps == len(ref), \
        (eng.decode_steps, len(ref))       # 1.0 dispatch per token step
    # unknown trace: a typed None, never a crash
    assert eng.poll("never-a-trace", 0) is None
    # TTL expiry: terminal buffers past stream_ttl_s sweep away and a
    # late poll is a DECLARED unknown (serving.stream.expired counts)
    eng.stream_ttl_s = 0.0
    eng.sweep_streams()
    assert eng.poll(req.trace, cursor=0) is None
    _idle_pages_ok(eng)


def check_stream_cancel(net):
    """The typed ``cancelled`` verdict: mid-decode (slot + pages
    released between decode steps) AND queued; idempotent; survivors'
    streams bit-identical to their unfaulted references."""
    rng = np.random.RandomState(20)
    prompts = [rng.randint(0, VOCAB, (6,)).astype(np.int32)
               for _ in range(4)]                # num_slots=3 → 1 queues
    refs = [_ref(net, p, 8) for p in prompts]
    eng = _engine(net)
    free0 = eng.alloc.free_pages
    reqs = [eng.submit(p, 8) for p in prompts]
    eng.step()
    assert reqs[3].state == "queued"
    eng.step()
    mid = eng.cancel(reqs[1].trace)              # resident, mid-decode
    assert mid["verdict"] == "cancelled"
    assert reqs[1].done and reqs[1].verdict == "cancelled"
    assert 0 < len(reqs[1].tokens) < 8           # partial tokens kept
    que = eng.cancel(reqs[3].trace)              # still queued
    assert que["verdict"] == "cancelled"
    again = eng.cancel(reqs[1].trace)            # idempotent no-op
    assert again["verdict"] == "cancelled"
    eng.run_until_idle()
    for i in (0, 2):
        assert reqs[i].state == "finished"
        assert reqs[i].tokens == refs[i], \
            "cancel perturbed survivor %d" % i
    cached = 0 if eng._prefix is None else eng._prefix.cached_pages
    assert eng.alloc.free_pages == free0 - cached
    _idle_pages_ok(eng)


def check_stream_abandon_reclaim(net):
    """A client vanishes: its poller falls silent mid-stream while the
    process lives on, and after MXTPU_SERVE_ABANDON_S the sweep
    reclaims the orphans with the typed ``abandoned`` verdict — pages
    back in the pool, conservation green, the still-polling survivor
    and the never-polled UNARY request both untouched."""
    import time as _time
    from mxnet_tpu import telemetry
    rng = np.random.RandomState(21)
    prompts = [rng.randint(0, VOCAB, (5,)).astype(np.int32)
               for _ in range(3)]
    refs = [_ref(net, p, 8) for p in prompts]
    os.environ["MXTPU_SERVE_ABANDON_S"] = "0.05"
    try:
        eng = _engine(net)
    finally:
        del os.environ["MXTPU_SERVE_ABANDON_S"]
    assert eng.abandon_s == 0.05
    c0 = telemetry.counter("serving.stream.abandoned").value
    reqs = [eng.submit(p, 8) for p in prompts]
    # reqs[0] and reqs[1] become STREAMS (polled); reqs[2] stays unary
    cursors = [0, 0]
    vanished = set()
    for step in range(40):
        if all(r.done for r in reqs):
            break
        eng.step()
        for i in (0, 1):
            if i in vanished or reqs[i].done:
                continue
            if i == 1 and step >= 2:
                vanished.add(i)          # poller dies; process lives
                continue
            reply = eng.poll(reqs[i].trace, cursor=cursors[i])
            cursors[i] += len(reply["tokens"])
        _time.sleep(0.02)                # real time ages last_poll_t
    assert vanished == {1}
    assert reqs[1].done and reqs[1].verdict == "abandoned", \
        (reqs[1].state, reqs[1].verdict)
    assert telemetry.counter("serving.stream.abandoned").value > c0
    assert eng.snapshot()["stream"]["abandoned"] >= 1
    # the default rule watching that counter fires (here, or at an
    # earlier drain of the registry inside this check)
    telemetry.check_alerts()
    assert "orphan_reclaim" in [
        e["args"]["rule"] for e in telemetry.request_events()
        if e["event"] == "alert"]
    # the survivor poller and the unary request were NEVER reclaimed
    assert reqs[0].state == "finished" and reqs[0].tokens == refs[0]
    assert reqs[2].state == "finished" and reqs[2].tokens == refs[2], \
        "a never-polled unary request must not be swept as an orphan"
    _idle_pages_ok(eng)


# -- quantized KV pages (ISSUE 20) ------------------------------------------

def check_kvq_pools_and_scale_accounting(net):
    """int8 engine laws, fast tier (ONE extra compile set for the whole
    kvq block; later checks reuse the engine / the AOT memo): 4-tuple
    pools with fp32 ``[num_pages, K_kv]`` absmax scale rows, the
    allocator as the ONE byte authority, conservation + finite scales
    after staggered churn, and greedy determinism quantized-to-ITSELF
    (a fresh identically-configured engine replays the exact streams —
    bit-identity to the fp path is explicitly NOT the law)."""
    import jax.numpy as jnp
    eng = _engine(net, kv_dtype="int8")
    assert eng.kv_dtype == "int8" and eng.alloc.kv_dtype == "int8"
    assert eng.alloc.kv_itemsize == 1
    kc, vc, ks, vs = eng._kv[0]
    assert kc.dtype == jnp.int8 and vc.dtype == jnp.int8
    assert ks.dtype == jnp.float32 and vs.dtype == jnp.float32
    assert ks.shape == vs.shape == (eng.alloc.num_pages, eng.kv_heads)
    # the allocator's page_bytes is the byte authority: the device
    # pools weigh exactly num_pages * page_bytes per layer
    total = sum(sum(np.asarray(a).nbytes for a in entry)
                for entry in eng._kv)
    assert total == (eng._n_layers * eng.alloc.num_pages
                     * eng.alloc.page_bytes(eng.kv_heads,
                                            eng._head_dim)), total
    fp32 = _engine(net)
    assert eng.kv_bytes_per_token < fp32.kv_bytes_per_token / 3.0

    rng = np.random.RandomState(30)
    prompts = [rng.randint(0, VOCAB, (l,)).astype(np.int32)
               for l in (11, 4, 7)]
    handles = []
    for p in prompts:
        handles.append(eng.submit(p, 6))
        eng.step()                        # staggered joins
    eng.run_until_idle()
    twin = _engine(net, kv_dtype="int8")  # AOT-memo hit, fresh pools
    for h, p in zip(handles, prompts):
        assert h.verdict == "completed"
        assert h.tokens == twin.generate([p], 6)[0], \
            "quantized greedy failed to reproduce on a twin engine"
    for entry in eng._kv:
        assert np.isfinite(np.asarray(entry[2])).all()
        assert np.isfinite(np.asarray(entry[3])).all()
    _idle_pages_ok(eng)
    return eng


def check_kvq_cow_copies_scales(net, eng):
    """Prefix COW on quantized pages copies BYTES AND SCALES: a
    mid-page divergence off a cached int8 page must stream exactly what
    a cache-off int8 engine streams (a dropped or stale scale would
    corrupt every dequantized read of the copied page), with the
    cow_dst scale grow-only from the donor's."""
    rng = np.random.RandomState(31)
    pa = rng.randint(0, VOCAB, (16,)).astype(np.int32)  # 2 FULL pages
    off = _engine(net, kv_dtype="int8", prefix_cache=False)
    ra = eng.generate([pa], 4)[0]        # miss; caches both pages
    assert ra == off.generate([pa], 4)[0]
    pc = np.concatenate([pa[:11], rng.randint(0, VOCAB, (2,))
                         .astype(np.int32)])
    rc = eng.submit(pc, 4)
    eng.step()
    assert rc.cow_src is not None and rc.cow_dst is not None
    ks = np.asarray(eng._kv[0][2])
    assert np.isfinite(ks[rc.cow_dst]).all()
    # grow-only scatter: the copied page's scale never shrinks below
    # the donor's (suffix rows can only max it upward)
    assert (ks[rc.cow_dst] >= ks[rc.cow_src] - 1e-7).all(), \
        (ks[rc.cow_dst], ks[rc.cow_src])
    eng.run_until_idle()
    assert rc.tokens == off.generate([pc], 4)[0], \
        "COW page diverged from the cache-off quantized stream"
    _idle_pages_ok(eng)


def check_kvq_spec_rollback_scales(net):
    """Speculative decoding over int8 pages: rejected draft positions
    roll back with NO stale scale slots — the spec stream equals the
    plain int8 engine's greedy stream, and (under the serve.spec.poison
    drill, which forces every draft to be REJECTED) the rollback still
    leaves clear speculative marks and finite scales everywhere."""
    from mxnet_tpu import fault, telemetry
    rng = np.random.RandomState(32)
    spec = _engine(net, kv_dtype="int8", spec_k=4)
    plain = _engine(net, kv_dtype="int8")
    prompts = [_periodic(rng, 12), rng.randint(0, VOCAB, (5,))
               .astype(np.int32), _periodic(rng, 7)]
    handles = []
    for p in prompts:
        handles.append(spec.submit(p, 7))
        spec.step()
    spec.run_until_idle()
    for h, p in zip(handles, prompts):
        assert h.tokens == plain.generate([p], 7)[0], \
            "int8 spec stream diverged from the int8 plain engine"
    # force mass rejection (the rollback path) with poisoned drafts:
    # the emitted stream must still be the plain quantized chain
    rej0 = telemetry.counter("serving.spec.rejected").value
    fault.configure("serve.spec.poison:999")
    try:
        out = spec.generate([prompts[0]], 7)[0]
    finally:
        fault.reset()
    assert out == handles[0].tokens, \
        "poisoned drafts leaked into the quantized stream"
    assert telemetry.counter("serving.spec.rejected").value > rej0, \
        "no rejection happened — the rollback path was not exercised"
    assert spec.alloc.speculative_pages == 0
    for entry in spec._kv:
        assert np.isfinite(np.asarray(entry[2])).all()
        assert np.isfinite(np.asarray(entry[3])).all()
    _idle_pages_ok(spec)
    return plain


def check_kvq_sampled_determinism_swap_failover(net, eng, plain):
    """Per-request SAMPLED determinism quantized-to-itself across
    churn, hot-swap, and failover: the same seeded request reproduces
    bit-exactly on the original engine under neighbor churn, across a
    same-weights hot-swap mid-decode, and on a replacement engine (the
    failover re-decode path)."""
    from mxnet_tpu.serving import SamplingParams
    rng = np.random.RandomState(33)
    p0 = rng.randint(0, VOCAB, (6,)).astype(np.int32)
    p1 = rng.randint(0, VOCAB, (9,)).astype(np.int32)
    sp = SamplingParams(temperature=0.9, top_k=16, top_p=0.95, seed=5)
    # churn: a greedy neighbor joins mid-flight.  Quantize-on-scatter
    # and the kernel's dequant live INSIDE the one donated program: a
    # dispatch a decode step and a prefill, no compile
    with _one_program_a_step(eng):
        r = eng.submit(p0, 6, sampling=sp)
        eng.step()
        eng.submit(p1, 5)
        eng.run_until_idle()
    want = r.tokens
    assert eng.generate([p0], 6, sampling=sp)[0] == want
    # hot-swap with identical weights mid-decode: stream unchanged
    r2 = eng.submit(p0, 6, sampling=sp)
    eng.step()
    eng.swap_params(eng.params_from_net(net))
    eng.run_until_idle()
    assert r2.tokens == want, "hot-swap perturbed a sampled stream"
    # failover: a replacement engine re-decodes the same request
    assert plain.generate([p0], 6, sampling=sp)[0] == want, \
        "failover replacement diverged on a sampled quantized stream"
    _idle_pages_ok(eng)


def check_kvq_scale_poison_drill(net, eng):
    """The ``serve.kv.scale_poison`` drill: one resident page's scale
    NaN-poisoned between steps — the quantized divergence guard sees
    non-finite victim logits, discards that step's output, and
    re-prefills the victim's committed context; the victim still
    completes with its unfaulted stream, neighbors never notice, one
    ``serving.kv.scale_repairs`` tick, conservation green."""
    from mxnet_tpu import fault, telemetry
    rng = np.random.RandomState(34)
    pa = rng.randint(0, VOCAB, (9,)).astype(np.int32)
    pb = rng.randint(0, VOCAB, (5,)).astype(np.int32)
    want_a = eng.generate([pa], 8)[0]     # unfaulted references
    want_b = eng.generate([pb], 8)[0]
    rep0 = telemetry.counter("serving.kv.scale_repairs").value
    ra = eng.submit(pa, 8)
    eng.step()                            # ra resident -> the victim
    rb = eng.submit(pb, 8)
    fault.configure("serve.kv.scale_poison:1")
    try:
        eng.run_until_idle()
        fired = fault.fire_count("serve.kv.scale_poison")
    finally:
        fault.reset()
    assert fired == 1, "the scale-poison site never fired"
    assert ra.verdict == "completed" and rb.verdict == "completed"
    assert ra.tokens == want_a, "victim re-prefill diverged"
    assert rb.tokens == want_b, "a neighbor was perturbed by the repair"
    assert telemetry.counter("serving.kv.scale_repairs").value \
        == rep0 + 1
    for entry in eng._kv:
        assert np.isfinite(np.asarray(entry[2])).all()
        assert np.isfinite(np.asarray(entry[3])).all()
    _idle_pages_ok(eng)


def check_kvq_dtype_sweep(net):
    """Exhaustive kv_dtype sweep (slow tier: every mode+shape compiles
    its own serving programs): fp32 stays bit-identical to the dense
    reference at off-default shapes, bf16/int8 reproduce on twin
    engines (pinned to themselves), bytes/token strictly ordered fp32 >
    bf16 > int8, the GQA x int8 composition multiplies, and the env
    opt-in wires through."""
    rng = np.random.RandomState(35)
    kw = dict(num_slots=2, page_size=4, max_prefill_len=12,
              max_seq_len=24)
    prompts = [rng.randint(0, VOCAB, (l,)).astype(np.int32)
               for l in (10, 3)]
    bpt = {}
    for dt in ("fp32", "bf16", "int8"):
        a = _engine(net, kv_dtype=dt, **kw)
        b = _engine(net, kv_dtype=dt, **kw)
        bpt[dt] = a.kv_bytes_per_token
        ta = [a.generate([p], 6)[0] for p in prompts]
        tb = [b.generate([p], 6)[0] for p in prompts]
        assert ta == tb, "kv_dtype=%s failed to reproduce on a twin" % dt
        if dt == "fp32":
            for p, t in zip(prompts, ta):
                assert t == _ref(net, p, 6), \
                    "fp32 pools must stay bit-identical to dense"
        _idle_pages_ok(a)
        _idle_pages_ok(b)
    assert bpt["fp32"] > bpt["bf16"] > bpt["int8"], bpt
    # the same pool bytes hold >= 1.8x the tokens in int8 as in bf16:
    # the payload halves, a page's scale rows cost 8 * K_kv bytes
    assert bpt["int8"] < bpt["bf16"] / 1.8, bpt
    # GQA x int8 composition: K_kv = H/2 halves the rows int8 already
    # quartered — bytes/token divides multiplicatively
    gqa8 = _engine(net, kv_dtype="int8", kv_heads=HEADS // 2, **kw)
    assert gqa8.kv_bytes_per_token < bpt["int8"] / 1.8
    t1 = [gqa8.generate([p], 6)[0] for p in prompts]
    gqa8b = _engine(net, kv_dtype="int8", kv_heads=HEADS // 2, **kw)
    assert t1 == [gqa8b.generate([p], 6)[0] for p in prompts]
    _idle_pages_ok(gqa8)
    # env opt-in: MXTPU_SERVE_KV_DTYPE picks the mode when the ctor
    # arg is absent; a typo must refuse to serve
    os.environ["MXTPU_SERVE_KV_DTYPE"] = "int8"
    try:
        e = _engine(net, **kw)
        assert e.kv_dtype == "int8"
        os.environ["MXTPU_SERVE_KV_DTYPE"] = "int9"
        try:
            _engine(net, **kw)
            raise AssertionError("typo'd MXTPU_SERVE_KV_DTYPE accepted")
        except ValueError as exc:
            assert "kv_dtype" in str(exc)
    finally:
        del os.environ["MXTPU_SERVE_KV_DTYPE"]


def check_kvq_greedy_match_rate_vs_fp():
    """Quantized greedy is pinned to ITSELF; how far it drifts from the
    fp path is pinned here as a count: on a seeded mix of 24 requests
    (prompts 4-24, 8-24 new tokens; a 2-layer 128-wide net, pages of 8
    so an absmax scale covers 8 rows) at least 99% of the int8
    engine's greedy tokens are the fp32 engine's."""
    np.random.seed(0)
    mx.random.seed(0)
    net = gpt.GPTLM(256, 2, 128, 4, max_len=64)
    net.initialize()
    rng = np.random.RandomState(7)
    prompts, news = [], []
    for _ in range(24):
        rng.exponential(0.004)      # the mix's arrival draw, unused here
        prompts.append(rng.randint(0, 256, int(rng.randint(4, 25)))
                       .astype(np.int32))
        news.append(int(rng.randint(8, 25)))
    kw = dict(num_slots=8, page_size=8, max_prefill_len=32,
              max_seq_len=48)
    streams = {}
    for dt in ("fp32", "int8"):
        eng = _engine(net, kv_dtype=dt, **kw)
        reqs = [eng.submit(p, n) for p, n in zip(prompts, news)]
        eng.run_until_idle()
        streams[dt] = [r.tokens for r in reqs]
        _idle_pages_ok(eng)
    total = sum(news)
    matched = sum(a == b for got, want in zip(streams["int8"],
                                              streams["fp32"])
                  for a, b in zip(got, want))
    assert sum(len(t) for t in streams["fp32"]) == total
    assert matched >= 0.99 * total, (matched, total)


def main(section):
    if section in ("kernel", "all"):
        check_kernel_vs_reference_mixed_lengths()
        check_kernel_empty_slot_zero()
        check_kernel_vs_dense_flash()
        check_kernel_gqa_vs_reference()
        check_kernel_multi_vs_reference()
        print("SERVING_KERNEL_OK")
    if section in ("engine", "all"):
        net = _net()
        check_engine_matches_dense_generate(net)
        check_eos_and_slot_reuse(net)
        check_join_leave_bitexact(net)
        check_oom_admission(net)
        check_dispatch_contract_and_telemetry(net)
        print("SERVING_ENGINE_OK")
        # fast ISSUE-15 siblings ride the SAME subprocess: the default
        # ENGINE_KW engines hit the in-process AOT memo, so these cost
        # decode steps, not XLA compiles (the tier-1 wall budget; the
        # compile-heavy configs live in the slow `capacity` section)
        check_prefix_sharing_and_cow(net)
        check_sampling_laws(net)
        print("SERVING_CAPACITY_FAST_OK")
        # ISSUE 16 fast spec laws ride here too: ONE spec_k=4 config
        # (one extra compile set for the whole block), the exhaustive
        # per-k sweep lives in the slow `spec_sweep` section
        spec_eng = check_spec_greedy_laws(net)
        check_spec_poison_drill(net, spec_eng)
        print("SERVING_SPEC_FAST_OK")
        # ISSUE 19 streamed delivery rides the SAME subprocess too:
        # default ENGINE_KW engines, AOT-memo-shared — cursor laws,
        # cancel, and the vanish/abandon drill cost decode steps and a
        # few 20 ms sleeps, never a compile
        check_stream_cursor_laws(net)
        check_stream_cancel(net)
        check_stream_abandon_reclaim(net)
        print("SERVING_STREAM_OK")
        # ISSUE 20 quantized-KV fast laws ride the SAME subprocess:
        # ONE int8 ENGINE_KW config (+ its spec_k=4 sibling) pays the
        # block's compile cost once, every later check reuses those
        # engines or the in-process AOT memo; the exhaustive
        # dtype/shape sweep lives in the slow `capacity` section
        kvq_eng = check_kvq_pools_and_scale_accounting(net)
        check_kvq_cow_copies_scales(net, kvq_eng)
        kvq_plain = check_kvq_spec_rollback_scales(net)
        check_kvq_sampled_determinism_swap_failover(net, kvq_eng,
                                                    kvq_plain)
        check_kvq_scale_poison_drill(net, kvq_eng)
        print("SERVING_KVQ_FAST_OK")
    if section in ("capacity", "all"):
        net = _net()
        check_prefix_cache_off_token_identity(net)
        check_prefix_eviction_under_pressure(net)
        check_gqa_engine_self_consistent(net)
        check_gqa_capacity_multiplier(net)
        check_kvq_dtype_sweep(net)
        check_kvq_greedy_match_rate_vs_fp()
        print("SERVING_CAPACITY_OK")
    if section in ("spec_sweep", "all"):
        net = _net()
        check_spec_k_sweep(net)
        print("SERVING_SPEC_SWEEP_OK")


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "all")
