"""GigaChat3.5's language model (gluon/model_zoo/gigachat35.py) at its tiny
preset on the CPU, against the plain reference
(perfbench/reference/gigachat35.py: the delta rule token by token): the
whole-sequence forward; the engine's CHUNKED prefill, whose chunks hand
each delta-rule layer's state and convolution tail on, and its decode
through the slot states and the latent pages; the grouped, scalar-gated
delta rule's chunked form and decode kernel against their oracles; pad
rows, the share and the SwiGLU clamp.
"""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

from mxnet_tpu import telemetry                              # noqa: E402
from mxnet_tpu.gluon.model_zoo import gigachat35 as gc       # noqa: E402
from mxnet_tpu.gluon.model_zoo import decoder_blocks         # noqa: E402
from mxnet_tpu.ops.pallas import delta_rule                  # noqa: E402
from mxnet_tpu.parallel import moe as pmoe                   # noqa: E402
from mxnet_tpu.serving import ServingEngine                  # noqa: E402
from mxnet_tpu.serving.programs import LatentPages, SlotState  # noqa: E402
from reference import gigachat35 as reference                # noqa: E402

PAGE, CHUNK = 4, 8


@pytest.fixture(scope="module")
def net():
    return gc.gigachat35_tiny().init_seeded(2 ** 31 + 5)


def tokens(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(0, vocab, n) \
        .astype(np.int32)


def engine(net, **kw):
    args = dict(num_slots=2, page_size=PAGE, num_pages=48,
                max_prefill_len=CHUNK, max_seq_len=64, record_logits=True)
    args.update(kw)
    return ServingEngine(net, **args)


def reference_rows(eng, net, req):
    """The reference's logits at the positions whose tokens ``req`` got."""
    seq = np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)])[:-1]
    want, _ = reference.forward(
        eng._p, seq, net.cfg, rows=np.arange(req.prompt.size - 1, seq.size))
    return np.asarray(want)


def test_the_layers_are_the_published_pattern():
    cfg = dict(gc.PUBLISHED, layers=[0, 4, 5, 6, 7])
    assert gc.layer_kinds(cfg) == [("gdn", "dense")] + [("gdn", "moe")] * 3 \
        + [("mla", "moe")]
    full = gc.PUBLISHED["full_attention_layers"]
    assert full == list(range(3, 40, 4))
    # 3 : 1 over the 40 published layers
    kinds = gc.layer_kinds(dict(cfg, layers=list(range(40))))
    assert [m for m, _ in kinds].count("mla") == 10


@pytest.mark.parametrize("length", [1, 5, 19])
def test_forward_agrees_with_the_reference(net, length):
    """The cache-free program form (the delta rule's chunked form,
    absorbed latent attention) against the reference (token by token,
    keys and values expanded), on logits; and the reference computed in
    blocks of 8 positions against itself whole."""
    p = gc.decode_params(net)
    toks = jnp.asarray(tokens(length, length))
    got, routing = jax.jit(lambda p, t: gc.forward(p, t, net.cfg))(p, toks)
    want, ref_routing = reference.forward(p, toks, net.cfg)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    for mine, ref in zip(routing, ref_routing):
        assert (np.sort(mine, -1) == np.sort(ref["experts"], -1)).all()
    if length == 1:
        assert np.asarray(net(toks)._data).shape == (length, 256)


@pytest.mark.parametrize("block", [8, 16])
def test_the_reference_in_blocks_is_the_reference_whole(net, monkeypatch,
                                                       block):
    """A delta-rule layer carries its state and tail from one block of
    positions to the next, and latent attention sees every key before a
    block: the blocked reference is the whole one."""
    p = gc.decode_params(net)
    toks = tokens(37, 3)
    whole, _ = reference.forward(p, toks, net.cfg)
    monkeypatch.setattr(reference, "TIME_BLOCK", block)
    monkeypatch.setattr(reference, "QUERY_ROWS", 4)
    blocked, _ = reference.forward(p, toks, net.cfg)
    assert np.abs(np.asarray(blocked) - np.asarray(whole)).max() < 2e-6


# -- the grouped, scalar-gated delta rule -----------------------------------

def _delta_inputs(t, hk=2, hv=4, d=16, seed=0, decay=0.2):
    rng = np.random.default_rng(seed)
    q, k = (rng.normal(size=(t, hk, d)) for _ in range(2))
    q, k = (a / np.linalg.norm(a, axis=-1, keepdims=True) for a in (q, k))
    v = rng.normal(size=(t, hv, d))
    g = -rng.uniform(0, decay, size=(t, hv))
    beta = rng.uniform(0, 1, size=(t, hv))
    state = rng.normal(size=(hv, d, d))
    return [jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta, state)]


@pytest.mark.parametrize("t,chunk", [(1, 64), (23, 8), (64, 16), (70, 64)])
def test_chunked_form_from_a_state_is_the_token_recurrence(t, chunk):
    """``gdn_chunked`` started from a non-zero state (64 value heads'
    worth of grouping in small: 4 value heads over 2 key heads) against
    the reference's token-by-token recurrence: outputs and final state."""
    q, k, v, g, beta, s0 = _delta_inputs(t, seed=t)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = reference.delta_rule(q, k, v, g, beta, s0)
    got_o, got_s = delta_rule.gdn_chunked(q, k, v, g, beta, s0, chunk=chunk)
    assert np.abs(np.asarray(got_o - want_o)).max() < 2e-5
    assert np.abs(np.asarray(got_s - want_s)).max() < 2e-5


def test_chunked_form_leaves_the_state_alone_at_padding():
    q, k, v, g, beta, s0 = _delta_inputs(12, seed=4)
    real = 7
    g = g.at[real:].set(0.0)
    beta = beta.at[real:].set(0.0)
    o, s = delta_rule.gdn_chunked(q, k, v, g, beta, s0, chunk=4)
    o_real, s_real = delta_rule.gdn_chunked(q[:real], k[:real], v[:real],
                                            g[:real], beta[:real], s0,
                                            chunk=4)
    assert np.abs(np.asarray(s - s_real)).max() < 1e-6
    assert np.abs(np.asarray(o[:real] - o_real)).max() < 1e-6


@pytest.mark.parametrize("hv,hk,hb", [(4, 2, 4), (16, 8, 8), (64, 32, 16)])
def test_gdn_step_kernel_on_the_interpreter(hv, hk, hb):
    """The decode step (``kda_step``, value heads ``hb`` a grid cell, on
    the key heads repeated and the scalar decay broadcast) against
    ``kda_step``'s jnp oracle and the recurrence's one step; inactive
    slots and the scratch row are left unchanged."""
    rng = np.random.default_rng(hv)
    s_n, d = 3, 16
    q, k = (rng.normal(size=(s_n, hk, d)) for _ in range(2))
    q, k = (jnp.asarray(a / np.linalg.norm(a, axis=-1, keepdims=True),
                        jnp.float32) for a in (q, k))
    v = jnp.asarray(rng.normal(size=(s_n, hv, d)), jnp.float32)
    g = jnp.asarray(-rng.uniform(0, 1, size=(s_n, hv)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, size=(s_n, hv)), jnp.float32)
    state = jnp.asarray(rng.normal(size=(s_n + 1, hv, d, d)), jnp.float32)
    active = jnp.asarray([True, False, True])
    o, new = gc.gdn_step(state, q, k, v, g, beta, active, heads_per_cell=hb)
    group = hv // hk
    o_ref, new_ref = delta_rule.kda_step_reference(
        state, jnp.repeat(q, group, 1), jnp.repeat(k, group, 1), v,
        jnp.broadcast_to(g[..., None], (s_n, hv, d)), beta, active)
    assert np.abs(np.asarray(o - o_ref)).max() < 1e-5
    assert np.abs(np.asarray(new - new_ref)).max() < 1e-5
    for row in (1, 3):
        assert np.array_equal(np.asarray(new[row]), np.asarray(state[row]))
    # slot 0's step is the recurrence's first token from its state
    with jax.default_matmul_precision("highest"):
        o_tok, s_tok = reference.delta_rule(q[:1], k[:1], v[:1], g[:1],
                                            beta[:1], state[0])
    assert np.abs(np.asarray(o[0] - o_tok[0])).max() < 1e-5
    assert np.abs(np.asarray(new[0] - s_tok)).max() < 1e-5


# -- the engine: chunked prefill that hands the state on, then decode -------

#: (prompt, new tokens): one chunk; exactly two (the last row on a chunk's
#: edge); ragged over three (the state handed on twice); shorter than the
#: convolution (a chunk with fewer real rows than the tail holds); long
WORK = [(5, 4), (16, 3), (21, 6), (2, 5), (41, 7), (9, 3)]


@pytest.fixture(scope="module")
def served(net):
    """Six requests through two slots: prompts of one chunk and of
    several, every slot reused, decode beside a prefilling slot."""
    telemetry.reset()
    eng = engine(net)
    reqs = [eng.submit(tokens(n, 200 + n), new) for n, new in WORK]
    eng.run_until_idle()
    return eng, reqs


@pytest.mark.parametrize("i", range(len(WORK)))
def test_engine_agrees_with_the_reference_full_forward(net, served, i):
    eng, reqs = served
    r = reqs[i]
    assert r.done and len(r.tokens) == r.max_new
    got = np.stack(r.logits_trace)
    assert np.abs(got - reference_rows(eng, net, r)).max() < 2e-5
    assert (got.argmax(-1) == np.asarray(r.tokens)).all()


def test_engine_counts_chunks_handoffs_and_state(net, served):
    eng, _ = served
    runs = [-(-n // CHUNK) for n, _ in WORK]
    assert eng.prefills == len(WORK)
    assert eng.prefill_chunks == sum(runs)
    snap = telemetry.report()
    # a chunk run at an offset starts from the state the one before left
    assert snap["counters"]["serving.state.handoffs"] \
        == sum(r - 1 for r in runs)
    # four delta-rule layers: a [4, 16, 16] state and a tail of 3 raw
    # inputs of 2 x 2 x 16 + 4 x 16 channels, float32; one latent layer:
    # a row of 32 + 8 values padded to 128 lanes
    per_slot = 4 * (4 * 16 * 16 + 3 * 128) * 4
    assert eng.state_bytes_per_slot == per_slot
    assert eng.snapshot()["state_bytes_per_slot"] == per_slot
    assert eng.kv_bytes_per_token == 128 * 4
    kinds = eng._kinds
    assert [type(k) for k in kinds] == [SlotState] * 4 + [LatentPages]
    assert all(k.role == "state" for k in kinds[:4])
    assert [tuple(a.shape) for a in eng._kv[0]] == [(3, 4, 16, 16),
                                                   (3, 3, 128)]
    assert [tuple(a.shape) for a in eng._kv[4]] == [(48, PAGE, 128)]
    # the latent rows the programs' queries read, by their own count
    ctx = sum(np.arange(1, n + new).sum() for n, new in WORK)
    got = sum(eng.stat_totals[p]["latent.rows_read"]
              for p in ("prefill", "decode"))
    assert got == ctx
    assert "serving.latent.rows_read" in snap["counters"]


def test_the_handed_state_is_the_recurrences(net):
    """After a prompt of three chunks (the last ragged) the slot's rows
    hold the state and the convolution tail the token-by-token reference
    leaves at the prompt's end, in every delta-rule layer."""
    eng = engine(net, num_slots=1)
    prompt = tokens(21, 9)
    req = eng.submit(prompt, 1)
    eng.run_until_idle()
    assert req.done
    p = eng._p
    cfg = net.cfg
    key = repr(sorted((k, repr(v)) for k, v in cfg.items()))
    reference._CFGS[key] = cfg
    x = jnp.asarray(p["wte"][jnp.asarray(prompt)], jnp.float32)
    hv, d = cfg["linear_num_value_heads"], cfg["linear_key_head_dim"]
    for i, (lp, (mix, ffn)) in enumerate(zip(p["layers"],
                                             gc.layer_kinds(cfg))):
        if mix != "gdn":
            break
        x, state, tail, _ = reference._gdn_block(
            lp, x, jnp.zeros((hv, d, d)), jnp.zeros((3, 128)), None, ffn,
            key, 0.0, None)
        assert np.abs(np.asarray(eng._kv[i][0][0] - state)).max() < 2e-5
        assert np.abs(np.asarray(eng._kv[i][1][0] - tail)).max() < 2e-5


@pytest.mark.parametrize("ahead", [0, 2])
def test_a_reused_slot_starts_from_a_zero_state(net, ahead):
    """One slot, two tenants: the second's logits are the reference's
    though the first left its state and tail in every layer, and though
    those rows are then filled with large values."""
    eng = engine(net, num_slots=1, decode_ahead=ahead)
    first = eng.submit(tokens(19, 1), 4)
    eng.run_until_idle()
    assert first.done
    eng._kv = [tuple(jnp.full_like(a, 1e3) for a in entry)
               if isinstance(kind, SlotState) else entry
               for entry, kind in zip(eng._kv, eng._kinds)]
    for n in (3, 17):
        second = eng.submit(tokens(n, 50 + n), 5)
        eng.run_until_idle()
        assert second.done and second.slot == first.slot
        got = np.stack(second.logits_trace)
        assert np.abs(got - reference_rows(eng, net, second)).max() < 2e-5


def test_decode_ahead_gives_the_same_tokens_and_logits(net, served):
    _, reqs = served
    eng = engine(net, decode_ahead=2)
    ahead = [eng.submit(tokens(n, 200 + n), new) for n, new in WORK]
    eng.run_until_idle()
    for a, b in zip(ahead, reqs):
        assert a.tokens == b.tokens
        assert np.abs(np.stack(a.logits_trace)
                      - np.stack(b.logits_trace)).max() < 1e-6


@pytest.mark.parametrize("kw,match", [
    (dict(spec_k=2), "a recurrent state cannot be rolled back"),
    (dict(kv_dtype="int8"), "per-slot recurrent state"),
    (dict(prefix_cache=True), "a recurrent layer's prefix is a state"),
    (dict(kv_heads=2), "kv_heads"),
])
def test_engine_refuses_what_a_recurrent_model_cannot_do(net, kw, match):
    with pytest.raises(ValueError, match=match):
        engine(net, **kw)


# -- pad rows, the share, the clamp ------------------------------------------

def test_a_chunks_pad_rows_leave_state_and_held_experts_alone(net):
    """A ragged chunk's pad rows (here the same token, as the engine pads
    with 0) move neither the delta-rule state nor any held expert: the
    prefill of 5 real rows in a chunk of 8 leaves the state and counts the
    local assignments of the 5 rows alone."""
    p = gc.decode_params(net)
    cfg = net.cfg
    eng = engine(net, num_slots=1)
    caches = eng._kv
    toks = np.zeros(CHUNK, np.int32)
    toks[:5] = tokens(5, 11)
    bt = jnp.asarray(eng.sched.block_tables[0])
    run = jax.jit(lambda toks, n: gc.paged_prefill(
        p, toks, n, 0, bt, 0, 0, caches, 4, slot=0, cfg=cfg))
    _, _, padded, aux = run(jnp.asarray(toks), 5)
    _, _, alone, aux_alone = jax.jit(lambda toks: gc.paged_prefill(
        p, toks, 5, 0, bt, 0, 0, caches, 4, slot=0, cfg=cfg))(
        jnp.asarray(toks[:5]))
    for i, (mix, _) in enumerate(gc.layer_kinds(cfg)):
        if mix == "gdn":
            for a, b in zip(padded[i], alone[i]):
                assert np.abs(np.asarray(a - b)).max() < 1e-5
    names = list(gc.DECODE_STATS)
    local = names.index("local_assignments")
    assert float(aux["stats"][local]) == float(aux_alone["stats"][local])
    assert float(aux["stats"][names.index("latent.rows_read")]) \
        == sum(range(1, 6))


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The partial outputs of the four shares (experts 0-3, ..., 12-15),
    with the shared expert counted once, are the uncut reference layer,
    the SwiGLU clamped in every one."""
    whole = gc.gigachat35_tiny(experts_held=[0, 16]).init_seeded(8)
    cfg = whole.cfg
    lp = next(l["moe"] for l in gc.decode_params(whole)["layers"]
              if "moe" in l)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(24, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.moe(lp, x, cfg)
        shared = decoder_blocks.swiglu(x, lp["sh_gu_w"], lp["sh_down_w"],
                                       cfg["swiglu_limit"])
        total = jnp.zeros_like(x)
        for first in range(0, 16, 4):
            share = dict(lp, gu_w=lp["gu_w"][first:first + 4],
                         down_w=lp["down_w"][first:first + 4])
            part = dict(cfg, experts_held=[first, 4])
            y, _, _ = jax.jit(lambda s, x, part=part: decoder_blocks.moe(
                s, x, part))(share, x)
            ref_share, _ = jax.jit(lambda s, x, part=part: reference.moe(
                s, x, part))(share, x)
            assert np.abs(np.asarray(y - ref_share)).max() < 1e-5
            total = total + (y - shared)
    assert np.abs(np.asarray(total + shared - want)).max() < 1e-5


def test_the_swiglu_clamp_binds_where_it_should():
    """With weights scaled so that gate and up reach past the limit, the
    dense SwiGLU, the held experts and the reference clamp alike
    (``silu(min(gate, 10)) * clip(up, -10, 10)``), and without the clamp
    the result differs."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    gu = jnp.asarray(rng.normal(size=(32, 48)) * 2.0, jnp.float32)
    down = jnp.asarray(rng.normal(size=(24, 32)) * 0.1, jnp.float32)
    with jax.default_matmul_precision("highest"):
        pre = x @ gu
        assert float(pre[:, :24].max()) > 10 and float(pre[:, 24:].min()) < -10
        got = decoder_blocks.swiglu(x, gu, down, 10)
        want = (jax.nn.silu(jnp.minimum(pre[:, :24], 10))
                * jnp.clip(pre[:, 24:], -10, 10)) @ down
        ref = reference._swiglu(x, gu, down, 10)
        free = decoder_blocks.swiglu(x, gu, down)
        assert np.abs(np.asarray(got - want)).max() < 1e-4
        assert np.abs(np.asarray(ref - want)).max() < 1e-4
        assert np.abs(np.asarray(free - want)).max() > 1.0
        # the held experts' layout: one expert, every token on it
        experts = jnp.zeros((16, 1), jnp.int32)
        y, _ = pmoe.held_experts_ffn(x, experts, jnp.ones((16, 1)),
                                     gu[None], down[None], 0, 1, limit=10)
        assert np.abs(np.asarray(y - want)).max() < 1e-3


def _swiglu_before_the_clamp(x, gu_w, down_w):
    """``decoder_blocks.swiglu`` as it was written before it took a
    ``limit``."""
    gu = decoder_blocks.mm(x, gu_w)
    half = gu.shape[-1] // 2
    return decoder_blocks.mm(jax.nn.silu(gu[..., :half]) * gu[..., half:],
                             down_w)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_an_unclamped_swiglu_traces_what_it_traced_before(dtype):
    """Without a ``swiglu_limit`` (every other served model) the shared
    SwiGLU and the held experts' activation trace the program they traced
    before the clamp was added: ``silu(gate) * up``, in that order."""
    gu = jax.ShapeDtypeStruct((8, 48), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 32), jnp.float32)
    gu_w = jax.ShapeDtypeStruct((32, 48), dtype)
    down_w = jax.ShapeDtypeStruct((24, 32), dtype)
    jaxpr = lambda f, *a: str(jax.make_jaxpr(f)(*a))
    assert jaxpr(pmoe.swiglu_activation, gu) == jaxpr(
        lambda a: jax.nn.silu(a[:, :24]) * a[:, 24:], gu)
    assert jaxpr(decoder_blocks.swiglu, x, gu_w, down_w) \
        == jaxpr(_swiglu_before_the_clamp, x, gu_w, down_w)


@pytest.mark.parametrize("length", [4, 13])
def test_norm_gains_are_read_as_stated(net, length):
    """With every norm gain away from 0 the program agrees with the
    reference: a layer's norms and the final one are ``x / rms(x) * 2
    sigmoid(w)`` (``layernorm_gating_weight``), the latent's query and key
    norms and the delta rule's output norm ``x / rms(x) * (1 + w)``."""
    rng = np.random.default_rng(length)

    def gains(path, leaf):
        if str(getattr(path[-1], "key", "")).endswith("_g"):
            return jnp.asarray(rng.normal(size=leaf.shape) * 0.5, leaf.dtype)
        return leaf

    p0 = gc.decode_params(net)
    p = jax.tree_util.tree_map_with_path(gains, p0)
    toks = jnp.asarray(tokens(length, 7))
    run = jax.jit(lambda p, t: gc.forward(p, t, net.cfg)[0])
    got = np.asarray(run(p, toks))
    want, _ = reference.forward(p, toks, net.cfg)
    assert np.abs(got - np.asarray(want)).max() < 2e-5
    assert np.abs(got - np.asarray(run(p0, toks))).max() > 1e-3
    x = jnp.asarray(rng.normal(size=(3, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=64), jnp.float32)
    unit = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(gc._layer_norm(x, w, net.cfg),
                               unit * 2 * jax.nn.sigmoid(w), rtol=1e-5)
    np.testing.assert_allclose(gc._norm(x, w, 1e-6), unit * (1 + w),
                               rtol=1e-5)


def test_a_delta_rule_layer_rotates_nothing_and_latent_keys_turn(net):
    """Rotary positions reach the latent layer's query and shared key
    (interleaved pairs, YaRN's frequencies) and nothing of a delta-rule
    layer: shifting every position moves the former only."""
    p = gc.decode_params(net)
    lp = p["layers"][4]["attn"]
    h = jnp.asarray(np.random.default_rng(1).normal(size=(6, 64)),
                    jnp.float32)
    pos = jnp.arange(6)
    a = gc._mla_inputs(lp, h, pos, net.cfg)
    b = gc._mla_inputs(lp, h, pos + 3, net.cfg)
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert np.array_equal(np.asarray(a[2]), np.asarray(b[2]))
    for i in (1, 3):
        assert float(np.abs(np.asarray(a[i] - b[i])).max()) > 1e-3
    np.testing.assert_allclose(
        np.asarray(gc._freqs(net.cfg)), reference.yarn_inv_freq(net.cfg),
        rtol=1e-6)
    assert gc.softmax_scale(net.cfg) == pytest.approx(
        reference.softmax_scale(net.cfg))


def test_the_reference_in_a_lower_precision_fails_the_tiny_limits(net,
                                                                  served):
    """The cell's tiny limits lie between this program's error and the
    reference's when it computes in float8_e4m3."""
    with open(os.path.join(REPO, "perfbench", "workloads",
                           "gigachat-serve-longdoc.json")) as f:
        check = json.load(f)["tiny"]["correct"]
    eng, reqs = served
    r = reqs[4]
    got = np.stack(r.logits_trace)
    seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])[:-1]
    rows = np.arange(r.prompt.size - 1, seq.size)
    assert np.abs(got - reference_rows(eng, net, r)).max() \
        < check["tol_logit"] / 3
    low, _ = reference.forward(eng._p, seq, net.cfg, rows=rows,
                               compute_as="float8_e4m3fn")
    assert np.abs(got - np.asarray(low)).max() > check["tol_logit"]


def test_both_programs_name_their_scopes(net):
    eng = engine(net)
    for prog, scopes in ((eng._decode, ("gdn_step", "attn.mla")),
                         (eng._prefill, ("gdn_scan", "attn.mla"))):
        text = prog.__wrapped__.as_text()
        for scope in scopes + ("gdn.proj", "gdn.out", "state_write", "moe"):
            assert scope in text, scope


def test_this_model_shares_its_layer_functions():
    for name in ("mm", "head", "swiglu", "moe", "latent_rows",
                 "rows_per_block"):
        assert getattr(gc, "_" + name) is getattr(decoder_blocks, name)
    assert gc.DECODE_STATS[:len(decoder_blocks.MOE_STATS)] \
        == decoder_blocks.MOE_STATS
