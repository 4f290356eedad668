"""The hybrid KDA / MLA / routed-expert decoder (gluon/model_zoo/ling3.py)
at its tiny preset on the CPU, against the plain reference
(perfbench/reference/ling3.py): the whole-sequence forward, the engine's
prefill and decode through both kinds of cache, each kernel on the
interpreter against jax.numpy, the router, and the share.
"""
import importlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

from mxnet_tpu import telemetry                              # noqa: E402
from mxnet_tpu.gluon.model_zoo import ling3                  # noqa: E402
from mxnet_tpu.parallel import moe                           # noqa: E402
from mxnet_tpu.serving import ServingEngine                  # noqa: E402
from mxnet_tpu.serving.kv_cache import PagedKVAllocator      # noqa: E402
from reference import ling3 as reference                     # noqa: E402

delta_rule = importlib.import_module("mxnet_tpu.ops.pallas.delta_rule")
latent = importlib.import_module("mxnet_tpu.ops.pallas.latent_attention")
gmm = importlib.import_module("mxnet_tpu.ops.pallas.grouped_matmul")


@pytest.fixture(scope="module")
def net():
    return ling3.ling3_tiny().init_seeded(2 ** 31 + 3)


def tokens(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(0, vocab, n) \
        .astype(np.int32)


# -- the whole-sequence forward ---------------------------------------------

@pytest.mark.parametrize("length", [1, 7, 64, 65, 150])
def test_forward_agrees_with_the_reference(net, length):
    """Chunked KDA, plain MLA and the grouped expert matmul against the
    token-by-token, loop-over-experts reference, on logits; lengths
    below, at and across the 64-token chunk."""
    p = ling3.decode_params(net)
    toks = jnp.asarray(tokens(length, length))
    got, routing = ling3.forward(p, toks, net.cfg)
    want, ref_routing = reference.forward(p, toks, net.cfg)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    for mine, ref in zip(routing, ref_routing):
        assert (np.sort(mine, -1) == np.sort(ref["experts"], -1)).all()
    assert np.asarray(net(toks)._data).shape == (length, 256)


def test_forward_in_bfloat16_stays_near_the_reference():
    """The published storage type: bfloat16 weights and matmul inputs,
    float32 accumulation.  Looser than float32, far tighter than an
    8-bit computation (whose error is ~0.05 here)."""
    net16 = ling3.ling3_tiny(dtype="bfloat16").init_seeded(5)
    p = ling3.decode_params(net16)
    toks = jnp.asarray(tokens(40, 9))
    got, routing = ling3.forward(p, toks, net16.cfg)
    want, docs = reference.forward(p, toks, net16.cfg,
                                   sys_experts=routing, delta=2e-3)
    assert not any(np.asarray(d["mismatch"]).any() for d in docs)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 0.01


# -- the engine: prefill then decode through both caches --------------------

def engine(net, **kw):
    args = dict(num_slots=2, page_size=8, num_pages=64,
                max_prefill_len=136, max_seq_len=160, record_logits=True)
    args.update(kw)
    return ServingEngine(net, **args)


@pytest.fixture(scope="module")
def served(net):
    """Six requests through two slots: every slot is reused (its state
    zeroed by the next prefill), prompts cross the chunk (64) and page
    (8) boundaries."""
    eng = engine(net)
    reqs = [eng.submit(tokens(n, 100 + n), new)
            for n, new in [(5, 4), (63, 3), (64, 3), (65, 5), (130, 3),
                           (8, 9)]]
    eng.run_until_idle()
    return eng, reqs


@pytest.mark.parametrize("i", range(6))
def test_engine_agrees_with_the_reference_full_forward(net, served, i):
    eng, reqs = served
    r = reqs[i]
    assert r.done and len(r.tokens) == r.max_new
    seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])[:-1]
    want, _ = reference.forward(eng._p, seq, net.cfg,
                                rows=np.arange(r.prompt.size - 1, seq.size))
    got = np.stack(r.logits_trace)
    assert np.abs(got - np.asarray(want)).max() < 2e-5
    assert (got.argmax(-1) == np.asarray(r.tokens)).all()


def test_engine_counts_state_latent_and_routing(net, served):
    eng, reqs = served
    h, d = net.cfg["num_attention_heads"], net.cfg["head_dim"]
    n_kda = sum(1 for mix, _ in ling3.layer_kinds(net.cfg) if mix == "kda")
    per_slot = n_kda * (h * d * d * 4 + 3 * 3 * h * d * 4)
    assert eng.state_bytes_per_slot == per_slot
    assert eng.alloc.state_bytes(2) == 2 * per_slot
    # one latent layer: a padded row of 128 float32 lanes a token
    assert eng.kv_bytes_per_token == ling3.latent_width(net.cfg) * 4
    assert eng.alloc.latent_page_bytes(128) == 8 * 128 * 4
    assert eng.alloc.used_pages == 0 and eng.sched.occupancy == 0
    dec = eng.stat_totals["decode"]
    assert dec["assignments"] == dec["expert_layers"] * 2 * 4
    assert 0 < dec["local_assignments"] < dec["assignments"]
    assert dec["experts_hit"] <= dec["local_assignments"]
    snap = telemetry.report()
    assert "serving.moe.local_assignments" in snap["counters"]
    for g in ("serving.moe.local_share", "serving.state.live_slots",
              "serving.state.live_bytes", "serving.latent.live_pages",
              "serving.moe.max_tokens_per_expert"):
        assert g in snap["gauges"], g
    assert eng.snapshot()["state_bytes_per_slot"] == per_slot


@pytest.mark.parametrize("kw,match", [
    (dict(spec_k=2), "spec_k must be 0"),
    (dict(kv_dtype="int8"), "int8 pages"),
    (dict(prefix_cache=True), "prefix cache"),
    (dict(kv_heads=2), "kv_heads")])
def test_engine_refuses_what_only_paged_kv_supports(net, kw, match):
    with pytest.raises(ValueError, match=match):
        engine(net, **kw)


def test_engine_turns_prefix_reuse_off_for_recurrent_layers(net, served):
    eng, _ = served
    assert eng._prefix is None
    assert eng.snapshot()["prefix_cached_pages"] is None


# -- KDA: chunked form, one-step kernel --------------------------------------

def kda_inputs(t, h, d, seed):
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.normal(size=(t, h, d)), jnp.float32)
               for _ in range(3))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(d)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -5.0 * jax.nn.sigmoid(jnp.asarray(rng.normal(size=(t, h, d)),
                                          jnp.float32))
    beta = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(t, h)),
                                      jnp.float32))
    return q, k, v, g, beta


def token_by_token(q, k, v, g, beta):
    def step(s, x):
        o, s = delta_rule.kda_step_reference(s, *(a[None] for a in x))
        return s, o[0]
    h, d = q.shape[1], q.shape[2]
    s, o = jax.lax.scan(step, jnp.zeros((1, h, d, d), jnp.float32),
                        (q, k, v, g, beta))
    return o, s[0]


@pytest.mark.parametrize("t,chunk", [(5, 64), (64, 64), (100, 64),
                                     (130, 16), (48, 8)])
def test_chunked_kda_is_the_token_by_token_recurrence(t, chunk):
    """Decays down to exp(-5) a step: a 64-token chunk spans exp(-320),
    which the chunked form must survive (it only forms differences of
    running sums with a non-positive sign)."""
    x = kda_inputs(t, 2, 16, t)
    o, s = delta_rule.kda_chunked(*x, chunk=chunk)
    o_ref, s_ref = token_by_token(*x)
    assert np.abs(np.asarray(o - o_ref)).max() < 2e-5
    assert np.abs(np.asarray(s - s_ref)).max() < 2e-5


def test_chunked_kda_leaves_the_state_alone_at_padding():
    q, k, v, g, beta = kda_inputs(40, 2, 16, 1)
    valid = jnp.arange(40) < 23
    g = jnp.where(valid[:, None, None], g, 0.0)
    beta = jnp.where(valid[:, None], beta, 0.0)
    _, s = delta_rule.kda_chunked(q, k, v, g, beta, chunk=16)
    _, s_ref = token_by_token(*(a[:23] for a in (q, k, v, g, beta)))
    assert np.abs(np.asarray(s - s_ref)).max() < 2e-5


@pytest.mark.parametrize("heads,hb", [(4, 2), (8, 8), (3, 8)])
def test_kda_step_kernel_on_the_interpreter(heads, hb):
    """In place on ``[slots + 1, H, D, D]``: live slots advance, a slot
    that is not live and the scratch row keep their state."""
    s_n, d = 3, 16
    rng = np.random.default_rng(heads)
    state = jnp.asarray(rng.normal(size=(s_n + 1, heads, d, d)),
                        jnp.float32)
    x = kda_inputs(s_n, heads, d, 7)
    active = jnp.asarray([True, False, True])
    o, new = delta_rule.kda_step(state, *x, active, heads_per_cell=hb)
    o_ref, new_ref = delta_rule.kda_step_reference(state, *x, active)
    assert np.abs(np.asarray(o - o_ref)).max() < 1e-5
    assert np.abs(np.asarray(new - new_ref)).max() < 1e-5
    assert (np.asarray(new[1]) == np.asarray(state[1])).all()
    assert (np.asarray(new[3]) == np.asarray(state[3])).all()


# -- MLA: the paged latent kernel, absorbed against plain --------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mla_paged_decode_on_the_interpreter(dtype):
    rng = np.random.default_rng(3)
    s_n, h, w, v_w, page, pages = 4, 4, 128, 32, 8, 24
    pool = jnp.asarray(rng.normal(size=(pages, page, w)), dtype)
    pool = pool.at[:, :, 40:].set(0)
    q = jnp.asarray(rng.normal(size=(s_n, h, w)), jnp.float32) \
        .at[:, :, 40:].set(0)
    tables = jnp.asarray(rng.permutation(np.arange(1, pages))[:20]
                         .reshape(s_n, 5), jnp.int32)
    ctx = jnp.asarray([0, 1, 17, 40], jnp.int32)
    got = latent.mla_paged_decode(q, pool, tables, ctx, v_w, 0.25)
    want = latent.mla_paged_decode_reference(q, pool, tables, ctx, v_w,
                                             0.25)
    assert got.shape == (s_n, h, v_w)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    assert (np.asarray(got[0]) == 0).all()


def test_absorbed_mla_decode_is_plain_mla(net):
    """The decode form (query absorbed, scores against the cached rows)
    at position t equals the plain form's row t over the same prefix."""
    cfg = net.cfg
    lp = next(l["mla"] for l in ling3.decode_params(net)["layers"]
              if "mla" in l)
    t, page = 21, 8
    x = jnp.asarray(np.random.default_rng(4).normal(size=(t, 64)),
                    jnp.float32)
    y_plain, c, k_rope = ling3._mla_plain(lp, x, jnp.ones(t, bool), cfg)
    width = ling3.latent_width(cfg)
    pool = jnp.zeros((8, page, width), jnp.float32)
    rows = ling3._latent_rows(c, k_rope, width, jnp.float32)
    table = jnp.asarray([[3, 5, 1, 0]], jnp.int32)
    pos = jnp.arange(t - 1)
    pool = pool.at[table[0][pos // page], pos % page].set(rows[:-1])
    last = jnp.asarray([t - 1])
    y, pool = ling3._mla_absorbed(
        lp, x[-1:], last, pool, table, jnp.asarray([t], jnp.int32),
        table[0][last // page], last % page, cfg)
    assert np.abs(np.asarray(y[0] - y_plain[-1])).max() < 1e-5
    assert np.abs(np.asarray(pool[1, 4] - rows[-1])).max() == 0


# -- the router and the share -------------------------------------------------

ROUTER = dict(n_group=4, topk_group=2, num_experts_per_tok=4,
              routed_scaling_factor=2.5)


def route_both(x, w, b):
    mine = moe.grouped_topk_route(x, w, b, 4, 2, 4, 2.5)
    scores = jax.nn.sigmoid(jnp.dot(x, w, precision="highest"))
    ref, _ = reference.route(scores, b, ROUTER)
    return mine, np.asarray(ref), np.asarray(scores)


@pytest.mark.parametrize("case", ["random", "bias", "ties", "one_expert"])
def test_router_agrees_with_the_reference(case):
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(12, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    b = jnp.zeros(16, jnp.float32)
    if case == "bias":
        # a bias large enough to pull experts in; the weights ignore it
        b = b.at[jnp.asarray([2, 9])].set(3.0)
    if case == "ties":
        # equal scores everywhere: the order is top_k's (lowest index)
        w = jnp.zeros_like(w)
    if case == "one_expert":
        # every token's best expert is expert 6, whatever the token
        b = b.at[6].set(10.0)
    (experts, weights), ref, scores = route_both(x, w, b)
    experts, weights = np.asarray(experts), np.asarray(weights)
    assert (np.sort(experts, -1) == np.sort(ref, -1)).all()
    assert experts.shape == (12, 4)                    # none dropped
    assert all(len(set(row)) == 4 for row in experts)
    chosen = np.take_along_axis(scores, experts, -1)
    assert np.allclose(weights,
                       2.5 * chosen / chosen.sum(-1, keepdims=True),
                       atol=1e-6)
    assert np.allclose(weights.sum(-1), 2.5, atol=1e-5)
    if case == "bias":
        assert all({2, 9} <= set(row) or len({2, 9} & set(row)) >= 1
                   for row in experts)
    if case == "one_expert":
        assert (experts == 6).any(-1).all()


def test_every_token_to_one_expert_drops_none():
    """All assignments on one held expert: the tile layout still holds
    every row and the layer equals the dense computation."""
    rng = np.random.default_rng(5)
    t, c, f, held = 40, 16, 8, 4
    x = jnp.asarray(rng.normal(size=(t, c)), jnp.float32)
    gu = jnp.asarray(rng.normal(size=(held, c, 2 * f)), jnp.float32)
    down = jnp.asarray(rng.normal(size=(held, f, c)), jnp.float32)
    experts = jnp.full((t, 2), 6, jnp.int32).at[:, 1].set(1)  # 6 held, 1 not
    weights = jnp.asarray(rng.uniform(0.1, 1, size=(t, 2)), jnp.float32)
    y, stats = moe.held_experts_ffn(x, experts, weights, gu, down, first=4,
                                    num_experts=8, tile_rows=8)
    h = jnp.dot(x, gu[2], precision="highest")
    want = weights[:, :1] * jnp.dot(jax.nn.silu(h[:, :f]) * h[:, f:],
                                    down[2], precision="highest")
    assert np.abs(np.asarray(y - want)).max() < 1e-4
    assert float(stats["experts_hit"]) == 1
    assert float(stats["local_assignments"]) == t
    assert float(stats["max_tokens_per_expert"]) == t


@pytest.mark.parametrize("tile_rows", [8, 16, 128])
def test_moe_gmm_kernel_on_the_interpreter(tile_rows):
    rng = np.random.default_rng(tile_rows)
    local = jnp.asarray(rng.integers(0, 5, 50), jnp.int32)   # 4 = elsewhere
    dest, tile_expert, n_valid, counts, rows = moe.expert_tiles(
        local, 4, tile_rows)
    dest, counts = np.asarray(dest), np.asarray(counts)
    assert rows % tile_rows == 0 and counts.sum() == (local < 4).sum()
    placed = dest[np.asarray(local) < 4]
    assert len(set(placed)) == len(placed) and placed.max() < rows
    assert (dest[np.asarray(local) == 4] == rows).all()
    for r, e in zip(placed, np.asarray(local)[np.asarray(local) < 4]):
        assert int(tile_expert[r // tile_rows]) == e
    x = jnp.asarray(rng.normal(size=(rows, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 16, 128)), jnp.float32)
    got = gmm.moe_gmm(x, w, tile_expert, n_valid, tile_rows)
    want = gmm.moe_gmm_reference(x, w, tile_expert, n_valid, tile_rows)
    live = int(n_valid[0]) * tile_rows
    assert np.abs(np.asarray(got - want))[:live].max() < 1e-4


def test_the_four_shares_add_up_to_the_uncut_layer(net):
    """The partial outputs of the four shares (experts 0-3, 4-7, 8-11,
    12-15), with the shared expert counted once, are the uncut
    reference layer."""
    cfg = dict(net.cfg, experts_held=[0, 16])
    whole = ling3.ling3_tiny(experts_held=[0, 16]).init_seeded(8)
    lp = next(l["moe"] for l in ling3.decode_params(whole)["layers"]
              if "moe" in l)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(24, 64)),
                    jnp.float32)
    want, _ = reference.moe(lp, x, cfg, block=8)
    shared = ling3._swiglu(x, lp["sh_gu_w"], lp["sh_down_w"])
    total = jnp.zeros_like(x)
    for first in (0, 4, 8, 12):
        share = dict(lp, gu_w=lp["gu_w"][first:first + 4],
                     down_w=lp["down_w"][first:first + 4])
        y, experts, _ = ling3._moe(share, x, dict(cfg,
                                                  experts_held=[first, 4]))
        ref_share, _ = reference.moe(share, x, dict(
            cfg, experts_held=[first, 4]), block=4)
        assert np.abs(np.asarray(y - ref_share)).max() < 1e-5
        total = total + (y - shared)
    assert np.abs(np.asarray(total + shared - want)).max() < 1e-5


# -- the allocator's byte counts ------------------------------------------------

@pytest.mark.parametrize("kv_dtype,item", [("fp32", 4), ("bf16", 2)])
def test_allocator_counts_latent_pages_and_slot_state(kv_dtype, item):
    alloc = PagedKVAllocator(16, 64, kv_dtype=kv_dtype,
                             slot_state_bytes=13_000_000)
    assert alloc.latent_page_bytes(640) == 64 * 640 * item
    assert alloc.state_bytes(0) == 0
    assert alloc.state_bytes(128) == 128 * 13_000_000
    assert PagedKVAllocator(16, 64).slot_state_bytes == 0
