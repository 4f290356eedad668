"""K-EXAONE's language model (gluon/model_zoo/exaone_moe.py) at its tiny
preset on the CPU, against the plain reference
(perfbench/reference/kexaone.py): the whole-sequence forward, the
engine's CHUNKED prefill and its decode through pages (the full layer)
and rings (the sliding layers), with a window of 8, pages of 4 and chunks
of 12 so that rings wrap and chunks start mid-ring; the two kinds of
cache in one manager; the blocked attention passes against the plain
one; the near-tie rule and the share.
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
from mxnet_tpu.gluon.model_zoo import exaone_moe as ex       # noqa: E402
from mxnet_tpu.gluon.model_zoo import decoder_blocks         # noqa: E402
from mxnet_tpu.gluon.model_zoo import gpt                    # noqa: E402
from mxnet_tpu.serving import ServingEngine                  # noqa: E402
from mxnet_tpu.serving.programs import KVPages, SlotState    # noqa: E402
from reference import kexaone as reference                   # noqa: E402

WINDOW, PAGE, CHUNK = 8, 4, 12


@pytest.fixture(scope="module")
def net():
    return ex.exaone_moe_tiny().init_seeded(2 ** 31 + 7)


def tokens(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(0, vocab, n) \
        .astype(np.int32)


def engine(net, **kw):
    args = dict(num_slots=2, page_size=PAGE, num_pages=48,
                max_prefill_len=CHUNK, max_seq_len=80, record_logits=True)
    args.update(kw)
    return ServingEngine(net, **args)


def reference_rows(eng, net, req):
    """The reference's logits at the positions whose tokens ``req`` got."""
    seq = np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)])[:-1]
    want, _ = reference.forward(
        eng._p, seq, net.cfg, rows=np.arange(req.prompt.size - 1, seq.size))
    return np.asarray(want)


#: (prompt, new tokens): shorter than the window; one chunk exactly; two
#: chunks exactly (the last row on a chunk's edge); ragged over three
#: chunks; a window exactly; long enough to wrap a ring five times, then
#: decode across a page and a ring edge
WORK = [(5, 4), (12, 3), (24, 5), (31, 6), (8, 9), (40, 7), (13, 3)]


# -- the whole-sequence forward ---------------------------------------------

@pytest.mark.parametrize("length", [1, 7, 8, 9, 40])
def test_forward_agrees_with_the_reference(net, length):
    """The cache-free program form against the reference, on logits;
    lengths below, at and past the window (8)."""
    p = ex.decode_params(net)
    toks = jnp.asarray(tokens(length, length))
    got, routing = ex.forward(p, toks, net.cfg)
    want, ref_routing = reference.forward(p, toks, net.cfg)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    for mine, ref in zip(routing, ref_routing):
        assert (np.sort(mine, -1) == np.sort(ref["experts"], -1)).all()
    assert np.asarray(net(toks)._data).shape == (length, 256)


def test_the_layers_are_the_published_pattern():
    cfg = dict(ex.PUBLISHED, layers=[0, 4, 5, 6, 7])
    assert ex.layer_kinds(cfg) == [ex.SLIDING] * 4 + [ex.FULL]
    assert ex.ffn_kinds(cfg) == ["dense"] + ["moe"] * 4
    assert ex.PUBLISHED["layer_types"].count(ex.FULL) == 12
    assert ex.PUBLISHED["layer_types"][3::4] == [ex.FULL] * 12


def test_a_sliding_layer_rotates_and_a_full_layer_does_not(net):
    """Rotary positions on sliding layers only: shifting every position
    changes a sliding layer's queries and leaves a full layer's alone."""
    lp = ex.decode_params(net)["layers"][1]["attn"]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(6, 64)),
                    jnp.float32)
    pos = jnp.arange(6)
    for sliding in (True, False):
        a = ex._qkv(lp, x, pos, sliding, net.cfg)
        b = ex._qkv(lp, x, pos + 3, sliding, net.cfg)
        moved = float(np.abs(np.asarray(a[0] - b[0])).max())
        assert (moved > 1e-3) == sliding
        # the value is never rotated
        assert np.array_equal(np.asarray(a[2]), np.asarray(b[2]))


# -- the blocked attention passes against the plain one ---------------------

@pytest.mark.parametrize("t,first,rows", [(12, 0, 12), (12, 12, 12),
                                          (12, 24, 7), (16, 16, 16),
                                          (6, 36, 1)])
def test_a_chunk_of_a_sliding_layer_is_the_banded_softmax(t, first, rows):
    """``decoder_blocks.attend_window`` (a block of queries against the
    keys it can see, the rows before the chunk from the ring) against one
    softmax under the banded mask over the whole sequence."""
    rng = np.random.default_rng(t + first)
    total = first + t
    q, k, v = (jnp.asarray(rng.normal(size=(total, h, 16)), jnp.float32)
               for h in (4, 2, 2))
    pos = jnp.arange(total)
    gap = pos[:, None] - pos[None, :]
    want = ex._attend(q, k, v, (gap >= 0) & (gap < WINDOW))[first:]
    # the ring as the chunks before left it: position p at row p % window
    ring_k, ring_v = (np.full((WINDOW, 2, 16), 1e3, np.float32)
                      for _ in range(2))
    for p in range(first):
        ring_k[p % WINDOW], ring_v[p % WINDOW] = k[p], v[p]
    behind = (first + np.arange(WINDOW)) % WINDOW
    got = decoder_blocks.attend_window(q[first:], k[first:], v[first:],
                                       jnp.asarray(ring_k[behind]),
                                       jnp.asarray(ring_v[behind]), first,
                                       WINDOW)
    assert np.abs(np.asarray(got - want))[:rows].max() < 2e-6


@pytest.mark.parametrize("first", [0, 12, 36])
def test_a_chunk_of_a_full_layer_walks_the_pages_in_blocks(first,
                                                           monkeypatch):
    """``decoder_blocks.attend_pages`` with blocks of 4 queries and 8
    keys (so that a chunk takes several of each and the blocks behind a
    query block are skipped) against one softmax under the causal
    mask."""
    monkeypatch.setattr(decoder_blocks, "FULL_QUERY_BLOCK", 4)
    monkeypatch.setattr(decoder_blocks, "FULL_KEY_BLOCK", 8)
    rng = np.random.default_rng(first)
    total = first + CHUNK
    q, k, v = (jnp.asarray(rng.normal(size=(total, h, 16)), jnp.float32)
               for h in (4, 2, 2))
    pos = jnp.arange(total)
    want = ex._attend(q, k, v, pos[:, None] >= pos[None, :])[first:]
    # the slot's pages in a scrambled order, stale rows past the chunk
    table = np.random.default_rng(1).permutation(np.arange(1, 14))
    pools = [np.full((16, PAGE, 32), 1e3, np.float32) for _ in range(2)]
    for pool, rows in zip(pools, (k, v)):
        for p in range(total):
            pool[table[p // PAGE], p % PAGE] = np.asarray(rows[p]).ravel()
    got = decoder_blocks.attend_pages(q[first:], jnp.asarray(pools[0]),
                                      jnp.asarray(pools[1]),
                                      jnp.asarray(table, jnp.int32), first)
    assert np.abs(np.asarray(got - want)).max() < 2e-6


# -- the engine: chunked prefill, then decode through pages and rings -------

@pytest.fixture(scope="module")
def served(net):
    """Seven requests through two slots: prompts of one chunk and of
    several, every slot reused, decode beside a prefilling slot, two
    slots live at different lengths."""
    telemetry.reset()
    eng = engine(net)
    reqs = [eng.submit(tokens(n, 100 + n), new) for n, new in WORK]
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


def test_engine_counts_chunks_pages_and_rings(net, served):
    eng, reqs = served
    assert eng.prefills == len(WORK)
    assert eng.prefill_chunks == sum(-(-n // CHUNK) for n, _ in WORK)
    # one full layer in pages: K and V of 2 heads x 16 in float32 a
    # token; four sliding layers in rings of 8 rows a slot
    assert eng.kv_bytes_per_token == 2 * 2 * 16 * 4
    assert eng.state_bytes_per_slot == 4 * 2 * WINDOW * 2 * 16 * 4
    assert eng.snapshot()["state_bytes_per_slot"] == eng.state_bytes_per_slot
    kinds = eng._kinds
    assert [type(k) for k in kinds] == [SlotState] * 4 + [KVPages]
    assert all(k.role == "ring" for k in kinds[:4])
    # the pools are the full layer's alone; a ring has a row a slot and
    # a scratch row
    for (a, b), kind in zip(eng._kv, kinds):
        want = (48, PAGE, 32) if isinstance(kind, KVPages) \
            else (eng.num_slots + 1, WINDOW, 32)
        assert a.shape == b.shape == want
    # rows of K/V read, by the programs' own count: a prompt's row t
    # reads t + 1 keys on the full layer and min(t + 1, 8) on each of
    # the four sliding ones, and so does every decode step
    full = ring = 0
    for n, new in WORK:
        ctx = np.arange(1, n + new)
        full += ctx.sum()
        ring += np.minimum(ctx, WINDOW).sum()
    got = {k: sum(eng.stat_totals[p].get(k, 0)
                  for p in ("prefill", "decode"))
           for k in ("kv.rows_read", "kv.rows_full")}
    assert got == {"kv.rows_read": full + 4 * ring, "kv.rows_full": 5 * full}
    snap = telemetry.report()
    for name in ("serving.kv.rows_read", "serving.kv.rows_full",
                 "serving.moe.local_assignments", "serving.prefill.chunks"):
        assert name in snap["counters"], name
    assert snap["gauges"]["serving.cache.ring_bytes"] \
        == eng.num_slots * eng.state_bytes_per_slot
    assert snap["gauges"]["serving.cache.page_bytes_per_token"] \
        == eng.kv_bytes_per_token


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_engine_counts_the_experts_weight_tiles(served, program):
    """``weight_tiles`` (the row tiles that held a token: how often an
    expert's weights were read) comes back from both programs with the
    other counts and sums into ``serving.moe.weight_tiles``: a tile a
    hit expert at least, a tile a local assignment at most.  The layout
    held every step at the expected share's size: ``layout_overflow``
    (a layer that took the worst case's size) counts 0 in both."""
    eng, _ = served
    assert decoder_blocks.MOE_STATS[-2:] == ("weight_tiles",
                                             "layout_overflow")
    got = eng.stat_totals[program]
    assert 0 < got["experts_hit"] <= got["weight_tiles"] \
        <= got["local_assignments"]
    both = sum(eng.stat_totals[p]["weight_tiles"]
               for p in ("prefill", "decode"))
    # engines of later tests count into the same counter
    assert telemetry.report()["counters"]["serving.moe.weight_tiles"] \
        >= both
    assert got["layout_overflow"] == 0
    assert "serving.moe.layout_overflow" in telemetry.report()["counters"]


@pytest.mark.parametrize("ahead", [0, 2])
def test_a_reused_slot_does_not_see_the_last_tenants_ring(net, ahead):
    """One slot, two tenants: the second's logits are the reference's
    though the first left its rows in every ring, and though the rings
    are then filled with large values."""
    eng = engine(net, num_slots=1, decode_ahead=ahead)
    first = eng.submit(tokens(29, 1), 4)
    eng.run_until_idle()
    assert first.done
    eng._kv = [tuple(jnp.full_like(a, 1e3) for a in entry)
               if isinstance(kind, SlotState) else entry
               for entry, kind in zip(eng._kv, eng._kinds)]
    for n in (3, 17):
        second = eng.submit(tokens(n, 50 + n), 6)
        eng.run_until_idle()
        assert second.done and second.slot == first.slot
        got = np.stack(second.logits_trace)
        assert np.abs(got - reference_rows(eng, net, second)).max() < 2e-5


def test_decode_ahead_gives_the_same_tokens_and_logits(net, served):
    _, reqs = served
    eng = engine(net, decode_ahead=2)
    ahead = [eng.submit(tokens(n, 100 + n), new) for n, new in WORK]
    eng.run_until_idle()
    for a, b in zip(ahead, reqs):
        assert a.tokens == b.tokens
        assert np.abs(np.stack(a.logits_trace)
                      - np.stack(b.logits_trace)).max() < 1e-6


@pytest.mark.parametrize("ahead", [0, 2])
def test_pages_and_rings_are_conserved_from_admission_to_abort(net, ahead):
    """``PagedKVAllocator.assert_conservation`` across admit, chunked
    prefill, decode, finish and abort; a PREFILLING slot that is
    cancelled frees its pages and its ring, and the next tenant of that
    slot decodes the reference's logits."""
    telemetry.reset()
    eng = engine(net, decode_ahead=ahead)
    free = eng.alloc.free_pages
    per_slot = eng.state_bytes_per_slot
    short = eng.submit(tokens(6, 7), 12)
    long_ = eng.submit(tokens(40, 8), 5)
    waiting = eng.submit(tokens(21, 9), 4)

    def gauge(name):
        return telemetry.report()["gauges"][name]

    eng.step()
    eng.alloc.assert_conservation()
    assert eng.sched.occupancy == 2 and eng.alloc.free_pages < free
    while not (long_.prefilling and long_.prefilled >= CHUNK):
        eng.step()
        eng.alloc.assert_conservation()
    assert gauge("serving.state.live_bytes") == 2 * per_slot
    slot = long_.slot
    # abort mid-prompt: one chunk in, two to go
    assert eng.cancel(long_.trace)["verdict"] == "cancelled"
    eng.alloc.assert_conservation()
    assert long_.done and not long_.tokens
    eng.step()
    eng.alloc.assert_conservation()
    assert waiting.slot == slot
    eng.run_until_idle()
    eng.alloc.assert_conservation()
    assert eng.alloc.free_pages == free and eng.sched.occupancy == 0
    assert gauge("serving.state.live_bytes") == 0
    for r in (short, waiting):
        assert r.done and len(r.tokens) == r.max_new
        got = np.stack(r.logits_trace)
        assert np.abs(got - reference_rows(eng, net, r)).max() < 2e-5


def test_admission_counts_pages_for_the_full_layer_alone(net):
    """A pool too small for every layer of the starting population's
    tokens, were the sliding layers paged, admits it: a request reserves
    ``ceil((prompt + new) / page)`` pages whatever the number of
    layers, and the rings are the slots'."""
    eng = engine(net, num_pages=1 + 2 * (-(-46 // PAGE)), max_seq_len=46)
    a, b = (eng.submit(tokens(40, s), 6) for s in (1, 2))
    eng.step()
    assert a.admit_t is not None and b.admit_t is not None
    assert eng.alloc.free_pages == 0
    eng.run_until_idle()
    assert a.done and b.done and len(b.tokens) == 6
    eng.alloc.assert_conservation()


@pytest.mark.parametrize("kw,match", [
    (dict(spec_k=2), "spec_k must be 0"),
    (dict(spec_k=2), "a rejected draft has overwritten ring rows"),
    (dict(kv_dtype="int8"), "int8 pages"),
    (dict(kv_dtype="int8"), "per-slot window rings"),
    (dict(prefix_cache=True), "prefix cache"),
    (dict(prefix_cache=True), "a cached prefix has no ring"),
    (dict(kv_heads=2), "kv_heads"),
])
def test_engine_refuses_what_a_model_with_rings_cannot_do(net, kw, match):
    with pytest.raises(ValueError, match=match):
        engine(net, **kw)


def test_both_programs_name_their_scopes(net):
    """``attn.window``, ``attn.full`` and ``moe`` are named scopes of the
    decode and of the prefill program (the compiled text's op names)."""
    eng = engine(net)
    for prog in (eng._decode, eng._prefill):
        text = prog.__wrapped__.as_text()
        for scope in ("attn.window", "attn.full", "moe"):
            assert scope in text, scope


# -- the reference: precision, near ties, the share -------------------------

def test_the_reference_in_a_lower_precision_fails_the_tiny_limits(net,
                                                                  served):
    """The cell's tiny limits lie between this program's error and the
    reference's when it computes in float8_e4m3."""
    with open(os.path.join(REPO, "perfbench", "workloads",
                           "kexaone-serve-mixedlen.json")) as f:
        check = json.load(f)["tiny"]["correct"]
    eng, reqs = served
    r = reqs[5]
    got = np.stack(r.logits_trace)
    seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])[:-1]
    rows = np.arange(r.prompt.size - 1, seq.size)
    assert np.abs(got - reference_rows(eng, net, r)).max() \
        < check["tol_logit"] / 3
    low, _ = reference.forward(eng._p, seq, net.cfg, rows=rows,
                               compute_as="float8_e4m3fn")
    assert np.abs(got - np.asarray(low)).max() > check["tol_logit"]


def test_near_tie_rule_adopts_only_what_is_near(net):
    p = ex.decode_params(net)
    lp = next(l["moe"] for l in p["layers"] if "moe" in l)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(12, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        _, own = reference.moe(lp, x, net.cfg)
        scores = jax.nn.sigmoid(x @ lp["router_w"])
    idx = np.asarray(own["experts"])
    order = np.argsort(-np.asarray(scores), -1)
    # swap each row's 4th choice for its 5th (the tiny preset's k is 4)
    swapped = idx.copy()
    swapped[:, -1] = order[:, 4]
    gap = np.take_along_axis(np.asarray(scores), order, -1)
    gap = gap[:, 3] - gap[:, 4]
    with jax.default_matmul_precision("highest"):
        _, wide = reference.moe(lp, x, net.cfg, jnp.asarray(swapped),
                                float(gap.max()) + 1e-6)
        _, tight = reference.moe(lp, x, net.cfg, jnp.asarray(swapped),
                                 float(gap.min()) / 2)
        _, bad = reference.moe(lp, x, net.cfg,
                               jnp.asarray(np.repeat(idx[:, :1], 4, 1)), 1.0)
    assert np.asarray(wide["adopted"]).all()
    assert not np.asarray(wide["mismatch"]).any()
    assert np.allclose(np.asarray(wide["need"]), gap, atol=1e-6)
    assert np.asarray(tight["mismatch"]).all()
    assert np.asarray(bad["mismatch"]).all()


def test_the_eight_shares_add_up_to_the_uncut_layer(net):
    """The partial outputs of the eight shares (experts 0-1, 2-3, ...,
    14-15), with the shared expert counted once, are the uncut
    reference layer (model-configs guide, section 4)."""
    cfg = dict(net.cfg, experts_held=[0, 16])
    whole = ex.exaone_moe_tiny(experts_held=[0, 16]).init_seeded(8)
    lp = next(l["moe"] for l in ex.decode_params(whole)["layers"]
              if "moe" in l)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(24, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.moe(lp, x, cfg)
        shared = decoder_blocks.swiglu(x, lp["sh_gu_w"], lp["sh_down_w"])
        total = jnp.zeros_like(x)
        for first in range(0, 16, 2):
            share = dict(lp, gu_w=lp["gu_w"][first:first + 2],
                         down_w=lp["down_w"][first:first + 2])
            y, _, _ = decoder_blocks.moe(
                share, x, dict(cfg, experts_held=[first, 2]))
            ref_share, _ = reference.moe(
                share, x, dict(cfg, experts_held=[first, 2]))
            assert np.abs(np.asarray(y - ref_share)).max() < 1e-5
            total = total + (y - shared)
    assert np.abs(np.asarray(total + shared - want)).max() < 1e-5


def test_a_pad_row_is_given_to_no_held_expert(net):
    """A chunk's pad rows and an empty slot's row are routed and cost no
    held expert a row: the real rows' result and the counts are those of
    the real rows alone."""
    lp = next(l["moe"] for l in ex.decode_params(net)["layers"]
              if "moe" in l)
    x = jnp.asarray(np.random.default_rng(6).normal(size=(16, 64)),
                    jnp.float32)
    valid = jnp.arange(16) < 11
    y_all, experts_all, st_all = decoder_blocks.moe(lp, x, net.cfg)
    y, experts, st = decoder_blocks.moe(lp, x, net.cfg, valid)
    y_real, _, st_real = decoder_blocks.moe(lp, x[:11], net.cfg)
    assert np.array_equal(np.asarray(experts), np.asarray(experts_all))
    assert np.abs(np.asarray(y[:11] - y_real)).max() < 1e-6
    assert float(st["local_assignments"]) \
        == float(st_real["local_assignments"]) \
        < float(st_all["local_assignments"])
    # a pad row keeps the shared expert's term alone
    shared = decoder_blocks.swiglu(x, lp["sh_gu_w"], lp["sh_down_w"])
    assert np.abs(np.asarray(y[11:] - shared[11:])).max() < 1e-6


def test_this_model_shares_its_layer_functions():
    from mxnet_tpu.gluon.model_zoo import deepseek_v32, ling3
    for name in ("mm", "rms", "swiglu", "moe", "head"):
        assert getattr(ex, "_" + name) is getattr(decoder_blocks, name)
        assert getattr(ling3, "_" + name) is getattr(decoder_blocks, name)
    assert deepseek_v32._rows_per_block is decoder_blocks.rows_per_block
    assert ex._attend is decoder_blocks.attend
    assert gpt._page_scatter is decoder_blocks.page_scatter
    assert ex.DECODE_STATS[:len(decoder_blocks.MOE_STATS)] \
        == decoder_blocks.MOE_STATS
