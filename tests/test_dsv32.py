"""DeepSeek-V3.2's language model (gluon/model_zoo/deepseek_v32.py) at
its tiny preset on the CPU, against the plain reference
(perfbench/reference/dsv32.py): the whole-sequence forward, the engine's
CHUNKED prefill and its decode through both paged pools, the selection
against dense attention, each new kernel on the interpreter against
jax.numpy, the near-tie rule, and the share.
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
from mxnet_tpu.gluon.model_zoo import deepseek_v32 as ds     # noqa: E402
from mxnet_tpu.gluon.model_zoo import decoder_blocks         # noqa: E402
from mxnet_tpu.gluon.model_zoo import gpt                    # noqa: E402
from mxnet_tpu.serving import ServingEngine                  # noqa: E402
from reference import dsv32 as reference                     # noqa: E402

sla = importlib.import_module(
    "mxnet_tpu.ops.pallas.sparse_latent_attention")


@pytest.fixture(scope="module")
def net():
    return ds.deepseek_v32_tiny().init_seeded(2 ** 31 + 5)


def tokens(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(0, vocab, n) \
        .astype(np.int32)


def engine(net, **kw):
    args = dict(num_slots=2, page_size=8, num_pages=40,
                max_prefill_len=16, max_seq_len=72, record_logits=True)
    args.update(kw)
    return ServingEngine(net, **args)


#: prompts below, at and across the chunk (16), the page (8) and the
#: selection (8 rows), with a few generated tokens each
WORK = [(5, 4), (16, 3), (17, 3), (40, 5), (64, 3), (8, 9)]


# -- the whole-sequence forward ---------------------------------------------

@pytest.mark.parametrize("length", [1, 7, 8, 9, 40])
def test_forward_agrees_with_the_reference(net, length):
    """The cache-free program form against the reference, on logits;
    lengths below, at and past ``index_topk`` (8)."""
    p = ds.decode_params(net)
    toks = jnp.asarray(tokens(length, length))
    got, routing = ds.forward(p, toks, net.cfg)
    want, ref_routing, _ = reference.forward(p, toks, net.cfg)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    for mine, ref in zip(routing, ref_routing):
        assert (np.sort(mine, -1) == np.sort(ref["experts"], -1)).all()
    assert np.asarray(net(toks)._data).shape == (length, 256)


def test_yarn_frequencies_and_scale_are_the_references(net):
    cfg = net.cfg
    rs = cfg["rope_scaling"]
    mine = decoder_blocks.yarn_inv_freq(
        cfg["qk_rope_head_dim"], float(cfg["rope_theta"]), rs["factor"],
        rs["original_max_position_embeddings"], rs["beta_fast"],
        rs["beta_slow"])
    assert np.allclose(mine, reference.yarn_inv_freq(cfg), rtol=1e-7)
    assert abs(ds.softmax_scale(cfg) - reference.softmax_scale(cfg)) < 1e-9
    # published: 192^-1/2 x (0.1 ln 40 + 1)^2
    full = dict(ds.PUBLISHED)
    assert abs(ds.softmax_scale(full)
               - 192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2) < 1e-9
    # low pairs keep the plain frequency, high pairs are divided by 40
    pub = decoder_blocks.yarn_inv_freq(64, 10000.0, 40, 4096, 32, 1)
    plain = 1.0 / 10000.0 ** (np.arange(32) / 32)
    assert np.isclose(pub[0], plain[0]) and np.isclose(pub[-1],
                                                       plain[-1] / 40)


@pytest.mark.parametrize("interleaved", [False, True])
def test_rope_layouts_agree_with_the_reference(interleaved):
    x = jnp.asarray(np.random.default_rng(3).normal(size=(6, 3, 8)),
                    jnp.float32)
    pos = jnp.arange(6) + 11
    inv = jnp.asarray(1.0 / 10000.0 ** (np.arange(4) / 4), jnp.float32)
    got = decoder_blocks.rope(x, pos, inv, interleaved=interleaved)
    want = reference._rope(x, pos, inv, interleaved)
    assert np.abs(np.asarray(got - want)).max() < 1e-6
    # a rotation keeps every pair's length
    assert np.allclose((np.asarray(got) ** 2).sum(-1),
                       (np.asarray(x) ** 2).sum(-1), rtol=1e-5)


# -- the engine: chunked prefill, then decode through both pools ------------

@pytest.fixture(scope="module")
def served(net):
    """Six requests through two slots: prompts of one chunk and of
    several, every slot reused, decode beside a prefilling slot."""
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
    seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])[:-1]
    want, _, _ = reference.forward(
        eng._p, seq, net.cfg, rows=np.arange(r.prompt.size - 1, seq.size))
    got = np.stack(r.logits_trace)
    assert np.abs(got - np.asarray(want)).max() < 2e-5
    assert (got.argmax(-1) == np.asarray(r.tokens)).all()


def test_engine_counts_chunks_pools_and_sparsity(net, served):
    eng, reqs = served
    chunks = sum(-(-n // 16) for n, _ in WORK)
    assert eng.prefills == len(WORK) and eng.prefill_chunks == chunks
    # two pools a layer: 128 padded latent lanes + 16 index values
    assert eng.kv_bytes_per_token == 3 * (128 + 16) * 4
    assert [tuple(a.shape) for a in eng._kv[0]] == [(40, 8, 128),
                                                   (40, 8, 16)]
    assert eng.alloc.used_pages == 0 and eng.sched.occupancy == 0
    snap = telemetry.report()["counters"]
    assert snap["serving.prefill.chunks"] == chunks
    assert snap["serving.prefill.chunk_rows"] == sum(n for n, _ in WORK)
    assert snap["serving.prefill.chunk_rows_padded"] == 16 * chunks
    # rows attended: min(t + 1, 8) a query a layer; in context: t + 1
    dec, pre = eng.stat_totals["decode"], eng.stat_totals["prefill"]
    want_att = want_ctx = 0
    for n, new in WORK:
        for t in range(n, n + new - 1):
            want_att += 3 * min(t + 1, 8)
            want_ctx += 3 * (t + 1)
    assert dec["dsa.rows_attended"] == want_att
    assert dec["dsa.rows_in_context"] == want_ctx
    assert pre["dsa.rows_attended"] == sum(
        3 * min(t + 1, 8) for n, _ in WORK for t in range(n))
    assert snap["serving.dsa.rows_attended"] == want_att \
        + pre["dsa.rows_attended"]
    assert 0 < dec["local_assignments"] < dec["assignments"]
    events = [e for e in telemetry.request_events()
              if e["event"] == "prefill_chunk"]
    assert len(events) == chunks
    mine = [e["args"] for e in events if e["trace"] == reqs[3].trace]
    assert [(a["offset"], a["rows"], a["last"]) for a in mine] == [
        (0, 16, False), (16, 16, False), (32, 8, True)]


@pytest.mark.parametrize("ahead", [0, 2])
def test_chunked_prefill_is_the_unchunked_prefill(net, ahead):
    """The same prompts through a program of 16 rows (five runs for 72)
    and through one of 72 rows (one run): the same logits, with and
    without dispatches sent ahead; decode goes on beside a prefilling
    slot."""
    runs = {}
    for chunk in (16, 72):
        eng = engine(net, max_prefill_len=chunk, max_seq_len=80,
                     num_pages=48, decode_ahead=ahead)
        reqs = [eng.submit(tokens(n, 7 + n), new)
                for n, new in [(9, 12), (72, 4), (33, 5)]]
        eng.run_until_idle()
        assert all(r.done and len(r.tokens) == r.max_new for r in reqs)
        runs[chunk] = (eng, reqs)
    assert runs[16][0].prefill_chunks == 1 + 5 + 3
    assert runs[72][0].prefill_chunks == 3
    for a, b in zip(runs[16][1], runs[72][1]):
        assert a.tokens == b.tokens
        assert np.abs(np.stack(a.logits_trace)
                      - np.stack(b.logits_trace)).max() < 2e-5


def test_a_slot_decodes_while_another_prefills(net):
    """One chunk run a step: the short request's tokens arrive while the
    long prompt is still prefilling (the third state of a slot)."""
    eng = engine(net, max_seq_len=80, num_pages=48)
    short = eng.submit(tokens(5, 1), 12)
    long_ = eng.submit(tokens(64, 2), 2)
    seen = []
    while not (short.done and long_.done):
        eng.step()
        seen.append((len(short.tokens), long_.prefilled,
                     long_.prefilling, len(long_.tokens)))
    # the first step's one chunk run is the short prompt's; the long
    # prompt then takes four steps of one chunk each ...
    assert seen[0][:3] == (2, 0, True)
    assert [s[1] for s in seen[1:5]] == [16, 32, 48, 64]
    assert [s[2] for s in seen[1:5]] == [True, True, True, False]
    # ... during which the short request went on decoding
    assert [s[0] for s in seen[1:5]] == [3, 4, 5, 6]
    assert seen[3][3] == 0 and seen[4][3] >= 1
    assert eng.sched.prefilling == []


@pytest.mark.parametrize("ahead", [0, 2])
def test_one_chunk_run_a_step_for_the_slot_admitted_first(net, ahead):
    """Two slots prefill at once: every engine step sends ONE chunk run,
    the older admission's until its prompt is through."""
    eng = engine(net, max_seq_len=80, num_pages=48, decode_ahead=ahead)
    first = eng.submit(tokens(48, 1), 2)
    second = eng.submit(tokens(40, 2), 2)
    seen = []
    for _ in range(6):
        eng.step()
        seen.append((first.prefilled, second.prefilled))
    assert seen == [(16, 0), (32, 0), (48, 0), (48, 16), (48, 32), (48, 40)]
    eng.run_until_idle()
    assert eng.prefill_chunks == 6 and eng.prefills == 2


@pytest.mark.parametrize("model,ok", [("dsv32", True), ("gpt2", False)])
def test_a_prompt_over_max_prefill_len(net, model, ok):
    """Admitted for a model that declares a chunked prefill, refused as
    before for one that does not."""
    if ok:
        eng = engine(net)
        req = eng.submit(tokens(40, 4), 2)
        eng.run_until_idle()
        assert req.done and len(req.tokens) == 2
        return
    small = gpt.gpt2_tiny()
    small.initialize()
    eng = ServingEngine(small, num_slots=1, page_size=8,
                        max_prefill_len=16, max_seq_len=48)
    with pytest.raises(ValueError, match="exceeds max_prefill_len"):
        eng.submit(tokens(17, 1), 2)


@pytest.mark.parametrize("kw,match", [
    (dict(spec_k=2), "spec_k must be 0"),
    (dict(kv_dtype="int8"), "int8 pages"),
    (dict(prefix_cache=True), "prefix cache"),
])
def test_engine_refuses_what_this_model_cannot_do(net, kw, match):
    with pytest.raises(ValueError, match=match):
        engine(net, **kw)


# -- the selection ----------------------------------------------------------

def test_topk_over_the_context_is_dense_absorbed_mla():
    """With ``index_topk`` >= every context the selection keeps every
    row: the sparse path is plain latent attention (the form ``ling3``'s
    decode computes), whatever the indexer scores."""
    dense = ds.deepseek_v32_tiny(index_topk=4096).init_seeded(9)
    eng = engine(dense)
    reqs = [eng.submit(tokens(n, 50 + n), new) for n, new in [(21, 4),
                                                              (40, 3)]]
    eng.run_until_idle()
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])[:-1]
        # the reference with a selection that keeps all: its own
        # index_topk is past the sequence too
        want, _, _ = reference.forward(
            eng._p, seq, dense.cfg,
            rows=np.arange(r.prompt.size - 1, seq.size))
        assert np.abs(np.stack(r.logits_trace)
                      - np.asarray(want)).max() < 2e-5
    # and a scrambled indexer changes nothing
    p2 = jax.tree_util.tree_map(lambda a: a, eng._p)
    for lp in p2["layers"]:
        lp["idx"] = {k: v[::-1] if v.ndim == 1 else -v
                     for k, v in lp["idx"].items()}
    toks = jnp.asarray(tokens(30, 3))
    a, _ = ds.forward(eng._p, toks, dense.cfg)
    b, _ = ds.forward(p2, toks, dense.cfg)
    assert np.abs(np.asarray(a - b)).max() < 1e-6


def test_the_selection_changes_the_result(net):
    """Past ``index_topk`` rows the model is NOT dense attention: the
    test above would pass on a program that ignored the indexer."""
    p = ds.decode_params(net)
    toks = jnp.asarray(tokens(40, 3))
    sparse, _ = ds.forward(p, toks, net.cfg)
    dense, _ = ds.forward(p, toks, dict(net.cfg, index_topk=4096))
    assert np.abs(np.asarray(sparse - dense))[:8].max() < 1e-6
    assert np.abs(np.asarray(sparse - dense))[8:].max() > 1e-4


def test_the_engine_reports_the_rows_it_selected(net):
    """``aux["selected"]`` is what the comparison on the chip hands the
    reference: with it adopted at delta 0 nothing differs, a wrong row
    far from the boundary is a mismatch."""
    eng = engine(net)
    req = eng.submit(tokens(16, 6), 3)
    eng.step()
    logits, aux = eng.last_prefill
    sel = np.asarray(aux["selected"])                  # [layers, 16, 8]
    assert sel.shape == (3, 16, 8)
    assert (np.asarray(aux["n_selected"]) == np.minimum(
        np.arange(16) + 1, 8)).all()
    seq = req.prompt
    _, _, docs = reference.forward(eng._p, seq, net.cfg,
                                   sys_selected=list(sel))
    assert not any(np.asarray(d["mismatch"]).any() for d in docs)
    assert not any(np.asarray(d["adopted"]).any() for d in docs)
    # the worst-scored visible row instead of a selected one
    lp = eng._p["layers"][0]
    h = reference._rms(reference._f32(eng._p["wte"][seq]),
                       reference._f32(lp["ln1_g"]))
    c_q = reference._rms(reference._dot(h, lp["attn"]["q_a_w"]),
                         reference._f32(lp["attn"]["q_a_norm_g"]))
    scores = np.asarray(reference.index_scores(
        lp["idx"], h, c_q, net.cfg, reference.yarn_inv_freq(net.cfg)))
    bad = sel.copy()
    bad[0, 15, 0] = int(np.argmin(scores[15, :16]))
    assert bad[0, 15, 0] not in sel[0, 15]
    _, _, docs = reference.forward(eng._p, seq, net.cfg,
                                   sys_selected=list(bad),
                                   select_delta=1e-6)
    assert np.asarray(docs[0]["mismatch"])[15]
    big = float(np.asarray(docs[0]["need"])[15])
    _, _, docs = reference.forward(eng._p, seq, net.cfg,
                                   sys_selected=list(bad),
                                   select_delta=big + 1e-6)
    assert np.asarray(docs[0]["adopted"])[15]
    # a row given twice leaves the count short: never adopted
    bad[0, 15, 1] = bad[0, 15, 0]
    _, _, docs = reference.forward(eng._p, seq, net.cfg,
                                   sys_selected=list(bad), select_delta=1e9)
    assert np.asarray(docs[0]["mismatch"])[15]


@pytest.mark.parametrize("ahead", [0, 2])
def test_first_layer_selection_of_live_slots_from_their_tokens(net, ahead):
    """What the long-context cell checks after its window
    (``runners/serve_dsv32.timed_selection``): the first layer's index
    scores need a slot's tokens alone, and the rows the engine reports
    for a late chunk run and for every decoding slot's last decode row
    are the reference's; a report for another position is caught."""
    from runners import serve_dsv32
    eng = engine(net, num_slots=3, num_pages=60, decode_ahead=ahead,
                 record_logits=False)
    for i, n in enumerate([40, 33, 50, 44]):
        eng.submit(tokens(n, 10 + i), 12)
    for _ in range(9):
        eng.step()
    check = dict(chunk_offset_min=32, chunk_rows=4, max_steps=8,
                 select_delta=1e-4)
    ok, doc = serve_dsv32.timed_selection(eng, net.cfg, check)
    assert ok and doc["timed_select_mismatch"] == 0
    assert doc["timed_chunk_offset"] >= 32 and doc["timed_decode_rows"] >= 2
    assert doc["timed_select_rows"] > doc["timed_decode_rows"]
    assert doc["timed_context_max"] > 16 + net.cfg["index_topk"]
    # the same report held to the position BEFORE fails
    logits, aux = eng.last_decode
    eng.last_decode = (logits, dict(
        aux, selected=jnp.roll(aux["selected"], 1, axis=-1) + 1))
    ok, doc = serve_dsv32.timed_selection(eng, net.cfg,
                                          dict(check, max_steps=0))
    assert not ok and doc["timed_select_mismatch"] > 0
    assert doc["timed_chunk_offset"] is None


def test_reference_scores_of_chosen_rows_are_the_whole_matrix_rows(net):
    """``first_layer_index_scores`` (keys a block of rows at a time, a
    padded length) against the layer's own [T, T] scores."""
    p = ds.decode_params(net)
    cfg = net.cfg
    toks = tokens(45, 7)
    lp = p["layers"][0]
    with jax.default_matmul_precision("highest"):
        h = reference._rms(reference._f32(p["wte"][jnp.asarray(toks)]),
                           reference._f32(lp["ln1_g"]))
        c_q = reference._rms(reference._dot(h, lp["attn"]["q_a_w"]),
                             reference._f32(lp["attn"]["q_a_norm_g"]))
        whole = np.asarray(reference.index_scores(
            lp["idx"], h, c_q, cfg, reference.yarn_inv_freq(cfg)))
    rows = np.asarray([0, 9, 44])
    got = np.asarray(reference.first_layer_index_scores(
        p, toks, cfg, rows, pad_to=64))
    assert got.shape[0] == 3 and got.shape[1] >= 64
    assert np.allclose(got[:, :45], whole[rows], atol=1e-5)
    assert np.isneginf(got[:, 45:]).all()
    mask, doc = reference.select(jnp.asarray(got), cfg["index_topk"],
                                 positions=rows)
    own, _ = reference.select(jnp.asarray(whole), cfg["index_topk"])
    assert (np.asarray(mask)[:, :45] == np.asarray(own)[rows]).all()


# -- the kernels on the interpreter -----------------------------------------

POOL_BT = np.array([[3, 5, 7, 9, 0, 0], [2, 4, 6, 8, 10, 12]], np.int32)


@pytest.mark.parametrize("case", ["decode", "chunk", "empty", "bf16"])
def test_dsa_index_kernel_on_the_interpreter(case, monkeypatch):
    """Blocks of two pages: contexts that end inside a block, on its
    edge and before the first; a group of rows of one slot."""
    monkeypatch.setattr(sla, "INDEX_BLOCK_KEYS", 16)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    dtype = jnp.bfloat16 if case == "bf16" else jnp.float32
    pool = jax.random.normal(ks[0], (20, 8, 16), jnp.float32).astype(dtype)
    g, r, tbl, ctx, pos = {
        "decode": (2, 1, [0, 1], [27, 44], [26, 43]),
        "bf16": (2, 1, [0, 1], [32, 16], [31, 15]),
        "chunk": (3, 4, [1, 1, 1], [32, 36, 40], [28, 32, 36]),
        "empty": (2, 1, [0, 1], [0, 5], [0, 4])}[case]
    q = jax.random.normal(ks[1], (g, r, 4, 16), jnp.float32).astype(dtype)
    w = jax.random.normal(ks[2], (g, r, 4), jnp.float32)
    got = sla.dsa_index(q, w, pool, POOL_BT, tbl, ctx, pos)
    want = sla.dsa_index_reference(q, w, pool, POOL_BT, tbl, ctx, pos)
    assert got.shape == (g, r, 48)
    assert np.abs(np.asarray(got - want)).max() < 1e-4
    live = np.asarray(want) > -1e29
    assert live.sum() == sum(
        min(c, p + i + 1) for c, p in zip(ctx, pos) for i in range(r))


@pytest.mark.parametrize("n_valid", [[16, 5, 0], [9, 16, 1], [8, 8, 8]])
def test_mla_sparse_kernel_on_the_interpreter(n_valid, monkeypatch):
    """A list of 16 rows in blocks of 8, gathered by row through the
    block table: full lists, short ones, an empty one."""
    monkeypatch.setattr(sla, "SPARSE_BLOCK_ROWS", 8)
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    pool = jax.random.normal(ks[0], (20, 8, 128), jnp.float32)
    q = jax.random.normal(ks[1], (3, 4, 128), jnp.float32)
    pos = jax.random.randint(ks[2], (3, 16), 0, 30)
    rows = sla.gather_rows(pool, POOL_BT[[0, 1, 1]], pos)
    for i, t in enumerate([0, 1, 1]):
        for j in (0, 7, 15):
            p_ = int(pos[i, j])
            assert (np.asarray(rows[i, j])
                    == np.asarray(pool[POOL_BT[t, p_ // 8], p_ % 8])).all()
    assert (np.asarray(sla.gather_rows(pool, POOL_BT[1], pos[1:]))
            == np.asarray(rows[1:])).all()
    got = sla.mla_sparse(q, rows, n_valid, 96, 0.3)
    want = sla.mla_sparse_reference(q, rows, n_valid, 96, 0.3)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    for i, n in enumerate(n_valid):
        if n == 0:
            assert not np.asarray(got[i]).any()


def test_sparse_decode_is_the_selected_rows_softmax(net):
    """The absorbed, gathered decode form of one layer against plain
    attention over the same selected rows."""
    cfg = net.cfg
    lp = ds.decode_params(net)["layers"][1]["attn"]
    rng = np.random.default_rng(4)
    n_h, dn, dr, dv, rank = 4, 16, 8, 16, 32
    pool = jnp.asarray(rng.normal(size=(6, 8, 128)), jnp.float32) \
        .at[:, :, rank + dr:].set(0.0)
    bt = jnp.asarray([[1, 3, 5, 0]], jnp.int32)
    q_nope = jnp.asarray(rng.normal(size=(1, n_h, dn)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(1, n_h, dr)), jnp.float32)
    sel = jnp.asarray([[20, 3, 9, 17, 0, 0, 0, 0]], jnp.int32)
    got = ds._attend(lp, q_nope, q_rope, sla.gather_rows(pool, bt, sel),
                     jnp.asarray([4]), cfg)
    rows = np.stack([np.asarray(pool[int(bt[0, p // 8]), p % 8])
                     for p in (20, 3, 9, 17)])
    kv = (rows[:, :rank] @ np.asarray(lp["kvb_w"])).reshape(4, n_h, dn + dv)
    s = (np.einsum("hd,khd->hk", np.asarray(q_nope[0]), kv[..., :dn])
         + np.asarray(q_rope[0]) @ rows[:, rank:rank + dr].T) \
        * ds.softmax_scale(cfg)
    pr = np.exp(s - s.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    want = np.einsum("hk,khd->hd", pr, kv[..., dn:]).reshape(-1)
    assert np.abs(np.asarray(got[0]) - want).max() < 1e-5


def _select_case(name):
    """``(scores float32 [T, W], contexts [T], k)`` of one case of
    :func:`test_dsa_select_keeps_the_set_top_k_keeps`."""
    rng = np.random.default_rng(len(name))
    k, rows, w = 16, 8, 1024
    scores = rng.normal(size=(rows, w)).astype(np.float32)
    if name.startswith("context_"):
        ctx = [{"0": 0, "1": 1, "k-1": k - 1, "k": k, "k+1": k + 1}
               [name[len("context_"):]]] * rows
    elif name == "ragged_chunk":
        # a chunk of 40 rows at offset 900: row r sees 900 + r + 1 keys
        rows, w = 40, 2048
        scores = rng.normal(size=(rows, w)).astype(np.float32)
        ctx = 900 + 1 + np.arange(rows)
    elif name == "contexts_of_a_decode_step":
        ctx = [0, 1000, 3, 17, 1024, 16, 500, 129]
    elif name == "width_of_no_whole_tile":
        # 9 tiles of 128 lanes and 8 lanes more, 11 rows
        rows, w = 11, 1160
        scores = rng.normal(size=(rows, w)).astype(np.float32)
        ctx = rng.integers(k, w + 1, size=rows)
    elif name == "exact_zeros":
        # a score is 0 where no head's ReLU fires: the lower position
        scores = np.maximum(scores - 2.0, 0.0)
        scores[0] = 0.0
        scores[1, 5:] = -0.0
        ctx = [1024, 1024, 700, 300, 1024, 18, 1024, 999]
    elif name == "one_repeated_value":
        scores[:] = np.float32(0.37)
        scores[1] = -2.5
        scores[2, ::3] = 1.0
        ctx = [1024, 900, 1024, 17, 16, 15, 1024, 129]
    elif name == "negative_and_masked":
        scores = -np.abs(scores) - 1.0
        scores[:, ::2] = -1e30
        scores[3] = -1e30
        scores[4, 40:] = -np.inf
        ctx = [1024, 1000, 31, 1024, 1024, 200, 64, 1024]
    elif name == "selection_is_most_of_the_context":
        k = 300
        ctx = [301, 300, 1024, 600, 299, 310, 1000, 512]
    elif name == "cell_decode_rows":
        # the cell's decode run: 16 rows over 32,768 keys, 2,048 kept
        k, rows, w = 2048, 16, 32768
        scores = np.maximum(
            rng.normal(size=(rows, w)).astype(np.float32) - 1.5, 0.0)
        ctx = np.linspace(6900, 31100, rows).astype(np.int64)
        ctx[5] = 0
    else:
        raise ValueError(name)
    return scores, np.asarray(ctx, np.int64), k


@pytest.mark.parametrize("name", [
    "context_0", "context_1", "context_k-1", "context_k", "context_k+1",
    "ragged_chunk", "contexts_of_a_decode_step", "width_of_no_whole_tile",
    "exact_zeros", "one_repeated_value", "negative_and_masked",
    "selection_is_most_of_the_context", "cell_decode_rows"])
def test_dsa_select_keeps_the_set_top_k_keeps(name):
    """The selection without a sort against ``lax.top_k`` AS SETS over
    the first ``min(context, k)`` entries (equal scores go to the lower
    position); every index in range, none twice."""
    scores, ctx, k = _select_case(name)
    w = scores.shape[1]
    got = np.asarray(sla.dsa_select(jnp.asarray(scores),
                                    jnp.asarray(ctx, jnp.int32), k))
    assert got.shape == (len(ctx), k) and got.dtype == np.int32
    assert (got >= 0).all() and (got < w).all()
    masked = np.where(np.arange(w)[None, :] < ctx[:, None], scores, -np.inf)
    want = np.asarray(jax.lax.top_k(jnp.asarray(masked), k)[1])
    for r, c in enumerate(ctx):
        n = min(int(c), k)
        assert len(set(got[r, :n])) == n, (name, r)
        assert set(got[r, :n]) == set(want[r, :n]), (name, r)


# -- the share --------------------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer(net):
    """The partial outputs of the four shares (experts 0-3, 4-7, 8-11,
    12-15), with the shared expert counted once, are the uncut
    reference layer (model-configs guide, section 4)."""
    cfg = dict(net.cfg, experts_held=[0, 16])
    whole = ds.deepseek_v32_tiny(experts_held=[0, 16]).init_seeded(8)
    lp = next(l["moe"] for l in ds.decode_params(whole)["layers"]
              if "moe" in l)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(24, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.moe(lp, x, cfg)
        shared = decoder_blocks.swiglu(x, lp["sh_gu_w"], lp["sh_down_w"])
        total = jnp.zeros_like(x)
        for first in (0, 4, 8, 12):
            share = dict(lp, gu_w=lp["gu_w"][first:first + 4],
                         down_w=lp["down_w"][first:first + 4])
            y, _, _ = decoder_blocks.moe(
                share, x, dict(cfg, experts_held=[first, 4]))
            ref_share, _ = reference.moe(
                share, x, dict(cfg, experts_held=[first, 4]))
            assert np.abs(np.asarray(y - ref_share)).max() < 1e-5
            total = total + (y - shared)
    assert np.abs(np.asarray(total + shared - want)).max() < 1e-5


def test_ling3_and_this_model_share_their_layer_functions():
    from mxnet_tpu.gluon.model_zoo import ling3
    for name in ("mm", "rms", "swiglu", "moe", "latent_rows", "head"):
        assert getattr(ling3, "_" + name) is getattr(decoder_blocks, name)
        assert getattr(ds, "_" + name) is getattr(decoder_blocks, name)
    assert ling3.DECODE_STATS == decoder_blocks.MOE_STATS
    assert ds.DECODE_STATS[:len(decoder_blocks.MOE_STATS)] \
        == decoder_blocks.MOE_STATS
