"""DeepSeek-V3.2's language model for serving: latent attention (MLA)
with a compressed query, a lightning indexer that chooses the
``index_topk`` cached tokens each query attends to, and routed experts,
held as ONE CHIP'S SHARE of a stated deployment.

Layer ``l`` (published index), pre-norm residual, RMSNorm eps 1e-6::

    h = x + Attn_l(RMSNorm(x));   y = h + FFN_l(RMSNorm(h))

``Attn_l``: ``cQ = RMSNorm(h W_DQ)``; heads ``q_i = cQ W_UQ,i = [qC ;
qR]``; the cached row ``[cKV ; kR] = h W_DKV`` (``cKV`` RMSNormed, ``kR``
rotated, one row for all heads); the indexer ``I[t, s] = sum_j w[t, j]
relu(qI[t, j] . kI[s])`` with ``qI = cQ W_IQ``, ``kI = LayerNorm(h
W_IK)`` (the indexer's cached row) and ``w = h W_Iw / sqrt(heads x
dim)``; softmax over the ``index_topk`` largest ``I[t, s <= t]`` only,
with YaRN's frequencies and softmax scale.  ``FFN_l`` is a dense SwiGLU
for ``l < first_k_dense_replace``, else the routed experts
(``parallel.moe``: sigmoid scores, group-limited top-k) plus one shared
expert.  ``perfbench/reference/dsv32.py`` is the plain float32
statement of the same equations (dense attention under the selection as
a mask); this file is the program: bfloat16 weights as published,
float32 residual stream and accumulation, and it READS ``min(t + 1,
index_topk)`` latent rows a query.

**The share** (``cfg["experts_held"]``, ``cfg["layers"]``,
``cfg["vocab_size"]``) is ``ling3.py``'s: the router scores all
``num_experts``, this chip computes its held experts' terms and the
shared expert, the layers listed are the pipeline stage's, the
vocabulary is the slice held.  Nothing stands in for the absent chips.

**Serving** (:func:`paged_decode_step`, :func:`paged_prefill`): every
layer keeps TWO paged pools that one block table addresses
(``serving.programs.LatentPages`` of two widths): the latent rows ``[c |
k_rope | zeros]`` (576 values in 640 lanes, as ``ling3.py`` stores
them) and the indexer's keys (128 values a token).  Both programs
write their tokens' rows, score the slot's paged indexer keys
(``dsa_index``), take the ``index_topk`` positions of the largest
scores (``dsa_select``: the set ``lax.top_k`` gives, found without a
sort), gather those latent rows BY ROW and attend over the list
(``mla_sparse``).

The prefill is CHUNKED: one program of ``T`` rows that takes the
position of its first row (the engine's ``prefix_len`` argument) and
the prompt's length so far, reads the slot's own pages for everything
before the chunk, and is run as often as the prompt needs.  No prefix
reuse across requests, no speculative decoding, no int8 rows.
"""
from __future__ import annotations

import functools

import numpy as _np

from ...telemetry import device_scope
from ..block import Block
from . import decoder_blocks as _blocks
from .decoder_blocks import (latent_width, mm as _mm, rms as _rms,
                             head as _head, swiglu as _swiglu, moe as _moe,
                             latent_rows as _latent_rows,
                             rows_per_block as _rows_per_block)

__all__ = ["DeepseekV32LM", "deepseek_v32", "deepseek_v32_tiny",
           "decode_params", "param_tree", "forward", "paged_decode_step",
           "paged_prefill", "ffn_kinds", "DECODE_STATS"]

#: the published sizes (config.json of the source); ``num_experts`` is
#: its ``n_routed_experts``, the router's width
PUBLISHED = {
    "num_hidden_layers": 61, "hidden_size": 7168,
    "intermediate_size": 18432, "moe_intermediate_size": 2048,
    "n_shared_experts": 1, "num_attention_heads": 128,
    "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "index_n_heads": 64,
    "index_head_dim": 128, "index_topk": 2048, "num_experts": 256,
    "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
    "routed_scaling_factor": 2.5, "first_k_dense_replace": 3,
    "vocab_size": 129280, "rope_theta": 10000,
    "max_position_embeddings": 163840, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
}

#: query rows a block of a prefill chunk's attention (the gathered lists
#: of a block are ``[rows, index_topk, width]`` at once)
PREFILL_ATTN_ROWS = 64
#: query rows a group of a prefill chunk's index scoring
PREFILL_INDEX_ROWS = 32

#: the programs' trailing counts: the expert layers' (``ling3.py``'s)
#: and, summed over layers, the latent rows attended and in context
DECODE_STATS = _blocks.MOE_STATS + ("dsa.rows_attended",
                                    "dsa.rows_in_context")


def ffn_kinds(cfg):
    """"dense" or "moe" of every kept layer, by its PUBLISHED index."""
    return ["dense" if l < cfg["first_k_dense_replace"] else "moe"
            for l in cfg["layers"]]


def _param_shapes(cfg):
    """``{path: (shape, init)}`` of every parameter; matrices are
    ``[in, out]``."""
    c, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rank, qr = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    n_i, d_i = cfg["index_n_heads"], cfg["index_head_dim"]
    f = cfg["moe_intermediate_size"]
    fs = f * cfg["n_shared_experts"]
    held = cfg["experts_held"][1]
    out = {"wte": ((cfg["vocab_size"], c), "normal"),
           "head": ((cfg["vocab_size"], c), "normal"),
           "lnf_gamma": ((c,), "ones")}
    for i, ffn in enumerate(ffn_kinds(cfg)):
        pre = "l%d_" % i
        out.update({
            pre + "ln1_gamma": ((c,), "ones"),
            pre + "ln2_gamma": ((c,), "ones"),
            pre + "attn_q_a_w": ((c, qr), "normal"),
            pre + "attn_q_a_norm_gamma": ((qr,), "ones"),
            pre + "attn_q_b_w": ((qr, h * (dn + dr)), "normal"),
            pre + "attn_kva_w": ((c, rank + dr), "normal"),
            pre + "attn_kv_norm_gamma": ((rank,), "ones"),
            pre + "attn_kvb_w": ((rank, h * (dn + dv)), "normal"),
            pre + "attn_o_w": ((h * dv, c), "normal"),
            pre + "idx_q_w": ((qr, n_i * d_i), "normal"),
            pre + "idx_k_w": ((c, d_i), "normal"),
            pre + "idx_k_norm_gamma": ((d_i,), "ones"),
            pre + "idx_k_norm_bias": ((d_i,), "zeros"),
            pre + "idx_w_w": ((c, n_i), "normal")})
        if ffn == "dense":
            out.update({
                pre + "mlp_gu_w": ((c, 2 * cfg["intermediate_size"]),
                                   "normal"),
                pre + "mlp_down_w": ((cfg["intermediate_size"], c),
                                     "normal")})
        else:
            out.update({
                pre + "moe_router_w": ((c, cfg["num_experts"]), "normal"),
                pre + "moe_router_bias": ((cfg["num_experts"],), "zeros"),
                pre + "moe_gu_w": ((held, c, 2 * f), "normal"),
                pre + "moe_down_w": ((held, f, c), "normal"),
                pre + "moe_sh_gu_w": ((c, 2 * fs), "normal"),
                pre + "moe_sh_down_w": ((fs, c), "normal")})
    return out


class DeepseekV32LM(Block):
    """The decoder as a Gluon block: parameters by name, ``net(tokens)``
    the whole-sequence forward (``tokens`` int [T] -> logits [T, V]),
    and :meth:`serving_programs` for ``ServingEngine``."""

    def __init__(self, cfg, dtype="bfloat16", **kwargs):
        super().__init__(**kwargs)
        self.cfg = dict(cfg)
        self._max_len = int(cfg["max_position_embeddings"])
        self._inits = {}
        with self.name_scope():
            for path, (shape, init) in _param_shapes(self.cfg).items():
                p = self.params.get(path, shape=shape, dtype=dtype,
                                    grad_req="null")
                self._inits[p.name] = init
                setattr(self, path, p)

    def init_seeded(self, seed):
        """Seeded values in the stored type (``decoder_blocks``)."""
        return _blocks.init_seeded(self, self._inits, seed)

    def forward(self, tokens):
        import jax.numpy as jnp
        from ...ndarray import NDArray
        toks = jnp.asarray(getattr(tokens, "_data", tokens), jnp.int32)
        return NDArray(forward(decode_params(self), toks, self.cfg)[0])

    def serving_programs(self):
        """What ``ServingEngine`` needs of a model, in one object."""
        from ...serving.programs import ServingPrograms, LatentPages
        cfg = self.cfg
        kind = LatentPages((latent_width(cfg), cfg["index_head_dim"]))
        return ServingPrograms(
            n_heads=cfg["num_attention_heads"], max_len=self._max_len,
            decode_params=lambda net, kv_heads=None: decode_params(net),
            decode_step=functools.partial(paged_decode_step, cfg=cfg),
            prefill=functools.partial(paged_prefill, cfg=cfg),
            cache_kinds=[kind] * len(cfg["layers"]),
            decode_stats=DECODE_STATS, chunked_prefill=True,
            config_key=repr(sorted((k, repr(v)) for k, v in cfg.items())))


def deepseek_v32(dtype="bfloat16", **overrides):
    """The published widths as one chip's share of a deployment in
    which 16 chips share each layer by expert parallelism: a pipeline
    stage of published layers 0 and 3-6 (one dense layer, four expert
    layers), experts 0-15 of 256, an eighth of the vocabulary."""
    cfg = dict(PUBLISHED, layers=[0, 3, 4, 5, 6], experts_held=[0, 16],
               vocab_size=16160)
    cfg.update(overrides)
    return DeepseekV32LM(cfg, dtype=dtype)


def deepseek_v32_tiny(dtype="float32", **overrides):
    """Test-scale preset: hidden 64, 4 heads, query rank 48, latent 32 +
    8 rope, an indexer of 4 heads x 16 choosing 8 rows, 16 experts in 4
    groups (top 2 groups, top 4 experts), 4 held; one dense layer and
    two expert layers."""
    cfg = dict(PUBLISHED, hidden_size=64, intermediate_size=96,
               moe_intermediate_size=32, num_attention_heads=4,
               q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, index_n_heads=4,
               index_head_dim=16, index_topk=8, num_experts=16,
               n_group=4, topk_group=2, num_experts_per_tok=4,
               vocab_size=256, max_position_embeddings=4096,
               rope_scaling=dict(PUBLISHED["rope_scaling"],
                                 original_max_position_embeddings=32),
               layers=[0, 3, 4], experts_held=[4, 4])
    cfg.update(overrides)
    return DeepseekV32LM(cfg, dtype=dtype)


def param_tree(cfg, leaf):
    """The parameter tree the programs take, by layer, with
    ``leaf(path, shape)`` at every parameter."""
    return _blocks.param_tree(_param_shapes(cfg), len(cfg["layers"]),
                              ("attn", "idx", "mlp", "moe"), leaf)


def decode_params(net):
    """The net's live arrays (no copy) as the programs' tree."""
    return param_tree(net.cfg,
                      lambda path, _: getattr(net, path).data()._data)


# ---------------------------------------------------------------------------
# the layer, as functions of the parameter tree
# ---------------------------------------------------------------------------

def _freqs(cfg):
    rs = cfg["rope_scaling"]
    return _blocks.yarn_inv_freq(
        cfg["qk_rope_head_dim"], float(cfg["rope_theta"]), rs["factor"],
        rs["original_max_position_embeddings"], rs["beta_fast"],
        rs["beta_slow"])


def softmax_scale(cfg):
    """``(dn + dr) ** -0.5 * m * m`` with YaRN's ``m = 0.1 ln(factor) +
    1``."""
    rs = cfg["rope_scaling"]
    m = 0.1 * rs["mscale_all_dim"] * _np.log(rs["factor"]) + 1.0
    return float((cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
                 * m * m)


def _layer_norm(x, g, b):
    import jax
    import jax.numpy as jnp
    with device_scope("norm"):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + _blocks.EPS) \
            * g.astype(jnp.float32) + b.astype(jnp.float32)


def _attn_inputs(lp, ip, h, pos, cfg):
    """What every form of the layer's attention needs of the normed
    input ``h`` [T, C] at positions ``pos``: ``q_nope, q_rope`` [T, H,
    .], the latent ``c`` [T, rank] and ``k_rope`` [T, dr] (the cached
    row), the indexer's queries ``qi`` [T, N, D], key ``ki`` [T, D] (its
    cached row) and head weights ``wi`` [T, N]."""
    import jax.numpy as jnp
    t = h.shape[0]
    n_h = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank = cfg["kv_lora_rank"]
    n_i, d_i = cfg["index_n_heads"], cfg["index_head_dim"]
    freqs = _freqs(cfg)
    with device_scope("attn.proj"):
        c_q = _rms(_mm(h, lp["q_a_w"]), lp["q_a_norm_g"])
        q = _mm(c_q, lp["q_b_w"]).reshape(t, n_h, dn + dr)
        kva = _mm(h, lp["kva_w"])
    with device_scope("index"):
        qi = _mm(c_q, ip["q_w"]).reshape(t, n_i, d_i)
        qi = jnp.concatenate([_blocks.rope(qi[..., :dr], pos, freqs),
                              qi[..., dr:]], -1)
        ki = _layer_norm(_mm(h, ip["k_w"]), ip["k_norm_g"],
                         ip["k_norm_bias"])
        ki = jnp.concatenate([_blocks.rope(ki[:, :dr], pos, freqs),
                              ki[:, dr:]], -1)
        wi = _mm(h, ip["w_w"]) * _np.float32(n_i ** -0.5 * d_i ** -0.5)
    with device_scope("attn.proj"):
        return (q[..., :dn],
                _blocks.rope(q[..., dn:], pos, freqs, interleaved=True),
                _rms(kva[:, :rank], lp["kv_norm_g"]),
                _blocks.rope(kva[:, rank:], pos, freqs, interleaved=True),
                qi, ki, wi)


def _attend(lp, q_nope, q_rope, rows, n_valid, cfg):
    """Absorbed latent attention of queries ``q_*`` [N, H, .], each over
    its own list of selected latent ``rows`` [N, K, W] (the first
    ``n_valid`` count), under the caller's ``attn`` scope.  Returns
    ``o`` float32 [N, H * dv]."""
    import jax.numpy as jnp
    from ...ops.pallas.sparse_latent_attention import mla_sparse
    n_h = cfg["num_attention_heads"]
    dn, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    rank = cfg["kv_lora_rank"]
    with device_scope("attn.proj"):
        kvb = lp["kvb_w"].reshape(rank, n_h, dn + dv)
        q_lat = jnp.einsum("shd,chd->shc", q_nope.astype(kvb.dtype),
                           kvb[..., :dn],
                           preferred_element_type=jnp.float32)
        q = jnp.concatenate([q_lat, q_rope], -1)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, rows.shape[2] - q.shape[2])))
    o_lat = mla_sparse(q, rows, n_valid, rank, softmax_scale(cfg))
    with device_scope("attn.out"):
        o = jnp.einsum("shc,chd->shd", o_lat.astype(kvb.dtype),
                       kvb[..., dn:], preferred_element_type=jnp.float32)
        return o.reshape(o.shape[0], n_h * dv)


def _write_rows(pool, rows, phys, offs):
    return pool.at[phys, offs].set(rows.astype(pool.dtype))


def _ffn(lp, x, cfg, routing, stats):
    h = _rms(x, lp["ln2_g"])
    if "mlp" in lp:
        with device_scope("mlp"):
            return x + _swiglu(h, lp["mlp"]["gu_w"], lp["mlp"]["down_w"])
    y, experts, st = _moe(lp["moe"], h, cfg)
    routing.append(experts)
    stats.append(st)
    with device_scope("moe"):
        return x + y


def _aux(cfg, n_rows, routing, stats, selected, n_valid, in_context):
    """The programs' report of a dispatch: the counts
    (``DECODE_STATS``), the chosen experts int32 [expert layers, rows,
    k] and the selected positions int32 [layers, rows, K] with the
    number that count, int32 [rows]."""
    import jax.numpy as jnp
    k = cfg["num_experts_per_tok"]
    n_layers = len(cfg["layers"])
    with device_scope("moe"), device_scope("moe.route"):
        counts = jnp.stack([n_valid.sum(), in_context.sum()]) \
            .astype(jnp.float32) * n_layers
        stats = jnp.concatenate([
            _blocks.moe_stats_vector(stats, n_rows * k,
                                     cfg["experts_held"][1]), counts])
        experts = jnp.stack(routing) if routing \
            else jnp.zeros((0, n_rows, k), jnp.int32)
    with device_scope("index"):
        return {"stats": stats, "experts": experts,
                "selected": jnp.stack(selected), "n_selected": n_valid}


def forward(p, tokens, cfg):
    """Whole-sequence forward WITHOUT a cache, for small sizes: tokens
    int32 [T] -> ``(logits float32 [T, V], chosen experts per expert
    layer)``.  Dense attention under the selection as a mask."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    t = tokens.shape[0]
    n_h = cfg["num_attention_heads"]
    dn, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    rank = cfg["kv_lora_rank"]
    pos = jnp.arange(t)
    causal = jnp.tril(jnp.ones((t, t), bool))
    x = p["wte"][tokens].astype(jnp.float32)
    routing, stats = [], []
    for lp in p["layers"]:
        h = _rms(x, lp["ln1_g"])
        q_nope, q_rope, c, k_rope, qi, ki, wi = _attn_inputs(
            lp["attn"], lp["idx"], h, pos, cfg)
        s = jnp.einsum("tnd,sd->tns", qi, ki)
        scores = jnp.where(causal, (jax.nn.relu(s) * wi[..., None]).sum(1),
                           -jnp.inf)
        top, idx = lax.top_k(scores, min(cfg["index_topk"], t))
        keep = jnp.zeros((t, t), bool).at[pos[:, None], idx].set(
            top > -jnp.inf)
        kv = _mm(c, lp["attn"]["kvb_w"]).reshape(t, n_h, dn + dv)
        st = (jnp.einsum("qhd,khd->hqk", q_nope, kv[..., :dn])
              + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)) \
            * softmax_scale(cfg)
        pr = jax.nn.softmax(jnp.where(keep[None], st, -jnp.inf), -1)
        o = jnp.einsum("hqk,khd->qhd", pr, kv[..., dn:])
        x = x + _mm(o.reshape(t, n_h * dv), lp["attn"]["o_w"])
        x = _ffn(lp, x, cfg, routing, stats)
    return _head(_rms(x, p["lnf_g"]), p["head"]), routing


def paged_decode_step(p, tokens, positions, active, caches, block_tables,
                      n_heads, sampling=None, cfg=None):
    """ONE decode step for every serving slot (the contract of
    ``gpt.paged_decode_step``): ``caches`` holds ``(latent pool, index
    pool)`` a layer, donated by the caller's jit.

    Returns ``(logits [S, V], next_tokens [S], new_keys, new_caches,
    aux)`` with sampling and ``(logits, next_tokens, new_caches, aux)``
    without; ``aux`` as :func:`_aux` has it.
    """
    import jax.numpy as jnp
    from jax import lax
    from .gpt import sample_tokens
    from ...ops.pallas.sparse_latent_attention import (dsa_index, dsa_select,
                                                       gather_rows)

    s_n = tokens.shape[0]
    topk = cfg["index_topk"]
    with device_scope("embed"):
        x = p["wte"][tokens].astype(jnp.float32)
    ctx = jnp.where(active, positions + 1, 0).astype(jnp.int32)
    n_valid = jnp.minimum(ctx, topk)
    slots = jnp.arange(s_n, dtype=jnp.int32)
    new_caches, routing, stats, selected = [], [], [], []
    for lp, (pool, ipool) in zip(p["layers"], caches):
        page_size = pool.shape[1]
        k_sel = min(topk, block_tables.shape[1] * page_size)
        h = _rms(x, lp["ln1_g"])
        q_nope, q_rope, c, k_rope, qi, ki, wi = _attn_inputs(
            lp["attn"], lp["idx"], h, positions, cfg)
        with device_scope("kv_write"):
            phys = jnp.where(active, jnp.take_along_axis(
                block_tables, (positions // page_size)[:, None],
                axis=1)[:, 0], 0)
            offs = positions % page_size
            pool = _write_rows(pool, _latent_rows(
                c, k_rope, pool.shape[2], pool.dtype), phys, offs)
            ipool = _write_rows(ipool, ki, phys, offs)
        with device_scope("index"):
            scores = dsa_index(qi[:, None], wi[:, None], ipool,
                               block_tables, slots, ctx, positions)[:, 0]
            with device_scope("index.select"):
                sel = dsa_select(scores, ctx, k_sel)
        with device_scope("attn"):
            with device_scope("attn.gather"):
                rows = gather_rows(pool, block_tables, sel)
            o = _attend(lp["attn"], q_nope, q_rope, rows, n_valid, cfg)
        with device_scope("attn.out"):
            x = x + _mm(o, lp["attn"]["o_w"])
        x = _ffn(lp, x, cfg, routing, stats)
        new_caches.append((pool, ipool))
        selected.append(sel)
    with device_scope("lm_head"):
        logits = _head(_rms(x, p["lnf_g"]), p["head"])
    aux = _aux(cfg, s_n, routing, stats, selected, n_valid, ctx)
    if sampling is None:
        return logits, logits.argmax(-1).astype(jnp.int32), new_caches, aux
    temps, top_ks, top_ps, keys = sampling
    with device_scope("sample"):
        nxt, new_keys = lax.cond(
            jnp.any(temps > 0),
            lambda: sample_tokens(logits, temps, top_ks, top_ps, keys),
            lambda: (logits.argmax(-1).astype(jnp.int32), keys))
    return logits, nxt, new_keys, new_caches, aux


def paged_prefill(p, tokens, prompt_len, prefix_len, block_table_row,
                  cow_src, cow_dst, caches, n_heads, sampling=None,
                  cfg=None):
    """ONE CHUNK of a prompt into the slot whose block-table row is
    ``block_table_row`` (the contract of ``gpt.paged_prefill``, read for
    a chunk): ``tokens`` [T] holds the prompt's positions ``prefix_len
    ..`` (padded), ``prompt_len`` is the prompt's length SO FAR
    (``prefix_len`` + this chunk's real rows); everything before
    ``prefix_len`` is in the slot's pages already, written by the
    chunks before.  Writes the chunk's rows, scores and selects against
    the slot's pages, attends over the selected rows.  ``cow_*`` are
    unused: no prefix is shared.

    Returns ``gpt._first_token``'s tuple for the chunk's LAST real row
    (the first generated token when the chunk is the prompt's last)
    with ``aux`` appended (:func:`_aux`, rows = the chunk's padded rows).
    """
    import jax.numpy as jnp
    from jax import lax
    from .gpt import _first_token
    from ...ops.pallas.sparse_latent_attention import dsa_index, dsa_select

    del cow_src, cow_dst
    t_pad = tokens.shape[0]
    topk = cfg["index_topk"]
    positions = prefix_len + jnp.arange(t_pad, dtype=jnp.int32)
    valid = positions < prompt_len
    in_context = jnp.where(valid, positions + 1, 0)
    n_valid = jnp.minimum(in_context, topk)
    with device_scope("embed"):
        x = p["wte"][tokens].astype(jnp.float32)
    new_caches, routing, stats, selected = [], [], [], []
    for lp, (pool, ipool) in zip(p["layers"], caches):
        page_size = pool.shape[1]
        max_ctx = block_table_row.shape[0] * page_size
        k_sel = min(topk, max_ctx)
        h = _rms(x, lp["ln1_g"])
        q_nope, q_rope, c, k_rope, qi, ki, wi = _attn_inputs(
            lp["attn"], lp["idx"], h, positions, cfg)
        with device_scope("kv_write"):
            phys = jnp.where(
                valid, block_table_row[jnp.minimum(positions, max_ctx - 1)
                                       // page_size], 0)
            offs = positions % page_size
            pool = _write_rows(pool, _latent_rows(
                c, k_rope, pool.shape[2], pool.dtype), phys, offs)
            ipool = _write_rows(ipool, ki, phys, offs)
        # index scores a group of rows at a time, the selection over each
        # row's own context
        with device_scope("index"):
            r_i = _rows_per_block(t_pad, PREFILL_INDEX_ROWS)
            groups = t_pad // r_i
            first = positions[::r_i]
            scores = dsa_index(
                qi.reshape(groups, r_i, *qi.shape[1:]),
                wi.reshape(groups, r_i, -1), ipool, block_table_row[None],
                jnp.zeros(groups, jnp.int32),
                jnp.minimum(first + r_i, prompt_len), first) \
                .reshape(t_pad, max_ctx)
            with device_scope("index.select"):
                sel = dsa_select(scores, in_context, k_sel)
        # attention a block of rows at a time (a block's gathered lists
        # are [rows, K, width] at once), gathered BY POSITION from the
        # slot's pages laid side by side once a layer: a page-table
        # lookup a selected row cost half of what its gather costs
        with device_scope("attn"):
            r_a = _rows_per_block(t_pad, PREFILL_ATTN_ROWS)
            blocks = t_pad // r_a
            with device_scope("attn.gather"):
                in_order = pool[block_table_row].reshape(max_ctx,
                                                         pool.shape[2])

            def one_block(args, lp=lp, in_order=in_order):
                qn, qr, s, n = args
                with device_scope("attn.gather"):
                    rows = in_order[s]
                return _attend(lp["attn"], qn, qr, rows, n, cfg)

            o = lax.map(one_block, (
                q_nope.reshape(blocks, r_a, *q_nope.shape[1:]),
                q_rope.reshape(blocks, r_a, *q_rope.shape[1:]),
                sel.reshape(blocks, r_a, k_sel),
                n_valid.reshape(blocks, r_a)))
        with device_scope("attn.out"):
            x = x + _mm(o.reshape(t_pad, -1), lp["attn"]["o_w"])
        x = _ffn(lp, x, cfg, routing, stats)
        new_caches.append((pool, ipool))
        selected.append(sel)
    with device_scope("lm_head"):
        last = lax.dynamic_index_in_dim(
            _rms(x, p["lnf_g"]), prompt_len - 1 - prefix_len, 0,
            keepdims=False)
        logits = _head(last, p["head"])
    aux = _aux(cfg, t_pad, routing, stats, selected, n_valid, in_context)
    return _first_token(logits, sampling, new_caches) + (aux,)
