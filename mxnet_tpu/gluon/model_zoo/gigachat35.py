"""GigaChat3.5's language model (``model_type`` ``gigachat3_5``) for
serving: gated delta-rule layers (32 query/key heads for 64 value heads,
one decay a head) beside gated latent attention, three to one, over
routed experts, held as ONE CHIP'S SHARE of a stated deployment.

A layer's norms and the final one are gated RMSNorm (``norm_type``
"ZeroCenteredGatedNorm"), ``N(x; w) = x / rms(x) * 2 sigmoid(w)`` with
the factor ``layernorm_gating_weight`` 2; the latent's query and key
norms and the delta rule's output norm are zero-centred, ``x / rms(x) *
(1 + w)``.  Both are a gain of 1 at ``w = 0``; eps 1e-6.  Layer ``l``
(published index), normed on both sides of each sub-layer
(``layernorm_type`` "pre_post")::

    h = x + N_2(Mix_l(N_1(x)));   y = h + N_4(FFN_l(N_3(h)))

``Mix_l`` is latent attention for ``l`` in ``full_attention_layers``,
else the gated delta rule; ``FFN_l`` is a dense SwiGLU for ``l <
first_k_dense_replace``, else the routed experts (``parallel.moe``:
sigmoid scores over all experts, the 8 largest of ``s + b``, weights
``2.5 s / sum of the chosen s``) plus one shared expert without a gate.
Every SwiGLU is clamped: ``silu(min(gate, 10)) * clip(up, -10, 10)``.
Final norm, untied head.

- **Gated delta rule**: ``raw = x W_qkv`` (4,096 query, 4,096 key, 8,192
  value channels), a depthwise causal convolution of width 4 without
  bias, SiLU; ``q``, ``k`` L2-normalised a head, ``q`` scaled by
  ``128 ** -0.5``; ``beta_h = sigmoid(x . b_h)``, ``g_h = -exp(A_h)
  softplus(x . a_h + dt_h)``; value head ``h`` reads key head ``j = h //
  2``: ``S_h <- exp(g_h) S_h; S_h <- S_h + k_j (beta_h (v_h - S_h^T
  k_j))^T; o_h = S_h^T q_j``; output ``W_o [(o_h / rms(o_h) (1 + w_o))
  2 sigmoid(z_h)]`` with ``z = x W_z``.
- **Latent attention**: 64 heads, ``q = W_qb N(W_qa x)`` (192 a head:
  128 without and 64 with rotary positions), ``[c | k_r] = W_kva x`` with
  ``c`` (512) normed and ``k_r`` (64) shared by all heads, ``[k_nope |
  v] = W_kvb c``; rotary pairs interleaved, YaRN frequencies (theta 1e5,
  factor 8 over 32,768); softmax scale ``192 ** -0.5 m ** 2``, ``m = 0.1
  ln 8 + 1``; output ``W_o [o * sigmoid(W_g x)]`` (a gate a value).

``perfbench/reference/gigachat35.py`` is the plain float32 statement of
the same equations (the delta rule token by token); this file is the
program: bfloat16 weights as published, float32 residual stream, state
and accumulation.

**The share** (``cfg["experts_held"]``, ``cfg["layers"]``,
``cfg["vocab_size"]``) is ``ling3.py``'s: the router scores all
``num_experts``, this chip computes its held experts' terms and the
shared expert, the layers listed are the pipeline stage's, the
vocabulary is the slice held.  Nothing stands in for the absent chips.

**Serving** (:func:`paged_decode_step`, :func:`paged_prefill`): a latent
layer keeps one paged pool of ``[c | k_r | zeros]`` rows (576 values in
640 lanes, ``serving.programs.LatentPages``); a delta-rule layer keeps a
slot's state ``[64, 128, 128]`` float32 and the convolution's last three
inputs (``SlotState`` of role "state").  The prefill is CHUNKED: one
program of ``T`` rows that takes the position of its first row
(``prefix_len``), the prompt's length so far and the slot.  A chunk at
``prefix_len`` 0 scans from a zero state and a zero tail (the reset at
admission); a later one starts from what the chunk before left in the
slot's rows and leaves its own there.  A latent layer writes the chunk's
rows as whole pages and scores the chunk against the slot's pages a
block at a time in the absorbed form (:func:`_attend_pages`).  No prefix
reuse across requests (a recurrent prefix is a state), no speculative
decoding (a state cannot be rolled back), no int8 rows.
"""
from __future__ import annotations

import functools

import numpy as _np

from ...telemetry import device_scope
from ..block import Block
from . import decoder_blocks as _blocks
from .decoder_blocks import (latent_width, mm as _mm, head as _head,
                             swiglu as _swiglu, moe as _moe,
                             latent_rows as _latent_rows,
                             rows_per_block as _rows_per_block)

__all__ = ["GigaChat35LM", "gigachat35", "gigachat35_tiny",
           "decode_params", "param_tree", "forward", "paged_decode_step",
           "paged_prefill", "layer_kinds", "DECODE_STATS"]

#: the published sizes (config.json of the source); ``num_experts`` is
#: its ``n_routed_experts``, the router's width
PUBLISHED = {
    "num_hidden_layers": 40, "hidden_size": 7168,
    "intermediate_size": 18432, "moe_intermediate_size": 2048,
    "n_shared_experts": 1, "num_attention_heads": 64,
    "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "num_experts": 256,
    "num_experts_per_tok": 8, "n_group": 1, "topk_group": 1,
    "routed_scaling_factor": 2.5, "first_k_dense_replace": 3,
    "vocab_size": 128256, "rope_theta": 100000,
    "max_position_embeddings": 262144, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32768,
                     "type": "yarn"},
    "full_attention_layers": [3, 7, 11, 15, 19, 23, 27, 31, 35, 39],
    "linear_key_head_dim": 128, "linear_value_head_dim": 128,
    "linear_conv_kernel_dim": 4, "linear_num_key_heads": 32,
    "linear_num_value_heads": 64, "linear_sigmoid_gate_scale": 2,
    "linear_attn_o_norm_eps": 1e-06, "swiglu_limit": 10,
    "layernorm_gating_weight": 2,
}

#: query rows and latent rows (keys) a block of a chunk's attention over
#: the slot's pages (a block's scores are ``[H, rows, keys]`` float32)
QUERY_BLOCK = 512
KEY_BLOCK = 1024
#: positions a step of the chunked delta-rule scan
SCAN_CHUNK = 64
#: value heads a grid cell of the decode step's kernel
STEP_HEADS = 16

#: the programs' trailing counts: the expert layers' and, summed over
#: the latent layers, the latent rows the dispatch's queries read (a
#: query's whole context)
DECODE_STATS = _blocks.MOE_STATS + ("latent.rows_read",)

_NEG = -1e30


def layer_kinds(cfg):
    """``(mix, ffn)`` of every kept layer, by its PUBLISHED index: mix
    "mla" or "gdn", ffn "dense" or "moe"."""
    return [("mla" if l in cfg["full_attention_layers"] else "gdn",
             "dense" if l < cfg["first_k_dense_replace"] else "moe")
            for l in cfg["layers"]]


def _gdn_dims(cfg):
    """``(key heads, value heads, key size, value size)``."""
    return (cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"])


def _param_shapes(cfg):
    """``{path: (shape, init)}`` of every parameter; matrices are
    ``[in, out]``.  Norm gains are stored at 0: a gain of 1 in either
    form of :func:`_norm`."""
    c, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rank, qr = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    hk, hv, dk, dvl = _gdn_dims(cfg)
    f = cfg["moe_intermediate_size"]
    fs = f * cfg["n_shared_experts"]
    held = cfg["experts_held"][1]
    out = {"wte": ((cfg["vocab_size"], c), "normal"),
           "head": ((cfg["vocab_size"], c), "normal"),
           "lnf_gamma": ((c,), "zeros")}
    for i, (mix, ffn) in enumerate(layer_kinds(cfg)):
        pre = "l%d_" % i
        out.update({
            pre + "ln1_gamma": ((c,), "zeros"),
            pre + "ln2_gamma": ((c,), "zeros"),
            pre + "post_mix_gamma": ((c,), "zeros"),
            pre + "post_ffn_gamma": ((c,), "zeros")})
        if mix == "mla":
            out.update({
                pre + "attn_q_a_w": ((c, qr), "normal"),
                pre + "attn_q_a_norm_gamma": ((qr,), "zeros"),
                pre + "attn_q_b_w": ((qr, h * (dn + dr)), "normal"),
                pre + "attn_kva_w": ((c, rank + dr), "normal"),
                pre + "attn_kv_norm_gamma": ((rank,), "zeros"),
                pre + "attn_kvb_w": ((rank, h * (dn + dv)), "normal"),
                pre + "attn_g_w": ((c, h * dv), "normal"),
                pre + "attn_o_w": ((h * dv, c), "normal")})
        else:
            width = 2 * hk * dk + hv * dvl
            out.update({
                pre + "gdn_qkv_w": ((c, width), "normal"),
                pre + "gdn_conv_w": ((cfg["linear_conv_kernel_dim"], width),
                                     "normal"),
                pre + "gdn_z_w": ((c, hv * dvl), "normal"),
                pre + "gdn_b_w": ((c, hv), "normal"),
                pre + "gdn_a_w": ((c, hv), "normal"),
                pre + "gdn_a_log": ((hv,), "normal"),
                pre + "gdn_dt_bias": ((hv,), "normal"),
                pre + "gdn_o_norm_gamma": ((dvl,), "zeros"),
                pre + "gdn_o_w": ((hv * dvl, c), "normal")})
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


class GigaChat35LM(Block):
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
        """What ``ServingEngine`` needs of a model: latent pages on a
        latent layer, a recurrent state and a convolution tail a slot on
        a delta-rule layer, and a chunked prefill that hands them on."""
        from ...serving.programs import (ServingPrograms, LatentPages,
                                         SlotState)
        cfg = self.cfg
        hk, hv, dk, dv = _gdn_dims(cfg)
        state = SlotState((
            ("state", (hv, dk, dv), "float32"),
            ("conv", (cfg["linear_conv_kernel_dim"] - 1,
                      2 * hk * dk + hv * dv), "float32")), "state")
        return ServingPrograms(
            n_heads=cfg["num_attention_heads"], max_len=self._max_len,
            decode_params=lambda net, kv_heads=None: decode_params(net),
            decode_step=functools.partial(paged_decode_step, cfg=cfg),
            prefill=functools.partial(paged_prefill, cfg=cfg),
            cache_kinds=[LatentPages((latent_width(cfg),)) if mix == "mla"
                         else state for mix, _ in layer_kinds(cfg)],
            decode_stats=DECODE_STATS, chunked_prefill=True,
            config_key=repr(sorted((k, repr(v)) for k, v in cfg.items())))


def gigachat35(dtype="bfloat16", **overrides):
    """The published widths as one chip's share of a deployment in which
    16 chips share each layer by expert parallelism: a pipeline stage of
    published layers 0 and 4-7 (a delta-rule layer over the dense SwiGLU,
    then one whole period of three delta-rule layers and a latent one
    over routed experts), experts 0-15 of 256, an eighth of the
    vocabulary."""
    cfg = dict(PUBLISHED, layers=[0, 4, 5, 6, 7], experts_held=[0, 16],
               vocab_size=16032)
    cfg.update(overrides)
    return GigaChat35LM(cfg, dtype=dtype)


def gigachat35_tiny(dtype="float32", **overrides):
    """Test-scale preset: hidden 64; latent attention of 4 heads (query
    rank 48, latent 32 + 8 rotary); a delta rule of 2 key heads for 4
    value heads of 16; 16 experts (top 4), 4 held; the leading dense
    layer and one period (delta, delta, delta, latent)."""
    cfg = dict(PUBLISHED, hidden_size=64, intermediate_size=96,
               moe_intermediate_size=32, num_attention_heads=4,
               q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, linear_num_key_heads=2,
               linear_num_value_heads=4, linear_key_head_dim=16,
               linear_value_head_dim=16, num_experts=16,
               num_experts_per_tok=4, vocab_size=256,
               max_position_embeddings=4096,
               rope_scaling=dict(PUBLISHED["rope_scaling"],
                                 original_max_position_embeddings=32),
               layers=[0, 4, 5, 6, 7], experts_held=[4, 4])
    cfg.update(overrides)
    return GigaChat35LM(cfg, dtype=dtype)


def param_tree(cfg, leaf):
    """The parameter tree the programs take, by layer, with
    ``leaf(path, shape)`` at every parameter."""
    return _blocks.param_tree(_param_shapes(cfg), len(cfg["layers"]),
                              ("post", "attn", "gdn", "mlp", "moe"), leaf)


def decode_params(net):
    """The net's live arrays (no copy) as the programs' tree."""
    return param_tree(net.cfg,
                      lambda path, _: getattr(net, path).data()._data)


# ---------------------------------------------------------------------------
# the layers, as functions of the parameter tree
# ---------------------------------------------------------------------------

def _norm(x, w, eps, gate_scale=None):
    """Zero-centred RMSNorm, ``x / rms(x) * (1 + w)``; with a
    ``gate_scale`` the gated form ``x / rms(x) * gate_scale sigmoid(w)``."""
    import jax
    import jax.numpy as jnp
    with device_scope("norm"):
        x = x.astype(jnp.float32)
        w = w.astype(jnp.float32)
        gain = 1.0 + w if gate_scale is None \
            else gate_scale * jax.nn.sigmoid(w)
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
            * gain


def _layer_norm(x, w, cfg):
    """A layer's norm or the final one: the gated form of :func:`_norm`
    (``layernorm_gating_weight``)."""
    return _norm(x, w, cfg["rms_norm_eps"], cfg["layernorm_gating_weight"])


_freqs = _blocks.yarn_freqs
#: ``use_mla_scaling_factor``: YaRN's factor on the softmax scale
softmax_scale = _blocks.yarn_softmax_scale


def _mla_inputs(lp, h, pos, cfg):
    """``q_nope`` [T, H, dn], ``q_rope`` [T, H, dr], the normed latent
    ``c`` [T, rank] and ``k_rope`` [T, dr] of the normed input ``h`` at
    positions ``pos``."""
    t = h.shape[0]
    n_h = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    freqs = _freqs(cfg)
    with device_scope("attn.proj"):
        q = _mm(_norm(_mm(h, lp["q_a_w"]), lp["q_a_norm_g"], eps),
                lp["q_b_w"]).reshape(t, n_h, dn + dr)
        kva = _mm(h, lp["kva_w"])
        return (q[..., :dn],
                _blocks.rope(q[..., dn:], pos, freqs, interleaved=True),
                _norm(kva[:, :rank], lp["kv_norm_g"], eps),
                _blocks.rope(kva[:, rank:], pos, freqs, interleaved=True))


def _absorbed_query(lp, q_nope, q_rope, width, cfg):
    """``[q_nope W_kvb^K | q_rope | zeros]`` [T, H, width] float32: a
    query that scores against the latent rows themselves."""
    import jax.numpy as jnp
    n_h = cfg["num_attention_heads"]
    dn, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    rank = cfg["kv_lora_rank"]
    kvb = lp["kvb_w"].reshape(rank, n_h, dn + dv)
    q_lat = jnp.einsum("shd,chd->shc", q_nope.astype(kvb.dtype),
                       kvb[..., :dn], preferred_element_type=jnp.float32)
    q = jnp.concatenate([q_lat, q_rope], -1)
    return jnp.pad(q, ((0, 0), (0, 0), (0, width - q.shape[2])))


def _mla_out(lp, h, o_lat, cfg):
    """The latent output ``o_lat`` [T, H, rank] up-projected to values,
    gated a value by ``sigmoid(h W_g)``, through ``W_o``."""
    import jax
    import jax.numpy as jnp
    t = h.shape[0]
    n_h = cfg["num_attention_heads"]
    dn, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    rank = cfg["kv_lora_rank"]
    with device_scope("attn.out"):
        kvb = lp["kvb_w"].reshape(rank, n_h, dn + dv)
        o = jnp.einsum("shc,chd->shd", o_lat.astype(kvb.dtype),
                       kvb[..., dn:], preferred_element_type=jnp.float32)
        gate = jax.nn.sigmoid(_mm(h, lp["g_w"]))
        return _mm(o.reshape(t, n_h * dv) * gate, lp["o_w"])


def _attend_pages(q, pool, block_table_row, first_pos, rank, scale):
    """A chunk's latent attention: queries ``q`` [T, H, W] (absorbed, at
    positions ``first_pos ..``) over the slot's latent pages, which hold
    every row up to the chunk's own.  ``QUERY_BLOCK`` queries at a time
    walk the blocks of ``KEY_BLOCK`` rows up to their last position with
    a running softmax; a row's first ``rank`` lanes are its value.
    Returns ``o_lat`` float32 [T, H, rank]."""
    import jax.numpy as jnp
    from jax import lax
    t, h, w = q.shape
    page = pool.shape[1]
    pages = max(1, min(KEY_BLOCK // page, block_table_row.shape[0]))
    keys = pages * page
    table = jnp.pad(block_table_row,
                    (0, -block_table_row.shape[0] % pages))
    rows = _rows_per_block(t, QUERY_BLOCK)

    def one_block(args):
        qb, first = args                                    # [rows, H, W]
        q_pos = first + jnp.arange(rows)

        def step(j, carry):
            m, total, acc = carry
            at = lax.dynamic_slice_in_dim(table, j * pages, pages)
            kb = pool[at].reshape(keys, w)
            mask = (j * keys + jnp.arange(keys))[None, :] <= q_pos[:, None]
            s = jnp.where(mask, jnp.einsum(
                "qhw,kw->hqk", qb, kb,
                preferred_element_type=jnp.float32) * scale, _NEG)
            m_new = jnp.maximum(m, s.max(-1))
            e = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
            keep = jnp.exp(m - m_new)
            return (m_new, total * keep + e.sum(-1),
                    acc * keep[..., None] + jnp.einsum(
                        "hqk,kc->hqc", e.astype(kb.dtype), kb[:, :rank],
                        preferred_element_type=jnp.float32))

        _, total, acc = lax.fori_loop(
            0, (first + rows - 1) // keys + 1, step,
            (jnp.full((h, rows), _NEG, jnp.float32),
             jnp.zeros((h, rows), jnp.float32),
             jnp.zeros((h, rows, rank), jnp.float32)))
        return acc / total[..., None]

    o = lax.map(one_block, (
        q.reshape(t // rows, rows, h, w).astype(pool.dtype),
        first_pos + jnp.arange(t // rows) * rows))
    return o.transpose(0, 2, 1, 3).reshape(t, h, rank)


def _conv_silu(window, conv_w):
    """The depthwise causal convolution and SiLU of ``T`` positions:
    ``window`` [..., K - 1 + T, C] of raw inputs, taps ``conv_w`` [K, C]
    (the last tap on the newest input)."""
    import jax
    import jax.numpy as jnp
    k = conv_w.shape[0]
    t = window.shape[-2] - k + 1
    w = conv_w.astype(jnp.float32)
    return jax.nn.silu(sum(window[..., i:i + t, :] * w[i] for i in range(k)))


def _gdn_inputs(lp, h, qkv, cfg):
    """From the convolved ``qkv`` [T, C_qkv] and the normed input ``h``:
    ``q, k`` [T, Hk, Dk] (L2-normed, ``q`` scaled), ``v`` [T, Hv, Dv],
    the log decay ``g`` and ``beta`` [T, Hv]."""
    import jax
    import jax.numpy as jnp
    t = h.shape[0]
    hk, hv, dk, dv = _gdn_dims(cfg)
    q = qkv[:, :hk * dk].reshape(t, hk, dk)
    k = qkv[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)
    v = qkv[:, 2 * hk * dk:].reshape(t, hv, dv)
    eps = _blocks.EPS
    q = q * jax.lax.rsqrt((q * q).sum(-1, keepdims=True) + eps) \
        * _np.float32(dk ** -0.5)
    k = k * jax.lax.rsqrt((k * k).sum(-1, keepdims=True) + eps)
    beta = jax.nn.sigmoid(_mm(h, lp["b_w"]))
    g = -jnp.exp(lp["a_log"].astype(jnp.float32)) * jax.nn.softplus(
        _mm(h, lp["a_w"]) + lp["dt_bias"].astype(jnp.float32))
    return q, k, v, g, beta


def _gdn_out(lp, h, o, cfg):
    """``W_o [N(o_h; w_o) 2 sigmoid(z_h)]``, ``z = h W_z``."""
    import jax
    t = h.shape[0]
    hv = cfg["linear_num_value_heads"]
    with device_scope("gdn.out"):
        z = _mm(h, lp["z_w"]).reshape(t, hv, -1)
        gated = _norm(o, lp["o_norm_g"], cfg["linear_attn_o_norm_eps"]) \
            * (cfg["linear_sigmoid_gate_scale"] * jax.nn.sigmoid(z))
        return _mm(gated.reshape(t, -1), lp["o_w"])


def _gdn_sequence(lp, h, valid, state, tail, cfg):
    """A (padded) run of positions FROM ``state`` [Hv, Dk, Dv] and the
    convolution's ``tail`` [K - 1, C_qkv] of raw inputs before it;
    ``valid`` marks real positions (a pad row has ``g = 0, beta = 0`` and
    leaves the state alone).  Returns ``(y [T, C], final state, the raw
    inputs with the tail before them [K - 1 + T, C_qkv])``."""
    import jax.numpy as jnp
    from ...ops.pallas.delta_rule import gdn_chunked
    with device_scope("gdn.proj"):
        raw = jnp.concatenate([tail.astype(jnp.float32),
                               _mm(h, lp["qkv_w"])])
        q, k, v, g, beta = _gdn_inputs(lp, h, _conv_silu(raw, lp["conv_w"]),
                                       cfg)
        g = jnp.where(valid[:, None], g, 0.0)
        beta = jnp.where(valid[:, None], beta, 0.0)
    with device_scope("gdn_scan"):
        o, state = gdn_chunked(q, k, v, g, beta, state, chunk=SCAN_CHUNK)
    return _gdn_out(lp, h, o, cfg), state, raw


def gdn_step(state, q, k, v, g, beta, active, heads_per_cell=STEP_HEADS):
    """One decode step of the grouped, scalar-gated delta rule on every
    slot's state, in place: ``delta_rule.kda_step`` on the key heads
    repeated for the value heads that read them and the decay broadcast
    along the key channels.  ``q, k`` [S, Hk, Dk]; ``v`` [S, Hv, Dv];
    ``g``, ``beta`` [S, Hv]; ``state`` [R, Hv, Dk, Dv], ``R >= S``.
    Returns ``(o float32 [S, Hv, Dv], new_state)``."""
    import jax.numpy as jnp
    from ...ops.pallas.delta_rule import kda_step
    group = v.shape[1] // q.shape[1]
    q, k = (jnp.repeat(a, group, axis=1) for a in (q, k))
    g = jnp.broadcast_to(g[..., None], k.shape)
    return kda_step(state, q, k, v, g, beta, active,
                    heads_per_cell=heads_per_cell)


def _ffn(lp, x, cfg, routing, stats, valid=None):
    """The layer's second half: ``x + N_4(FFN(N_3(x)))``."""
    limit = cfg.get("swiglu_limit")
    h = _layer_norm(x, lp["ln2_g"], cfg)
    if "mlp" in lp:
        with device_scope("mlp"):
            return x + _layer_norm(_swiglu(h, lp["mlp"]["gu_w"],
                                           lp["mlp"]["down_w"], limit),
                                   lp["post"]["ffn_g"], cfg)
    y, experts, st = _moe(lp["moe"], h, cfg, valid)
    routing.append(experts)
    stats.append(st)
    with device_scope("moe"):
        return x + _layer_norm(y, lp["post"]["ffn_g"], cfg)


def _mix_add(lp, x, y, cfg, scope):
    """``x + N_2(y)`` under the mixer's output scope."""
    with device_scope(scope):
        return x + _layer_norm(y, lp["post"]["mix_g"], cfg)


def _aux(cfg, n_rows, routing, stats, in_context):
    """The programs' report of a dispatch: the counts
    (``DECODE_STATS``) and the chosen experts int32 [expert layers,
    rows, k].  ``in_context`` int [rows]: the keys each row's query sees
    (0 for a row that is not real)."""
    import jax.numpy as jnp
    k = cfg["num_experts_per_tok"]
    n_mla = [mix for mix, _ in layer_kinds(cfg)].count("mla")
    with device_scope("moe"), device_scope("moe.route"):
        return {"stats": jnp.concatenate([
            _blocks.moe_stats_vector(stats, n_rows * k,
                                     cfg["experts_held"][1]),
            (n_mla * in_context.sum()).astype(jnp.float32)[None]]),
            "experts": jnp.stack(routing) if routing
            else jnp.zeros((0, n_rows, k), jnp.int32)}


def forward(p, tokens, cfg):
    """Whole-sequence forward WITHOUT a cache, for small sizes: tokens
    int32 [T] -> ``(logits float32 [T, V], chosen experts per expert
    layer)``.  Dense causal latent attention, the delta rule's chunked
    form from a zero state."""
    import jax
    import jax.numpy as jnp
    t = tokens.shape[0]
    hk, hv, dk, dv = _gdn_dims(cfg)
    width = 2 * hk * dk + hv * dv
    pos = jnp.arange(t)
    valid = jnp.ones(t, bool)
    causal = pos[:, None] >= pos[None, :]
    x = p["wte"][tokens].astype(jnp.float32)
    routing, stats = [], []
    for lp, (mix, _) in zip(p["layers"], layer_kinds(cfg)):
        h = _layer_norm(x, lp["ln1_g"], cfg)
        if mix == "mla":
            q_nope, q_rope, c, k_rope = _mla_inputs(lp["attn"], h, pos, cfg)
            q = _absorbed_query(lp["attn"], q_nope, q_rope,
                                latent_width(cfg), cfg)
            rows = _latent_rows(c, k_rope, latent_width(cfg), jnp.float32)
            s = jnp.einsum("qhw,kw->hqk", q, rows) * softmax_scale(cfg)
            a = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
            o_lat = jnp.einsum("hqk,kc->qhc", a, c)
            x = _mix_add(lp, x, _mla_out(lp["attn"], h, o_lat, cfg), cfg,
                         "attn.out")
        else:
            y, _, _ = _gdn_sequence(
                lp["gdn"], h, valid, jnp.zeros((hv, dk, dv), jnp.float32),
                jnp.zeros((cfg["linear_conv_kernel_dim"] - 1, width),
                          jnp.float32), cfg)
            x = _mix_add(lp, x, y, cfg, "gdn.out")
        x = _ffn(lp, x, cfg, routing, stats)
    return _head(_layer_norm(x, p["lnf_g"], cfg), p["head"]), routing


def paged_decode_step(p, tokens, positions, active, caches, block_tables,
                      n_heads, sampling=None, cfg=None):
    """ONE decode step for every serving slot (the contract of
    ``gpt.paged_decode_step``): ``caches`` holds ``(pool,)`` for a latent
    layer and ``(state, conv)`` for a delta-rule layer, donated by the
    caller's jit.  An inactive slot writes to the scratch page, its
    state is left as it is, and it attends over nothing.

    Returns ``(logits [S, V], next_tokens [S], new_keys, new_caches,
    aux)`` with sampling and ``(logits, next_tokens, new_caches, aux)``
    without; ``aux`` holds the routing counts (``DECODE_STATS``) and the
    chosen experts int32 [expert layers, S, k].
    """
    import jax.numpy as jnp
    from jax import lax
    from .gpt import sample_tokens
    from ...ops.pallas.latent_attention import mla_paged_decode

    s_n = tokens.shape[0]
    rank = cfg["kv_lora_rank"]
    with device_scope("embed"):
        x = p["wte"][tokens].astype(jnp.float32)
    ctx = jnp.where(active, positions + 1, 0).astype(jnp.int32)
    new_caches, routing, stats = [], [], []
    for lp, (mix, _), entry in zip(p["layers"], layer_kinds(cfg), caches):
        h = _layer_norm(x, lp["ln1_g"], cfg)
        if mix == "mla":
            pool, = entry
            page_size = pool.shape[1]
            q_nope, q_rope, c, k_rope = _mla_inputs(lp["attn"], h,
                                                    positions, cfg)
            with device_scope("kv_write"):
                phys = jnp.where(active, jnp.take_along_axis(
                    block_tables, (positions // page_size)[:, None],
                    axis=1)[:, 0], 0)
                pool = pool.at[phys, positions % page_size].set(
                    _latent_rows(c, k_rope, pool.shape[2], pool.dtype))
            with device_scope("attn.proj"):
                q = _absorbed_query(lp["attn"], q_nope, q_rope,
                                    pool.shape[2], cfg)
            with device_scope("attn.mla"):
                o_lat = mla_paged_decode(q, pool, block_tables, ctx, rank,
                                         softmax_scale(cfg))
            x = _mix_add(lp, x, _mla_out(lp["attn"], h, o_lat, cfg), cfg,
                         "attn.out")
            new_caches.append((pool,))
        else:
            state, conv = entry
            gp = lp["gdn"]
            with device_scope("gdn.proj"):
                window = jnp.concatenate(
                    [conv[:s_n], _mm(h, gp["qkv_w"])[:, None]], 1)
                q, k, v, g, beta = _gdn_inputs(
                    gp, h, _conv_silu(window, gp["conv_w"])[:, 0], cfg)
            with device_scope("gdn_step"):
                o, state = gdn_step(state, q, k, v, g, beta, active)
            with device_scope("state_write"):
                conv = conv.at[:s_n].set(
                    jnp.where(active[:, None, None], window[:, 1:],
                              conv[:s_n]).astype(conv.dtype))
            x = _mix_add(lp, x, _gdn_out(gp, h, o, cfg), cfg, "gdn.out")
            new_caches.append((state, conv))
        x = _ffn(lp, x, cfg, routing, stats, active)
    with device_scope("lm_head"):
        logits = _head(_layer_norm(x, p["lnf_g"], cfg), p["head"])
    aux = _aux(cfg, s_n, routing, stats, ctx)
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
                  slot=None, cfg=None):
    """ONE CHUNK of a prompt into slot ``slot``, whose block-table row
    is ``block_table_row`` (the contract of ``gpt.paged_prefill``, read
    for a chunk, plus the slot): ``tokens`` [T] holds the prompt's
    positions ``prefix_len ..`` (padded), ``prompt_len`` is the prompt's
    length SO FAR.  Everything before ``prefix_len`` is in the slot's
    latent pages and, on a delta-rule layer, in the state and tail the
    chunk before left in the slot's rows; at ``prefix_len`` 0 the scan
    starts from zeros whatever the rows hold.  The chunk leaves its own.
    ``cow_*`` are unused: no prefix is shared.

    Returns ``gpt._first_token``'s tuple for the chunk's LAST real row
    (the first generated token when the chunk is the prompt's last) with
    ``aux`` appended (rows = the chunk's padded rows).
    """
    import jax.numpy as jnp
    from jax import lax
    from .gpt import _first_token

    del cow_src, cow_dst
    t_pad = tokens.shape[0]
    rank = cfg["kv_lora_rank"]
    positions = prefix_len + jnp.arange(t_pad, dtype=jnp.int32)
    n_rows = prompt_len - prefix_len
    valid = positions < prompt_len
    handed = prefix_len > 0
    with device_scope("embed"):
        x = p["wte"][tokens].astype(jnp.float32)
    new_caches, routing, stats = [], [], []
    for lp, (mix, _), entry in zip(p["layers"], layer_kinds(cfg), caches):
        h = _layer_norm(x, lp["ln1_g"], cfg)
        if mix == "mla":
            pool, = entry
            q_nope, q_rope, c, k_rope = _mla_inputs(lp["attn"], h,
                                                    positions, cfg)
            with device_scope("kv_write"):
                pool = _blocks.page_scatter(
                    pool, block_table_row,
                    _latent_rows(c, k_rope, pool.shape[2], pool.dtype),
                    prefix_len, n_rows)
            with device_scope("attn.proj"):
                q = _absorbed_query(lp["attn"], q_nope, q_rope,
                                    pool.shape[2], cfg)
            with device_scope("attn.mla"):
                o_lat = _attend_pages(q, pool, block_table_row, prefix_len,
                                      rank, softmax_scale(cfg))
            x = _mix_add(lp, x, _mla_out(lp["attn"], h, o_lat, cfg), cfg,
                         "attn.out")
            new_caches.append((pool,))
        else:
            state, conv = entry
            hist = conv.shape[1]
            with device_scope("state_write"):
                s0, tail0 = (jnp.where(handed, lax.dynamic_index_in_dim(
                    a, slot, 0, keepdims=False), 0.0) for a in (state, conv))
            y, final, raw = _gdn_sequence(lp["gdn"], h, valid, s0, tail0,
                                          cfg)
            with device_scope("state_write"):
                # the last `hist` real raw inputs: the chunk's, or the
                # tail's where the chunk has fewer
                tail = lax.dynamic_slice_in_dim(raw, n_rows, hist, 0)
                new_caches.append((
                    lax.dynamic_update_index_in_dim(state, final, slot, 0),
                    lax.dynamic_update_index_in_dim(
                        conv, tail.astype(conv.dtype), slot, 0)))
            x = _mix_add(lp, x, y, cfg, "gdn.out")
        x = _ffn(lp, x, cfg, routing, stats, valid)
    with device_scope("lm_head"):
        last = lax.dynamic_index_in_dim(
            _layer_norm(x, p["lnf_g"], cfg), n_rows - 1, 0, keepdims=False)
        logits = _head(last, p["head"])
    aux = _aux(cfg, t_pad, routing, stats,
               jnp.where(valid, positions + 1, 0))
    return _first_token(logits, sampling, new_caches) + (aux,)
