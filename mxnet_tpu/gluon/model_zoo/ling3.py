"""The language model of Ling-3.0-flash-VL for serving: a hybrid decoder
of KDA (per-channel gated delta rule) and MLA (latent attention) layers
over routed experts, held as ONE CHIP'S SHARE of a stated deployment.

Layer ``l`` (published index), pre-norm residual, RMSNorm eps 1e-6::

    h = x + Mix_l(RMSNorm(x));   y = h + FFN_l(RMSNorm(h))

``Mix_l`` is MLA where ``(l + 1) % layer_group_size == 0``, else KDA;
``FFN_l`` is a dense SwiGLU for ``l < first_k_dense_replace``, else the
routed experts (``parallel.moe``: sigmoid scores, group-limited top-k,
no dropped token) plus one shared expert.  Final RMSNorm, untied head.
``perfbench/reference/ling3.py`` is the plain float32 statement of the
same equations, with every reading of an ambiguous key listed; this
file is the program: bfloat16 weights as published, float32 residual
stream, float32 accumulation.

**The share** (``cfg["experts_held"] = [first, count]``,
``cfg["layers"]``, ``cfg["vocab_size"]``): the router keeps its
published width and scores all ``num_experts``; this chip computes the
terms of the experts it holds, with the weights as normalised over all
chosen, and adds the shared expert in full.  The layers listed are the
published indices kept (the others lie on further pipeline stages); the
vocabulary is the slice this chip's embedding and head hold.  There is
no exchange on one chip and nothing stands in for the absent chips.

**Serving** (:func:`paged_decode_step`, :func:`paged_prefill`, the same
contract as ``gpt.paged_decode_step`` / ``gpt.paged_prefill``): two
kinds of cache side by side, which the net declares to the engine
through :meth:`Ling3LM.serving_programs`:

- an MLA layer: a paged LATENT pool ``[num_pages, page_size, W]``, one
  row ``[c | k_rope | zeros]`` a token (``W`` = 576 padded to 640, a
  multiple of 128: the chip keeps it row-major), read by the absorbed
  decode kernel ``mla_paged_decode`` as key and value at once;
- a KDA layer: per-SLOT recurrent state ``[slots + 1, H, Dk, Dv]``
  float32 (updated in place by ``kda_step``) and the short
  convolution's last ``width - 1`` inputs ``[slots + 1, width - 1,
  3 H D]``; row ``slots`` is scratch.  A prefill computes the slot's
  state from zero with the chunked form and overwrites the slot's rows:
  that is the reset at admission.

A prompt is one prefill program (no prefix reuse: a recurrent layer's
prefix is a state, not pages).
"""
from __future__ import annotations

import functools

import numpy as _np

from ...telemetry import device_scope
from ..block import Block
from . import decoder_blocks as _blocks
from .decoder_blocks import (EPS, LATENT_ALIGN, latent_width,
                             mm as _mm, rms as _rms, head as _head,
                             swiglu as _swiglu, moe as _moe,
                             latent_rows as _latent_rows,
                             moe_stats_vector as _stats_vector)

__all__ = ["Ling3LM", "ling3_flash_vl", "ling3_tiny", "decode_params",
           "param_tree",
           "forward", "paged_decode_step", "paged_prefill",
           "layer_kinds", "LATENT_ALIGN"]

#: the published widths (config.json of the source, language model)
PUBLISHED = {
    "num_hidden_layers": 42, "hidden_size": 2560, "intermediate_size": 6144,
    "first_k_dense_replace": 2, "max_position_embeddings": 131072,
    "moe_intermediate_size": 768, "num_experts_per_tok": 8,
    "num_attention_heads": 32, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "num_experts": 512, "rope_theta": 6000000, "rms_norm_eps": 1e-06,
    "head_dim": 128, "vocab_size": 157184, "routed_scaling_factor": 2.5,
    "n_group": 8, "topk_group": 4, "moe_shared_expert_intermediate_size": 768,
    "layer_group_size": 6, "short_conv_kernel_size": 4,
    "kda_lower_bound": -5,
}


def layer_kinds(cfg):
    """``(mix, ffn)`` of every kept layer, by its PUBLISHED index."""
    return [("mla" if (l + 1) % cfg["layer_group_size"] == 0 else "kda",
             "dense" if l < cfg["first_k_dense_replace"] else "moe")
            for l in cfg["layers"]]


def _param_shapes(cfg):
    """``{path: (shape, init)}`` of every parameter, ``init`` one of
    "normal" (0, 0.02), "ones", "zeros"; matrices are ``[in, out]``."""
    c, h = cfg["hidden_size"], cfg["num_attention_heads"]
    d = cfg["head_dim"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rank, e = cfg["kv_lora_rank"], cfg["num_experts"]
    f, fs = (cfg["moe_intermediate_size"],
             cfg["moe_shared_expert_intermediate_size"])
    held = cfg["experts_held"][1]
    out = {"wte": ((cfg["vocab_size"], c), "normal"),
           "head": ((cfg["vocab_size"], c), "normal"),
           "lnf_gamma": ((c,), "ones")}
    for i, (mix, ffn) in enumerate(layer_kinds(cfg)):
        pre = "l%d_" % i
        out[pre + "ln1_gamma"] = ((c,), "ones")
        out[pre + "ln2_gamma"] = ((c,), "ones")
        if mix == "mla":
            out.update({
                pre + "mla_q_w": ((c, h * (dn + dr)), "normal"),
                pre + "mla_q_norm_gamma": ((dn + dr,), "ones"),
                pre + "mla_kva_w": ((c, rank + dr), "normal"),
                pre + "mla_kv_norm_gamma": ((rank,), "ones"),
                pre + "mla_kvb_w": ((rank, h * (dn + dv)), "normal"),
                pre + "mla_g_w": ((c, h), "normal"),
                pre + "mla_o_w": ((h * dv, c), "normal")})
        else:
            out.update({
                pre + "kda_qkv_w": ((c, 3 * h * d), "normal"),
                pre + "kda_conv_w": ((cfg["short_conv_kernel_size"],
                                      3 * h * d), "normal"),
                pre + "kda_f_w": ((c, h * d), "normal"),
                pre + "kda_f_b": ((h * d,), "normal"),
                pre + "kda_a_log": ((h,), "normal"),
                pre + "kda_b_w": ((c, h), "normal"),
                pre + "kda_g_w": ((c, h), "normal"),
                pre + "kda_o_norm_gamma": ((d,), "ones"),
                pre + "kda_o_w": ((h * d, c), "normal")})
        if ffn == "dense":
            out.update({
                pre + "mlp_gu_w": ((c, 2 * cfg["intermediate_size"]),
                                   "normal"),
                pre + "mlp_down_w": ((cfg["intermediate_size"], c),
                                     "normal")})
        else:
            out.update({
                pre + "moe_router_w": ((c, e), "normal"),
                pre + "moe_router_bias": ((e,), "zeros"),
                pre + "moe_gu_w": ((held, c, 2 * f), "normal"),
                pre + "moe_down_w": ((held, f, c), "normal"),
                pre + "moe_sh_gu_w": ((c, 2 * fs), "normal"),
                pre + "moe_sh_down_w": ((fs, c), "normal")})
    return out


class Ling3LM(Block):
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
        """Seeded values in the stored type, made on the device one
        parameter at a time (a share's expert stack is gigabytes):
        normal(0, 0.02), norm gains 1, the expert bias 0.  ``seed``: a
        whole number or a PRNG key."""
        return _blocks.init_seeded(self, self._inits, seed)

    def forward(self, tokens):
        import jax.numpy as jnp
        from ...ndarray import NDArray
        toks = jnp.asarray(getattr(tokens, "_data", tokens), jnp.int32)
        return NDArray(forward(decode_params(self), toks, self.cfg)[0])

    def serving_programs(self):
        """What ``ServingEngine`` needs of a model, in one object."""
        from ...serving.programs import ServingPrograms, LatentPages, \
            SlotState
        cfg = self.cfg
        h, d = cfg["num_attention_heads"], cfg["head_dim"]
        hist = cfg["short_conv_kernel_size"] - 1
        kinds = [LatentPages((latent_width(cfg),)) if mix == "mla"
                 else SlotState((("state", (h, d, d), "float32"),
                                 ("conv", (hist, 3 * h * d), None)))
                 for mix, _ in layer_kinds(cfg)]
        return ServingPrograms(
            n_heads=h, max_len=self._max_len,
            decode_params=lambda net, kv_heads=None: decode_params(net),
            decode_step=functools.partial(paged_decode_step, cfg=cfg),
            prefill=functools.partial(paged_prefill, cfg=cfg),
            cache_kinds=kinds, decode_stats=DECODE_STATS,
            config_key=repr(sorted(cfg.items())))


def ling3_flash_vl(dtype="bfloat16", **overrides):
    """The published widths as one chip's share of 24 v5e chips (6
    pipeline stages of a four-chip host, 4 chips sharing each layer):
    published layers 1-7 (one dense-MLP layer, then a whole 5 KDA : 1
    MLA period of expert layers), experts 0-127 of 512, a quarter of
    the vocabulary."""
    cfg = dict(PUBLISHED, layers=[1, 2, 3, 4, 5, 6, 7],
               experts_held=[0, 128], vocab_size=39296)
    cfg.update(overrides)
    return Ling3LM(cfg, dtype=dtype)


def ling3_tiny(dtype="float32", **overrides):
    """Test-scale preset: hidden 64, 4 heads of 16, 16 experts in 4
    groups (top 2 groups, top 4 experts), 4 held, one dense layer and
    one whole period of six."""
    cfg = dict(PUBLISHED, hidden_size=64, intermediate_size=96,
               moe_intermediate_size=32,
               moe_shared_expert_intermediate_size=32,
               num_attention_heads=4, head_dim=16, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               num_experts=16, n_group=4, topk_group=2,
               num_experts_per_tok=4, vocab_size=256,
               max_position_embeddings=4096,
               layers=[1, 2, 3, 4, 5, 6, 7], experts_held=[4, 4])
    cfg.update(overrides)
    return Ling3LM(cfg, dtype=dtype)


def param_tree(cfg, leaf):
    """The parameter tree the programs take, by layer, with
    ``leaf(path, shape)`` at every parameter (the net's live arrays, or
    shapes for a compile without weights)."""
    return _blocks.param_tree(_param_shapes(cfg), len(cfg["layers"]),
                              ("mla", "kda", "mlp", "moe"), leaf)


def decode_params(net):
    """The net's live arrays (no copy) as the programs' tree."""
    return param_tree(net.cfg,
                      lambda path, _: getattr(net, path).data()._data)


# ---------------------------------------------------------------------------
# the layers, as functions of the parameter tree
# ---------------------------------------------------------------------------

def _rope(x, pos, theta):
    """Rotate-half RoPE on the last axis; ``pos`` indexes the first."""
    return _blocks.rope(x, pos,
                        _blocks.rope_inv_freq(theta, x.shape[-1] // 2))


def _mla_qkv(lp, x, pos, cfg):
    """What both MLA forms share: ``q_nope, q_rope`` [T, H, .], the
    normalised latent ``c`` [T, rank] and the shared ``k_rope`` [T, dr]
    at positions ``pos``."""
    t = x.shape[0]
    h = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank = cfg["kv_lora_rank"]
    with device_scope("attn.proj"):
        q = _rms(_mm(x, lp["q_w"]).reshape(t, h, dn + dr), lp["q_norm_g"])
        kva = _mm(x, lp["kva_w"])
        return (q[..., :dn], _rope(q[..., dn:], pos, cfg["rope_theta"]),
                _rms(kva[:, :rank], lp["kv_norm_g"]),
                _rope(kva[:, rank:], pos, cfg["rope_theta"]))


def _mla_out(lp, x, o, cfg):
    import jax
    t = x.shape[0]
    with device_scope("attn.out"):
        gate = jax.nn.sigmoid(_mm(x, lp["g_w"]))            # [T, H]
        return _mm((o * gate[..., None]).reshape(t, -1), lp["o_w"])


def _mla_plain(lp, x, valid, cfg):
    """Prefill form over a (padded) sequence from position 0: keys and
    values expanded from the latent, causal softmax; ``valid`` masks
    pad keys.  Returns ``(y [T, C], c, k_rope)``."""
    import jax
    import jax.numpy as jnp
    t = x.shape[0]
    h = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    q_nope, q_rope, c, k_rope = _mla_qkv(lp, x, jnp.arange(t), cfg)
    with device_scope("attn.proj"):
        kv = _mm(c, lp["kvb_w"]).reshape(t, h, dn + dv)
    with device_scope("attn"):
        s = (jnp.einsum("qhd,khd->hqk", q_nope, kv[..., :dn])
             + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)) \
            / _np.float32(_np.sqrt(dn + dr))
        mask = jnp.tril(jnp.ones((t, t), bool)) & valid[None, :]
        p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, kv[..., dn:])
    return _mla_out(lp, x, o, cfg), c, k_rope


def _mla_absorbed(lp, x, pos, pool, block_tables, ctx, phys, offs, cfg):
    """Decode form: the up-projection absorbed into the query, scores
    against the cached rows themselves (``mla_paged_decode``).  Writes
    this token's row first.  Returns ``(y [S, C], new pool)``."""
    import jax.numpy as jnp
    from ...ops.pallas.latent_attention import mla_paged_decode
    s_n = x.shape[0]
    h = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    q_nope, q_rope, c, k_rope = _mla_qkv(lp, x, pos, cfg)
    with device_scope("kv_write"):
        pool = pool.at[phys, offs].set(
            _latent_rows(c, k_rope, pool.shape[2], pool.dtype))
    with device_scope("attn.proj"):
        kvb = lp["kvb_w"].reshape(rank, h, dn + dv)
        q_lat = jnp.einsum("shd,chd->shc", q_nope.astype(kvb.dtype),
                           kvb[..., :dn],
                           preferred_element_type=jnp.float32)
        q = jnp.concatenate([q_lat, q_rope], -1)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pool.shape[2] - q.shape[2])))
    with device_scope("attn"):
        o_lat = mla_paged_decode(q, pool, block_tables, ctx, rank,
                                 1.0 / _np.sqrt(dn + dr))
    with device_scope("attn.out"):
        o = jnp.einsum("shc,chd->shd", o_lat.astype(kvb.dtype),
                       kvb[..., dn:], preferred_element_type=jnp.float32)
    return _mla_out(lp, x, o, cfg), pool


def _kda_inputs(lp, x, qkv, cfg):
    """From the convolved ``qkv`` [T, 3HD] (after SiLU) and the layer
    input: normalised ``q, k``, ``v``, log decay ``g`` [T, H, D] and
    ``beta`` [T, H] (under the caller's ``attn.proj`` scope)."""
    import jax
    import jax.numpy as jnp
    t = x.shape[0]
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    q, k, v = (a.reshape(t, h, d) for a in jnp.split(qkv, 3, -1))
    q = q * jax.lax.rsqrt((q * q).sum(-1, keepdims=True) + EPS) \
        / _np.float32(_np.sqrt(d))
    k = k * jax.lax.rsqrt((k * k).sum(-1, keepdims=True) + EPS)
    f = (_mm(x, lp["f_w"]) + lp["f_b"].astype(jnp.float32)) \
        .reshape(t, h, d)
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(lp["a_log"].astype(jnp.float32))[None, :, None] * f)
    return q, k, v, g, jax.nn.sigmoid(_mm(x, lp["b_w"]))


def _kda_out(lp, x, o, cfg):
    import jax
    t = x.shape[0]
    with device_scope("attn.out"):
        o = _rms(o, lp["o_norm_g"])
        gate = jax.nn.sigmoid(_mm(x, lp["g_w"]))
        return _mm((o * gate[..., None]).reshape(t, -1), lp["o_w"])


def _kda_sequence(lp, x, valid, cfg):
    """Prefill form from a zero state (``delta_rule.kda_chunked``);
    ``valid`` marks real positions, pads leave the state alone.
    Returns ``(y [T, C], final state [H, D, D], the pre-convolution
    inputs [T, 3HD])``."""
    import jax
    import jax.numpy as jnp
    from ...ops.pallas.delta_rule import kda_chunked
    t = x.shape[0]
    width = cfg["short_conv_kernel_size"]
    with device_scope("attn.proj"):
        raw = _mm(x, lp["qkv_w"])                           # [T, 3HD]
        padded = jnp.concatenate(
            [jnp.zeros((width - 1, raw.shape[1]), jnp.float32), raw])
        conv_w = lp["conv_w"].astype(jnp.float32)
        qkv = jax.nn.silu(sum(padded[i:i + t] * conv_w[i]
                              for i in range(width)))
        q, k, v, g, beta = _kda_inputs(lp, x, qkv, cfg)
        g = jnp.where(valid[:, None, None], g, 0.0)
        beta = jnp.where(valid[:, None], beta, 0.0)
    with device_scope("kda_scan"):
        o, state = kda_chunked(q, k, v, g, beta)
    return _kda_out(lp, x, o, cfg), state, raw


def _kda_decode(lp, x, active, state, conv, cfg):
    """One token a slot on the slot states (``kda_step``, in place).
    Returns ``(y [S, C], new state, new conv)``."""
    import jax
    import jax.numpy as jnp
    from ...ops.pallas.delta_rule import kda_step
    s_n = x.shape[0]
    with device_scope("attn.proj"):
        raw = _mm(x, lp["qkv_w"])                           # [S, 3HD]
        window = jnp.concatenate(
            [conv[:s_n].astype(jnp.float32), raw[:, None]], 1)
        qkv = jax.nn.silu(                                  # [S, width, .]
            (window * lp["conv_w"].astype(jnp.float32)[None]).sum(1))
        q, k, v, g, beta = _kda_inputs(lp, x, qkv, cfg)
    with device_scope("kda_step"):
        o, state = kda_step(state, q, k, v, g, beta, active)
    with device_scope("state_write"):
        conv = conv.at[:s_n].set(window[:, 1:].astype(conv.dtype))
    return _kda_out(lp, x, o, cfg), state, conv


#: the decode program's last output: a float32 vector of these counts
DECODE_STATS = _blocks.MOE_STATS


def _ffn(lp, x, y, cfg, routing, stats):
    """The layer after its attention's output ``y``: both residual adds
    around the dense MLP or the routed experts."""
    with device_scope("attn.out"):
        x = x + y
    h = _rms(x, lp["ln2_g"])
    if "mlp" in lp:
        with device_scope("mlp"):
            return x + _swiglu(h, lp["mlp"]["gu_w"], lp["mlp"]["down_w"])
    y, experts, st = _moe(lp["moe"], h, cfg)
    routing.append(experts)
    stats.append(st)
    with device_scope("moe"):
        return x + y


def _sequence_pass(p, tokens, prompt_len, cfg):
    """A (padded) sequence from position 0 through every layer in its
    prefill form.  Returns the final hidden states [T, C] (normalised),
    per layer what its cache keeps (MLA: ``(c, k_rope)``; KDA:
    ``(state, raw conv inputs)``) and the chosen experts per expert
    layer."""
    import jax.numpy as jnp
    t = tokens.shape[0]
    valid = jnp.arange(t) < prompt_len
    with device_scope("embed"):
        x = p["wte"][tokens].astype(jnp.float32)
    kept, routing, stats = [], [], []
    for lp in p["layers"]:
        h = _rms(x, lp["ln1_g"])
        if "mla" in lp:
            y, c, k_rope = _mla_plain(lp["mla"], h, valid, cfg)
            kept.append((c, k_rope))
        else:
            y, state, raw = _kda_sequence(lp["kda"], h, valid, cfg)
            kept.append((state, raw))
        x = _ffn(lp, x, y, cfg, routing, stats)
    return _rms(x, p["lnf_g"]), kept, routing, stats


def forward(p, tokens, cfg):
    """Whole-sequence forward: tokens int32 [T] -> ``(logits float32
    [T, V], chosen experts per expert layer)``."""
    h, _, routing, _ = _sequence_pass(p, tokens, tokens.shape[0], cfg)
    return _head(h, p["head"]), routing


def paged_decode_step(p, tokens, positions, active, caches, block_tables,
                      n_heads, sampling=None, cfg=None):
    """ONE decode step for every serving slot (the contract of
    ``gpt.paged_decode_step``): ``caches`` holds, per layer, ``(pool,)``
    for an MLA layer and ``(state, conv)`` for a KDA layer, all donated
    by the caller's jit.

    Returns ``(logits [S, V], next_tokens [S], new_keys, new_caches,
    aux)`` with sampling (``aux``: ``{"stats": float32
    [len(DECODE_STATS)], "experts": int32 [expert layers, S, k]}``), and
    without it ``(logits, next_tokens, new_caches, aux)``.
    """
    import jax.numpy as jnp
    from jax import lax
    from .gpt import sample_tokens

    s_n = tokens.shape[0]
    with device_scope("embed"):
        x = p["wte"][tokens].astype(jnp.float32)
    ctx = jnp.where(active, positions + 1, 0).astype(jnp.int32)
    new_caches, routing, stats = [], [], []
    for lp, entry in zip(p["layers"], caches):
        h = _rms(x, lp["ln1_g"])
        if "mla" in lp:
            pool, = entry
            page_size = pool.shape[1]
            with device_scope("kv_write"):
                phys = jnp.where(active, jnp.take_along_axis(
                    block_tables, (positions // page_size)[:, None],
                    axis=1)[:, 0], 0)
                offs = positions % page_size
            y, pool = _mla_absorbed(lp["mla"], h, positions, pool,
                                    block_tables, ctx, phys, offs, cfg)
            new_caches.append((pool,))
        else:
            y, state, conv = _kda_decode(lp["kda"], h, active, *entry,
                                         cfg)
            new_caches.append((state, conv))
        x = _ffn(lp, x, y, cfg, routing, stats)
    with device_scope("lm_head"):
        logits = _head(_rms(x, p["lnf_g"]), p["head"])
    k = cfg["num_experts_per_tok"]
    with device_scope("moe"), device_scope("moe.route"):
        aux = {"stats": _stats_vector(stats, s_n * k,
                                      cfg["experts_held"][1]),
               "experts": jnp.stack(routing) if routing
               else jnp.zeros((0, s_n, k), jnp.int32)}
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
    """Admit one request into slot ``slot`` (the contract of
    ``gpt.paged_prefill`` plus the slot, which a per-slot state needs):
    one pass over the padded prompt that scatters every position's
    latent row into the slot's pages, OVERWRITES the slot's recurrent
    state and convolution history with what the prompt leaves (the
    reset at admission), and returns the last prompt position's logits
    and first token.  ``prefix_len`` is always 0 here and ``cow_*``
    unused: a model with recurrent layers reuses no prefix.

    Returns ``gpt._first_token``'s tuple with ``aux`` appended (as the
    decode step's, ``experts`` int32 [expert layers, T_pad, k]; the
    counts are over the padded prompt).
    """
    import jax.numpy as jnp
    from jax import lax
    from .gpt import _first_token

    del prefix_len, cow_src, cow_dst
    t_pad = tokens.shape[0]
    hist = cfg["short_conv_kernel_size"] - 1
    h, kept, routing, stats = _sequence_pass(p, tokens, prompt_len, cfg)
    positions = jnp.arange(t_pad)
    valid = positions < prompt_len
    new_caches = []
    for entry, held in zip(caches, kept):
        if len(entry) == 1:
            pool, = entry
            page_size = pool.shape[1]
            with device_scope("kv_write"):
                phys = jnp.where(
                    valid, block_table_row[positions // page_size], 0)
                new_caches.append((pool.at[phys, positions % page_size].set(
                    _latent_rows(*held, pool.shape[2], pool.dtype)),))
        else:
            state, conv = entry
            final, raw = held
            with device_scope("state_write"):
                # the last `hist` real inputs, zeros before position 0
                at = prompt_len - hist + jnp.arange(hist)
                tail = jnp.where((at >= 0)[:, None],
                                 raw[jnp.maximum(at, 0)], 0.0)
                new_caches.append((
                    lax.dynamic_update_index_in_dim(state, final, slot, 0),
                    lax.dynamic_update_index_in_dim(
                        conv, tail.astype(conv.dtype), slot, 0)))
    with device_scope("lm_head"):
        last = lax.dynamic_index_in_dim(h, prompt_len - 1, 0,
                                        keepdims=False)
        logits = _head(last, p["head"])
    k = cfg["num_experts_per_tok"]
    with device_scope("moe"), device_scope("moe.route"):
        aux = {"stats": _stats_vector(stats, t_pad * k,
                                      cfg["experts_held"][1]),
               "experts": jnp.stack(routing) if routing
               else jnp.zeros((0, t_pad, k), jnp.int32)}
    return _first_token(logits, sampling, new_caches) + (aux,)
