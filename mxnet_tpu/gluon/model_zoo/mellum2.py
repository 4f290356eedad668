"""Mellum 2's language model (``model_type`` ``mellum``) for serving:
grouped-query attention in two kinds of layer, three SLIDING layers that
see the last ``sliding_window`` keys to every FULL one that sees the
whole context, each over 64 routed experts (a softmax router, no shared
expert).

Layer ``l`` (published index) on the float32 residual stream ``x``, the
norm on each sub-layer's INPUT (RMSNorm eps 1e-6)::

    h = x + Attn_l(RMSNorm(x) g1);   y = h + MoE_l(RMSNorm(h) g2)

``Attn_l``: ``q = x Wq`` (32 heads of 128), ``k = x Wk``, ``v = x Wv`` (4
heads of 128); ``q`` and ``k`` RMSNormed per head, then rotated over all
128 values (pairs ``(i, i + 64)``) at the layer kind's frequencies: plain
powers of theta 5e5 on a sliding layer, YaRN's blend (factor 16 over an
original 8,192, ``beta_fast`` 32, ``beta_slow`` 1) on a full layer, whose
``attention_factor`` multiplies cos and sin alike (so ``q . k`` by its
square).  Query ``i`` sees key ``j`` iff ``0 <= i - j`` and, on a sliding
layer, ``i - j < sliding_window``; softmax in float32 at scale ``128 **
-0.5``.  ``MoE_l``: ``parallel.moe.softmax_topk_route`` (softmax over all
experts, the 8 largest, weights renormalised over the chosen) and the
held experts' SwiGLUs of width 896.  ``perfbench/reference/mellum2.py``
is the plain float32 statement of the same equations; this file is the
program: bfloat16 weights as published, float32 residual stream and
accumulation.

**The share** (``cfg["experts_held"]``, ``cfg["layers"]``) is
``exaone_moe.py``'s: the router scores all ``num_experts``, this chip
computes its held experts' terms, the layers listed are the pipeline
stage's.  The served configuration holds every expert and the whole
vocabulary.

**Serving** is ``exaone_moe.py``'s (``decoder_blocks.decode_attention``
and ``chunk_attention``): paged K and V on a full layer, a RING of
``sliding_window`` rows a slot on a sliding one, a CHUNKED prefill that
reads the pages and rings the chunks before left.  No prefix reuse, no
speculative decoding, no int8 rows.
"""
from __future__ import annotations

import functools

from ...telemetry import device_scope
from ..block import Block
from . import decoder_blocks as _blocks
from .decoder_blocks import (mm as _mm, rms as _rms, head as _head,
                             moe as _moe, attend as _attend)

__all__ = ["Mellum2LM", "mellum2", "mellum2_tiny", "decode_params",
           "param_tree", "forward", "paged_decode_step", "paged_prefill",
           "layer_kinds", "rope_of", "DECODE_STATS"]

FULL, SLIDING = "full_attention", "sliding_attention"

#: the published sizes (config.json of the source), and the router that
#: config.json leaves to the family's code (qwen3_moe: a softmax)
PUBLISHED = {
    "num_hidden_layers": 28, "hidden_size": 2304,
    "intermediate_size": 7168, "moe_intermediate_size": 896,
    "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
    "num_experts": 64, "num_experts_per_tok": 8, "norm_topk_prob": True,
    "vocab_size": 98304, "sliding_window": 1024,
    "max_position_embeddings": 131072, "rms_norm_eps": 1e-06,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 7,
    "rope_parameters": {
        FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
               "original_max_position_embeddings": 8192, "beta_fast": 32,
               "beta_slow": 1, "attention_factor": 1.2772588722239782},
        SLIDING: {"rope_type": "default", "rope_theta": 500000}},
    "scoring_func": "softmax",
}

#: the programs' trailing counts: the expert layers' and, summed over
#: layers, the rows of K/V the dispatch's attention read (a query's whole
#: context on a full layer, ``min(context, window)`` on a sliding one),
#: what it would have read were every layer full, and the RING rows the
#: sliding layers read (a decoding slot's ``min(context, window)``, a
#: chunk's ``min(offset, window)`` left by the chunks before)
DECODE_STATS = _blocks.MOE_STATS + ("kv.rows_read", "kv.rows_full",
                                    "kv.window_rows_read")


def layer_kinds(cfg):
    """``full_attention`` or ``sliding_attention`` of every kept layer,
    by its PUBLISHED index."""
    return [cfg["layer_types"][l] for l in cfg["layers"]]


def rope_of(cfg, kind):
    """``(inv_freq float32 [head_dim / 2], factor)`` of a layer kind:
    the frequencies and what multiplies cos and sin."""
    rp = cfg["rope_parameters"][kind]
    d = cfg["head_dim"]
    if rp["rope_type"] == "yarn":
        import jax.numpy as jnp
        return jnp.asarray(_blocks.yarn_inv_freq(
            d, float(rp["rope_theta"]), rp["factor"],
            rp["original_max_position_embeddings"], rp["beta_fast"],
            rp["beta_slow"])), float(rp["attention_factor"])
    return _blocks.rope_inv_freq(float(rp["rope_theta"]), d // 2), 1.0


def _param_shapes(cfg):
    """``{path: (shape, init)}`` of every parameter; matrices are
    ``[in, out]``, the query, key and value projections side by side."""
    c, h, kv, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"], cfg["head_dim"])
    f = cfg["moe_intermediate_size"]
    held = cfg["experts_held"][1]
    out = {"wte": ((cfg["vocab_size"], c), "normal"),
           "head": ((cfg["vocab_size"], c), "normal"),
           "lnf_gamma": ((c,), "ones")}
    for i in range(len(cfg["layers"])):
        pre = "l%d_" % i
        out.update({
            pre + "ln1_gamma": ((c,), "ones"),
            pre + "ln2_gamma": ((c,), "ones"),
            pre + "attn_qkv_w": ((c, (h + 2 * kv) * d), "normal"),
            pre + "attn_q_norm_gamma": ((d,), "ones"),
            pre + "attn_k_norm_gamma": ((d,), "ones"),
            pre + "attn_o_w": ((h * d, c), "normal"),
            pre + "moe_router_w": ((c, cfg["num_experts"]), "normal"),
            pre + "moe_gu_w": ((held, c, 2 * f), "normal"),
            pre + "moe_down_w": ((held, f, c), "normal")})
    return out


class Mellum2LM(Block):
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
        """What ``ServingEngine`` needs of a model, in one object: paged
        K/V on a full layer, a ring of ``sliding_window`` rows a slot
        on a sliding one."""
        from ...serving.programs import ServingPrograms, KVPages, SlotState
        cfg = self.cfg
        kv, d = cfg["num_key_value_heads"], cfg["head_dim"]
        row = (cfg["sliding_window"], kv * d)
        ring = SlotState((("ring_k", row, None), ("ring_v", row, None)),
                         "ring")
        return ServingPrograms(
            n_heads=cfg["num_attention_heads"], max_len=self._max_len,
            decode_params=lambda net, kv_heads=None: decode_params(net),
            decode_step=functools.partial(paged_decode_step, cfg=cfg),
            prefill=functools.partial(paged_prefill, cfg=cfg),
            cache_kinds=[KVPages(kv, d) if kind == FULL else ring
                         for kind in layer_kinds(cfg)],
            decode_stats=DECODE_STATS, chunked_prefill=True,
            config_key=repr(sorted((k, repr(v)) for k, v in cfg.items())))


def mellum2(dtype="bfloat16", **overrides):
    """The published widths as one pipeline stage of a deployment that
    keeps each layer whole on a chip: published layers 0-7 (two whole
    periods of three sliding layers and a full one), all 64 experts, the
    whole vocabulary."""
    cfg = dict(PUBLISHED, layers=list(range(8)), experts_held=[0, 64])
    cfg.update(overrides)
    return Mellum2LM(cfg, dtype=dtype)


def mellum2_tiny(dtype="float32", **overrides):
    """Test-scale preset: hidden 64, 4 query heads over 2 K/V heads of
    16, a window of 8, 8 experts (top 4) all held, one period (sliding,
    sliding, sliding, full)."""
    cfg = dict(PUBLISHED, hidden_size=64, intermediate_size=96,
               moe_intermediate_size=32, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, sliding_window=8,
               num_experts=8, num_experts_per_tok=4, vocab_size=256,
               max_position_embeddings=4096, layers=[0, 1, 2, 3],
               experts_held=[0, 8])
    cfg.update(overrides)
    return Mellum2LM(cfg, dtype=dtype)


def param_tree(cfg, leaf):
    """The parameter tree the programs take, by layer, with
    ``leaf(path, shape)`` at every parameter."""
    return _blocks.param_tree(_param_shapes(cfg), len(cfg["layers"]),
                              ("attn", "moe"), leaf)


def decode_params(net):
    """The net's live arrays (no copy) as the programs' tree."""
    return param_tree(net.cfg,
                      lambda path, _: getattr(net, path).data()._data)


# ---------------------------------------------------------------------------
# the layer, as functions of the parameter tree
# ---------------------------------------------------------------------------

def _qkv(lp, x, pos, kind, cfg):
    """Queries ``[T, H, D]``, keys and values ``[T, K_kv, D]`` of the
    layer's input ``x`` [T, C] (the residual stream; normed here) at
    positions ``pos``: ``q`` and ``k`` normed per head, then rotated at
    the kind's frequencies and scaled by its factor."""
    t = x.shape[0]
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    with device_scope("attn.proj"):
        qkv = _mm(_rms(x, lp["ln1_g"], eps), lp["attn"]["qkv_w"])
        q = _rms(qkv[:, :h * d].reshape(t, h, d), lp["attn"]["q_norm_g"],
                 eps)
        k = _rms(qkv[:, h * d:(h + kv) * d].reshape(t, kv, d),
                 lp["attn"]["k_norm_g"], eps)
        v = qkv[:, (h + kv) * d:].reshape(t, kv, d)
        freqs, factor = rope_of(cfg, kind)
        q, k = _blocks.rope(q, pos, freqs), _blocks.rope(k, pos, freqs)
        if factor != 1.0:
            q, k = q * factor, k * factor
        return q, k, v


def _finish(lp, x, o, cfg, routing, stats, valid=None):
    """The layer after its attention ``o`` [T, H, D]: the output
    projection onto the residual stream, then the routed experts on the
    normed stream.  ``valid`` marks the rows that are tokens
    (``decoder_blocks.moe``)."""
    with device_scope("attn.out"):
        h = x + _mm(o.reshape(o.shape[0], -1), lp["attn"]["o_w"])
    with device_scope("moe"):
        hn = _rms(h, lp["ln2_g"], cfg["rms_norm_eps"])
    f, experts, st = _moe(lp["moe"], hn, cfg, valid)
    routing.append(experts)
    stats.append(st)
    with device_scope("moe"):
        return h + f


def _aux(cfg, n_rows, routing, stats, in_context, ring_rows):
    """The programs' report of a dispatch: the counts
    (``DECODE_STATS``) and the chosen experts int32 [layers, rows, k].
    ``in_context`` int [rows]: the keys each row's query sees on a full
    layer (0 for a row that is not real); ``ring_rows``: the ring rows
    ONE sliding layer read."""
    import jax.numpy as jnp
    k = cfg["num_experts_per_tok"]
    kinds = layer_kinds(cfg)
    n_full = kinds.count(FULL)
    with device_scope("moe"), device_scope("moe.route"):
        full = in_context.sum().astype(jnp.float32)
        ring = jnp.minimum(in_context, cfg["sliding_window"]).sum() \
            .astype(jnp.float32)
        return {"stats": jnp.concatenate([
            _blocks.moe_stats_vector(stats, n_rows * k,
                                     cfg["experts_held"][1]),
            jnp.stack([n_full * full + (len(kinds) - n_full) * ring,
                       len(kinds) * full,
                       (len(kinds) - n_full)
                       * ring_rows.astype(jnp.float32)])]),
            "experts": jnp.stack(routing)}


def forward(p, tokens, cfg):
    """Whole-sequence forward WITHOUT a cache, for small sizes: tokens
    int32 [T] -> ``(logits float32 [T, V], chosen experts per layer)``.
    Dense attention under the causal or the banded mask."""
    import jax.numpy as jnp
    t = tokens.shape[0]
    pos = jnp.arange(t)
    gap = pos[:, None] - pos[None, :]
    x = p["wte"][tokens].astype(jnp.float32)
    routing, stats = [], []
    for lp, kind in zip(p["layers"], layer_kinds(cfg)):
        q, k, v = _qkv(lp, x, pos, kind, cfg)
        mask = gap >= 0
        if kind == SLIDING:
            mask &= gap < cfg["sliding_window"]
        x = _finish(lp, x, _attend(q, k, v, mask), cfg, routing, stats)
    return _head(_rms(x, p["lnf_g"], cfg["rms_norm_eps"]), p["head"]), \
        routing


def paged_decode_step(p, tokens, positions, active, caches, block_tables,
                      n_heads, sampling=None, cfg=None):
    """ONE decode step for every serving slot (the contract of
    ``exaone_moe.paged_decode_step``): ``caches`` holds ``(k pool, v
    pool)`` for a full layer and ``(ring_k, ring_v)`` for a sliding one,
    donated by the caller's jit.

    Returns ``(logits [S, V], next_tokens [S], new_keys, new_caches,
    aux)`` with sampling and ``(logits, next_tokens, new_caches, aux)``
    without; ``aux`` as :func:`_aux` has it.
    """
    import jax.numpy as jnp
    from jax import lax
    from .gpt import sample_tokens

    s_n = tokens.shape[0]
    with device_scope("embed"):
        x = p["wte"][tokens].astype(jnp.float32)
    ctx = jnp.where(active, positions + 1, 0).astype(jnp.int32)
    ring_row = jnp.where(active, jnp.arange(s_n), s_n)
    new_caches, routing, stats = [], [], []
    for lp, kind, (kc, vc) in zip(p["layers"], layer_kinds(cfg), caches):
        q, k, v = _qkv(lp, x, positions, kind, cfg)
        o, kc, vc = _blocks.decode_attention(
            q, k, v, kc, vc, kind == FULL, positions, active, ctx,
            ring_row, block_tables, cfg)
        x = _finish(lp, x, o, cfg, routing, stats, active)
        new_caches.append((kc, vc))
    with device_scope("lm_head"):
        logits = _head(_rms(x, p["lnf_g"], cfg["rms_norm_eps"]), p["head"])
    aux = _aux(cfg, s_n, routing, stats, ctx,
               jnp.minimum(ctx, cfg["sliding_window"]).sum())
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
    """ONE CHUNK of a prompt into slot ``slot`` (the contract of
    ``exaone_moe.paged_prefill``): ``tokens`` [T] holds the prompt's
    positions ``prefix_len ..`` (padded), ``prompt_len`` is the prompt's
    length so far.  ``cow_*`` are unused: no prefix is shared.

    Returns ``gpt._first_token``'s tuple for the chunk's LAST real row
    with ``aux`` appended (:func:`_aux`, rows = the chunk's padded rows).
    """
    import jax.numpy as jnp
    from jax import lax
    from .gpt import _first_token

    del cow_src, cow_dst
    t_pad = tokens.shape[0]
    positions = prefix_len + jnp.arange(t_pad, dtype=jnp.int32)
    n_rows = prompt_len - prefix_len
    valid = positions < prompt_len
    with device_scope("embed"):
        x = p["wte"][tokens].astype(jnp.float32)
    new_caches, routing, stats = [], [], []
    for lp, kind, (kc, vc) in zip(p["layers"], layer_kinds(cfg), caches):
        q, k, v = _qkv(lp, x, positions, kind, cfg)
        o, kc, vc = _blocks.chunk_attention(
            q, k, v, kc, vc, kind == FULL, block_table_row, slot,
            prefix_len, prompt_len, n_rows, cfg)
        x = _finish(lp, x, o, cfg, routing, stats, valid)
        new_caches.append((kc, vc))
    with device_scope("lm_head"):
        last = lax.dynamic_index_in_dim(
            _rms(x, p["lnf_g"], cfg["rms_norm_eps"]), n_rows - 1, 0,
            keepdims=False)
        logits = _head(last, p["head"])
    aux = _aux(cfg, t_pad, routing, stats,
               jnp.where(valid, positions + 1, 0),
               jnp.minimum(prefix_len, cfg["sliding_window"]))
    return _first_token(logits, sampling, new_caches) + (aux,)
