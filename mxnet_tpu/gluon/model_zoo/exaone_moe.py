"""K-EXAONE's language model (``model_type`` ``exaone_moe``) for serving:
grouped-query attention in two kinds of layer, three SLIDING layers that
see the last ``sliding_window`` keys to every FULL one that sees the
whole context, over routed experts, held as ONE CHIP'S SHARE of a stated
deployment.

Layer ``l`` (published index) on the float32 residual stream ``x``, the
norm on each sub-layer's OUTPUT (RMSNorm eps 1e-5)::

    h = x + RMSNorm(Attn_l(x)) g1;   y = h + RMSNorm(FFN_l(h)) g2

``Attn_l``: ``q = x Wq`` (64 heads of 128), ``k = x Wk``, ``v = x Wv`` (8
heads of 128); ``q`` and ``k`` RMSNormed per head; on a sliding layer
both rotated (theta 1e6 over all 128 values, pairs ``(i, i + 64)``), on a
full layer not; query ``i`` sees key ``j`` iff ``0 <= i - j`` and, on a
sliding layer, ``i - j < sliding_window``; softmax in float32 at scale
``128 ** -0.5``.  ``FFN_l`` is a dense SwiGLU for ``l <
first_k_dense_replace``, else the routed experts (``parallel.moe``:
sigmoid scores over all experts, the 8 largest of ``p + b``, weights
``2.5 p / sum of the chosen p``) plus one shared expert.
``perfbench/reference/kexaone.py`` is the plain float32 statement of the
same equations; this file is the program: bfloat16 weights as
published, float32 residual stream and accumulation.

**The share** (``cfg["experts_held"]``, ``cfg["layers"]``,
``cfg["vocab_size"]``) is ``ling3.py``'s: the router scores all
``num_experts``, this chip computes its held experts' terms and the
shared expert, the layers listed are the pipeline stage's, the
vocabulary is the slice held.  Nothing stands in for the absent chips.

**Serving** (:func:`paged_decode_step`, :func:`paged_prefill`): a full
layer keeps paged K and V pools (``serving.programs.KVPages``, read at
decode by ``ops.pallas.paged_attention``); a sliding layer keeps a RING
of ``sliding_window`` rows a SLOT (``serving.programs.SlotState`` of
role "ring": ``[slots + 1, window, K_kv * D]`` for K and for V), in
which position ``p`` lives at row ``p % window``.  A ring is never
cleared: the row ``r`` of a slot whose newest position is ``pos`` holds
position ``pos - (pos - r) % window``, and a negative one is masked, so
a slot's new tenant cannot see the last one's rows.

The prefill is CHUNKED: one program of ``T`` rows that takes the
position of its first row (the engine's ``prefix_len`` argument), the
prompt's length so far and the slot.  On a full layer it writes the
chunk's K and V as whole pages and scores the chunk against the slot's
pages a block of keys at a time (:func:`_attend_pages`: no ``[H, T,
context]`` tensor exists); on a sliding layer it scores the chunk
against its own rows and the ``window`` rows the chunk before left in
the ring, then leaves its own last rows there.  No prefix reuse across
requests (a cached prefix has no ring), no speculative decoding (a
rejected draft has overwritten ring rows), no int8 rows.
"""
from __future__ import annotations

import functools

from ...telemetry import device_scope
from ..block import Block
from . import decoder_blocks as _blocks
from .decoder_blocks import (mm as _mm, rms as _rms, head as _head,
                             swiglu as _swiglu, moe as _moe,
                             rows_per_block as _rows_per_block)

__all__ = ["ExaoneMoeLM", "k_exaone", "exaone_moe_tiny", "decode_params",
           "param_tree", "forward", "paged_decode_step", "paged_prefill",
           "layer_kinds", "ffn_kinds", "DECODE_STATS"]

FULL, SLIDING = "full_attention", "sliding_attention"

#: the published sizes (config.json of the source)
PUBLISHED = {
    "num_hidden_layers": 48, "hidden_size": 6144,
    "intermediate_size": 18432, "moe_intermediate_size": 2048,
    "num_shared_experts": 1, "num_attention_heads": 64,
    "num_key_value_heads": 8, "head_dim": 128, "num_experts": 128,
    "num_experts_per_tok": 8, "n_group": 1, "topk_group": 1,
    "routed_scaling_factor": 2.5, "first_k_dense_replace": 1,
    "vocab_size": 153600, "rope_theta": 1000000, "sliding_window": 128,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 12,
    "max_position_embeddings": 262144, "rms_norm_eps": 1e-05,
}

#: query rows and keys a block of a chunk's attention over the slot's
#: pages (a block's scores are ``[H, rows, keys]`` float32 at once)
FULL_QUERY_BLOCK = 512
FULL_KEY_BLOCK = 1024

#: the programs' trailing counts: the expert layers' (``ling3.py``'s)
#: and, summed over layers, the rows of K/V the dispatch's attention
#: read (a query's whole context on a full layer, ``min(context,
#: window)`` on a sliding one) and what it would have read were every
#: layer full
DECODE_STATS = _blocks.MOE_STATS + ("kv.rows_read", "kv.rows_full")

_NEG = -1e30


def layer_kinds(cfg):
    """``full_attention`` or ``sliding_attention`` of every kept layer,
    by its PUBLISHED index."""
    return [cfg["layer_types"][l] for l in cfg["layers"]]


def ffn_kinds(cfg):
    """"dense" or "moe" of every kept layer, by its PUBLISHED index."""
    return ["dense" if l < cfg["first_k_dense_replace"] else "moe"
            for l in cfg["layers"]]


def _param_shapes(cfg):
    """``{path: (shape, init)}`` of every parameter; matrices are
    ``[in, out]``, the query, key and value projections side by side."""
    c, h, kv, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"], cfg["head_dim"])
    f = cfg["moe_intermediate_size"]
    fs = f * cfg["num_shared_experts"]
    held = cfg["experts_held"][1]
    out = {"wte": ((cfg["vocab_size"], c), "normal"),
           "head": ((cfg["vocab_size"], c), "normal"),
           "lnf_gamma": ((c,), "ones")}
    for i, ffn in enumerate(ffn_kinds(cfg)):
        pre = "l%d_" % i
        out.update({
            pre + "ln1_gamma": ((c,), "ones"),
            pre + "ln2_gamma": ((c,), "ones"),
            pre + "attn_qkv_w": ((c, (h + 2 * kv) * d), "normal"),
            pre + "attn_q_norm_gamma": ((d,), "ones"),
            pre + "attn_k_norm_gamma": ((d,), "ones"),
            pre + "attn_o_w": ((h * d, c), "normal")})
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


class ExaoneMoeLM(Block):
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


def k_exaone(dtype="bfloat16", **overrides):
    """The published widths as one chip's share of a deployment in
    which 8 chips share each layer by expert parallelism: a pipeline
    stage of published layers 0 and 4-7 (the dense layer, then one whole
    period of three sliding layers and a full one), experts 0-15 of 128,
    an eighth of the vocabulary."""
    cfg = dict(PUBLISHED, layers=[0, 4, 5, 6, 7], experts_held=[0, 16],
               vocab_size=19200)
    cfg.update(overrides)
    return ExaoneMoeLM(cfg, dtype=dtype)


def exaone_moe_tiny(dtype="float32", **overrides):
    """Test-scale preset: hidden 64, 4 query heads over 2 K/V heads of
    16, a window of 8, 16 experts (top 4), 4 held; the dense layer and
    one period (sliding, sliding, sliding, full)."""
    cfg = dict(PUBLISHED, hidden_size=64, intermediate_size=96,
               moe_intermediate_size=32, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, sliding_window=8,
               num_experts=16, num_experts_per_tok=4, vocab_size=256,
               max_position_embeddings=4096, layers=[0, 4, 5, 6, 7],
               experts_held=[4, 4])
    cfg.update(overrides)
    return ExaoneMoeLM(cfg, dtype=dtype)


def param_tree(cfg, leaf):
    """The parameter tree the programs take, by layer, with
    ``leaf(path, shape)`` at every parameter."""
    return _blocks.param_tree(_param_shapes(cfg), len(cfg["layers"]),
                              ("attn", "mlp", "moe"), leaf)


def decode_params(net):
    """The net's live arrays (no copy) as the programs' tree."""
    return param_tree(net.cfg,
                      lambda path, _: getattr(net, path).data()._data)


# ---------------------------------------------------------------------------
# the layer, as functions of the parameter tree
# ---------------------------------------------------------------------------

def _qkv(lp, x, pos, sliding, cfg):
    """Queries ``[T, H, D]``, keys and values ``[T, K_kv, D]`` of the
    residual stream ``x`` [T, C] at positions ``pos``: ``q`` and ``k``
    normed per head, both rotated on a sliding layer."""
    t = x.shape[0]
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    with device_scope("attn.proj"):
        qkv = _mm(x, lp["qkv_w"])
        q = _rms(qkv[:, :h * d].reshape(t, h, d), lp["q_norm_g"], eps)
        k = _rms(qkv[:, h * d:(h + kv) * d].reshape(t, kv, d),
                 lp["k_norm_g"], eps)
        v = qkv[:, (h + kv) * d:].reshape(t, kv, d)
        if sliding:
            freqs = _blocks.rope_inv_freq(float(cfg["rope_theta"]), d // 2)
            q, k = _blocks.rope(q, pos, freqs), _blocks.rope(k, pos, freqs)
        return q, k, v


def _attend(q, k, v, mask):
    """Grouped-query softmax attention of queries ``q`` [Q, H, D] over
    keys and values ``k``, ``v`` [K, K_kv, D] where ``mask`` [Q, K]
    holds: the operands in the keys' stored type, scores, softmax and
    sums in float32.  A query that sees no key gives zeros.  Returns
    float32 [Q, H, D]."""
    import jax.numpy as jnp
    n, h, d = q.shape
    kv = k.shape[1]
    s = jnp.einsum("qkgd,skd->kgqs",
                   q.reshape(n, kv, h // kv, d).astype(k.dtype), k,
                   preferred_element_type=jnp.float32) \
        * jnp.float32(d ** -0.5)
    s = jnp.where(mask, s, _NEG)
    e = jnp.where(mask, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    o = jnp.einsum("kgqs,skd->qkgd", e.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    total = e.sum(-1).transpose(2, 0, 1)[..., None]          # [Q, kv, g, 1]
    return (o / jnp.maximum(total, 1e-30)).reshape(n, h, d)


def _attend_ring(q, ring_k, ring_v, positions, active, cfg):
    """One decode step of a sliding layer: every slot's query ``q`` [S,
    H, D] over its ring ``[S, window, K_kv * D]``, a row valid by the
    position it holds (``pos - (pos - r) % window >= 0``)."""
    import jax
    import jax.numpy as jnp
    s_n, window = q.shape[0], ring_k.shape[1]
    kv, d = cfg["num_key_value_heads"], cfg["head_dim"]
    held = positions[:, None] - (positions[:, None]
                                 - jnp.arange(window)[None, :]) % window
    mask = active[:, None] & (held >= 0)
    o = jax.vmap(_attend)(q[:, None], ring_k.reshape(s_n, window, kv, d),
                          ring_v.reshape(s_n, window, kv, d),
                          mask[:, None, :])
    return o[:, 0]


def _attend_window(q, k, v, prev_k, prev_v, first_pos, window):
    """A chunk of a sliding layer: queries ``q`` [T, H, D] at positions
    ``first_pos ..`` over the chunk's own keys ``k`` [T, K_kv, D] and
    the ``prev`` rows [R, K_kv, D] at positions ``first_pos - R ..
    first_pos - 1``, a block of queries against the ``block + R`` keys
    it can see.  Returns float32 [T, H, D]."""
    import jax
    import jax.numpy as jnp
    t, r = q.shape[0], prev_k.shape[0]
    rows = _rows_per_block(t, window)
    blocks = t // rows
    idx = (jnp.arange(blocks) * rows)[:, None] \
        + jnp.arange(rows + r)[None, :]
    q_pos = first_pos + jnp.arange(t).reshape(blocks, rows)
    k_pos = first_pos - r + idx
    gap = q_pos[:, :, None] - k_pos[:, None, :]
    mask = (k_pos[:, None, :] >= 0) & (gap >= 0) & (gap < window)
    o = jax.vmap(_attend)(q.reshape(blocks, rows, *q.shape[1:]),
                          jnp.concatenate([prev_k, k])[idx],
                          jnp.concatenate([prev_v, v])[idx], mask)
    return o.reshape(q.shape)


def _attend_pages(q, k_pages, v_pages, block_table_row, first_pos):
    """A chunk of a full layer: queries ``q`` [T, H, D] at positions
    ``first_pos ..`` over the slot's pages, which hold every key up to
    the chunk's own.  ``FULL_QUERY_BLOCK`` queries at a time walk the
    blocks of ``FULL_KEY_BLOCK`` keys up to their last position with a
    running softmax; the blocks behind are not entered.  Returns
    float32 [T, H, D]."""
    import jax.numpy as jnp
    from jax import lax
    t, h, d = q.shape
    page = k_pages.shape[1]
    kv = k_pages.shape[2] // d
    g = h // kv
    pages = max(1, min(FULL_KEY_BLOCK // page, block_table_row.shape[0]))
    keys = pages * page
    table = jnp.pad(block_table_row,
                    (0, -block_table_row.shape[0] % pages))
    rows = _rows_per_block(t, FULL_QUERY_BLOCK)
    scale = jnp.float32(d ** -0.5)

    def one_block(args):
        qb, first = args                                  # [rows, kv, g, d]
        q_pos = first + jnp.arange(rows)

        def step(j, carry):
            m, total, acc = carry
            at = lax.dynamic_slice_in_dim(table, j * pages, pages)
            kb = k_pages[at].reshape(keys, kv, d)
            vb = v_pages[at].reshape(keys, kv, d)
            mask = (j * keys + jnp.arange(keys))[None, :] <= q_pos[:, None]
            s = jnp.where(mask, jnp.einsum(
                "qkgd,skd->kgqs", qb, kb,
                preferred_element_type=jnp.float32) * scale, _NEG)
            m_new = jnp.maximum(m, s.max(-1))
            e = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
            keep = jnp.exp(m - m_new)
            return (m_new, total * keep + e.sum(-1),
                    acc * keep[..., None] + jnp.einsum(
                        "kgqs,skd->kgqd", e.astype(vb.dtype), vb,
                        preferred_element_type=jnp.float32))

        _, total, acc = lax.fori_loop(
            0, (first + rows - 1) // keys + 1, step,
            (jnp.full((kv, g, rows), _NEG, jnp.float32),
             jnp.zeros((kv, g, rows), jnp.float32),
             jnp.zeros((kv, g, rows, d), jnp.float32)))
        return acc / total[..., None]

    o = lax.map(one_block, (
        q.reshape(t // rows, rows, kv, g, d).astype(k_pages.dtype),
        first_pos + jnp.arange(t // rows) * rows))
    return o.transpose(0, 3, 1, 2, 4).reshape(t, h, d)


def _finish(lp, x, o, cfg, routing, stats, valid=None):
    """The layer after its attention ``o`` [T, H, D]: the output
    projection, the norm on the attention's output, the feed-forward and
    the norm on its output.  ``valid`` marks the rows that are tokens
    (``decoder_blocks.moe``)."""
    eps = cfg["rms_norm_eps"]
    with device_scope("attn.out"):
        h = x + _rms(_mm(o.reshape(o.shape[0], -1), lp["attn"]["o_w"]),
                     lp["ln1_g"], eps)
    if "mlp" in lp:
        with device_scope("mlp"):
            return h + _rms(_swiglu(h, lp["mlp"]["gu_w"],
                                    lp["mlp"]["down_w"]), lp["ln2_g"], eps)
    f, experts, st = _moe(lp["moe"], h, cfg, valid)
    routing.append(experts)
    stats.append(st)
    with device_scope("moe"):
        return h + _rms(f, lp["ln2_g"], eps)


def _aux(cfg, n_rows, routing, stats, in_context):
    """The programs' report of a dispatch: the counts
    (``DECODE_STATS``) and the chosen experts int32 [expert layers,
    rows, k].  ``in_context`` int [rows]: the keys each row's query
    sees on a full layer (0 for a row that is not real)."""
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
                       len(kinds) * full])]),
            "experts": jnp.stack(routing) if routing
            else jnp.zeros((0, n_rows, k), jnp.int32)}


def forward(p, tokens, cfg):
    """Whole-sequence forward WITHOUT a cache, for small sizes: tokens
    int32 [T] -> ``(logits float32 [T, V], chosen experts per expert
    layer)``.  Dense attention under the causal or the banded mask."""
    import jax.numpy as jnp
    t = tokens.shape[0]
    pos = jnp.arange(t)
    gap = pos[:, None] - pos[None, :]
    x = p["wte"][tokens].astype(jnp.float32)
    routing, stats = [], []
    for lp, kind in zip(p["layers"], layer_kinds(cfg)):
        q, k, v = _qkv(lp["attn"], x, pos, kind == SLIDING, cfg)
        mask = gap >= 0
        if kind == SLIDING:
            mask &= gap < cfg["sliding_window"]
        x = _finish(lp, x, _attend(q, k, v, mask), cfg, routing, stats)
    return _head(_rms(x, p["lnf_g"], cfg["rms_norm_eps"]), p["head"]), \
        routing


def paged_decode_step(p, tokens, positions, active, caches, block_tables,
                      n_heads, sampling=None, cfg=None):
    """ONE decode step for every serving slot (the contract of
    ``gpt.paged_decode_step``): ``caches`` holds ``(k pool, v pool)`` for
    a full layer and ``(ring_k, ring_v)`` for a sliding one, donated by
    the caller's jit.  An inactive slot writes to the scratch page and
    the scratch ring row and attends over nothing.

    Returns ``(logits [S, V], next_tokens [S], new_keys, new_caches,
    aux)`` with sampling and ``(logits, next_tokens, new_caches, aux)``
    without; ``aux`` as :func:`_aux` has it.
    """
    import jax.numpy as jnp
    from jax import lax
    from .gpt import sample_tokens
    from ...ops.pallas.paged_attention import paged_attention

    s_n = tokens.shape[0]
    with device_scope("embed"):
        x = p["wte"][tokens].astype(jnp.float32)
    ctx = jnp.where(active, positions + 1, 0).astype(jnp.int32)
    ring_row = jnp.where(active, jnp.arange(s_n), s_n)
    new_caches, routing, stats = [], [], []
    for lp, kind, (kc, vc) in zip(p["layers"], layer_kinds(cfg), caches):
        q, k, v = _qkv(lp["attn"], x, positions, kind == SLIDING, cfg)
        with device_scope("kv_write"):
            k, v = k.reshape(s_n, -1), v.reshape(s_n, -1)
            if kind == FULL:
                page_size = kc.shape[1]
                phys = jnp.where(active, jnp.take_along_axis(
                    block_tables, (positions // page_size)[:, None],
                    axis=1)[:, 0], 0)
                at = (phys, positions % page_size)
            else:
                at = (ring_row, positions % kc.shape[1])
            kc = kc.at[at].set(k.astype(kc.dtype))
            vc = vc.at[at].set(v.astype(vc.dtype))
        if kind == FULL:
            with device_scope("attn.full"):
                o = paged_attention(q, kc, vc, block_tables, ctx)
        else:
            with device_scope("attn.window"):
                o = _attend_ring(q, kc[:s_n], vc[:s_n], positions, active,
                                 cfg)
        x = _finish(lp, x, o, cfg, routing, stats, active)
        new_caches.append((kc, vc))
    with device_scope("lm_head"):
        logits = _head(_rms(x, p["lnf_g"], cfg["rms_norm_eps"]), p["head"])
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
    for a chunk, plus the slot, which a ring needs): ``tokens`` [T]
    holds the prompt's positions ``prefix_len ..`` (padded),
    ``prompt_len`` is the prompt's length SO FAR (``prefix_len`` + this
    chunk's real rows).  Everything before ``prefix_len`` is in the
    slot's pages (full layers) and its newest ``window`` rows in the
    slot's rings (sliding layers), left by the chunks before; this chunk
    leaves its own.  ``cow_*`` are unused: no prefix is shared.

    Returns ``gpt._first_token``'s tuple for the chunk's LAST real row
    (the first generated token when the chunk is the prompt's last)
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
        q, k, v = _qkv(lp["attn"], x, positions, kind == SLIDING, cfg)
        if kind == FULL:
            with device_scope("kv_write"):
                kc, vc = (_blocks.page_scatter(pool, block_table_row, rows,
                                               prefix_len, n_rows)
                          for pool, rows in ((kc, k), (vc, v)))
            with device_scope("attn.full"):
                o = _attend_pages(q, kc, vc, block_table_row, prefix_len)
        else:
            window = kc.shape[1]
            with device_scope("attn.window"):
                k, v = k.astype(kc.dtype), v.astype(vc.dtype)
                old_k, old_v = (lax.dynamic_index_in_dim(
                    ring, slot, 0, keepdims=False)
                    .reshape(window, *k.shape[1:]) for ring in (kc, vc))
                # the ring's rows in the order of their positions,
                # prefix_len - window .. prefix_len - 1
                behind = (prefix_len + jnp.arange(window)) % window
                o = _attend_window(q, k, v, old_k[behind], old_v[behind],
                                   prefix_len, cfg["sliding_window"])
            with device_scope("kv_write"):
                # row r now holds the newest position p < prompt_len with
                # p % window == r: this chunk's row if the chunk reaches it
                newest = prompt_len - 1 \
                    - (prompt_len - 1 - jnp.arange(window)) % window
                mine = (newest >= prefix_len)[:, None, None]
                row = jnp.clip(newest - prefix_len, 0, t_pad - 1)
                kc, vc = (lax.dynamic_update_index_in_dim(
                    ring, jnp.where(mine, new[row], old)
                    .reshape(window, -1), slot, 0)
                    for ring, new, old in ((kc, k, old_k), (vc, v, old_v)))
        x = _finish(lp, x, o, cfg, routing, stats, valid)
        new_caches.append((kc, vc))
    with device_scope("lm_head"):
        last = lax.dynamic_index_in_dim(
            _rms(x, p["lnf_g"], cfg["rms_norm_eps"]), n_rows - 1, 0,
            keepdims=False)
        logits = _head(last, p["head"])
    aux = _aux(cfg, t_pad, routing, stats,
               jnp.where(valid, positions + 1, 0))
    return _first_token(logits, sampling, new_caches) + (aux,)
