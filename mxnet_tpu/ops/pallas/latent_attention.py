"""Paged decode attention over a LATENT page pool (multi-head latent
attention in its absorbed form) as a Pallas TPU kernel.

An MLA layer caches one row a token, ``[c (rank) | k_rope | pad]``, in
a pool ``[num_pages, page_size, W]`` that the allocator's block tables
address like any K/V pool.  With the up-projection absorbed into the
query (``q_lat,h = W_kvb,h^K^T q_nope,h``) every query head scores
against the SAME row, and the value is the row's first ``v_width``
lanes: one key/value head of width ``W`` / ``v_width`` under ``H`` query
heads, the page read once for both.

The kernel is ``paged_attention.py``'s structure with that one head:
grid ``(num_slots, max_pages_per_seq)``, the page axis sequential, block
table and context lengths by scalar prefetch (the gather is the
pipeline's address computation), pages past a slot's context skipped
under ``pl.when``, online softmax in VMEM scratch.  ``W`` is padded to a
multiple of 128 by the caller (query and row carry zeros there), so the
pool keeps one row-major layout between programs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .flash_attention import (_NEG_INF, _TINY, _pallas_call, _pl,
                              _scratch)


def _mla_kernel(ctx_ref, bt_ref, q_ref, page_ref, o_ref, o_acc, m_acc,
                l_acc, *, page_size, v_width, scale):
    pl = _pl()
    s = pl.program_id(0)
    j = pl.program_id(1)
    ctx = ctx_ref[s]

    @pl.when(j == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, _NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    @pl.when(j * page_size < ctx)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32) * scale            # (H, W)
        rows = page_ref[0].astype(jnp.float32)              # (page, W)
        h = q.shape[0]
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (h, page_size), 1)
        mask = pos < ctx
        st = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (H, page)
        st = jnp.where(mask, st, _NEG_INF)
        m_prev = m_acc[...]
        m_new = jnp.maximum(m_prev, st.max(axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(st - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_acc[...] = l_acc[...] * corr + p.sum(axis=-1, keepdims=True)
        # the value is a lane slice of the row the scores just read
        o_acc[...] = o_acc[...] * corr + jax.lax.dot_general(
            p, rows[:, :v_width], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_acc[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        # a slot with ctx == 0 never accumulated: emit zeros
        o_ref[0] = (o_acc[...] / jnp.maximum(l_acc[...], _TINY)) \
            .astype(o_ref.dtype)


def mla_paged_decode(q, pool, block_tables, context_lens, v_width, scale):
    """Absorbed-form MLA decode for every slot in one launch.

    - ``q``: [S, H, W] — ``[q_lat | q_rope | zeros]`` a head;
    - ``pool``: [num_pages, page_size, W] — ``[c | k_rope | zeros]`` a
      token (page 0 is the allocator's scratch page);
    - ``block_tables``: int32 [S, max_pages_per_seq];
    - ``context_lens``: int32 [S] (0: an empty slot, zeros out);
    - ``v_width``: the latent's width (the row's leading lanes are the
      value); ``scale``: the softmax scale of the UNABSORBED head.

    Returns ``o_lat`` float32 [S, H, v_width].
    """
    pl = _pl()
    from jax.experimental.pallas import tpu as pltpu
    s_n, h, w = q.shape
    if pool.ndim != 3 or pool.shape[2] != w or w % 128 or v_width > w:
        raise ValueError(
            "latent pool must be [num_pages, page_size, W] with W = %d "
            "a multiple of 128 and v_width <= W, got %r (v_width %d)"
            % (w, tuple(pool.shape), v_width))
    page_size = pool.shape[1]
    max_pages = block_tables.shape[1]
    q_spec = pl.BlockSpec((1, h, w), lambda s, j, c, b: (s, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_n, max_pages),
        in_specs=[q_spec,
                  pl.BlockSpec((1, page_size, w),
                               lambda s, j, c, b: (b[s, j], 0, 0))],
        out_specs=pl.BlockSpec((1, h, v_width),
                               lambda s, j, c, b: (s, 0, 0)),
        scratch_shapes=[_scratch((h, v_width)), _scratch((h, 1)),
                        _scratch((h, 1))])
    return _pallas_call(
        functools.partial(_mla_kernel, page_size=page_size,
                          v_width=v_width, scale=np.float32(scale)),
        [jnp.asarray(context_lens, jnp.int32),
         jnp.asarray(block_tables, jnp.int32), q, pool],
        name="mla_paged_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_n, h, v_width), jnp.float32))


def mla_paged_decode_reference(q, pool, block_tables, context_lens,
                               v_width, scale):
    """jnp oracle: gather each slot's rows, dense masked softmax."""
    s_n = q.shape[0]
    rows = pool[jnp.asarray(block_tables, jnp.int32)].astype(jnp.float32)
    rows = rows.reshape(s_n, -1, pool.shape[2])               # [S, T, W]
    ctx = jnp.asarray(context_lens, jnp.int32)
    st = jnp.einsum("shw,stw->sht", q.astype(jnp.float32), rows) * scale
    mask = jnp.arange(rows.shape[1])[None, None, :] < ctx[:, None, None]
    p = jax.nn.softmax(jnp.where(mask, st, _NEG_INF), axis=-1)
    p = jnp.where(ctx[:, None, None] > 0, p, 0.0)
    return jnp.einsum("sht,stv->shv", p, rows[..., :v_width])
