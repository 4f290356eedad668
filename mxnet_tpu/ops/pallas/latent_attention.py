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

:func:`mla_paged_prefill` is the same absorbed attention for the rows of
ONE slot's prefill chunk (positions ``first_pos ..``), causal over the
slot's pages, which hold every row up to the chunk's own:

- grid ``(T / tq, H / hg)``: a cell is a block of ``tq`` query rows by
  ``hg`` heads.  Every head scores against the same latent row, so the
  block is ONE matrix of ``tq * hg`` rows (query-major): one ``[tq * hg,
  W] x [W, keys]`` score matmul and one ``[tq * hg, keys] x [keys,
  v_width]`` value matmul a key block, one read of the key block for
  both.  ``hg`` is the fewest heads that fill whole sublane tiles of the
  pool's dtype, ``tq`` as many rows as give ``PREFILL_ROWS`` matrix rows;
- the cell walks key blocks of ``PREFILL_KEYS`` rows (whole pages) from
  position 0 to its last real query's position, a sequential loop: the
  pages of the next block are copied into the other half of a
  double-buffered VMEM buffer (``make_async_copy``, addressed through the
  block table by scalar prefetch) while this block is scored, and no
  copy is issued past the last block.  Only the blocks that reach past
  the cell's first query are masked;
- online softmax in VMEM scratch (float32 scores, max, sum and
  accumulator; the weights cast to the pool's dtype for the value
  matmul), so no score block ever reaches HBM;
- a cell whose rows are all padding (at or past ``n_rows``) walks no
  key block and emits zeros; the pad rows of the others are zeros too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .flash_attention import (_NEG_INF, _TINY, _pallas_call, _pl,
                              _scratch)
from .sparse_latent_attention import _prec

#: matrix rows a cell of :func:`mla_paged_prefill` (query rows x heads:
#: the M of its two matmuls), and latent rows a key block
PREFILL_ROWS = 1024
PREFILL_KEYS = 512


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


def prefill_blocks(t, h, page_size, max_pages, dtype):
    """``(tq, hg, ppb)`` of :func:`mla_paged_prefill`, from shapes alone:
    ``hg`` the fewest heads whose rows fill whole sublane tiles of
    ``dtype`` (8 float32 rows, 16 bfloat16; all ``h`` if none divides
    it), ``tq`` the largest divisor of ``t`` with ``tq * hg`` at most
    ``PREFILL_ROWS``, ``ppb`` the pages of a key block of
    ``PREFILL_KEYS`` rows (at most the table's)."""
    tile = 32 // np.dtype(dtype).itemsize
    hg = next((g for g in range(tile, h, tile) if h % g == 0), h)
    tq = max(1, min(t, PREFILL_ROWS // hg))
    while t % tq:
        tq -= 1
    return tq, hg, max(1, min(PREFILL_KEYS // page_size, max_pages))


def _prefill_vmem_bytes(tq, hg, w, v_width, ppb, page_size, dtype):
    """A cell's VMEM: two buffers of its query and output blocks and of
    the key block, the accumulators, and the score block's float32
    temporaries (scores, weights, their cast, the value product)."""
    rows, keys = tq * hg, ppb * page_size
    item = np.dtype(dtype).itemsize
    held = 2 * rows * (w * item + v_width * 4) + 2 * keys * w * item \
        + rows * (v_width + 2 * 128) * 4
    return held + rows * (keys * (8 + item) + v_width * 4)


def _prefill_kernel(bt_ref, span_ref, q_ref, pool_ref, o_ref, kbuf, sem,
                    o_acc, m_acc, l_acc, *, tq, hg, ppb, v_width):
    pl = _pl()
    from jax.experimental.pallas import tpu as pltpu
    first, n_rows = span_ref[0], span_ref[1]
    row0 = pl.program_id(0) * tq
    real = jnp.minimum(n_rows - row0, tq)
    keys = ppb * kbuf.shape[2]
    rows = tq * hg

    @pl.when(real <= 0)
    def _pad():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(real > 0)
    def _walk():
        q_first = first + row0
        # blocks up to the last real query's position; of them, those
        # that end at or before the first query's need no mask
        n_blk = (q_first + real - 1) // keys + 1
        n_full = jnp.minimum((q_first + 1) // keys, n_blk)

        def copies(j, buf):
            return [pltpu.make_async_copy(
                pool_ref.at[bt_ref[j * ppb + p]], kbuf.at[buf, p],
                sem.at[buf]) for p in range(ppb)]

        for c in copies(0, 0):
            c.start()
        q = q_ref[...].reshape(rows, q_ref.shape[-1])
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, _NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

        def step(j, masked):
            buf = j % 2

            @pl.when(j + 1 < n_blk)
            def _ahead():
                for c in copies(j + 1, 1 - buf):
                    c.start()

            for c in copies(j, buf):
                c.wait()
            k = kbuf[buf].reshape(keys, kbuf.shape[-1])
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), precision=_prec(k),
                preferred_element_type=jnp.float32)      # (rows, keys)
            if masked:
                # matrix row r is query r // hg: key <= its position
                # iff (key - q_first) * hg <= r
                at = j * keys - q_first + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                s = jnp.where(at * hg <= jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 0), s, _NEG_INF)
            # block 0 holds position 0, which every query sees: m is a
            # real score from there on and a masked score weighs 0
            m_prev = m_acc[...]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_acc[...] = l_acc[...] * corr + p.sum(axis=-1, keepdims=True)
            # the value is a lane slice of the rows the scores just read
            o_acc[...] = o_acc[...] * corr + jax.lax.dot_general(
                p.astype(k.dtype), k[:, :v_width], (((1,), (0,)), ((), ())),
                precision=_prec(k), preferred_element_type=jnp.float32)
            m_acc[...] = m_new

        for masked, lo, hi in ((False, 0, n_full), (True, n_full, n_blk)):
            jax.lax.fori_loop(
                lo, hi, lambda j, c, masked=masked: (step(j, masked), c)[1],
                0)
        keep = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) < real * hg
        o_ref[...] = jnp.where(keep, o_acc[...] / l_acc[...], 0.0) \
            .reshape(o_ref.shape)


def mla_paged_prefill(q, pool, block_table_row, first_pos, n_rows, v_width,
                      scale):
    """Absorbed-form MLA of ONE slot's prefill chunk over its pages.

    - ``q``: [T, H, W] ``[q_lat | q_rope | zeros]`` a head, the chunk's
      rows at positions ``first_pos ..``; scaled by ``scale`` and cast
      to the pool's dtype (the MXU's operand type) in one rounding;
    - ``pool``: [num_pages, page_size, W] ``[c | k_rope | zeros]`` a
      token, holding every row of the slot up to the chunk's own;
    - ``block_table_row``: int32 [max_pages_per_seq], the slot's;
    - ``first_pos``, ``n_rows``: int32 scalars, the chunk's first
      position and its real rows;
    - ``v_width``: the latent's width (a row's leading lanes are the
      value); ``scale``: the softmax scale of the unabsorbed head.

    Returns ``o_lat`` float32 [T, H, v_width], causal (query ``t`` sees
    positions up to ``first_pos + t``); rows at and past ``n_rows`` are
    zeros.
    """
    pl = _pl()
    from jax.experimental.pallas import tpu as pltpu
    t, h, w = q.shape
    if pool.ndim != 3 or pool.shape[2] != w or w % 128 or v_width > w:
        raise ValueError(
            "latent pool must be [num_pages, page_size, W] with W = %d "
            "a multiple of 128 and v_width <= W, got %r (v_width %d)"
            % (w, tuple(pool.shape), v_width))
    page_size = pool.shape[1]
    table = jnp.asarray(block_table_row, jnp.int32)
    tq, hg, ppb = prefill_blocks(t, h, page_size, table.shape[0],
                                 pool.dtype)
    # whole key blocks: the walk's last block may reach past the table
    table = jnp.pad(table, (0, -table.shape[0] % ppb))
    span = jnp.stack([jnp.asarray(first_pos, jnp.int32),
                      jnp.asarray(n_rows, jnp.int32)])
    q = (q.astype(jnp.float32) * np.float32(scale)).astype(pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t // tq, h // hg),
        in_specs=[pl.BlockSpec((tq, hg, w), lambda i, g, *_: (i, g, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tq, hg, v_width),
                               lambda i, g, *_: (i, g, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_size, w), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            _scratch((tq * hg, v_width)), _scratch((tq * hg, 1)),
            _scratch((tq * hg, 1))])
    # half as much again as the count, in whole MiB, at least the default
    mib = -(-3 * _prefill_vmem_bytes(tq, hg, w, v_width, ppb, page_size,
                                     pool.dtype) // (2 << 20))
    return _pallas_call(
        functools.partial(_prefill_kernel, tq=tq, hg=hg, ppb=ppb,
                          v_width=v_width),
        [table, span, q, pool],
        name="mla_paged_prefill",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, h, v_width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=max(16, mib) << 20))


#: query rows and latent rows a step of the XLA oracle
_REF_QUERIES, _REF_KEYS = 512, 1024


def mla_paged_prefill_reference(q, pool, block_table_row, first_pos, n_rows,
                                v_width, scale):
    """XLA oracle of :func:`mla_paged_prefill`: ``_REF_QUERIES`` queries
    at a time walk blocks of ``_REF_KEYS`` rows up to their last
    position with a running softmax, ``[H, queries, keys]`` float32
    scores a step (the query in the pool's dtype, the scale on the
    scores)."""
    from jax import lax
    t, h, w = q.shape
    page = pool.shape[1]
    block_table_row = jnp.asarray(block_table_row, jnp.int32)
    pages = max(1, min(_REF_KEYS // page, block_table_row.shape[0]))
    keys = pages * page
    table = jnp.pad(block_table_row,
                    (0, -block_table_row.shape[0] % pages))
    rows = next(r for r in range(min(_REF_QUERIES, t), 0, -1) if t % r == 0)

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
                preferred_element_type=jnp.float32) * scale, _NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            e = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
            keep = jnp.exp(m - m_new)
            return (m_new, total * keep + e.sum(-1),
                    acc * keep[..., None] + jnp.einsum(
                        "hqk,kc->hqc", e.astype(kb.dtype), kb[:, :v_width],
                        preferred_element_type=jnp.float32))

        _, total, acc = lax.fori_loop(
            0, (first + rows - 1) // keys + 1, step,
            (jnp.full((h, rows), _NEG_INF, jnp.float32),
             jnp.zeros((h, rows), jnp.float32),
             jnp.zeros((h, rows, v_width), jnp.float32)))
        return acc / total[..., None]

    o = lax.map(one_block, (
        q.reshape(t // rows, rows, h, w).astype(pool.dtype),
        first_pos + jnp.arange(t // rows) * rows))
    o = o.transpose(0, 2, 1, 3).reshape(t, h, v_width)
    return jnp.where((jnp.arange(t) < n_rows)[:, None, None], o, 0.0)
