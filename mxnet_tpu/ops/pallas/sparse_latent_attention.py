"""Learned sparse latent attention (a lightning indexer over paged keys,
then latent attention over the rows it selected) as Pallas TPU kernels.

A layer of this kind caches two rows a token, in two pools that ONE
block table addresses: the latent row ``[c | k_rope | pad]`` (as
``latent_attention.py``) and the indexer's key (``index_head_dim``
values).  A query first scores every cached token with the indexer,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s]),      s <= t,

keeps the ``index_topk`` largest, and attends over those rows only.

- :func:`dsa_index` computes ``I`` over the PAGED indexer keys: a group
  of query rows a grid cell (one row a slot in decode, a block of rows
  of one slot in a prefill chunk), the slot's pages copied a block at a
  time by the cell itself (the next block's copies run under this
  block's matmul), blocks past the group's context neither read nor
  scored;
- :func:`mla_sparse` is absorbed latent attention of ``N`` query rows,
  each over its own LIST of rows (``[N, K, W]``, the first
  ``n_valid[n]`` count): one row a slot in decode, a block of a
  chunk's rows in a prefill.  It reads ``min(t + 1, K)`` rows a query
  whatever the context.  The list is gathered from the pool BY ROW
  before the kernel (:func:`gather_rows`, one XLA gather): Mosaic
  copies no single row out of a tiled pool (a slice must cover whole
  tiles of 8 rows), and XLA's gather moves a row in 17 ns where a copy
  a row from a one-tile-a-token pool took 37 (v5e, PERF.md, PR 32).
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np

from .flash_attention import _NEG_INF, _TINY, _pallas_call, _pl, _scratch

# the package binds the function to the module's name
_flash = importlib.import_module(__package__ + ".flash_attention")

#: keys a block of :func:`dsa_index` (whole pages)
INDEX_BLOCK_KEYS = 1024
#: rows of a list a block of :func:`mla_sparse`
SPARSE_BLOCK_ROWS = 512


def _prec(operand):
    """The MXU's own product for bfloat16 operands, whatever the
    process's default says; float32 operands (the CPU tests) in full."""
    return jax.lax.Precision.HIGHEST if operand.dtype == jnp.float32 \
        else jax.lax.Precision.DEFAULT


# ---------------------------------------------------------------------------
# index scores over paged keys
# ---------------------------------------------------------------------------

def _index_kernel(bt_ref, tbl_ref, ctx_ref, pos_ref, q_ref, w_ref, pool_ref,
                  o_ref, kbuf, sem, *, page_size, ppb, rows, heads):
    pl = _pl()
    from jax.experimental.pallas import tpu as pltpu
    g, j = pl.program_id(0), pl.program_id(1)
    ctx, tbl = ctx_ref[g], tbl_ref[g]
    bk = ppb * page_size

    def copies(block, buf):
        return [pltpu.make_async_copy(
            pool_ref.at[bt_ref[tbl, block * ppb + p]], kbuf.at[buf, p],
            sem.at[buf]) for p in range(ppb)]

    @pl.when((j == 0) & (ctx > 0))
    def _first():
        for c in copies(0, 0):
            c.start()

    @pl.when((j + 1) * bk < ctx)
    def _next():
        for c in copies(j + 1, (j + 1) % 2):
            c.start()

    @pl.when(j * bk >= ctx)
    def _empty():
        o_ref[0] = jnp.full(o_ref.shape[1:], _NEG_INF, o_ref.dtype)

    @pl.when(j * bk < ctx)
    def _score():
        buf = j % 2
        for c in copies(j, buf):
            c.wait()
        k = kbuf[buf].reshape(bk, kbuf.shape[-1])
        s = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())), precision=_prec(k),
            preferred_element_type=jnp.float32)          # (rows * heads, bk)
        s = jnp.maximum(s, 0.0) * w_ref[0]
        s = s.reshape(rows, heads, bk).sum(axis=1)       # (rows, bk)
        key = j * bk + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
        row = pos_ref[g] + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 0)
        o_ref[0] = jnp.where((key <= row) & (key < ctx), s, _NEG_INF)


def dsa_index(q, w, pool, block_tables, table_of, context_lens, first_pos):
    """Index scores of ``G`` groups of ``R`` query rows over paged keys.

    - ``q``: [G, R, N, D] the indexer's queries (N heads), in the pool's
      dtype; ``w``: float32 [G, R, N] the heads' weights;
    - ``pool``: [num_pages, page_size, D] the indexer's keys;
    - ``block_tables``: int32 [tables, max_pages]; ``table_of``: int32
      [G], the table row of each group (decode: one a slot; a prefill
      chunk: every group the slot's);
    - ``context_lens``: int32 [G] keys in context (0: nothing scored);
    - ``first_pos``: int32 [G] the position of the group's first row;
      row ``r`` sees keys ``s <= first_pos + r``.

    Returns float32 [G, R, max_pages * page_size]; ``-1e30`` where a key
    is masked, past the context or never read.
    """
    return _dsa_index(q, w, pool, jnp.asarray(block_tables, jnp.int32),
                      jnp.asarray(table_of, jnp.int32),
                      jnp.asarray(context_lens, jnp.int32),
                      jnp.asarray(first_pos, jnp.int32),
                      interpret=_flash._use_interpret())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _dsa_index(q, w, pool, bt, table_of, ctx, first_pos, *, interpret):
    pl = _pl()
    from jax.experimental.pallas import tpu as pltpu
    n_g, rows, heads, d = q.shape
    page_size = pool.shape[1]
    max_pages = bt.shape[1]
    ppb = max(1, min(INDEX_BLOCK_KEYS // page_size, max_pages))
    while max_pages % ppb:
        ppb -= 1
    bk = ppb * page_size
    q2 = q.reshape(n_g, rows * heads, d).astype(pool.dtype)
    w2 = w.reshape(n_g, rows * heads, 1).astype(jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_g, max_pages // ppb),
        in_specs=[
            pl.BlockSpec((1, rows * heads, d), lambda g, j, *_: (g, 0, 0)),
            pl.BlockSpec((1, rows * heads, 1), lambda g, j, *_: (g, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, rows, bk), lambda g, j, *_: (g, 0, j)),
        scratch_shapes=[pltpu.VMEM((2, ppb, page_size, d), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))])
    return _pallas_call(
        functools.partial(_index_kernel, page_size=page_size, ppb=ppb,
                          rows=rows, heads=heads),
        [bt, table_of, ctx, first_pos, q2, w2, pool],
        interpret=interpret,
        name="dsa_index",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (n_g, rows, max_pages * page_size), jnp.float32),
        # a block's copies are started by the block before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")))


def dsa_index_reference(q, w, pool, block_tables, table_of, context_lens,
                        first_pos):
    """jnp oracle of :func:`dsa_index`."""
    n_g, rows = q.shape[:2]
    bt = jnp.asarray(block_tables, jnp.int32)[jnp.asarray(table_of)]
    keys = pool[bt].astype(jnp.float32).reshape(n_g, -1, pool.shape[2])
    s = jnp.einsum("grnd,gsd->grns",
                   q.astype(pool.dtype).astype(jnp.float32), keys,
                   precision="highest")
    s = (jnp.maximum(s, 0.0) * w.astype(jnp.float32)[..., None]).sum(2)
    key = jnp.arange(keys.shape[1])[None, None, :]
    row = jnp.asarray(first_pos)[:, None, None] \
        + jnp.arange(rows)[None, :, None]
    ok = (key <= row) & (key < jnp.asarray(context_lens)[:, None, None])
    return jnp.where(ok, s, _NEG_INF)


# ---------------------------------------------------------------------------
# latent attention over a list of rows
# ---------------------------------------------------------------------------

def gather_rows(pool, block_table_rows, positions):
    """The rows at logical ``positions`` int32 [N, K] of the sequences
    whose block-table rows are ``block_table_rows`` int32 [N, max_pages]
    (or [max_pages] for all), from ``pool`` [num_pages, page_size, W]:
    ``[N, K, W]``, by row."""
    page_size, w = pool.shape[1:]
    pos = jnp.asarray(positions, jnp.int32)
    bt = jnp.asarray(block_table_rows, jnp.int32)
    page = bt[pos // page_size] if bt.ndim == 1 \
        else jnp.take_along_axis(bt, pos // page_size, axis=1)
    return pool.reshape(-1, w)[page * page_size + pos % page_size]


def _sparse_kernel(n_ref, q_ref, rows_ref, o_ref, o_acc, m_acc, l_acc, *,
                   block_rows, v_width, scale):
    pl = _pl()
    s, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[s]

    @pl.when(j == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, _NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    @pl.when(j * block_rows < n)
    def _accumulate():
        rows = rows_ref[0]                                  # (B, W)
        st = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())), precision=_prec(rows),
            preferred_element_type=jnp.float32) * scale     # (H, B)
        mask = j * block_rows + jax.lax.broadcasted_iota(
            jnp.int32, st.shape, 1) < n
        st = jnp.where(mask, st, _NEG_INF)
        m_prev = m_acc[...]
        m_new = jnp.maximum(m_prev, st.max(axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(st - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_acc[...] = l_acc[...] * corr + p.sum(axis=-1, keepdims=True)
        # the value is a lane slice of the row the scores just read
        o_acc[...] = o_acc[...] * corr + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :v_width],
            (((1,), (0,)), ((), ())), precision=_prec(rows),
            preferred_element_type=jnp.float32)
        m_acc[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        # a query with no valid row never accumulated: emit zeros
        o_ref[0] = (o_acc[...] / jnp.maximum(l_acc[...], _TINY)) \
            .astype(o_ref.dtype)


def mla_sparse(q, rows, n_valid, v_width, scale):
    """Absorbed-form latent attention of ``N`` query rows, each over
    the rows selected for it.

    - ``q``: [N, H, W] ``[q_lat | q_rope | zeros]`` a head (cast to the
      rows' dtype: the MXU's operand type);
    - ``rows``: [N, K, W] the selected rows ``[c | k_rope | zeros]``
      (:func:`gather_rows`); the first ``n_valid[n]`` of query ``n``
      count (0: an empty slot or a pad row, zeros out);
    - ``v_width``: the latent's width (a row's leading lanes are the
      value); ``scale``: the softmax scale of the unabsorbed head.

    Returns ``o_lat`` float32 [N, H, v_width].  Online softmax over
    blocks of the list; blocks past ``n_valid`` are neither fetched
    again nor scored.
    """
    return _mla_sparse(q.astype(rows.dtype), rows,
                       jnp.asarray(n_valid, jnp.int32), v_width=v_width,
                       scale=float(scale),
                       interpret=_flash._use_interpret())


@functools.partial(jax.jit, static_argnames=("v_width", "scale",
                                             "interpret"))
def _mla_sparse(q, rows, n_valid, *, v_width, scale, interpret):
    pl = _pl()
    from jax.experimental.pallas import tpu as pltpu
    n_q, h, w = q.shape
    k = rows.shape[1]
    block_rows = min(SPARSE_BLOCK_ROWS, k)
    if rows.shape != (n_q, k, w) or w % 128 or k % block_rows:
        raise ValueError(
            "rows must be [N, K, W] = [%d, K, %d] with W a multiple of "
            "128 and K a multiple of %d, got %r"
            % (n_q, w, block_rows, tuple(rows.shape)))

    def block_of(s, j, n):
        # a block past the valid rows names the last valid one: the
        # pipeline fetches nothing for it
        return (s, jnp.minimum(j, jnp.maximum(n[s] - 1, 0) // block_rows),
                0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_q, k // block_rows),
        in_specs=[pl.BlockSpec((1, h, w), lambda s, j, n: (s, 0, 0)),
                  pl.BlockSpec((1, block_rows, w), block_of)],
        out_specs=pl.BlockSpec((1, h, v_width), lambda s, j, n: (s, 0, 0)),
        scratch_shapes=[_scratch((h, v_width)), _scratch((h, 1)),
                        _scratch((h, 1))])
    return _pallas_call(
        functools.partial(_sparse_kernel, block_rows=block_rows,
                          v_width=v_width, scale=np.float32(scale)),
        [n_valid, q, rows],
        interpret=interpret,
        name="mla_sparse",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_q, h, v_width), jnp.float32))


def mla_sparse_reference(q, rows, n_valid, v_width, scale):
    """jnp oracle of :func:`mla_sparse`."""
    q = q.astype(rows.dtype).astype(jnp.float32)
    rows = rows.astype(jnp.float32)
    n = jnp.asarray(n_valid, jnp.int32)
    st = jnp.einsum("shw,skw->shk", q, rows, precision="highest") * scale
    mask = jnp.arange(rows.shape[1])[None, None, :] < n[:, None, None]
    p = jax.nn.softmax(jnp.where(mask, st, _NEG_INF), axis=-1)
    p = jnp.where(n[:, None, None] > 0, p, 0.0)
    return jnp.einsum("shk,skv->shv", p, rows[..., :v_width],
                      precision="highest")
