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
- :func:`dsa_select` keeps each row's ``k`` largest scores: exactly the
  set ``lax.top_k`` keeps, found by the k-th largest score and a
  compaction, where XLA sorts all of a row's keys (70.7 ms at ``[2048,
  32768]``, v5e, PERF.md, PR 32);
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
#: rows a grid cell of :func:`dsa_select`, and the tiles of 128 lanes a
#: step of its passes over them
SELECT_ROWS = 8
SELECT_CHUNK_TILES = 32
_LANE_BITS = 7
_LANES = 1 << _LANE_BITS


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
# the selection: the k-th largest score and a compaction, no sort
# ---------------------------------------------------------------------------

def _select_kernel(tile_ctx_ref, s_ref, ctx_ref, o_ref, keys, *, k, tiles):
    pl = _pl()
    rows = s_ref.shape[0]
    chunk = tiles * _LANES
    int_min = jnp.int32(-2 ** 31)
    tile_ctx = tile_ctx_ref[pl.program_id(0)]
    n_chunks = (tile_ctx + chunk - 1) // chunk
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    out_tiles = [slice(q * _LANES, (q + 1) * _LANES)
                 for q in range(o_ref.shape[1] // _LANES)]

    # a row whose context is at most k keeps 0 .. context-1, and the
    # slots after them hold positions in range all the same
    for q, at in enumerate(out_tiles):
        o_ref[:, at] = lane + q * _LANES

    def lanes_of(j):
        return pl.ds(pl.multiple_of(j * chunk, chunk), chunk)

    def tiles_of(kc):
        return [kc[:, i * _LANES:(i + 1) * _LANES] for i in range(tiles)]

    def count(beats, bound):
        """How many keys of each row ``beats(key, bound)``: int32
        (rows, 128), the same in every lane."""
        def body(j, acc):
            hits = [beats(t, bound).astype(jnp.int32)
                    for t in tiles_of(keys[:, lanes_of(j)])]
            while len(hits) > 1:        # pairwise: no chain of adds
                hits = [a + b for a, b in zip(hits[::2], hits[1::2])] \
                    + hits[len(hits) & ~1:]
            return acc + hits[0]
        acc = jax.lax.fori_loop(0, n_chunks, body,
                                jnp.zeros((rows, _LANES), jnp.int32))
        return jnp.broadcast_to(acc.sum(axis=1, keepdims=True),
                                (rows, _LANES))

    @pl.when(tile_ctx > k)
    def _select():
        ctx = ctx_ref[...]                                   # (rows, 1)

        # int32 keys that order as the float32 scores do (-0.0 under
        # 0.0, as lax.top_k has them); past the context: the least key
        def to_keys(j, _):
            bits = jax.lax.bitcast_convert_type(s_ref[:, lanes_of(j)],
                                                jnp.int32)
            pos = j * chunk + jax.lax.broadcasted_iota(
                jnp.int32, (rows, chunk), 1)
            keys[:, lanes_of(j)] = jnp.where(
                pos < ctx, bits ^ ((bits >> 31) & jnp.int32(0x7fffffff)),
                int_min)
        jax.lax.fori_loop(0, n_chunks, to_keys, None)

        # the k-th largest key, a bit a pass from the top: the largest
        # ``thr`` that at least k keys reach (the least key where the
        # context holds fewer than k)
        def one_bit(b, thr):
            cand = jnp.where(b == 0, jnp.zeros_like(thr),
                             thr | jnp.left_shift(jnp.int32(1), 31 - b))
            return jnp.where(count(jnp.greater_equal, cand) >= k, cand, thr)
        thr = jax.lax.fori_loop(
            0, 32, one_bit, jnp.full((rows, _LANES), int_min, jnp.int32))
        # keys EQUAL to it fill what the keys above it leave of k, from
        # the lowest position up
        ties = k - count(jnp.greater, thr)

        upper = jax.lax.broadcasted_iota(jnp.int32, (_LANES, 2 * _LANES), 0) \
            <= jax.lax.broadcasted_iota(jnp.int32, (_LANES, 2 * _LANES), 1)
        upper = upper.astype(jnp.bfloat16)

        def compact(j, base):
            above, equal = base
            kc = tiles_of(keys[:, lanes_of(j)])
            # a running count inside each tile of 128 lanes, and the
            # tile's total in lanes 128.., in one exact product
            run = jax.lax.dot_general(
                jnp.concatenate(
                    [beats(t, thr).astype(jnp.float32)
                     for beats in (jnp.greater, jnp.equal) for t in kc],
                    0).astype(jnp.bfloat16),
                upper, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32).astype(jnp.int32)
            placed = []
            for i in range(tiles):
                up = run[i * rows:(i + 1) * rows]
                eq = run[(tiles + i) * rows:(tiles + i + 1) * rows]
                taken = jnp.minimum(equal, ties)
                # kept keys up to and with each lane; their first slot
                kept = up[:, :_LANES] - taken \
                    + jnp.minimum(equal + eq[:, :_LANES], ties)
                n_kept = up[:, _LANES:] - taken \
                    + jnp.minimum(equal + eq[:, _LANES:], ties)
                first = above + taken
                turn = first & (_LANES - 1)
                # output lane l takes the tile's kept key number
                # ``nth``: the first lane whose running count reaches it
                nth = (lane - turn) & (_LANES - 1)
                lo = jnp.zeros_like(lane)
                step = _LANES // 2
                while step:
                    seen = jnp.take_along_axis(
                        kept, lo + (step - 1), axis=1,
                        mode="promise_in_bounds")
                    lo = jnp.where(seen <= nth, lo + step, lo)
                    step //= 2
                # (position, tile of the output it lands in or -1)
                placed.append((
                    j * chunk + i * _LANES + lo,
                    jnp.where(nth < n_kept, (first >> _LANE_BITS)
                              + (lane < turn).astype(jnp.int32), -1)))
                above = above + up[:, _LANES:]
                equal = equal + eq[:, _LANES:]
            for q, at in enumerate(out_tiles):
                cur = o_ref[:, at]
                for pos, tile in placed:
                    cur = jnp.where(tile == q, pos, cur)
                o_ref[:, at] = cur
            return above, equal

        zero = jnp.zeros((rows, _LANES), jnp.int32)
        jax.lax.fori_loop(0, n_chunks, compact, (zero, zero))


def dsa_select(scores, context_lens, k):
    """The positions of each row's ``k`` largest scores among its first
    ``context_lens`` keys: int32 [T, k], the SET ``lax.top_k`` gives
    (equal scores go to the lower position), in ascending position.

    - ``scores``: float32 [T, W]; what lies at or past a row's context
      is never compared;
    - ``context_lens``: int32 [T].  A row with at most ``k`` keys in
      context gets ``0 .. context-1``; the slots after them hold
      positions in ``[0, W)`` that mean nothing.

    No sort: the k-th largest score a row is found a bit a pass
    (float32 as int32 keys of the same order), then what lies above it,
    and of what EQUALS it the lowest positions, is compacted into the
    row's k slots.  Both stop at the largest context of a tile of rows.
    """
    return _dsa_select(scores.astype(jnp.float32),
                       jnp.asarray(context_lens, jnp.int32), k=int(k),
                       interpret=_flash._use_interpret())


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _dsa_select(scores, ctx, *, k, interpret):
    pl = _pl()
    from jax.experimental.pallas import tpu as pltpu
    t, w = scores.shape
    if not 0 < k <= w:
        raise ValueError("k must lie in 1 .. %d (the width), got %d" % (w, k))
    tiles = min(SELECT_CHUNK_TILES, -(-w // _LANES))
    chunk = tiles * _LANES
    t_pad, w_pad = -(-t // SELECT_ROWS) * SELECT_ROWS, -(-w // chunk) * chunk
    k_pad = -(-k // _LANES) * _LANES
    scores = jnp.pad(scores, ((0, t_pad - t), (0, w_pad - w)))
    ctx = jnp.pad(jnp.minimum(ctx, w), (0, t_pad - t))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t_pad // SELECT_ROWS,),
        in_specs=[pl.BlockSpec((SELECT_ROWS, w_pad), lambda g, *_: (g, 0)),
                  pl.BlockSpec((SELECT_ROWS, 1), lambda g, *_: (g, 0))],
        out_specs=pl.BlockSpec((SELECT_ROWS, k_pad), lambda g, *_: (g, 0)),
        scratch_shapes=[pltpu.VMEM((SELECT_ROWS, w_pad), jnp.int32)])
    out = _pallas_call(
        functools.partial(_select_kernel, k=k, tiles=tiles),
        [ctx.reshape(-1, SELECT_ROWS).max(axis=1), scores, ctx[:, None]],
        interpret=interpret,
        name="dsa_select",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_pad, k_pad), jnp.int32))
    return out[:t, :k]


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
