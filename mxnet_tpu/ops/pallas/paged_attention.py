"""Ragged paged attention as a Pallas TPU kernel (decode path).

The serving runtime (mxnet_tpu/serving/) keeps every resident sequence's
KV history in fixed-size PAGES drawn from one shared pool
(``k_pages``/``v_pages``: [num_pages, page_size, K_kv * D], a token's
KV heads side by side on the minor axis) with a per-sequence BLOCK
TABLE mapping logical page index -> physical page id — the
vLLM/"Ragged Paged Attention" memory model (PAPERS.md, arXiv
2604.15464) that lets mixed-length sequences share one kernel launch
with zero padding waste beyond the last partial page.

Why the pools are stored flat: the TPU tiles the two minor dimensions
(8 sublanes x 128 lanes for 32-bit, 16 x 128 for bf16, 32 x 128 for
int8).  With ``K_kv * D`` a multiple of 128 the default device layout of
``[num_pages, page_size, K_kv * D]`` is row-major with no lane padding:
the layout the chip keeps between programs, the one the ``kv_write``
scatters and page gathers address, and the one this kernel's page block
reads are the same, and a donated pool is updated in place.  A pool
whose minor dimension is one head of ``D`` 64 gets a pages-minor-most
layout instead (half of every lane tile would be padding), and every
program then converts each whole pool to row-major and back around its
scatter and this kernel.  Where ``K_kv * D`` is NOT
a multiple of 128 (multi-query attention at ``D`` 64) the flat shape is
still correct, but the compiler again keeps the pool pages-minor and
the layout copies return; there is no second path for that case.

Kernel shape (one launch serves ALL resident slots, any lengths; the
Pallas call is named ``paged_decode``):

- grid ``(num_slots,)``: a cell is one slot, and loops over the slot's
  LIVE **blocks of ``P`` consecutive logical pages**, online-softmax
  state (o, m, l) in VMEM scratch.  Why a block and not a page: one
  16-token page fills a sixteenth of a vector register and an eighth of
  an MXU pass, so a cell of one page spent ~0.9 us on 0.08 us of bytes,
  and a grid of ``slots x max_pages`` cells cost ~0.1 us each even when
  skipped (PERF.md section 6, PR 25 and PR 27).  ``P`` comes from the
  shapes alone (:func:`pages_per_block`): 256 tokens of keys (two MXU
  passes of 128 columns), fewer where two double-buffered blocks of K
  and of V would outgrow a quarter of the default scoped VMEM;
- the pools stay in HBM (``memory_space=ANY``).  The block table, the
  per-slot context lengths and a three-row launch plan arrive via scalar
  prefetch (``pltpu.PrefetchScalarGridSpec``), and the cell copies each
  live page of a block to its place in a ``[2, P, page, K_kv * D]``
  buffer (``make_async_copy``): the logical->physical translation is
  the copy's address, no per-sequence contiguous KV ever exists in HBM.
  Copies run one block ahead of the scores, across slots too: the cell
  that ends a slot starts the first block of the next LIVE slot, which
  is why the grid axis is sequential (``arbitrary``) and the wrapper
  hands every cell the count of blocks before it (buffer parity) and
  its successor.  Pages past a slot's context are never read: an entry
  of the block table there may hold anything;
- **all heads of a group in one matmul pair a block.**  The slot's
  query rows are laid out once as a block-diagonal matrix: the row of
  head ``kv`` holds its ``D`` values in lanes ``kv * D:(kv + 1) * D``,
  the head's own lanes of a page row, and zeros elsewhere.  ``Q_bd x
  K_blk^T`` then scores every head against the block's flat rows as
  they lie in the pool: ``(rows, T)`` scores with heads on sublanes and
  the block's ``T`` tokens lane-dense, softmax along the lanes,
  ``P x V_blk`` into a ``(rows, K_kv * D)`` accumulator of which each
  head's own ``D`` lanes are kept at the end.  K and V go to the MXU as
  stored; with bf16 (or int8) pools the fp32 query and softmax weights
  go as THREE bf16 pieces stacked on the row axis, which carry all 24
  bits, so every product is exact and the sums are fp32 (3 x 16 = 48
  rows at decode: they ride under one K tile's load); fp32 pools
  multiply in full fp32 (``Precision.HIGHEST``);
- **grouped-query attention and verify**: KV head ``kv`` serves the
  ``n_q * g`` query rows of its group (``n_q`` positions, ``g = H //
  K_kv`` heads), row ``r * hb + kv`` of a group of ``hb`` KV heads.
  :func:`_heads_per_group` takes all ``K_kv`` heads in one matmul while
  ``pieces * hb * n_q * g`` rows stay under an MXU tile (decode), else
  as many whole 128-lane tiles of heads as do (verify at ``n_q`` 5:
  eight heads a matmul): one algorithm and one kernel body, its
  parameters from the static shapes (``n_q``, ``g``, width, dtype);
- blocks past a slot's longest context are not entered (raggedness
  costs control flow, not FLOPs); the last live block is masked per
  position, and its V rows past the context are cleared in VMEM, since
  a zero weight does not silence a stale NaN.

A slot with ``context_len == 0`` (an empty serving slot) attends to
nothing and emits zeros.  Off-TPU the same kernel runs under the Pallas
interpreter, so CPU tests exercise the identical code path.

**Quantized pages** (ISSUE 20): with ``k_scales``/``v_scales`` given
(fp32 ``[num_pages, K_kv]`` — one absmax scale per page per KV head)
the pools may hold int8 payloads, so HBM moves a quarter of the fp32
bytes.  No page is dequantized: an int8 value is a bf16 value, the
payload goes to the MXU as it is, and the scale multiplies the
``(rows, T)`` scores and the softmax weights instead (``q . (k_i8 * s)
= (q . k_i8) * s``), fp32 throughout.  The slot's scale rows are
gathered through the block table before the launch and reach the cell
block by block, a score row a row.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np

from .flash_attention import (_NEG_INF, _TINY, _pallas_call, _pl,
                              _scratch)

# the module itself (the package binds the function to this name): where
# tests steer ``_use_interpret``
_flash = importlib.import_module(__package__ + ".flash_attention")


#: what the cell's two double-buffered K and V blocks may hold of VMEM
#: (a quarter of Mosaic's default 16 MiB of scoped VMEM), and the most
#: tokens a block covers (two MXU passes of 128 key columns)
_BLOCK_VMEM_BYTES = 4 * 1024 * 1024
_BLOCK_TOKENS = 256
#: query rows a block-diagonal score matmul may stream against one
#: loaded K tile: up to here the rows ride under the tile's load
_MXU_ROWS = 128


def pages_per_block(page_size, width, dtype, max_pages=None):
    """``P``: the consecutive logical pages one step of the kernel reads
    and scores at once.  As many as give ``_BLOCK_TOKENS`` keys, fewer
    where two double-buffered blocks of K and of V (``4 * P *
    page_size * width`` values) would outgrow ``_BLOCK_VMEM_BYTES``,
    never more than a sequence has pages.  Derived from shapes alone
    (the serving engine's ``serving.paged.block_fill`` gauge asks here
    too)."""
    row_bytes = 4 * page_size * width * np.dtype(dtype).itemsize
    p = max(1, min(_BLOCK_TOKENS // page_size or 1,
                   _BLOCK_VMEM_BYTES // row_bytes))
    return p if max_pages is None else min(p, max_pages)


def _heads_per_group(n_kv, rows, d, pieces):
    """``hb``: the KV heads one block-diagonal matmul scores together.
    All of them while their query rows (``pieces * hb * rows``) ride
    under one K tile's load (decode: 48 rows for 16 heads), else the
    most that do and whose lanes ``hb * d`` are whole 128-lane tiles."""
    fits = [hb for hb in range(n_kv, 0, -1)
            if n_kv % hb == 0 and pieces * hb * rows <= _MXU_ROWS
            and (hb == n_kv or (hb * d) % 128 == 0)]
    if fits:
        return fits[0]
    whole = [hb for hb in range(1, n_kv + 1)
             if n_kv % hb == 0 and (hb * d) % 128 == 0]
    return whole[0] if whole else n_kv


def _split(x, pieces, dtype):
    """``x`` (fp32) as ``pieces`` addends of ``dtype`` stacked on the
    row axis: three bf16 pieces carry all 24 bits of an fp32 value, so
    the MXU multiplies them with bf16 keys exactly and their fp32 sum is
    the fp32 product.  One piece: ``x`` itself."""
    if pieces == 1:
        return x.astype(dtype)
    parts = []
    for _ in range(pieces):
        part = x.astype(dtype)
        parts.append(part)
        x = x - part.astype(jnp.float32)
    return jnp.concatenate(parts, axis=0)


def _unsplit(x, pieces):
    m = x.shape[0] // pieces
    out = x[:m]
    for i in range(1, pieces):
        out = out + x[i * m:(i + 1) * m]
    return out


def _paged_kernel(ctx_ref, bt_ref, plan_ref, q_ref, k_hbm, v_hbm, *rest,
                  page_size, ppb, n_kv, hb, g, n_q, pieces, scale,
                  quantized):
    """One slot a grid cell: loop over the slot's LIVE blocks of
    ``ppb`` pages, online softmax across them, all heads of a group in
    one block-diagonal matmul pair a block (see the module docstring).

    ``ctx_ref`` (``[S, n_q]``), ``bt_ref`` and ``plan_ref`` (``[3, S]``:
    the running count of live blocks before the slot, the next live
    slot, the slot's live pages) are scalar-prefetched into SMEM; the pools stay in HBM and the
    cell copies page by page into ``kbuf`` / ``vbuf`` (``[2, ppb, page,
    W]``), one buffer ahead, the next live slot's first block included.
    Query row ``r = i * g + gi`` of group-local head ``kv`` is row
    ``r * hb + kv`` of the group's scores; its context length is
    position ``i``'s.  A row whose context ends before a block
    multiplies its weights by zero there; rows with ``ctx == 0`` never
    accumulate and emit zeros."""
    if quantized:
        ks_ref, vs_ref, o_ref, kbuf, vbuf, sem, qbd, o_acc, m_acc, l_acc \
            = rest
    else:
        o_ref, kbuf, vbuf, sem, qbd, o_acc, m_acc, l_acc = rest
    pl = _pl()
    from jax.experimental.pallas import tpu as pltpu
    s = pl.program_id(0)
    s_n = pl.num_programs(0)
    rows = n_q * g
    m_rows = hb * rows
    d = q_ref.shape[-1] // n_kv
    lanes = hb * d
    n_grp = n_kv // hb
    t_blk = ppb * page_size
    mxu = qbd.dtype
    # fp32 operands multiply in full fp32; bf16 pieces are exact as
    # they are, whatever the ambient matmul precision says
    prec = (jax.lax.Precision.HIGHEST if mxu == jnp.float32
            else jax.lax.Precision.DEFAULT)

    def copies(slot, j, buf, wait):
        """Start (or wait for) the copies of block ``j`` of ``slot``:
        its live pages only."""
        def one(i, carry):
            page = bt_ref[slot, j * ppb + i]
            for c, (hbm, vm) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                cp = pltpu.make_async_copy(hbm.at[page], vm.at[buf, i],
                                           sem.at[buf, c])
                cp.wait() if wait else cp.start()
            return carry
        jax.lax.fori_loop(
            0, jnp.minimum(plan_ref[2, slot] - j * ppb, ppb), one, 0)

    n_blk = (plan_ref[2, s] + ppb - 1) // ppb
    ctxs = [ctx_ref[s, i] for i in range(n_q)]
    ctx_max = functools.reduce(jnp.maximum, ctxs)

    @pl.when(n_blk == 0)
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_blk > 0)
    def _slot():
        first = plan_ref[0, s]

        @pl.when(first == 0)
        def _prime():
            copies(s, 0, 0, wait=False)

        # which query row and which head of the group a score row is,
        # without a division: row = r * hb + kv
        row = jax.lax.broadcasted_iota(jnp.int32, (m_rows, 1), 0)
        r_of = jnp.zeros_like(row)
        for r in range(1, rows):
            r_of = r_of + (row >= r * hb).astype(jnp.int32)
        kv_of = row - hb * r_of
        lane = jax.lax.broadcasted_iota(jnp.int32, (m_rows, lanes), 1)
        diag = (lane >= kv_of * d) & (lane < (kv_of + 1) * d)
        ctx_rows = jnp.zeros_like(row) + ctxs[0]
        for i in range(1, n_q):
            ctx_rows = jnp.where(row >= i * g * hb, ctxs[i], ctx_rows)

        # the block-diagonal query of each group: head kv's row holds
        # its D values in the head's own lanes and zeros elsewhere, so
        # ONE matmul against the block's flat K rows scores every head
        for grp in range(n_grp):
            qg = q_ref[0, :, grp * lanes:(grp + 1) * lanes] \
                .astype(jnp.float32) * scale               # (rows, lanes)
            wide = jnp.broadcast_to(qg[0:1], (m_rows, lanes))
            for r in range(1, rows):
                wide = jnp.where(r_of == r, qg[r:r + 1], wide)
            qbd[grp] = _split(jnp.where(diag, wide, 0.0), pieces, mxu)
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, _NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

        def block(j, carry):
            buf = (first + j) % 2

            @pl.when(j + 1 < n_blk)
            def _ahead():
                copies(s, j + 1, 1 - buf, wait=False)

            @pl.when(j + 1 == n_blk)
            def _next_slot():
                nxt = plan_ref[1, s]

                @pl.when(nxt < s_n)
                def _():
                    copies(nxt, 0, 1 - buf, wait=False)

            copies(s, j, buf, wait=True)

            # rows of the block past the slot's longest context hold
            # whatever the page's last owner or an earlier block left:
            # a zero weight does not silence a NaN, so V is cleared there
            valid = ctx_max - j * t_blk

            @pl.when(valid < t_blk)
            def _clear_tail():
                pg = valid // page_size
                edge = vbuf[buf, pg].astype(jnp.float32)
                keep = jax.lax.broadcasted_iota(
                    jnp.int32, edge.shape, 0) < valid - pg * page_size
                vbuf[buf, pg] = jnp.where(keep, edge, 0.0) \
                    .astype(vbuf.dtype)

                def zero(i, c):
                    vbuf[buf, i] = jnp.zeros(vbuf.shape[2:], vbuf.dtype)
                    return c
                jax.lax.fori_loop(pg + 1, ppb, zero, 0)

            tok = jax.lax.broadcasted_iota(jnp.int32, (m_rows, t_blk), 1)
            mask = j * t_blk + tok < ctx_rows
            if quantized:
                def spread(sc):
                    """(m_rows, ppb) page scales -> (m_rows, t_blk)."""
                    out = jnp.broadcast_to(sc[:, ppb - 1:ppb],
                                           (m_rows, t_blk))
                    for i in range(ppb - 2, -1, -1):
                        out = jnp.where(tok < (i + 1) * page_size,
                                        sc[:, i:i + 1], out)
                    return out
            for grp in range(n_grp):
                sl = slice(grp * lanes, (grp + 1) * lanes)
                k = kbuf[buf, :, :, sl]
                v = vbuf[buf, :, :, sl]
                if k.dtype != mxu:
                    # int8 payloads: every value is a bf16 value
                    k = k.astype(jnp.float32)
                    v = v.astype(jnp.float32)
                k = k.reshape(t_blk, lanes).astype(mxu)
                v = v.reshape(t_blk, lanes).astype(mxu)
                st = _unsplit(jax.lax.dot_general(
                    qbd[grp], k, (((1,), (1,)), ((), ())),
                    precision=prec,
                    preferred_element_type=jnp.float32), pieces)
                if quantized:
                    # q . (k_i8 * s) = (q . k_i8) * s
                    st = st * spread(ks_ref[0, j, grp])
                st = jnp.where(mask, st, _NEG_INF)       # (m_rows, t_blk)
                m_prev = m_acc[grp]
                m_new = jnp.maximum(m_prev,
                                    st.max(axis=-1, keepdims=True))
                # a row with no key yet sits at m == -1e30, where
                # exp(st - m) would be 1: zero the masked weights
                p = jnp.where(mask, jnp.exp(st - m_new), 0.0)
                corr = jnp.exp(m_prev - m_new)
                l_acc[grp] = l_acc[grp] * corr \
                    + p.sum(axis=-1, keepdims=True)
                if quantized:
                    # (p * s) . v_i8 = p . (v_i8 * s); a masked page's
                    # scale may be anything
                    p = jnp.where(mask, p * spread(vs_ref[0, j, grp]), 0.0)
                pv = _unsplit(jax.lax.dot_general(
                    _split(p, pieces, mxu), v, (((1,), (0,)), ((), ())),
                    precision=prec,
                    preferred_element_type=jnp.float32), pieces)
                o_acc[grp] = o_acc[grp] * corr + pv     # (m_rows, lanes)
                m_acc[grp] = m_new
            return carry

        jax.lax.fori_loop(0, n_blk, block, 0)

        # head kv's output is its own D lanes of its own row: the
        # diagonal blocks, gathered back into one flat row a query row.
        # A row that never accumulated (ctx == 0) has l == 0: zeros
        for grp in range(n_grp):
            o = jnp.where(diag, o_acc[grp]
                          / jnp.maximum(l_acc[grp], _TINY), 0.0)
            for r in range(rows):
                o_r = o if rows == 1 else jnp.where(r_of == r, o, 0.0)
                o_ref[0, r:r + 1, grp * lanes:(grp + 1) * lanes] = \
                    o_r.sum(axis=0, keepdims=True).astype(o_ref.dtype)


def _kv_heads(k_pages, h, d):
    """``K_kv`` of a flat ``[num_pages, page_size, K_kv * D]`` pool read
    by ``h`` query heads of size ``d``."""
    if k_pages.ndim != 3 or k_pages.shape[2] % d:
        raise ValueError(
            "page pools must be [num_pages, page_size, K_kv * D] with "
            "D = %d, got %r" % (d, tuple(k_pages.shape)))
    n_kv = k_pages.shape[2] // d
    if h % n_kv:
        raise ValueError(
            "query heads (%d) must be a multiple of KV heads (%d)"
            % (h, n_kv))
    return n_kv


def _check_scales(k_pages, n_kv, k_scales, v_scales):
    """Both scale pools or neither; shape must be [num_pages, K_kv]."""
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be given together")
    if k_scales is None:
        return False
    want = (k_pages.shape[0], n_kv)
    for name, s in (("k_scales", k_scales), ("v_scales", v_scales)):
        if tuple(s.shape) != want:
            raise ValueError(
                "%s must be [num_pages, K_kv] = %r, got %r"
                % (name, want, tuple(s.shape)))
    return True


def paged_attention_multi(q, k_pages, v_pages, block_tables,
                          context_lens, scale=None, k_scales=None,
                          v_scales=None):
    """Paged attention for ``G`` query positions per slot in ONE kernel
    launch (``G == 1`` is the decode step, see :func:`paged_attention`;
    ``G > 1`` is speculative verification).

    - ``q``: [S, G, H, D] — G query positions per slot (the last
      emitted token plus the draft tokens, already scattered into the
      pages this step);
    - ``k_pages``/``v_pages``: [num_pages, page_size, K_kv * D] — the
      shared physical page pools, KV head ``kv`` of a token in lanes
      ``kv * D:(kv + 1) * D`` (page 0 is the serving allocator's
      scratch page, never referenced by an in-range block-table entry).
      ``K_kv`` (the pool's width over ``D``) must divide ``H``; each KV
      head serves a contiguous group of ``H // K_kv`` query heads (GQA;
      ``K_kv == H`` is classic multi-head, ``K_kv == 1`` multi-query);
    - ``block_tables``: int32 [S, max_pages_per_seq] — logical page j of
      slot s lives in physical page ``block_tables[s, j]``;
    - ``context_lens``: int32 [S, G] — per-POSITION context length
      (query ``i`` of slot ``s`` attends to positions
      ``< context_lens[s, i]``; 0 masks the row to zeros — inactive
      slots and rows past the slot's draft length);
    - ``k_scales``/``v_scales``: optional fp32 [num_pages, K_kv] —
      per-page-per-KV-head dequant scales for quantized (int8) pools;
      the kernel multiplies a block's scores and softmax weights by
      them, never the pages.

    Returns [S, G, H, D] in ``q``'s dtype.  Raggedness is free of
    FLOPs and of bytes: pages past a slot's longest context are neither
    read nor scored (their block-table entries may hold anything), the
    last live block is masked per position.  No argument chooses the
    kernel's shape: pages a block, the MXU operand type and the heads a
    matmul follow from the operands' shapes and dtypes.
    """
    s_n, n_q, h, d = q.shape
    page_size, width = k_pages.shape[1:]
    n_kv = _kv_heads(k_pages, h, d)
    _check_scales(k_pages, n_kv, k_scales, v_scales)
    ctx = jnp.asarray(context_lens, jnp.int32)
    if ctx.shape != (s_n, n_q):
        raise ValueError(
            "context_lens must be [S, G] = %r, got %r"
            % ((s_n, n_q), tuple(ctx.shape)))
    return _paged_decode(
        q, k_pages, v_pages, jnp.asarray(block_tables, jnp.int32), ctx,
        k_scales, v_scales,
        scale=float(d ** -0.5 if scale is None else scale),
        ppb=pages_per_block(page_size, width, k_pages.dtype,
                            block_tables.shape[1]),
        interpret=_flash._use_interpret())


@functools.partial(jax.jit, static_argnames=("scale", "ppb", "interpret"))
def _paged_decode(q, k_pages, v_pages, bt, ctx, k_scales, v_scales, *,
                  scale, ppb, interpret):
    """The launch behind :func:`paged_attention_multi`, a function of
    its own to the tracer: a model calls it once a layer with the same
    shapes, and the kernel is traced and lowered once a program, not
    once a layer (a third of a serving program's build time).  What
    decides the kernel's code besides the shapes is a static argument
    (pages a block, interpreter or Mosaic)."""
    pl = _pl()
    from jax.experimental.pallas import tpu as pltpu
    s_n, n_q, h, d = q.shape
    page_size, width = k_pages.shape[1:]
    n_kv = width // d
    g = h // n_kv
    rows = n_q * g
    quantized = k_scales is not None
    max_pages = bt.shape[1]

    # everything below is chosen from the shapes: MXU operand type and
    # its pieces, KV heads a block-diagonal group
    n_blocks = -(-max_pages // ppb)
    mxu = jnp.float32 if k_pages.dtype == jnp.float32 else jnp.bfloat16
    pieces = 1 if mxu == jnp.float32 else 3
    hb = _heads_per_group(n_kv, rows, d, pieces)
    n_grp, m_rows, lanes = n_kv // hb, hb * rows, hb * d

    # the cells run in slot order and each starts its successor's first
    # copies: how many live blocks ran before a slot (the parity of its
    # first buffer; 0 marks the first live slot, which starts its own),
    # which slot is live next (S: none), and the slot's live pages
    live = jnp.minimum(-(-ctx.max(axis=1) // page_size), max_pages)
    blocks = -(-live // ppb)
    slot_ids = jnp.arange(s_n, dtype=jnp.int32)
    later = jnp.where(blocks > 0, slot_ids, s_n)
    nxt = jnp.concatenate([
        jax.lax.cummin(later, reverse=True)[1:],
        jnp.full((1,), s_n, jnp.int32)])
    plan = jnp.stack([jnp.cumsum(blocks) - blocks, nxt, live]) \
        .astype(jnp.int32)

    # query rows position-major, a KV head's lanes side by side like a
    # page row: [S, n_q * g, K_kv * D] (a reshape when g == 1)
    qr = q.reshape(s_n, n_q, n_kv, g, d).transpose(0, 1, 3, 2, 4) \
        .reshape(s_n, rows, width)
    row_spec = pl.BlockSpec((1, rows, width),
                            lambda s, c, b, p: (s, 0, 0))
    hbm_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [row_spec, hbm_spec, hbm_spec]
    args = [ctx, bt, plan, qr, k_pages, v_pages]
    if quantized:
        # the scales of a slot's pages, gathered through the block
        # table and laid out as the cell multiplies them: block by
        # block, a score row a row, the block's pages on the lanes
        # (entries past the context gather whatever the table holds
        # there; the cell masks those positions)
        def by_block(scales):
            sc = scales[jnp.clip(bt, 0, scales.shape[0] - 1)]
            sc = jnp.pad(sc, ((0, 0), (0, n_blocks * ppb - max_pages),
                              (0, 0)))
            sc = sc.reshape(s_n, n_blocks, ppb, n_grp, 1, hb) \
                .transpose(0, 1, 3, 4, 5, 2)
            return jnp.broadcast_to(
                sc, (s_n, n_blocks, n_grp, rows, hb, ppb)) \
                .reshape(s_n, n_blocks, n_grp, m_rows, ppb)
        scale_spec = pl.BlockSpec(
            (1, n_blocks, n_grp, m_rows, ppb),
            lambda s, c, b, p: (s, 0, 0, 0, 0))
        in_specs += [scale_spec, scale_spec]
        args += [by_block(k_scales), by_block(v_scales)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s_n,),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_size, width), k_pages.dtype),
            pltpu.VMEM((2, ppb, page_size, width), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((n_grp, pieces * m_rows, lanes), mxu),
            _scratch((n_grp, m_rows, lanes)),
            _scratch((n_grp, m_rows, 1)),
            _scratch((n_grp, m_rows, 1))],
    )
    out = _pallas_call(
        functools.partial(_paged_kernel, page_size=page_size, ppb=ppb,
                          n_kv=n_kv, hb=hb, g=g, n_q=n_q, pieces=pieces,
                          scale=np.float32(scale), quantized=quantized),
        args,
        interpret=interpret,
        name="paged_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_n, rows, width), q.dtype),
        # a cell waits for copies its predecessor started
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)))
    return out.reshape(s_n, n_q, g, n_kv, d).transpose(0, 1, 3, 2, 4) \
        .reshape(s_n, n_q, h, d)


def paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                    scale=None, k_scales=None, v_scales=None):
    """Decode attention for every resident slot in ONE kernel launch:
    :func:`paged_attention_multi` at one query position per slot.

    - ``q``: [S, H, D] — the current token's query per slot;
    - ``context_lens``: int32 [S] — tokens of history per slot (0 for an
      empty slot, whose output row is zeros).

    Returns [S, H, D] in ``q``'s dtype.
    """
    ctx = jnp.asarray(context_lens, jnp.int32)
    return paged_attention_multi(
        q[:, None], k_pages, v_pages, block_tables, ctx[:, None],
        scale=scale, k_scales=k_scales, v_scales=v_scales)[:, 0]


def dequant_pages(pages, scales):
    """fp32 values of quantized pages ``[N, page_size, K_kv * D]`` under
    their ``[N, K_kv]`` scales: each page's per-KV-head scale broadcast
    over that head's (page_size, D) payload.  For the jnp oracles (whole
    pools) and the prefill's gathered prefix pages."""
    n, page_size, width = pages.shape
    heads = pages.astype(jnp.float32).reshape(
        n, page_size, scales.shape[1], -1)
    return (heads * scales[:, None, :, None]).reshape(n, page_size, width)


def _dequant_pools(k_pages, v_pages, n_kv, k_scales, v_scales):
    """fp32 pools for the oracles."""
    if _check_scales(k_pages, n_kv, k_scales, v_scales):
        k_pages = dequant_pages(k_pages, k_scales)
        v_pages = dequant_pages(v_pages, v_scales)
    return k_pages, v_pages


def paged_attention_multi_reference(q, k_pages, v_pages, block_tables,
                                    context_lens, scale=None,
                                    k_scales=None, v_scales=None):
    """jnp oracle for :func:`paged_attention_multi`: per-position dense
    masked softmax over the gathered pages; rows with ``ctx == 0``
    come back zero (the kernel's empty-row contract)."""
    s_n, n_q, h, d = q.shape
    page_size = k_pages.shape[1]
    n_kv = _kv_heads(k_pages, h, d)
    g = h // n_kv
    max_pages = block_tables.shape[1]
    if scale is None:
        scale = d ** -0.5
    k_pages, v_pages = _dequant_pools(k_pages, v_pages, n_kv,
                                      k_scales, v_scales)
    bt = jnp.asarray(block_tables, jnp.int32)
    ctx = jnp.asarray(context_lens, jnp.int32)
    k_seq = k_pages[bt].reshape(s_n, max_pages * page_size, n_kv, d)
    v_seq = v_pages[bt].reshape(s_n, max_pages * page_size, n_kv, d)
    if g > 1:
        k_seq = jnp.repeat(k_seq, g, axis=2)
        v_seq = jnp.repeat(v_seq, g, axis=2)
    st = jnp.einsum("sihd,sthd->siht", q.astype(jnp.float32),
                    k_seq.astype(jnp.float32)) * scale
    mask = (jnp.arange(max_pages * page_size)[None, None, None, :]
            < ctx[:, :, None, None])
    st = jnp.where(mask, st, _NEG_INF)
    p = jax.nn.softmax(st, axis=-1)
    p = jnp.where(ctx[:, :, None, None] > 0, p, 0.0)
    out = jnp.einsum("siht,sthd->sihd", p, v_seq.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_attention_reference(q, k_pages, v_pages, block_tables,
                              context_lens, scale=None, k_scales=None,
                              v_scales=None):
    """O(S·T) jnp oracle: gather each slot's pages contiguous, broadcast
    each KV head over its query group, dense masked softmax attention.
    Tests pin the kernel against this and against ``flash_attention``
    on the densely-packed equivalent."""
    s_n, h, d = q.shape
    page_size = k_pages.shape[1]
    n_kv = _kv_heads(k_pages, h, d)
    g = h // n_kv
    max_pages = block_tables.shape[1]
    if scale is None:
        scale = d ** -0.5
    k_pages, v_pages = _dequant_pools(k_pages, v_pages, n_kv,
                                      k_scales, v_scales)
    bt = jnp.asarray(block_tables, jnp.int32)
    ctx = jnp.asarray(context_lens, jnp.int32)
    # [S, max_pages, page, K_kv * D] -> [S, T_max, K_kv, D]
    k_seq = k_pages[bt].reshape(s_n, max_pages * page_size, n_kv, d)
    v_seq = v_pages[bt].reshape(s_n, max_pages * page_size, n_kv, d)
    if g > 1:
        k_seq = jnp.repeat(k_seq, g, axis=2)
        v_seq = jnp.repeat(v_seq, g, axis=2)
    st = jnp.einsum("shd,sthd->sht", q.astype(jnp.float32),
                    k_seq.astype(jnp.float32)) * scale
    mask = (jnp.arange(max_pages * page_size)[None, None, :]
            < ctx[:, None, None])
    st = jnp.where(mask, st, _NEG_INF)
    p = jax.nn.softmax(st, axis=-1)
    # empty slots (ctx == 0): softmax over all -inf is uniform garbage —
    # zero those rows to match the kernel's empty-slot contract
    p = jnp.where(ctx[:, None, None] > 0, p, 0.0)
    out = jnp.einsum("sht,sthd->shd", p, v_seq.astype(jnp.float32))
    return out.astype(q.dtype)
