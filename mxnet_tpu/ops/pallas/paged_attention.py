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

Kernel shape (one launch serves ALL resident slots, any lengths):

- grid ``(num_slots, max_pages_per_seq)`` with the page axis as the
  sequential innermost dimension, exactly like ``flash_attention.py``'s
  k-block sweep: each step streams ONE physical K/V page HBM->VMEM
  while the online-softmax state (o, m, l) rides in VMEM scratch;
- **grouped-query attention** (ISSUE 15): the pools carry ``K_kv <= H``
  KV heads; the ``H`` query heads are processed in ``H // K_kv``-sized
  GROUPS, one 2-D matmul pair per KV head, all inside the cell — the
  one physical page fetch serves the WHOLE query group, so KV bytes
  per token shrink by ``H / K_kv`` while the FLOPs stay put.
  ``K_kv == H`` degenerates to classic multi-head (bit-identical to
  the pre-GQA kernel: same shapes, same op order); ``K_kv == 1`` is
  multi-query attention;
- the block table and per-slot context lengths arrive via scalar
  prefetch (``pltpu.PrefetchScalarGridSpec``) so the BlockSpec index
  maps can do the logical->physical page translation — the gather IS
  the pipeline's address computation, no materialized per-sequence
  contiguous KV ever exists;
- pages at or beyond a slot's context length are skipped with
  ``pl.when`` (raggedness costs control flow, not FLOPs) and the final
  in-range page is masked per position.

A slot with ``context_len == 0`` (an empty serving slot) attends to
nothing and emits zeros.  Off-TPU the same kernel runs under the Pallas
interpreter, so CPU tests exercise the identical code path.

All matmuls accumulate in fp32 (MXU ``preferred_element_type``), same
discipline as flash_attention.py.

**Quantized pages** (ISSUE 20): with ``k_scales``/``v_scales`` given
(fp32 ``[num_pages, K_kv]`` — one absmax scale per page per KV head)
the pools may hold int8 payloads; each kernel cell dequantizes its ONE
fetched page row in VMEM (``int8 * scale``) right before the score
matmul, so HBM moves a quarter of the fp32 bytes while scores, softmax
and the output accumulate in fp32 exactly as before.  The slot's scale
rows are gathered through the block table before the launch and reach
the cell in SMEM, where it reads them as scalars.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .flash_attention import (_NEG_INF, _TINY, _pallas_call, _pl,
                              _scratch)


def _paged_kernel(ctx_ref, bt_ref, q_ref, k_ref, v_ref, *rest,
                  page_size, n_kv, g, n_q, scale, quantized=False):
    """One (slot, page) grid step: online-softmax accumulate the
    physical page the block table routed in, for ``n_q`` query
    positions per slot at once (``n_q == 1`` is plain decode; more is
    the speculative-verify sweep, where query position ``i`` has its
    OWN context length — the per-position causal mask of batched
    verification).

    The KV-head axis is an UNROLLED loop of 2-D matmuls inside the
    cell: KV head ``kv`` owns the ``n_q * g`` query rows
    ``q_ref[0, kv]`` (position-major, ``row = i * g + h``), so one
    page fetch serves every query position and every head of the
    group, and folding heads into one cell cuts grid-cell overhead
    ``n_kv``-fold.  ``ctx_ref`` (``[S, n_q]``) and ``bt_ref`` are
    scalar-prefetched into SMEM: the index maps already consumed
    ``bt_ref`` for the page gather, and the context lengths are read
    here as SCALARS (SMEM admits no vector loads).  A row whose context
    ends before this page multiplies its softmax weights by zero, so
    the page leaves that row's accumulators untouched exactly as if it
    had been skipped; rows with ``ctx == 0`` never accumulate and emit
    zeros.  With ``quantized`` the cell also sees the slot's gathered
    ``(n_kv, max_pages)`` scales in SMEM and dequantizes the fetched
    K/V page in VMEM before the fp32 matmuls."""
    if quantized:
        ks_ref, vs_ref, o_ref, o_acc, m_acc, l_acc = rest
    else:
        o_ref, o_acc, m_acc, l_acc = rest
    pl = _pl()
    s = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    rows = n_q * g
    d = q_ref.shape[-1]
    ctxs = [ctx_ref[s, i] for i in range(n_q)]
    ctx_max = functools.reduce(jnp.maximum, ctxs)

    @pl.when(j == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, _NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    @pl.when(j * page_size < ctx_max)
    def _accumulate():
        # positions past a row's context length (the ragged tail of its
        # final in-range page, or a later query position's keys)
        # contribute nothing
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, page_size), 0)
        ctx_rows = ctxs[0]
        for i in range(1, n_q):
            ctx_rows = jnp.where(row >= i * g, ctxs[i], ctx_rows)
        mask = pos < ctx_rows
        for kv in range(n_kv):
            q = q_ref[0, kv].astype(jnp.float32) * scale   # (rows, D)
            # KV head `kv` is a static lane slice of the flat page row
            k = k_ref[0, :, kv * d:(kv + 1) * d].astype(jnp.float32)
            v = v_ref[0, :, kv * d:(kv + 1) * d].astype(jnp.float32)
            if quantized:
                k = k * ks_ref[0, kv, j]
                v = v * vs_ref[0, kv, j]
            st = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)        # (rows, page)
            st = jnp.where(mask, st, _NEG_INF)
            m_prev = m_acc[kv]
            m_new = jnp.maximum(m_prev, st.max(axis=-1, keepdims=True))
            # a row with no key yet sits at m == -1e30, where
            # exp(st - m) would be 1: zero the masked weights
            p = jnp.where(mask, jnp.exp(st - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_acc[kv] = l_acc[kv] * corr + p.sum(axis=-1, keepdims=True)
            o_acc[kv] = o_acc[kv] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_acc[kv] = m_new

    @pl.when(j == nj - 1)
    def _emit():
        # a row that never accumulated (ctx == 0) has l == 0: emit zeros
        l_safe = jnp.maximum(l_acc[...], _TINY)
        o_ref[0] = (o_acc[...] / l_safe).astype(o_ref.dtype)


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
      each cell multiplies its fetched page by its scale row in VMEM
      before the fp32 score matmul.

    Returns [S, G, H, D] in ``q``'s dtype.  Raggedness is free of
    FLOPs: pages past a slot's longest context are skipped, the final
    partial page is masked per position.
    """
    pl = _pl()
    from jax.experimental.pallas import tpu as pltpu
    s_n, n_q, h, d = q.shape
    page_size = k_pages.shape[1]
    n_kv = _kv_heads(k_pages, h, d)
    g = h // n_kv
    rows = n_q * g
    quantized = _check_scales(k_pages, n_kv, k_scales, v_scales)
    max_pages = block_tables.shape[1]
    if scale is None:
        scale = d ** -0.5
    ctx = jnp.asarray(context_lens, jnp.int32)
    if ctx.shape != (s_n, n_q):
        raise ValueError(
            "context_lens must be [S, G] = %r, got %r"
            % ((s_n, n_q), tuple(ctx.shape)))
    bt = jnp.asarray(block_tables, jnp.int32)

    # KV-head-major query rows: the cell reads one aligned
    # (n_q * g, D) tile per KV head, and the regrouping of this small
    # tensor is XLA's, not the kernel's
    qk = q.reshape(s_n, n_q, n_kv, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(s_n, n_kv, rows, d)
    q_spec = pl.BlockSpec((1, n_kv, rows, d),
                          lambda s, j, c, b: (s, 0, 0, 0))
    page_spec = pl.BlockSpec(
        (1, page_size, n_kv * d), lambda s, j, c, b: (b[s, j], 0, 0))
    in_specs = [q_spec, page_spec, page_spec]
    args = [ctx, bt, qk, k_pages, v_pages]
    if quantized:
        # a (1, n_kv) row of the [num_pages, K_kv] pool is not a legal
        # TPU block, and the cell wants each scale as a SCALAR: gather
        # the slot's scale rows through the block table here and hand
        # them to the cell in SMEM, page axis last (SMEM pads the last
        # dim to 128 words)
        scale_spec = pl.BlockSpec((1, n_kv, max_pages),
                                  lambda s, j, c, b: (s, 0, 0),
                                  memory_space=pltpu.SMEM)
        in_specs += [scale_spec, scale_spec]
        args += [k_scales[bt].transpose(0, 2, 1),
                 v_scales[bt].transpose(0, 2, 1)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_n, max_pages),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[_scratch((n_kv, rows, d)),
                        _scratch((n_kv, rows, 1)),
                        _scratch((n_kv, rows, 1))],
    )
    out = _pallas_call(
        functools.partial(_paged_kernel, page_size=page_size,
                          n_kv=n_kv, g=g, n_q=n_q,
                          scale=np.float32(scale), quantized=quantized),
        args,
        name="paged_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_n, n_kv, rows, d), q.dtype))
    return out.reshape(s_n, n_kv, n_q, g, d).transpose(0, 2, 1, 3, 4) \
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
