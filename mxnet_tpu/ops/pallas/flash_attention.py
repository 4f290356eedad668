"""Flash attention as Pallas TPU kernels (fwd + bwd), with custom VJP.

Design (standard two-pass scheme, Dao et al., TPU grid-streamed):
- forward: grid (batch·heads, q-blocks, k-blocks) with the k axis as the
  sequential innermost dimension — Pallas pipelines each K/V block
  HBM→VMEM while the online-softmax (o, m, l) state lives in VMEM
  scratch across the sweep.  VMEM use is O(block), independent of
  sequence length (T=512k compiles the same program as T=4k); the T×T
  score matrix never exists.  Saves out + logsumexp for backward.
- backward: dq kernel (grid ..., q-blocks, k-blocks) and dk/dv kernel
  (grid ..., k-blocks, q-blocks) recompute P = exp(S - lse) blockwise on
  the MXU, accumulating into VMEM scratch the same way.
- causal masking skips fully-masked blocks via pl.when on the grid
  coordinates.

All matmuls run with preferred_element_type=float32 (MXU accumulates in
fp32 even for bf16 inputs).  Off-TPU the same kernels run under the
Pallas interpreter, so tests pass on CPU unchanged.

The 2017 reference has no attention op at all (SURVEY §5: pre-attention
era — its sequence story was bucketing); this kernel is the long-context
foundation `parallel/ring_attention.py` documents.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# kernel constants are fp32 by construction (see _pallas_call)
_NEG_INF = np.float32(-1e30)
_TINY = np.float32(1e-30)


def _pl():
    from jax.experimental import pallas as pl
    return pl


def _use_interpret():
    return jax.default_backend() != "tpu"


def _pallas_call(kernel, operands, interpret=None, **kwargs):
    """``pl.pallas_call(kernel, **kwargs)(*operands)`` for every kernel
    in this package: the Pallas interpreter off-TPU (so CPU tests run
    the identical kernel code), Mosaic on the chip — and traced with
    64-bit mode OFF.  Under ``jax_enable_x64`` every Python literal in
    a kernel body or an index map lowers as an f64/i64 constant, and
    Mosaic has neither type; the kernels' operands are explicitly
    32-bit or narrower, so tracing them in 32-bit mode changes no
    value."""
    if interpret is None:
        interpret = _use_interpret()
    with jax.enable_x64(False):
        return _pl().pallas_call(kernel, interpret=interpret,
                                 **kwargs)(*operands)


def _causal_mask(q_off, k_off, bq, bk):
    q_pos = q_off + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_off + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return q_pos >= k_pos


def _kv_bounds_mask(k_off, bq, bk, tk):
    """False on K columns beyond the true sequence length (block padding
    when tk is not a multiple of block_k)."""
    k_pos = k_off + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return k_pos < tk


def _q_bounds_mask(q_off, bq, bk, tq):
    q_pos = q_off + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    return q_pos < tq


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, scale, causal, tk_true, has_seg=False):
    """One (q-block, k-block) step; the k dimension is the grid's
    innermost (sequential) axis, so K/V stream HBM->VMEM one block at a
    time — VMEM use is O(block), independent of sequence length — while
    the online-softmax state lives in VMEM scratch across the k sweep.

    With ``has_seg`` two extra int32 refs carry per-position segment
    ids (sequence packing: tokens attend within their segment only —
    the TPU-first replacement for the reference's bucketing)."""
    pl = _pl()
    if has_seg:
        (q_ref, k_ref, v_ref, qs_ref, ks_ref, o_ref, lse_ref,
         o_acc, m_acc, l_acc) = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, lse_ref,
         o_acc, m_acc, l_acc) = refs
        qs_ref = ks_ref = None
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]
    q_off = qi * bq
    k_off = ki * bk

    @pl.when(ki == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, _NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    def _accumulate():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (bq, bk)
        mask = _kv_bounds_mask(k_off, bq, bk, tk_true)
        if causal:
            mask &= _causal_mask(q_off, k_off, bq, bk)
        if has_seg:
            mask &= _segment_mask(qs_ref, ks_ref)
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_acc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_acc[...] = l_acc[...] * corr + p.sum(axis=-1, keepdims=True)
        o_acc[...] = o_acc[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_acc[...] = m_new

    if causal:
        # blocks fully above the diagonal contribute nothing; skip them
        pl.when(k_off <= q_off + bq - 1)(_accumulate)
    else:
        _accumulate()

    @pl.when(ki == nk - 1)
    def _emit():
        l_safe = jnp.maximum(l_acc[...], _TINY)
        o_ref[0] = (o_acc[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_acc[...] + jnp.log(l_safe)


def _pad_to_val(x, axis, mult, val):
    """Pad axis up to a multiple of mult with a constant (pl.ds clamps
    out-of-range block starts, silently shifting the window — aligned
    shapes + masks keep the math exact; segment ids pad with ids that
    can never match a real segment)."""
    size = x.shape[axis]
    rem = size % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(x, pad, constant_values=val)


def _pad_to(x, axis, mult):
    return _pad_to_val(x, axis, mult, 0)


def _segment_mask(qs_ref, ks_ref):
    """Packing mask: attend iff the q and k positions share a segment
    (sibling of _causal_mask; refs are the (1, bq, 1) column and
    (1, 1, bk) row blocks of :func:`_seg_operands`)."""
    return qs_ref[0] == ks_ref[0]


def _seg_operands(qseg, kseg, block_q, block_k, h, q_axis):
    """(in_specs, operands) for the [B, T] segment ids.  A (1, block)
    slice of a [B, T] array is not a legal TPU block, so q ids ride as
    a [B, Tq, 1] column and k ids as a [B, 1, Tk] row, and the mask is
    one broadcast compare.  Ids are not duplicated per head: grid dim 0
    is b*h, so the index maps divide the head factor away.  ``q_axis``
    is the grid axis (1 or 2) that walks the q blocks; the other walks
    k."""
    pl = _pl()
    if q_axis == 1:
        q_map = lambda b, i, j: (b // h, i, 0)             # noqa: E731
        k_map = lambda b, i, j: (b // h, 0, j)             # noqa: E731
    else:
        q_map = lambda b, i, j: (b // h, j, 0)             # noqa: E731
        k_map = lambda b, i, j: (b // h, 0, i)             # noqa: E731
    return ([pl.BlockSpec((1, block_q, 1), q_map),
             pl.BlockSpec((1, 1, block_k), k_map)],
            [qseg[:, :, None], kseg[:, None, :]])


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, qseg=None,
               kseg=None, h=1):
    pl = _pl()
    bh, tq, d = q.shape
    tk = k.shape[1]
    dv = v.shape[2]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    if kseg is not None:
        kseg = _pad_to_val(kseg, 1, block_k, -1)
    if qseg is not None:
        # q itself stays unpadded: Pallas block-pads non-divisible dims
        # (interpret and Mosaic alike), so pre-padding qseg to the same
        # multiple keeps rows aligned while giving the tail a sentinel
        # id no real segment uses (tests: odd-length seg cases in
        # flash_attention_driver.check_segment_packing)
        qseg = _pad_to_val(qseg, 1, block_q, -2)
    if tk % block_k:
        # kernels mask on the padded length's tail via tk_true
        kp = _pad_to(k, 1, block_k)
        vp = _pad_to(v, 1, block_k)
        out, lse = _flash_fwd_aligned(q, kp, vp, scale, causal, block_q,
                                      block_k, tk_true=tk, qseg=qseg,
                                      kseg=kseg, h=h)
        return out, lse
    return _flash_fwd_aligned(q, k, v, scale, causal, block_q, block_k,
                              tk_true=tk, qseg=qseg, kseg=kseg, h=h)


def _scratch(shape):
    """VMEM scratch allocation (accumulators carried across the grid's
    sequential innermost dimension)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)


def _flash_fwd_aligned(q, k, v, scale, causal, block_q, block_k, tk_true,
                       qseg=None, kseg=None, h=1):
    pl = _pl()
    bh, tq, d = q.shape
    tk = k.shape[1]
    dv = v.shape[2]
    has_seg = qseg is not None
    grid = (bh, pl.cdiv(tq, block_q), pl.cdiv(tk, block_k))
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, j, 0)),
    ]
    operands = [q, k, v]
    if has_seg:
        specs, ops = _seg_operands(qseg, kseg, block_q, block_k, h, 1)
        in_specs += specs
        operands += ops
    out, lse = _pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          tk_true=tk_true, has_seg=has_seg),
        operands,
        name="flash_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32),
        ],
        scratch_shapes=[_scratch((block_q, dv)), _scratch((block_q, 1)),
                        _scratch((block_q, 1))],
    )
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_block_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    mask, scale):
    """Shared backward block math for one (q-block, k-block) pair:
    recompute p = exp(S − lse) under ``mask`` and ds = p·(dO·Vᵀ − Δ).
    Both backward kernels (dq, dk/dv) consume these; the
    explicit p zeroing handles rows whose lse is the padding sentinel
    (exp(−inf − (−inf)) would be 1)."""
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]      # (bq, 1)
    delta = delta_ref[0]  # (bq, 1)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (bq, bk)
    s = jnp.where(mask, s, _NEG_INF)
    p = jnp.exp(s - lse)
    p = jnp.where(mask, p, 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    return p, ds, q, k, do


def _bwd_dq_kernel(*refs, scale, causal, tk_true, has_seg=False):
    """dq for one (q-block, k-block) grid step; K/V stream via the
    sequential innermost grid axis, dq accumulates in VMEM scratch."""
    pl = _pl()
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qs_ref,
         ks_ref, dq_ref, dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_acc) = refs
        qs_ref = ks_ref = None
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]
    q_off = qi * bq
    k_off = ki * bk

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _accumulate():
        mask = _kv_bounds_mask(k_off, bq, bk, tk_true)
        if causal:
            mask &= _causal_mask(q_off, k_off, bq, bk)
        if has_seg:
            mask &= _segment_mask(qs_ref, ks_ref)
        _, ds, _, k, _ = _bwd_block_p_ds(q_ref, k_ref, v_ref, do_ref,
                                         lse_ref, delta_ref, mask, scale)
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        pl.when(k_off <= q_off + bq - 1)(_accumulate)
    else:
        _accumulate()

    @pl.when(ki == nk - 1)
    def _emit():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, tq_true, has_seg=False):
    """dk/dv for one (k-block, q-block) grid step; Q/dO/lse/delta stream
    via the sequential innermost grid axis."""
    pl = _pl()
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qs_ref,
         ks_ref, dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        qs_ref = ks_ref = None
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    bk = k_ref.shape[1]
    bq = q_ref.shape[1]
    k_off = ki * bk
    q_off = qi * bq

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _accumulate():
        # padded q rows (tq % block_q) must contribute zero to dk/dv
        mask = _q_bounds_mask(q_off, bq, bk, tq_true)
        if causal:
            mask &= _causal_mask(q_off, k_off, bq, bk)
        if has_seg:
            mask &= _segment_mask(qs_ref, ks_ref)
        p, ds, q, _, do = _bwd_block_p_ds(q_ref, k_ref, v_ref, do_ref,
                                          lse_ref, delta_ref, mask, scale)
        # dv += P^T @ dO
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        # a k-block sees only q rows at or below the diagonal
        pl.when(q_off + bq - 1 >= k_off)(_accumulate)
    else:
        _accumulate()

    @pl.when(qi == nq - 1)
    def _emit():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _unpack_res(res):
    """(q, k, v, out, lse[, qseg, kseg]) -> 7-tuple with None segs."""
    if len(res) == 7:
        return res
    q, k, v, out, lse = res
    return q, k, v, out, lse, None, None


def _flash_bwd(res, g, scale, causal, block_q, block_k, h=1):
    pl = _pl()
    q, k, v, out, lse, qseg, kseg = _unpack_res(res)
    do = g
    bh, tq, d = q.shape
    tk = k.shape[1]
    dv_dim = v.shape[2]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    # delta_i = rowsum(dO_i * O_i)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)

    # pad every block-streamed operand to its block multiple (partial
    # final blocks would read out of range otherwise); kernels mask on
    # the true lengths, outputs are sliced back
    kp = _pad_to(k, 1, block_k)
    vp = _pad_to(v, 1, block_k)
    qp = _pad_to(q, 1, block_q)
    dop = _pad_to(do, 1, block_q)
    lsep = _pad_to(lse, 1, block_q)
    deltap = _pad_to(delta, 1, block_q)
    tkp = kp.shape[1]
    tqp = qp.shape[1]
    has_seg = qseg is not None
    qsegp = _pad_to_val(qseg, 1, block_q, -2) if has_seg else None
    ksegp = _pad_to_val(kseg, 1, block_k, -1) if has_seg else None

    dq_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, dv_dim), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_q, dv_dim), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
    ]
    dq_ops = [q, kp, vp, do, lse, delta]
    if has_seg:
        specs, ops = _seg_operands(qseg, ksegp, block_q, block_k, h, 1)
        dq_specs += specs
        dq_ops += ops
    dq = _pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          tk_true=tk, has_seg=has_seg),
        dq_ops,
        name="flash_bwd_dq",
        grid=(bh, pl.cdiv(tq, block_q), tkp // block_k),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[_scratch((block_q, d))],
    )

    dkv_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, dv_dim), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, dv_dim), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, j, 0)),
    ]
    dkv_ops = [qp, k, v, dop, lsep, deltap]
    if has_seg:
        specs, ops = _seg_operands(qsegp, kseg, block_q, block_k, h, 2)
        dkv_specs += specs
        dkv_ops += ops
    dk, dv = _pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          tq_true=tq, has_seg=has_seg),
        dkv_ops,
        name="flash_bwd_dkv",
        grid=(bh, pl.cdiv(tk, block_k), tqp // block_q),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dv_dim), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[_scratch((block_k, d)),
                        _scratch((block_k, dv_dim))],
    )
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q3, k3, v3, scale, causal, block_q, block_k):
    out, _ = _flash_fwd(q3, k3, v3, scale, causal, block_q, block_k)
    return out


def _flash_vjp_fwd(q3, k3, v3, scale, causal, block_q, block_k):
    out, lse = _flash_fwd(q3, k3, v3, scale, causal, block_q, block_k)
    return out, (q3, k3, v3, out, lse)


def _flash_vjp_bwd(scale, causal, block_q, block_k, res, g):
    return _flash_bwd(res, g, scale, causal, block_q, block_k)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _int_zero_tangent(x):
    """The cotangent custom_vjp must return for an integer primal."""
    return np.zeros(x.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_seg(q3, k3, v3, qseg, kseg, scale, causal, block_q, block_k,
               h):
    out, _ = _flash_fwd(q3, k3, v3, scale, causal, block_q, block_k,
                        qseg=qseg, kseg=kseg, h=h)
    return out


def _flash_seg_vjp_fwd(q3, k3, v3, qseg, kseg, scale, causal, block_q,
                       block_k, h):
    out, lse = _flash_fwd(q3, k3, v3, scale, causal, block_q, block_k,
                          qseg=qseg, kseg=kseg, h=h)
    return out, (q3, k3, v3, out, lse, qseg, kseg)


def _flash_seg_vjp_bwd(scale, causal, block_q, block_k, h, res, g):
    dq, dk, dv = _flash_bwd(res, g, scale, causal, block_q, block_k,
                            h=h)
    qseg, kseg = res[5], res[6]
    return dq, dk, dv, _int_zero_tangent(qseg), _int_zero_tangent(kseg)


_flash_seg.defvjp(_flash_seg_vjp_fwd, _flash_seg_vjp_bwd)


def _mesh_batch_head_spec(b, h):
    """``(PartitionSpec for [B, H, ...] operands, mesh axes to go
    manual over)`` under the ambient mesh (``jax.set_mesh`` — the mesh
    step builders run under it), or None off-mesh.  Mosaic kernels
    cannot be partitioned automatically — a bare pallas_call inside a
    jit over several devices is refused — but attention is independent
    per (batch, head): B rides the data-parallel axis and H the
    tensor-parallel axis (parallel/mesh.py's names) where they divide,
    every other axis sees replicated operands, and
    :func:`flash_attention` shard_maps the kernels accordingly."""
    from jax.sharding import AxisType, PartitionSpec as P
    mesh = jax.sharding.get_abstract_mesh()
    auto = {n: s for n, s, t in zip(mesh.axis_names, mesh.axis_sizes,
                                    mesh.axis_types)
            if t != AxisType.Manual and s > 1}
    if not auto:
        return None
    dp = "dp" if b % auto.get("dp", b + 1) == 0 else None
    tp = "tp" if h % auto.get("tp", h + 1) == 0 else None
    return P(dp, tp), frozenset(auto)


def flash_attention(q, k, v, causal=False, scale=None, block_q=512,
                    block_k=512, segment_ids=None, kv_segment_ids=None):
    """Fused attention over [B, H, T, D] tensors.

    Memory O(T) per program instead of O(T²); differentiable (flash
    backward kernels).  Off-TPU backends run the same kernels in the
    Pallas interpreter.  Under an ambient multi-device mesh the kernels
    run per device on its (batch, head) block
    (:func:`_mesh_batch_head_spec`).

    ``segment_ids`` ([B, Tq] int32) enables SEQUENCE PACKING: tokens
    attend only within their own segment — multiple short documents
    share one fixed-shape row, the TPU-first replacement for the
    reference's bucketing (python/mxnet/module/bucketing_module.py).
    ``kv_segment_ids`` defaults to ``segment_ids`` (self-attention);
    give it for cross-attention over packed keys.  Use a dedicated id
    for padding tokens and they attend nothing/nobody.
    """
    scale = float(scale if scale is not None else q.shape[-1] ** -0.5)
    static = (scale, bool(causal), int(block_q), int(block_k))
    if segment_ids is None:
        if kv_segment_ids is not None:
            raise ValueError(
                "kv_segment_ids without segment_ids: packed keys need "
                "query ids too (pass segment_ids=jnp.ones for unpacked "
                "queries)")
        operands = (q, k, v)
    else:
        if kv_segment_ids is None:
            kv_segment_ids = segment_ids
        operands = (q, k, v, jnp.asarray(segment_ids, jnp.int32),
                    jnp.asarray(kv_segment_ids, jnp.int32))

    def local(q, k, v, *segs):
        b, h, tq, d = q.shape
        q3 = q.reshape(b * h, tq, d)
        k3 = k.reshape(b * h, k.shape[2], k.shape[3])
        v3 = v.reshape(b * h, v.shape[2], v.shape[3])
        if segs:
            out = _flash_seg(q3, k3, v3, *segs, *static, h)
        else:
            out = _flash(q3, k3, v3, *static)
        return out.reshape(b, h, tq, v.shape[3])

    on_mesh = _mesh_batch_head_spec(*q.shape[:2])
    if on_mesh is None:
        return local(*operands)
    spec, axes = on_mesh
    from jax.sharding import PartitionSpec as P
    seg_spec = P(spec[0])
    return jax.shard_map(
        local, in_specs=(spec,) * 3 + (seg_spec,) * (len(operands) - 3),
        out_specs=spec, axis_names=axes, check_vma=False)(*operands)


def flash_forward_with_lse(q, k, v, causal=False, scale=None, block_q=512,
                           block_k=512, segment_ids=None,
                           kv_segment_ids=None):
    """Forward-only kernel call returning (out, lse) over [B,H,T,D].

    ``lse = m + log l`` per query row — the merge quantity ring attention
    needs to combine per-block results (parallel/ring_attention.py).  Not
    differentiable; ring attention defines its own vjp around it.
    ``segment_ids``/``kv_segment_ids`` ([B, Tq]/[B, Tk] int32) apply the
    packing mask; rows with no visible key report ``lse = -inf`` so the
    ring merge weighs them zero.
    """
    b, h, tq, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    q3 = q.reshape(b * h, tq, d)
    k3 = k.reshape(b * h, k.shape[2], k.shape[3])
    v3 = v.reshape(b * h, v.shape[2], v.shape[3])
    qs = ks = None
    if segment_ids is not None:
        qs = jnp.asarray(segment_ids, jnp.int32)
        ks = jnp.asarray(kv_segment_ids if kv_segment_ids is not None
                         else segment_ids, jnp.int32)
    out, lse = _flash_fwd(q3, k3, v3, float(scale), bool(causal),
                          int(block_q), int(block_k), qseg=qs, kseg=ks,
                          h=h)
    return (out.reshape(b, h, tq, v.shape[3]),
            lse.reshape(b, h, tq, 1))


def flash_attention_reference(q, k, v, causal=False, scale=None,
                              segment_ids=None, kv_segment_ids=None):
    """O(T²) jnp oracle for tests."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        tq, tk = q.shape[2], k.shape[2]
        mask = _causal_mask(0, 0, tq, tk)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    if segment_ids is not None:
        if kv_segment_ids is None:
            kv_segment_ids = segment_ids
        seg = segment_ids[:, None, :, None] == \
            kv_segment_ids[:, None, None, :]
        s = jnp.where(seg, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
