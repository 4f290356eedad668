"""Fused residual-add + LayerNorm as a Pallas TPU kernel.

The transformer sublayer epilogue ``LayerNorm(x + r)`` appears twice per
block; unfused, XLA materializes the sum and runs two cross-row
reductions over separate HBM round-trips.  This kernel makes the whole
epilogue ONE VMEM pass: a row block streams HBM→VMEM once, the residual
add, mean/variance (fp32), normalize and γ/β scale all happen on the VPU
while the block is resident, and only the normalized result goes back.
The pattern-fusion graph pass (mxnet_tpu.graph.passes) emits it for the
``elemwise_add → LayerNorm`` chain alongside ``flash_attention`` /
``paged_attention`` on the Pallas path.

Grid: one dimension over row blocks (all leading axes collapsed to R
rows of D features; D is the normalized axis and must be the last).
Statistics accumulate in fp32 regardless of input dtype (the LayerNorm
op's AMP discipline) and come OUT of the kernel as extra row outputs.
Backward is a custom VJP computed with plain jnp from the saved inputs
plus those (mean, rstd) — one recomputed add, no fp32 copy of the sum
ever materializes.

Off-TPU the kernel runs under the Pallas interpreter (tests), but the
graph pass only emits the Pallas path on real TPU backends — interpret
mode would bloat the lowered HLO the pipeline exists to shrink.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .flash_attention import _pallas_call, _pl

__all__ = ["fused_layer_norm_residual", "use_pallas"]


def use_pallas(x, axis):
    """Should the graph-pass fused op lower through this kernel?  TPU
    backends with a last-axis norm only; MXTPU_LN_PALLAS=0 forces the
    jnp path, =1 forces the kernel (interpret mode off-TPU — tests)."""
    import os
    flag = os.environ.get("MXTPU_LN_PALLAS")
    if flag == "0":
        return False
    ok_axis = axis in (-1, x.ndim - 1)
    if flag == "1":
        return ok_axis
    return ok_axis and jax.default_backend() == "tpu"


def _ln_kernel(x_ref, r_ref, g_ref, b_ref, o_ref, m_ref, s_ref, *, eps):
    s = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    mean = s.mean(axis=-1, keepdims=True)
    d = s - mean
    var = (d * d).mean(axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = d * rstd * g_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)
    # statistics are kernel OUTPUTS: the VJP saves (mean, rstd) instead
    # of re-deriving them with a duplicate full-tensor jnp pass
    m_ref[...] = mean
    s_ref[...] = rstd


def _rows(shape):
    r = 1
    for s in shape[:-1]:
        r *= s
    return r


def _kernel_call(x2, r2, gamma, beta, eps, interpret, block_rows=256):
    """Returns (y, mean, rstd) — the normalized rows plus the per-row
    statistics the backward needs, all from the one VMEM pass."""
    pl = _pl()
    R, D = x2.shape
    bm = min(block_rows, R)
    grid = ((R + bm - 1) // bm,)
    return _pallas_call(
        functools.partial(_ln_kernel, eps=np.float32(eps)),
        (x2, r2, gamma, beta),
        name="layer_norm",
        interpret=interpret,
        out_shape=(jax.ShapeDtypeStruct((R, D), x2.dtype),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, D), lambda i: (i, 0)),
            pl.BlockSpec((bm, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=(pl.BlockSpec((bm, D), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0))),
    )


@functools.lru_cache(maxsize=None)
def _make_fused(eps, interpret):
    """One custom-VJP function per (eps, interpret) — forward through the
    kernel, backward the standard LayerNorm gradient in jnp over saved
    (s, mean, rstd)."""

    @jax.custom_vjp
    def fused(x, r, gamma, beta):
        y, _res = _fwd(x, r, gamma, beta)
        return y

    def _fwd(x, r, gamma, beta):
        shape = x.shape
        D = shape[-1]
        x2 = x.reshape((_rows(shape), D))
        r2 = r.reshape((_rows(shape), D))
        y2, mean, rstd = _kernel_call(x2, r2, gamma, beta, eps, interpret)
        # residuals: the INPUT rows (references, no new buffers) + the
        # kernel's own statistics; backward recomputes s = x+r with one
        # add instead of the forward materializing an fp32 copy
        return y2.reshape(shape), (x2, r2, mean, rstd, gamma)

    def _bwd(res, g):
        x2, r2, mean, rstd, gamma = res
        s = x2.astype(jnp.float32) + r2.astype(jnp.float32)
        # the cotangent carries the caller's shape/dtype — residuals
        # stay pure arrays (custom_vjp pytree discipline)
        g2 = g.reshape(s.shape).astype(jnp.float32)
        xhat = (s - mean) * rstd
        dgamma = (g2 * xhat).sum(axis=0).astype(gamma.dtype)
        dbeta = g2.sum(axis=0).astype(gamma.dtype)
        gg = g2 * gamma.astype(jnp.float32)
        # dL/ds for y = xhat*gamma + beta, xhat = (s - mean) * rstd
        ds = rstd * (gg - gg.mean(axis=-1, keepdims=True)
                     - xhat * (gg * xhat).mean(axis=-1, keepdims=True))
        ds = ds.reshape(g.shape).astype(g.dtype)
        return ds, ds, dgamma, dbeta

    fused.defvjp(_fwd, _bwd)
    return fused


def fused_layer_norm_residual(x, r, gamma, beta, eps=1e-5, interpret=None):
    """``LayerNorm(x + r)`` over the LAST axis as one Pallas kernel.
    ``interpret=None`` auto-selects interpreter mode off-TPU (the
    flash_attention convention)."""
    if interpret is not None:
        interpret = bool(interpret)
    return _make_fused(float(eps), interpret)(x, r, gamma, beta)
