"""Grouped matmul for routed experts as a Pallas TPU kernel
(``moe_gmm``): rows sorted by expert, each expert's rows padded to
whole row tiles, one weight matrix a tile.

``x``'s rows are laid out by :func:`mxnet_tpu.parallel.moe.expert_tiles`
so that every tile of ``tile_rows`` rows belongs to ONE expert;
``tile_expert[i]`` names it and arrives by scalar prefetch, so the
weight block's index map picks the expert's matrix: the gather of an
expert's weights is the pipeline's address computation.  The cost
follows the tiles that hold tokens: a tile past ``n_valid`` skips its
matmul under ``pl.when`` and repeats the block indices of the last
valid one, so nothing is fetched for it.

The grid is (row tiles, column blocks) with the columns innermost: a
row tile walks all of its expert's column blocks, so an expert's
weights cross HBM once a TILE.  They cross once a run where an
expert's rows fit one tile, and that is the layout's to arrange
(:func:`mxnet_tpu.parallel.moe.expert_tile_rows` picks the height from
the rows an expert can expect): with a few rows an expert (decode) the
kernel is bound by the hit experts' weight bytes, each read once; an
expert with more rows than a tile holds has its weights read again for
each further tile, which costs no time only at 256 rows, where a
tile's matmul takes as long as its weights' read.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .flash_attention import _pallas_call, _pl

#: the kernel's weight blocks (double-buffered) outgrow Mosaic's
#: default 16 MiB of scoped VMEM at the published expert width
_VMEM_LIMIT = 48 * 1024 * 1024


def _gmm_kernel(te_ref, nv_ref, x_ref, w_ref, o_ref):
    pl = _pl()

    @pl.when(pl.program_id(0) < nv_ref[0])
    def _tile():
        o_ref[...] = jnp.dot(
            x_ref[...], w_ref[0],
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _col_tile(n, k, itemsize, budget=4 * 1024 * 1024):
    """Widest column tile (a multiple of 128 that divides ``n``) whose
    weight block stays under ``budget`` bytes."""
    best = None
    for parts in range(1, n // 128 + 1):
        if n % parts == 0 and (n // parts) % 128 == 0:
            best = n // parts
            if k * best * itemsize <= budget:
                return best
    return best or n


def moe_gmm(x, w, tile_expert, n_valid, tile_rows, out_dtype=None):
    """``out[r] = x[r] @ w[expert of r's tile]``.

    - ``x``: [M, K], ``M`` a multiple of ``tile_rows``;
    - ``w``: [E, K, N] — the held experts' matrices;
    - ``tile_expert``: int32 [M // tile_rows]; ``n_valid``: int32 [1],
      tiles at or past it hold no token and are left unwritten.

    Returns [M, N] in ``out_dtype`` (``x``'s by default; float32
    accumulation).
    """
    pl = _pl()
    from jax.experimental.pallas import tpu as pltpu
    m, k = x.shape
    n = w.shape[2]
    tn = _col_tile(n, k, w.dtype.itemsize)
    n_n = n // tn

    def col(i, j, nv):
        return jnp.where(i < nv[0], j, n_n - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m // tile_rows, n_n),
        in_specs=[
            pl.BlockSpec((tile_rows, k), lambda i, j, te, nv: (
                jnp.minimum(i, jnp.maximum(nv[0] - 1, 0)), 0)),
            pl.BlockSpec((1, k, tn), lambda i, j, te, nv: (
                te[i], 0, col(i, j, nv)))],
        out_specs=pl.BlockSpec((tile_rows, tn),
                               lambda i, j, te, nv: (i, j)))
    return _pallas_call(
        _gmm_kernel,
        [jnp.asarray(tile_expert, jnp.int32),
         jnp.asarray(n_valid, jnp.int32).reshape(1), x, w],
        name="moe_gmm",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype or x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT))


def moe_gmm_reference(x, w, tile_expert, n_valid, tile_rows):
    """jnp oracle: every row against its tile's matrix; rows of tiles
    past ``n_valid`` come back zero."""
    m = x.shape[0]
    tiles = m // tile_rows
    row_expert = jnp.repeat(jnp.asarray(tile_expert, jnp.int32), tile_rows)
    out = jnp.einsum("mk,mkn->mn", x.astype(jnp.float32),
                     w[row_expert].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    live = jnp.repeat(jnp.arange(tiles) < jnp.reshape(n_valid, ()),
                      tile_rows)
    return jnp.where(live[:, None], out, 0.0).astype(x.dtype)
