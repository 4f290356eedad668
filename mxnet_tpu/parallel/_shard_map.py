"""``jax.shard_map`` with this package's defaults."""
from __future__ import annotations

import jax


def shard_map(fn, mesh, in_specs, out_specs, check_rep=False,
              axis_names=None):
    """``axis_names`` (iterable of mesh axis names) selects PARTIAL
    manual mode: listed axes are manual (specs may reference them),
    unlisted axes stay auto — GSPMD keeps propagating their shardings
    inside the body (used by the pipeline to run pp manually while tp
    rides XLA's Megatron propagation)."""
    kw = {} if axis_names is None else \
        {"axis_names": frozenset(axis_names)}
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_rep, **kw)
