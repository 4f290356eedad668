"""Named-axis collectives — the communication backend.

The TPU-native replacement for the reference's entire comm stack: ps-lite
ZPush/ZPull (/root/reference/src/kvstore/kvstore_dist.h:103-156), the
pinned-host OMP tree reduce (``CommCPU``, src/kvstore/comm.h:299-436) and
the CUDA P2P tree (``CommDevice``, comm.h:460-570).  Here every pattern is
one XLA collective over a named mesh axis; XLA routes it over ICI within a
slice and DCN across slices.

These are thin wrappers so the rest of the framework never imports
``jax.lax`` collectives directly — keeping one site to evolve (e.g. to
swap in a Pallas ring-reduce kernel).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def axis_index(axis):
    return lax.axis_index(axis)


def axis_size(axis):
    """Static size of a named mesh axis, resolvable inside shard_map: a
    Python int at trace time, so it stays usable as a loop bound (the
    pipeline/ring kernels unroll over it)."""
    return lax.axis_size(axis)


def allreduce(x, axis, op="sum"):
    """All-reduce over a mesh axis (the KVStore push+pull fast path)."""
    if op == "sum":
        return lax.psum(x, axis)
    if op == "mean":
        return lax.pmean(x, axis)
    if op == "max":
        return lax.pmax(x, axis)
    if op == "min":
        return lax.pmin(x, axis)
    raise ValueError("unknown reduce op %r" % op)


def allgather(x, axis, tiled_axis=0):
    """Gather shards along ``tiled_axis``; result is full on every device."""
    return lax.all_gather(x, axis, axis=tiled_axis, tiled=True)


def reduce_scatter(x, axis, scatter_axis=0):
    """Sum then scatter — the ZeRO/FSDP gradient primitive."""
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_axis,
                            tiled=True)


def alltoall(x, axis, split_axis, concat_axis):
    """All-to-all: resharding between two tensor dims (Ulysses / MoE)."""
    return lax.all_to_all(x, axis, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def ring_permute(x, axis, shift=1):
    """Send to the neighbour ``shift`` hops around the ring (ppermute).

    The building block of ring attention and of bandwidth-optimal
    allreduce: on TPU the ring maps to physical ICI links.
    """
    n = axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def broadcast_from(x, axis, root=0, idx=None):
    """Every device gets root's shard (KVStore pull semantics).

    ``idx`` overrides the device's own coordinate on the axis — callers
    under partial-manual shard_map pass a data-fed index because
    ``lax.axis_index`` lowers to a PartitionId instruction the SPMD
    partitioner (still running for the auto axes) cannot place."""
    n = axis_size(axis)
    if idx is None:
        idx = lax.axis_index(axis)
    zeroed = jnp.where(idx == root, x, jnp.zeros_like(x))
    return lax.psum(zeroed, axis)
