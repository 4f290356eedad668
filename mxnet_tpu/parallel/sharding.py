"""Parameter partition rules → `NamedSharding`.

The reference assigns whole arrays to devices (`Context` on every NDArray;
`nnvm::pass::PlaceDevice` for model parallelism,
/root/reference/src/executor/graph_executor.cc:309-395).  TPU-native
placement is finer: each array gets a `PartitionSpec` over mesh axes and
XLA materialises the layout.  Rules are regex patterns over parameter
names — the same name-driven dispatch the reference's initializer registry
uses (/root/reference/python/mxnet/initializer.py:53-160) — so model code
stays sharding-agnostic.
"""
from __future__ import annotations

import logging
import math
import re
import threading
import weakref

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import AXIS_DP, AXIS_TP


class PartitionRule:
    """(name_regex, ndim or None, PartitionSpec)."""

    def __init__(self, pattern, spec, ndim=None):
        self.pattern = re.compile(pattern)
        self.spec = spec if isinstance(spec, P) else P(*spec)
        self.ndim = ndim

    def matches(self, name, val):
        if self.ndim is not None and getattr(val, "ndim", None) != self.ndim:
            return False
        return self.pattern.search(name) is not None


def make_sharding_rules(*rules):
    return [r if isinstance(r, PartitionRule) else PartitionRule(*r)
            for r in rules]


#: default tensor-parallel rules for the framework's layer naming
#: (gluon Dense kernels are (units, in_units); conv kernels (O, I, kh, kw)).
#: Megatron-style: alternate column/row splits would need per-layer pairing,
#: so the generic default shards every big matmul's output dim and
#: all-reduces activations — correct for any graph.
DEFAULT_TP_RULES = make_sharding_rules(
    (r"(dense|fc|proj|embedding).*weight$", P(AXIS_TP, None), 2),
    (r"conv.*weight$", P(AXIS_TP, None, None, None), 4),
    (r"(dense|fc|proj).*bias$", P(AXIS_TP), 1),
)


def spec_for(name, val, rules):
    for r in rules:
        if r.matches(name, val):
            return r.spec
    return P()  # replicated


def match_partition_rules(rules, params, mesh=None, scalars_replicated=True):
    """Resolve a named param tree to a ``{name: PartitionSpec}`` tree.

    The rule-driven placement front door (SNIPPETS [2]'s
    ``match_partition_rules`` shape): every entry of ``params`` (a
    ``{name: array-or-ShapeDtypeStruct}`` dict) gets the spec of the first
    matching rule, replicated when none matches.  Scalars / single-element
    leaves are never partitioned.  With ``mesh`` each resolved spec is
    validated against the leaf's shape — a sharded dim not divisible by
    its mesh axes falls back to replication, warned once per param and
    counted on the ``sharding.fallbacks`` telemetry counter (a mis-sized
    mesh must be visible, not quietly slow).
    """
    rules = make_sharding_rules(*rules) if rules else []
    out = {}
    for name, val in params.items():
        shape = tuple(getattr(val, "shape", ()))
        if scalars_replicated and (not shape or math.prod(shape) == 1):
            out[name] = P()
            continue
        spec = spec_for(name, val, rules)
        if mesh is not None:
            spec = _validate_spec(spec, shape, mesh, name=name)
        out[name] = spec
    return out


def zero1_spec(shape, mesh, axis=AXIS_DP, base=None, name=None):
    """ZeRO-1 placement for one gradient / optimizer-state leaf: shard
    the first dim divisible by the ``axis`` size that the base (param)
    spec leaves unsharded, per "Automatic Cross-Replica Sharding of
    Weight Update in Data-Parallel Training" (arXiv 2004.13336) — the
    optimizer update runs 1/N per replica between a gradient
    reduce-scatter and a parameter all-gather.  Falls back to the base
    spec (replication) when no dim divides — counted/warned via
    :func:`_note_fallback` so a mesh too wide for its smallest params is
    visible."""
    shape = tuple(shape)
    base_t = tuple(base or ()) + (None,) * (len(shape) - len(base or ()))
    if axis not in mesh.shape:
        # same contract as _validate_spec: an absent axis is a counted
        # fallback, not a KeyError — the zero axis name is shared across
        # mesh shapes too
        if math.prod(shape or (1,)) > 1:
            _note_missing_axis(name, shape, [axis], mesh)
        return P(*base_t) if base else P()
    n = mesh.shape[axis]
    if n > 1:
        for d, s in enumerate(shape):
            if base_t[d] is None and s and s % n == 0:
                return P(*(base_t[:d] + (axis,) + base_t[d + 1:]))
    # only a leaf that ends up with NO sharded dim at all is a
    # replication fallback worth flagging — a tp-sharded base that
    # merely couldn't ALSO take the dp dim still lives partitioned
    if n > 1 and math.prod(shape or (1,)) > 1 and \
            all(a is None for a in base_t):
        _note_fallback(name, shape, (axis,), n)
    return P(*base_t) if base else P()


def zero1_partition(params, mesh, axis=AXIS_DP, base_specs=None):
    """{name: PartitionSpec} sharding every leaf 1/N over ``axis`` where
    its shape allows (:func:`zero1_spec`); ``base_specs`` carries any
    existing param placement (e.g. tp) the zero dim must compose with."""
    base_specs = base_specs or {}
    return {
        name: zero1_spec(getattr(val, "shape", ()), mesh, axis=axis,
                         base=base_specs.get(name), name=name)
        for name, val in params.items()}


def fresh_device_put(x, target):
    """Place ``x`` onto ``target`` through a jitted identity, which
    guarantees the result is a FRESH XLA-owned allocation sharing no
    buffers with ``x``.  An eager ``device_put`` may hand back buffers
    aliasing the source (observed on this backend for same-device
    replica shards) — donating such a result while the source stays
    referenced (checkpoint-loaded params held by ``Module._arg_params``,
    optimizer state retained by the Updater) frees memory out from
    under the live alias: flaky SIGSEGV / "corrupted double-linked
    list" on the FIRST fused dispatch after a resume (PR-7 root cause).
    Use this, not device_put, for anything that feeds a donated input
    tree.  Setup-path cost only — callers short-circuit when the data
    already has the target sharding.

    Two steps because jit refuses inputs committed to a narrower device
    set than ``out_shardings`` span: the eager move first (its result
    may alias ``x`` — harmless, it is never donated and dies here), then
    the jitted identity whose outputs XLA allocates fresh.  The jitted
    mover is cached per target sharding (one wrapper serving every
    shape), so a K-param resume costs K shape-compiles of a trivial
    program, not K cold trace+compile wrappers."""
    moved = jax.device_put(x, target)
    return _fresh_mover(target)(moved)


#: Mesh (weak) -> {PartitionSpec: jitted identity}.  Weakly keyed on the
#: mesh so an elastic rebind that retires a mesh drops its movers (and
#: their per-shape compiled executables) instead of pinning every mesh
#: this process ever made; races just build a duplicate jit (benign).
_movers = weakref.WeakKeyDictionary()


def _fresh_mover(target):
    per_mesh = _movers.setdefault(target.mesh, {})
    fn = per_mesh.get(target.spec)
    if fn is None:
        fn = per_mesh[target.spec] = \
            jax.jit(lambda v: v, out_shardings=target)
    return fn


def named_sharding(mesh, spec):
    return NamedSharding(mesh, spec if isinstance(spec, P) else P(*spec))


def replicated(mesh):
    return NamedSharding(mesh, P())


def logical_to_mesh(mesh, tree_of_specs):
    """Map a pytree of PartitionSpec to NamedSharding on ``mesh``."""
    return jax.tree_util.tree_map(
        lambda s: named_sharding(mesh, s), tree_of_specs,
        is_leaf=lambda s: isinstance(s, P))


def shard_params(params, mesh, rules=None, donate=False):
    """Place a {name: array} pytree onto the mesh per the rules.

    Arrays whose sharded dim is not divisible by the axis size fall back
    to replication (the reference similarly falls back to copying small
    arrays whole, kvstore_dist.h big-array bound) — warned once per name
    and counted on ``sharding.fallbacks``.

    ``donate`` frees each source buffer once its resharded copy exists:
    a re-placement of a large param tree briefly holds source + target
    otherwise, which at scale is the difference between fitting the
    reshard in HBM or not.  The hazard making this non-trivial: a
    ``device_put`` that does NOT move data may ALIAS the source buffer
    (the NDArray.copyto lesson, PERF.md §6) — deleting the source then
    tears down the result too.  (jit-identity donation can't help
    either: a cross-layout donation is "not usable" to XLA and the
    source survives.)  So the source is deleted only when the placement
    actually changed AND the result demonstrably shares no device
    buffers with it.  Sources that are not live jax arrays (numpy
    inputs) have nothing to donate and take the plain path.
    """
    rules = make_sharding_rules(*rules) if rules else []
    out = {}
    for name, val in params.items():
        spec = spec_for(name, val, rules)
        spec = _validate_spec(spec, getattr(val, "shape", ()), mesh,
                              name=name)
        target = named_sharding(mesh, spec)
        if donate and isinstance(val, jax.Array) and \
                getattr(val, "sharding", None) != target:
            # fresh_device_put, NOT a bare device_put: an eager
            # same-device device_put may hand back buffers aliasing the
            # source (observed on this backend: one shard of the
            # dp-split output pointed into the replicated source),
            # making the delete below a use-after-free — and a bare
            # jitted reshard rejects sources committed to fewer devices
            # than the mesh (checkpoint-loaded params).  The alias
            # check still guards the delete because the fresh-buffer
            # guarantee is the whole safety argument.
            placed = fresh_device_put(val, target)
            if not _shares_buffers(placed, val):
                val.delete()
        else:
            placed = jax.device_put(val, target)
        out[name] = placed
    return out


def _shares_buffers(a, b):
    """True when two arrays have any device buffer in common (or when it
    cannot be proven they don't — deleting a maybe-aliased source is the
    one unrecoverable outcome, so uncertainty reads as 'shares')."""
    try:
        pa = {s.data.unsafe_buffer_pointer() for s in a.addressable_shards}
        pb = {s.data.unsafe_buffer_pointer() for s in b.addressable_shards}
    except Exception:
        return True
    return bool(pa & pb)


#: param names already warned about a replication fallback — the warning
#: is one-time per name so an epoch loop can't flood the log, but the
#: ``sharding.fallbacks`` counter ticks every placement decision.
_fallback_warned = set()
_fallback_lock = threading.Lock()


def _note_fallback(name, shape, axes, size):
    from .. import telemetry as _telemetry
    _telemetry.counter("sharding.fallbacks").inc()
    label = name if name is not None else "<unnamed>"
    with _fallback_lock:
        if label in _fallback_warned:
            return
        _fallback_warned.add(label)
    logging.warning(
        "mxnet_tpu.parallel.sharding: %r (shape %s) cannot shard over "
        "mesh axes %s (size %d does not divide the dim) — replicating "
        "instead.  A replicated fallback costs memory and bandwidth, "
        "not correctness; resize the mesh axis or the layer if this "
        "param matters (counter: sharding.fallbacks)",
        label, tuple(shape), tuple(axes), size)


def _validate_spec(spec, shape, mesh, name=None):
    fixed = []
    for d, axis in enumerate(tuple(spec) + (None,) * (len(shape) - len(spec))):
        if axis is None:
            fixed.append(None)
            continue
        axes = axis if isinstance(axis, tuple) else (axis,)
        # a rule may name an axis this bind's mesh simply doesn't have
        # (the tp cookbook rules on a dp-only Module bind): that's a
        # counted replication fallback, not a KeyError — rule sets are
        # written once and reused across mesh shapes
        missing = [a for a in axes if a not in mesh.shape]
        if missing:
            fixed.append(None)
            _note_missing_axis(name, shape, missing, mesh)
            continue
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if shape[d] % size == 0:
            fixed.append(axis)
        else:
            fixed.append(None)
            _note_fallback(name, shape, axes, size)
    if all(a is None for a in fixed):  # canonical: replicated is P()
        fixed = []
    return P(*fixed)


def _note_missing_axis(name, shape, missing, mesh):
    from .. import telemetry as _telemetry
    _telemetry.counter("sharding.fallbacks").inc()
    label = name if name is not None else "<unnamed>"
    with _fallback_lock:
        if (label, "axis") in _fallback_warned:
            return
        _fallback_warned.add((label, "axis"))
    logging.warning(
        "mxnet_tpu.parallel.sharding: %r (shape %s) names mesh axes %s "
        "this bind's mesh %s does not have — replicating that dim "
        "instead.  Harmless if the rule set is shared across mesh "
        "shapes; counted on sharding.fallbacks",
        label, tuple(shape), missing, dict(mesh.shape))


def batch_spec(ndim, axis=AXIS_DP):
    """PartitionSpec sharding dim 0 (the batch) over ``axis``."""
    return P(axis, *([None] * (ndim - 1)))
