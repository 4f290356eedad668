"""The graph rewrite pass pipeline (ROADMAP item 3).

NNVM-style graph-level optimization as a first-class, inspectable
compiler stage between ``simple_bind`` and trace→jit, in the spirit of
TVM (arXiv 1802.04799) and Relay (arXiv 1810.00952): each pass is a pure
``Graph -> Graph`` function registered in an ordered, env-configurable
pipeline.  Built-in passes, in default order:

- ``fuse`` — pattern fusion: ``Convolution→BatchNorm(→Activation)``
  (pre-scaled weights in eval, the exact composition in train),
  ``FullyConnected→Activation`` (transpose-free dot), and
  ``elemwise_add→LayerNorm`` (the transformer sublayer epilogue, a
  Pallas kernel on TPU) — ops/fused.py.
- ``fold`` — constant folding: parameter-free subgraphs (attention
  masks, position ids, shape constants) evaluate ONCE here and become
  ``_graph_constant`` literals; RNG-consuming, train-dependent and
  aux-mutating ops never fold.
- ``cse`` — common-subexpression elimination over the topo order (same
  op, same canonical params, same inputs; RNG/stateful ops excluded).
- ``dce`` — dead-node elimination: drops nodes unreachable from the
  heads (the orphans fuse/cse leave behind).

Configuration: ``MXTPU_GRAPH_PASSES`` — comma-separated pass names, in
run order; unset/empty means the default pipeline; ``0``/``off``/
``none`` disables rewriting entirely.  The pipeline version + enabled
set are part of the AOT cache fingerprint (aot_cache.fingerprint), so a
rewritten graph can never replay a pre-rewrite executable.

Every :func:`optimize` call produces a structured pass report — nodes
before/after, rewrites by pattern, per-pass wall time — published on
``graph.*`` telemetry gauges and stored as AOT entry metadata next to
the ``xla.cost.*`` attribution (executor._analyze_compiled).
"""
from __future__ import annotations

import logging
import os
import time

import numpy as _np

from ..base import MXNetError
from ..ops.fused import ACT_FUSABLE, ConstPayload
from ..ops.registry import _hashable, get_op
from ..symbol.symbol import _SymNode
from .graph import Graph, _clone_node, rebuild

__all__ = ["PIPELINE_VERSION", "register_pass", "list_passes",
           "pipeline_config", "enabled", "pipeline_fingerprint",
           "optimize", "run_pass", "last_report"]

#: bump when pass semantics change in a way that alters emitted graphs —
#: part of the AOT cache fingerprint
PIPELINE_VERSION = 1

_DEFAULT_PIPELINE = ("fuse", "fold", "cse", "dce")
_OFF_VALUES = ("0", "off", "none", "false")

_PASSES = {}
_warned_unknown = set()

#: the most recent optimize() report (debugging)
_last_report = None


def register_pass(name):
    """Register ``fn(graph) -> (graph, stats)`` as pass ``name`` — the
    extension point future kernels (MoE dispatch, quantized matmul)
    plug their patterns into."""
    def _reg(fn):
        _PASSES[name] = fn
        return fn
    return _reg


def list_passes():
    return sorted(_PASSES)


def pipeline_config():
    """The enabled pass names, in run order, from MXTPU_GRAPH_PASSES."""
    raw = os.environ.get("MXTPU_GRAPH_PASSES")
    if raw is None or not raw.strip():
        return _DEFAULT_PIPELINE
    if raw.strip().lower() in _OFF_VALUES:
        return ()
    names = []
    for name in raw.split(","):
        name = name.strip()
        if not name:
            continue
        if name not in _PASSES:
            if name not in _warned_unknown:
                _warned_unknown.add(name)
                logging.warning(
                    "mxnet_tpu.graph: unknown pass %r in "
                    "MXTPU_GRAPH_PASSES (have: %s) — skipping it",
                    name, ", ".join(list_passes()))
            continue
        names.append(name)
    return tuple(names)


def enabled():
    return bool(pipeline_config())


def pipeline_fingerprint():
    """Identity text for the AOT cache: version + enabled-pass set +
    every env knob that changes what the passes emit (the fold size cap
    decides which subgraphs become literals; MXTPU_LN_PALLAS decides
    the fused LN lowering).  A graph rewritten differently is a
    different program — stale pre-rewrite entries must miss, never
    execute."""
    return "graphpass-v%d:%s:fold%d:lnp%s" % (
        PIPELINE_VERSION, ",".join(pipeline_config()),
        _fold_max_bytes(), os.environ.get("MXTPU_LN_PALLAS", ""))


def last_report():
    return _last_report


# ---------------------------------------------------------------------------
# pass: pattern fusion
# ---------------------------------------------------------------------------

def _single_consumer(consumers, node):
    """The (consumer, slot) of ``node`` iff it has exactly one use and
    is not a head, else None."""
    uses = consumers.get(id(node), [])
    if len(uses) != 1 or uses[0][0] is None:
        return None
    return uses[0]


def _opname(node):
    return node.op.name if node.op is not None else "null"


def _merge_attrs(tail, members):
    attrs = {}
    for m in members:
        attrs.update(m.attrs or {})
    attrs.update(tail.attrs or {})
    attrs["__fused_ops__"] = "+".join(_opname(m) for m in members)
    attrs["__fused_names__"] = ",".join(m.name for m in members)
    return attrs


def _match_conv_bn_act(node, consumers):
    """``node`` is the chain tail.  Returns (conv, bn, act_type, members)
    or None.  The interior links must be single-consumer non-heads; BN
    must be the plain 1-output channel-axis form."""
    act_type = "linear"
    bn = node
    members = [node]
    if _opname(node) == "Activation":
        act_type = node.op.canon_params(node.params).get("act_type", "relu")
        if act_type not in ACT_FUSABLE:
            return None
        bn_entry = node.inputs[0]
        bn = bn_entry[0]
        if bn_entry[1] != 0 or _opname(bn) != "BatchNorm" or \
                _single_consumer(consumers, bn) is None:
            return None
        members = [bn, node]
    elif _opname(node) != "BatchNorm":
        return None
    bnp = bn.op.canon_params(bn.params)
    if bnp.get("output_mean_var") or int(bnp.get("axis", 1)) != 1:
        return None
    conv_entry = bn.inputs[0]
    conv = conv_entry[0]
    if conv_entry[1] != 0 or _opname(conv) != "Convolution" or \
            _single_consumer(consumers, conv) is None:
        return None
    convp = conv.op.canon_params(conv.params)
    if convp.get("layout") not in (None, "NCHW", "NCW", "NCDHW"):
        return None
    return conv, bn, act_type, [conv] + members


def _fuse_conv_bn_act(node, remap, consumers, stats):
    m = _match_conv_bn_act(node, consumers)
    if m is None:
        return None
    conv, bn, act_type, members = m
    convp = conv.op.canon_params(conv.params)
    bnp = bn.op.canon_params(bn.params)
    params = {k: convp.get(k) for k in
              ("kernel", "stride", "dilate", "pad", "num_filter",
               "num_group", "no_bias", "workspace")}
    params.update({k: bnp.get(k) for k in
                   ("eps", "momentum", "fix_gamma", "use_global_stats")})
    params["act_type"] = act_type
    # inputs: conv's data/weight(/bias), then bn's gamma/beta + aux
    inputs = [remap(e) for e in conv.inputs]
    inputs += [remap(e) for e in bn.inputs[1:]]  # gamma, beta, mm, mv
    stats["conv_bn_act"] = stats.get("conv_bn_act", 0) + 1
    return _SymNode(get_op("_fused_conv_bn_act"), node.name, params,
                    inputs, attrs=_merge_attrs(node, members))


def _dense_params(fc, act_type):
    fcp = fc.op.canon_params(fc.params)
    return {"num_hidden": fcp.get("num_hidden"),
            "no_bias": fcp.get("no_bias", False),
            "flatten": fcp.get("flatten", True),
            "act_type": act_type}


def _fuse_dense_act(node, remap, consumers, stats):
    if _opname(node) != "Activation":
        return None
    act_type = node.op.canon_params(node.params).get("act_type", "relu")
    if act_type not in ACT_FUSABLE:
        return None
    fc_entry = node.inputs[0]
    fc = fc_entry[0]
    if fc_entry[1] != 0 or _opname(fc) != "FullyConnected" or \
            _single_consumer(consumers, fc) is None:
        return None
    inputs = [remap(e) for e in fc.inputs]
    stats["dense_act"] = stats.get("dense_act", 0) + 1
    return _SymNode(get_op("_fused_dense_act"), node.name,
                    _dense_params(fc, act_type), inputs,
                    attrs=_merge_attrs(node, [fc, node]))


def _fuse_dense_bare(node, remap, consumers, stats):
    """A FullyConnected with no fusable activation still rewrites to the
    fused dense op with act_type='linear': the matmul contracts with
    dot_general directly, so the per-call weight transpose
    (``matmul(data, w.T)``) disappears from the lowered program —
    bit-identical output (same contraction, no reassociation)."""
    if _opname(node) != "FullyConnected":
        return None
    inputs = [remap(e) for e in node.inputs]
    stats["dense_bare"] = stats.get("dense_bare", 0) + 1
    return _SymNode(get_op("_fused_dense_act"), node.name,
                    _dense_params(node, "linear"), inputs,
                    attrs=_merge_attrs(node, [node]))


#: equal-shape adds only: a broadcast_add residual (e.g. a positional
#: embedding) would hand the Pallas kernel mismatched lhs/rhs shapes
_RESIDUAL_ADDS = ("elemwise_add", "_grad_add", "_Plus", "_plus")


def _fuse_layer_norm_residual(node, remap, consumers, stats):
    if _opname(node) != "LayerNorm":
        return None
    add_entry = node.inputs[0]
    add = add_entry[0]
    if add_entry[1] != 0 or _opname(add) not in _RESIDUAL_ADDS or \
            add.is_var or _single_consumer(consumers, add) is None:
        return None
    lnp = node.op.canon_params(node.params)
    params = {"axis": lnp.get("axis", -1), "eps": lnp.get("eps", 1e-5)}
    inputs = [remap(add.inputs[0]), remap(add.inputs[1])]
    inputs += [remap(e) for e in node.inputs[1:]]  # gamma, beta
    stats["layer_norm_residual"] = stats.get("layer_norm_residual", 0) + 1
    return _SymNode(get_op("_fused_layer_norm_residual"), node.name,
                    params, inputs, attrs=_merge_attrs(node, [add, node]))


def _fuse_batch_dot(node, remap, consumers, stats):
    """batch_dot with a transpose flag → transpose-free dot_general
    (same contraction, bit-identical; the swapaxes disappears from the
    lowered program).  Flag-free batch_dot already lowers to one
    dot_general and stays put."""
    if _opname(node) != "batch_dot":
        return None
    p = node.op.canon_params(node.params)
    if not (p.get("transpose_a") or p.get("transpose_b")):
        return None
    params = {"transpose_a": bool(p.get("transpose_a")),
              "transpose_b": bool(p.get("transpose_b"))}
    inputs = [remap(e) for e in node.inputs]
    stats["batch_dot"] = stats.get("batch_dot", 0) + 1
    return _SymNode(get_op("_fused_batch_dot"), node.name, params,
                    inputs, attrs=_merge_attrs(node, [node]))


_FUSE_MATCHERS = (_fuse_conv_bn_act, _fuse_dense_act,
                  _fuse_layer_norm_residual, _fuse_dense_bare,
                  _fuse_batch_dot)


@register_pass("fuse")
def fuse_patterns(graph):
    """Collapse known multi-op patterns into fused-region nodes.  Each
    match fires at the chain's TAIL; interiors it absorbs become
    unreachable (DCE removes them).  A BatchNorm whose only consumer is
    a fusable Activation defers to the longer conv→bn→act match."""
    consumers = graph.consumers()
    stats = {}

    def deferred_to_act(node):
        # bn/fc tail whose single consumer is a fusable act: let the
        # act tail claim the longer chain
        if _opname(node) not in ("BatchNorm", "FullyConnected"):
            return False
        use = _single_consumer(consumers, node)
        if use is None or use[1] != 0:
            return False
        consumer = use[0]
        if _opname(consumer) != "Activation":
            return False
        act = consumer.op.canon_params(consumer.params).get("act_type",
                                                            "relu")
        return act in ACT_FUSABLE

    def make(node, remap):
        if node.is_var or deferred_to_act(node):
            return None
        for matcher in _FUSE_MATCHERS:
            fused = matcher(node, remap, consumers, stats)
            if fused is not None:
                return fused
        return None

    return rebuild(graph, make), stats


# ---------------------------------------------------------------------------
# pass: constant folding
# ---------------------------------------------------------------------------

def _fold_max_bytes():
    return int(os.environ.get("MXTPU_GRAPH_FOLD_MAX_BYTES", 1 << 22))


@register_pass("fold")
def fold_constants(graph):
    """Evaluate parameter-free subgraphs once, at bind, and splice the
    results in as ``_graph_constant`` literals.  A node is foldable when
    it is not a variable, consumes no randomness (``needs_rng``), has no
    train-dependent behaviour (``takes_train``), mutates no auxiliary
    state (``mutate_aux``), and every input is foldable — RNG and
    side-effecting ops therefore never move, and neither does anything
    downstream of a variable.  Results larger than
    MXTPU_GRAPH_FOLD_MAX_BYTES stay unfolded (a literal that big belongs
    in HBM as a computed tensor, not in the program text)."""
    nodes = graph.nodes
    foldable = {}
    for node in nodes:
        if node.is_var or node.op is None:
            foldable[id(node)] = False
            continue
        foldable[id(node)] = (
            not node.op.needs_rng and not node.op.takes_train and
            not node.op.mutate_aux and node.op.name != "_graph_constant" and
            all(foldable.get(id(inp), False) for inp, _ in node.inputs))
    if not any(foldable.values()):
        return graph, {"folded": 0, "constants": 0}

    # boundary entries: (const node, out idx) consumed by a NON-const
    # node or exported as a head — these materialize as literals
    boundary = set()
    for node in nodes:
        if node.is_var or foldable[id(node)]:
            continue
        for inp, idx in node.inputs:
            if foldable.get(id(inp), False):
                boundary.add((id(inp), idx))
    for n, i in graph.heads:
        if foldable.get(id(n), False):
            boundary.add((id(n), i))
    if not boundary:
        return graph, {"folded": 0, "constants": 0}

    # evaluate the const region eagerly, once, node by node
    values = {}

    def value_of(node):
        if id(node) in values:
            return values[id(node)]
        inputs = [value_of(inp)[idx] for inp, idx in node.inputs]
        out = node.op.fn(*inputs, **node.op.canon_params(dict(node.params)))
        flat = list(out) if isinstance(out, (tuple, list)) else [out]
        values[id(node)] = flat
        return flat

    const_nodes = {}   # (id(producer), idx) -> _graph_constant node
    cap = _fold_max_bytes()
    for node in nodes:
        for idx in range(0 if node.is_var else node.num_outputs()):
            if (id(node), idx) not in boundary:
                continue
            try:
                val = _np.asarray(value_of(node)[idx])
            except Exception as e:  # a fold that can't evaluate stays put
                logging.warning("mxnet_tpu.graph: constant fold of %s "
                                "failed (%s: %s); leaving it in the graph",
                                node.name, type(e).__name__, e)
                continue
            if val.nbytes > cap:
                continue
            name = node.name if idx == 0 else "%s_out%d" % (node.name, idx)
            const_nodes[(id(node), idx)] = _SymNode(
                get_op("_graph_constant"), "%s_folded" % name,
                {"value": ConstPayload(val)}, [],
                attrs=dict(node.attrs or {}))

    if not const_nodes:
        return graph, {"folded": 0, "constants": 0}

    # splice: walk the topo order redirecting every boundary entry at
    # its literal; const nodes (no inputs) go right after their producer
    # so the node list stays topologically sorted
    new_of = {}

    def map_entry(entry):
        old, idx = entry
        c = const_nodes.get((id(old), idx))
        return (c, 0) if c is not None else (new_of[id(old)], idx)

    new_nodes = []
    for node in nodes:
        if node.is_var:
            new_of[id(node)] = node
            new_nodes.append(node)
        else:
            new_inputs = [map_entry(e) for e in node.inputs]
            if all(n is o[0]
                   for (n, _), o in zip(new_inputs, node.inputs)):
                node2 = node
            else:
                node2 = _clone_node(node, new_inputs)
            new_of[id(node)] = node2
            new_nodes.append(node2)
            for idx in range(node.num_outputs()):
                c = const_nodes.get((id(node), idx))
                if c is not None:
                    new_nodes.append(c)
    heads = [map_entry(h) for h in graph.heads]
    out = Graph(new_nodes, heads)
    # honest accounting: "folded" counts only region ops the splice
    # actually disconnected from the heads — a boundary that stayed put
    # (over the size cap, failed eval) keeps its subtree live and those
    # nodes must not be reported as removed
    live = out.reachable()
    n_folded = sum(1 for node in nodes
                   if not node.is_var and foldable[id(node)]
                   and id(new_of[id(node)]) not in live)
    return out, {"folded": n_folded, "constants": len(const_nodes)}


# ---------------------------------------------------------------------------
# pass: common-subexpression elimination
# ---------------------------------------------------------------------------

@register_pass("cse")
def eliminate_common_subexpr(graph):
    """Merge structurally identical nodes: same op, same canonical
    params, same (already-merged) inputs.  RNG-consuming and
    aux-mutating nodes never merge (two Dropouts with identical inputs
    are two independent draws; two BatchNorms own distinct moving
    stats).  Variables never merge — their NAME is their identity."""
    rep = {}       # id(node) -> representative node (in the new graph)
    by_key = {}
    merged = 0
    new_nodes = []
    for node in graph.nodes:
        if node.is_var:
            rep[id(node)] = node
            new_nodes.append(node)
            continue
        new_inputs = [(rep[id(i)], idx) for i, idx in node.inputs]
        changed = any(n is not o[0]
                      for (n, _), o in zip(new_inputs, node.inputs))
        if node.op.needs_rng or node.op.mutate_aux:
            key = None
        else:
            try:
                key = (id(node.op),
                       _hashable(node.op.canon_params(dict(node.params))),
                       tuple((id(n), idx) for n, idx in new_inputs))
            except TypeError:
                key = None
        if key is not None and key in by_key:
            rep[id(node)] = by_key[key]
            merged += 1
            continue
        if changed:
            node2 = _clone_node(node, new_inputs)
        else:
            node2 = node
        rep[id(node)] = node2
        if key is not None:
            by_key[key] = node2
        new_nodes.append(node2)
    heads = [(rep[id(n)], i) for n, i in graph.heads]
    return Graph(new_nodes, heads), {"merged": merged}


# ---------------------------------------------------------------------------
# pass: dead-node elimination
# ---------------------------------------------------------------------------

@register_pass("dce")
def eliminate_dead_nodes(graph):
    """Drop nodes unreachable from the heads — ONLY those (the
    equivalence law tests pin this): everything contributing to any
    head survives, including aux-mutating ops feeding nothing else."""
    live = graph.reachable()
    kept = [n for n in graph.nodes if id(n) in live]
    removed = len(graph.nodes) - len(kept)
    return Graph(kept, graph.heads), {"removed": removed}


# ---------------------------------------------------------------------------
# the pipeline
# ---------------------------------------------------------------------------

def run_pass(name, graph):
    """Run one registered pass; returns (graph, stats)."""
    fn = _PASSES.get(name)
    if fn is None:
        raise MXNetError("unknown graph pass %r (have: %s)"
                         % (name, ", ".join(list_passes())))
    return fn(graph)


def optimize(symbol, passes=None):
    """Run the configured pipeline over ``symbol``'s graph.  Returns
    ``(rewritten_symbol, report)``; with the pipeline disabled (or no
    rewrites fired) the original symbol comes back unchanged.  The
    report lands on ``graph.*`` telemetry gauges and rides into AOT
    entry metadata next to the ``xla.cost.*`` attribution."""
    global _last_report
    from .. import telemetry as _telemetry

    names = tuple(passes) if passes is not None else pipeline_config()
    g = Graph.from_symbol(symbol)
    before = len(g)
    before_ops = g.num_ops()
    report = {"version": PIPELINE_VERSION, "pipeline": list(names),
              "nodes_before": before, "ops_before": before_ops,
              "passes": [], "rewrites": {}}
    t_total = time.perf_counter()
    changed = False
    for name in names:
        fn = _PASSES.get(name)
        if fn is None:
            raise MXNetError("unknown graph pass %r" % name)
        n0 = len(g)
        t0 = time.perf_counter()
        g, stats = fn(g)
        ms = (time.perf_counter() - t0) * 1e3
        entry = {"name": name, "nodes_before": n0, "nodes_after": len(g),
                 "ms": round(ms, 3)}
        entry.update(stats)
        report["passes"].append(entry)
        for k, v in stats.items():
            if isinstance(v, int) and v:
                report["rewrites"][k] = report["rewrites"].get(k, 0) + v
                changed = True
    report["nodes_after"] = len(g)
    report["ops_after"] = g.num_ops()
    report["total_ms"] = round((time.perf_counter() - t_total) * 1e3, 3)
    _telemetry.gauge("graph.nodes_before").set(before)
    _telemetry.gauge("graph.nodes_after").set(report["nodes_after"])
    _telemetry.gauge("graph.rewrites").set(
        sum(report["rewrites"].values()))
    _telemetry.gauge("graph.pass_ms").set(report["total_ms"])
    _telemetry.counter("graph.optimize_calls").inc()
    _last_report = report
    if not changed:
        # nothing fired: hand back the ORIGINAL symbol so executors can
        # share plans/identity with the unrewritten path
        return symbol, report
    return g.to_symbol(), report
