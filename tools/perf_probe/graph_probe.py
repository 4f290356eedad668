"""BENCH_MODE=graph probe: the rewrite pipeline's measurable contract.

Builds the two bench graphs (PERF.md §6) as symbols — a ResNet-style
conv→bn→relu residual tower and a post-LN GPT transformer stack whose
attention masks are built symbolically per block — binds each with the
pipeline ON and OFF, and measures:

- **HLO instruction count** of the lowered forward program (the
  pre-optimization module ``jit(...).lower()`` hands XLA): the number
  the graph stage directly controls — what a graph-level rewrite saves
  BEFORE the backend ever sees it.  Contract: >= 15% fewer with the
  pipeline on, for both graphs.  The post-XLA compiled count is
  reported alongside for reference.
- **output equivalence**: pipeline-on forward == pipeline-off forward
  (rtol 1e-6 fp32), eval and train.
- **step-time**: median wall time of the compiled forward, on vs off
  (reported; eval-mode conv+bn folding and constant-folded masks are
  where the win comes from).
- **steptrace invariants with the pipeline enabled**: a short fused fit
  loop over the fusable conv net must hold 1.0 dispatch/step with 0
  steady-state recompiles (the recompile contract).

Prints one JSON document; bench.py BENCH_MODE=graph asserts the
contracts and emits the driver row.

Usage: JAX_PLATFORMS=cpu python tools/perf_probe/graph_probe.py
"""
from __future__ import annotations

import contextlib
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

HLO_CONTRACT = 0.15  # >= 15% fewer lowered-HLO instructions


# ---------------------------------------------------------------------------
# bench graphs
# ---------------------------------------------------------------------------

def build_resnet_sym(blocks=8, filters=16):
    """Conv→BN→ReLU residual tower with a BN'd projection stem and a
    dense head — every unit is the pattern the fuse pass targets."""
    import mxnet_tpu as mx

    def conv_bn_relu(x, name, act=True, **kw):
        x = mx.sym.Convolution(x, kernel=(3, 3), pad=(1, 1), no_bias=True,
                               num_filter=filters, name="%s_conv" % name,
                               **kw)
        x = mx.sym.BatchNorm(x, fix_gamma=False, name="%s_bn" % name)
        if act:
            x = mx.sym.Activation(x, act_type="relu", name="%s_relu" % name)
        return x

    net = mx.sym.Variable("data")
    net = conv_bn_relu(net, "stem")
    for i in range(blocks):
        inner = conv_bn_relu(net, "b%d_u1" % i)
        inner = conv_bn_relu(inner, "b%d_u2" % i, act=False)
        net = mx.sym.Activation(net + inner, act_type="relu",
                                name="b%d_out" % i)
    net = mx.sym.Pooling(net, global_pool=True, pool_type="avg",
                         name="gap")
    net = mx.sym.FullyConnected(net, num_hidden=64, name="head_fc")
    net = mx.sym.Activation(net, act_type="relu", name="head_relu")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="logits")
    return mx.sym.SoftmaxOutput(net, name="softmax"), \
        {"data": (8, 3, 16, 16), "softmax_label": (8,)}


def build_gpt_sym(layers=4, units=64, heads=4, seq=128, vocab=128):
    """Post-LN transformer stack over symbols.  The causal mask is
    constructed SYMBOLICALLY inside every block (arange → reshape →
    compare → scale), exactly the per-layer redundancy an op-by-op
    frontend emits — constant folding evaluates each chain once at bind
    and CSE merges the copies; LayerNorm(x + sublayer) is the
    fused-epilogue pattern; FFN is FullyConnected→gelu."""
    import mxnet_tpu as mx
    d = units // heads

    def causal_bias(name):
        # (T, T) additive bias: 0 where k<=q, -1e9 above the diagonal —
        # parameter-free, so the fold pass turns the whole chain into
        # one literal (and CSE dedups it across blocks first)
        q = mx.sym.Reshape(mx.sym._arange(start=0, stop=seq,
                                          name="%s_qpos" % name),
                           shape=(seq, 1))
        k = mx.sym.Reshape(mx.sym._arange(start=0, stop=seq,
                                          name="%s_kpos" % name),
                           shape=(1, seq))
        keep = mx.sym.broadcast_greater_equal(q, k)  # 1 where visible
        return (keep - 1.0) * 1e9  # 0 visible, -1e9 masked

    def block(x, name):
        # attention sublayer (batched heads via reshape+batch_dot)
        qkv = mx.sym.FullyConnected(x, num_hidden=3 * units, flatten=False,
                                    name="%s_qkv" % name)
        qkv = mx.sym.Reshape(qkv, shape=(-1, seq, 3, heads, d))
        qkv = mx.sym.transpose(qkv, axes=(2, 0, 3, 1, 4))
        q = mx.sym.Reshape(mx.sym.slice_axis(qkv, axis=0, begin=0, end=1),
                           shape=(-1, seq, d))
        k = mx.sym.Reshape(mx.sym.slice_axis(qkv, axis=0, begin=1, end=2),
                           shape=(-1, seq, d))
        v = mx.sym.Reshape(mx.sym.slice_axis(qkv, axis=0, begin=2, end=3),
                           shape=(-1, seq, d))
        scores = mx.sym.batch_dot(q, k, transpose_b=True) * (d ** -0.5)
        scores = mx.sym.broadcast_add(scores, causal_bias(name))
        att = mx.sym.batch_dot(mx.sym.softmax(scores, axis=-1), v)
        att = mx.sym.Reshape(att, shape=(-1, heads, seq, d))
        att = mx.sym.Reshape(mx.sym.transpose(att, axes=(0, 2, 1, 3)),
                             shape=(-1, seq, units))
        att = mx.sym.FullyConnected(att, num_hidden=units, flatten=False,
                                    name="%s_proj" % name)
        x = mx.sym.LayerNorm(x + att, name="%s_ln1" % name)
        # FFN sublayer
        h = mx.sym.FullyConnected(x, num_hidden=4 * units, flatten=False,
                                  name="%s_fc1" % name)
        h = mx.sym.Activation(h, act_type="gelu", name="%s_gelu" % name)
        h = mx.sym.FullyConnected(h, num_hidden=units, flatten=False,
                                  name="%s_fc2" % name)
        return mx.sym.LayerNorm(x + h, name="%s_ln2" % name)

    tokens = mx.sym.Variable("data")
    h = mx.sym.Embedding(tokens, input_dim=vocab, output_dim=units,
                         name="wte")
    pos = mx.sym._arange(start=0, stop=seq, name="pos_ids")
    h = mx.sym.broadcast_add(
        h, mx.sym.expand_dims(
            mx.sym.Embedding(pos, input_dim=seq, output_dim=units,
                             name="wpe"), axis=0))
    for i in range(layers):
        h = block(h, "h%d" % i)
    h = mx.sym.FullyConnected(h, num_hidden=vocab, flatten=False,
                              name="lm_head")
    return mx.sym.SoftmaxOutput(h, preserve_shape=True, name="softmax"), \
        {"data": (2, seq), "softmax_label": (2, seq)}


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------

_INSTR_RE = re.compile(r"^\s+\S+ = ", re.M)


def count_instructions(hlo_text):
    return len(_INSTR_RE.findall(hlo_text))


@contextlib.contextmanager
def pipeline(on):
    prev = os.environ.get("MXTPU_GRAPH_PASSES")
    os.environ["MXTPU_GRAPH_PASSES"] = "" if on else "off"
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("MXTPU_GRAPH_PASSES", None)
        else:
            os.environ["MXTPU_GRAPH_PASSES"] = prev


def _bind(sym, shapes, on, type_dict=None):
    import mxnet_tpu as mx
    with pipeline(on):
        return sym.simple_bind(mx.cpu(), grad_req="null",
                               type_dict=type_dict, **shapes)


def _seed_params(exe, shapes, rs):
    import numpy as np
    for name, arr in sorted(exe.arg_dict.items()):
        if name in shapes:
            continue
        arr[:] = rs.randn(*arr.shape).astype(np.float32) * 0.1
    for name, arr in sorted(exe.aux_dict.items()):
        if name.endswith("moving_var"):
            arr[:] = np.abs(rs.randn(*arr.shape).astype(np.float32)) + 0.5
        else:
            arr[:] = rs.randn(*arr.shape).astype(np.float32) * 0.1


def measure_graph(name, sym, shapes, data_fn, train=False, reps=30):
    """Lowered/compiled instruction counts, forward equivalence and
    median step time, pipeline on vs off."""
    import numpy as np
    import jax

    feeds = data_fn()
    sides = {}
    for on in (False, True):
        exe = _bind(sym, shapes, on)
        rs = np.random.RandomState(7)
        _seed_params(exe, shapes, rs)
        for k, v in feeds.items():
            exe.arg_dict[k][:] = v
        plan = exe._plan
        args = {k: v._data for k, v in exe.arg_dict.items()}
        aux = {k: v._data for k, v in exe.aux_dict.items()}
        rng = jax.random.PRNGKey(0)

        def fwd(a, x):
            return plan(a, x, rng, train)[0]

        lowered = jax.jit(fwd).lower(args, aux)
        compiled = lowered.compile()
        out = compiled(args, aux)
        jax.block_until_ready(out)
        sides[on] = {
            "lowered_instructions": count_instructions(lowered.as_text()),
            "compiled_instructions":
                count_instructions(compiled.as_text()),
            "outputs": [np.asarray(o) for o in out],
            "report": exe._graph_report,
            "_call": (compiled, args, aux),
        }
    # interleaved timing (paired off/on segments, median — cancels the
    # slow CPU drift that dwarfs small effects, bench_telemetry style)
    for side in sides.values():
        compiled, args, aux = side["_call"]
        jax.block_until_ready(compiled(args, aux))
    times = {False: [], True: []}
    for _ in range(reps):
        for on in (False, True):
            compiled, args, aux = sides[on]["_call"]
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(args, aux))
            times[on].append(time.perf_counter() - t0)
    for on in (False, True):
        ts = sorted(times[on])
        sides[on]["fwd_ms_p50"] = round(ts[len(ts) // 2] * 1e3, 3)
        del sides[on]["_call"]
    off, on = sides[False], sides[True]
    err = 0.0
    for a, b in zip(off["outputs"], on["outputs"]):
        denom = np.maximum(np.abs(a), 1e-6)
        err = max(err, float(np.max(np.abs(a - b) / denom)))
    reduction = 1.0 - on["lowered_instructions"] / \
        max(1, off["lowered_instructions"])
    return {
        "graph": name,
        "train": train,
        "lowered_instructions_off": off["lowered_instructions"],
        "lowered_instructions_on": on["lowered_instructions"],
        "lowered_reduction": round(reduction, 4),
        "compiled_instructions_off": off["compiled_instructions"],
        "compiled_instructions_on": on["compiled_instructions"],
        "fwd_ms_p50_off": off["fwd_ms_p50"],
        "fwd_ms_p50_on": on["fwd_ms_p50"],
        "fwd_speedup": round(
            off["fwd_ms_p50"] / max(on["fwd_ms_p50"], 1e-9), 3),
        "max_rel_err": err,
        "pass_report": on["report"],
    }


def steptrace_with_pipeline():
    """The recompile contract: a fused fit loop over a FUSABLE net
    (conv→bn→relu stem + dense head) with the pipeline enabled must
    keep the steptrace invariants — 1.0 dispatch/step, 0 steady-state
    compiles."""
    import numpy as np
    import mxnet_tpu as mx
    import steptrace as _steptrace

    rs = np.random.RandomState(0)
    X = rs.randn(4 * 8, 3, 8, 8).astype(np.float32)
    y = rs.randint(0, 4, 4 * 8).astype(np.float32)
    train = mx.io.NDArrayIter(X, y, batch_size=8, shuffle=False,
                              label_name="softmax_label")
    net = mx.sym.Variable("data")
    net = mx.sym.Convolution(net, kernel=(3, 3), pad=(1, 1), num_filter=8,
                             no_bias=True, name="c1")
    net = mx.sym.BatchNorm(net, fix_gamma=False, name="bn1")
    net = mx.sym.Activation(net, act_type="relu", name="r1")
    net = mx.sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="fa1")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    s = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(s, context=mx.cpu())
    mod.bind(data_shapes=train.provide_data,
             label_shapes=train.provide_label)
    mod.init_params(mx.initializer.Uniform(0.1))
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.05),
                                         ("momentum", 0.9)))
    batches = list(train)
    stats = _steptrace.trace(mod.fit_step, batches)
    stats["fused_patterns"] = (mod.graph_report or {}).get("rewrites")
    return stats


def run():
    import numpy as np
    import jax  # noqa: F401 — fail early off-thread if backend is broken

    rs = np.random.RandomState(3)
    resnet_sym, resnet_shapes = build_resnet_sym()
    gpt_sym, gpt_shapes = build_gpt_sym()

    def resnet_feed():
        return {"data": rs.randn(*resnet_shapes["data"])
                .astype(np.float32)}

    def gpt_feed():
        return {"data": rs.randint(0, 128, gpt_shapes["data"])
                .astype(np.float32)}

    out = {
        "resnet": measure_graph("resnet", resnet_sym, resnet_shapes,
                                resnet_feed),
        "gpt": measure_graph("gpt", gpt_sym, gpt_shapes, gpt_feed),
        "steptrace": steptrace_with_pipeline(),
        "hlo_contract": HLO_CONTRACT,
    }
    return out


if __name__ == "__main__":
    print(json.dumps(run()))
