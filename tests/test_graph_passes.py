"""Equivalence laws for the graph rewrite pipeline (mxnet_tpu.graph).

Every pass must be semantics-preserving: pipeline-on executions match
pipeline-off executions on randomized graphs (rtol 1e-6 fp32; train-mode
fused regions are literal compositions and must be bit-exact), DCE
removes only unreachable nodes, folding never moves RNG or stateful
ops, and the pipeline is idempotent (optimizing twice == once).
"""
import contextlib
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import graph as G
from mxnet_tpu import nd
from mxnet_tpu.graph.passes import run_pass
from mxnet_tpu.graph.graph import Graph

pytestmark = pytest.mark.graph


@contextlib.contextmanager
def pipeline_env(value):
    """MXTPU_GRAPH_PASSES override ('' = default pipeline, 'off' =
    disabled, 'fuse,dce' = explicit)."""
    prev = os.environ.get("MXTPU_GRAPH_PASSES")
    os.environ["MXTPU_GRAPH_PASSES"] = value
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("MXTPU_GRAPH_PASSES", None)
        else:
            os.environ["MXTPU_GRAPH_PASSES"] = prev


# ---------------------------------------------------------------------------
# randomized graph builders
# ---------------------------------------------------------------------------

def random_conv_graph(seed):
    """Randomized conv tower: conv→bn(→relu) chains, residual adds,
    pooling, dense head — every fusion pattern plus plain ops."""
    r = np.random.RandomState(seed)
    x = mx.sym.Variable("data")
    c = 4
    for i in range(r.randint(2, 4)):
        y = mx.sym.Convolution(x, kernel=(3, 3), pad=(1, 1), num_filter=c,
                               no_bias=bool(r.randint(2)),
                               name="c%d_%d" % (seed, i))
        y = mx.sym.BatchNorm(y, fix_gamma=bool(r.randint(2)),
                             name="bn%d_%d" % (seed, i))
        if r.randint(2):
            y = mx.sym.Activation(y, act_type="relu",
                                  name="a%d_%d" % (seed, i))
        x = y + x if r.randint(2) else y
    x = mx.sym.Pooling(x, global_pool=True, pool_type="avg",
                       name="gap%d" % seed)
    x = mx.sym.FullyConnected(x, num_hidden=8, name="fc%d" % seed)
    if r.randint(2):
        x = mx.sym.Activation(x, act_type="tanh", name="ft%d" % seed)
    x = mx.sym.FullyConnected(x, num_hidden=3, name="out%d" % seed)
    return mx.sym.SoftmaxOutput(x, name="softmax"), \
        {"data": (2, c, 6, 6), "softmax_label": (2,)}


def random_transformer_graph(seed):
    """Randomized post-LN transformer-ish stack: LN(x+h) epilogues,
    dense+gelu, symbolic (foldable) position chain, batch_dot."""
    r = np.random.RandomState(100 + seed)
    T, C = 6, 8
    x = mx.sym.Variable("data")
    pos = mx.sym.Reshape(mx.sym._arange(start=0, stop=T,
                                        name="pos%d" % seed),
                         shape=(1, T, 1))
    h = mx.sym.broadcast_add(x, pos * 0.01)
    for i in range(r.randint(1, 3)):
        a = mx.sym.FullyConnected(h, num_hidden=C, flatten=False,
                                  name="att%d_%d" % (seed, i))
        if r.randint(2):
            s = mx.sym.batch_dot(a, a, transpose_b=True,
                                 name="bd%d_%d" % (seed, i))
            a = mx.sym.batch_dot(mx.sym.softmax(s, axis=-1), a,
                                 name="bo%d_%d" % (seed, i))
        h = mx.sym.LayerNorm(h + a, name="ln%d_%d" % (seed, i))
        f = mx.sym.FullyConnected(h, num_hidden=2 * C, flatten=False,
                                  name="f1%d_%d" % (seed, i))
        f = mx.sym.Activation(f, act_type="gelu",
                              name="g%d_%d" % (seed, i))
        f = mx.sym.FullyConnected(f, num_hidden=C, flatten=False,
                                  name="f2%d_%d" % (seed, i))
        h = mx.sym.LayerNorm(h + f, name="lf%d_%d" % (seed, i))
    h = mx.sym.FullyConnected(h, num_hidden=4, name="head%d" % seed)
    return mx.sym.SoftmaxOutput(h, name="softmax"), \
        {"data": (2, T, C), "softmax_label": (2,)}


def _bind_and_run(sym, shapes, passes, seed, train, data=None):
    """Bind under the given pipeline config, seed params identically,
    run forward (+backward when train) — returns (outs, grads, exe).
    ``data`` replaces the seeded normal input (token ids, say)."""
    with pipeline_env(passes):
        exe = sym.simple_bind(mx.cpu(), grad_req="write" if train
                              else "null", **shapes)
    r = np.random.RandomState(seed)
    feeds = {}
    for name, arr in sorted(exe.arg_dict.items()):
        if name == "data":
            feeds[name] = r.randn(*arr.shape).astype(np.float32) \
                if data is None else data
        elif name.endswith("label"):
            feeds[name] = r.randint(0, 3, arr.shape).astype(np.float32)
        else:
            arr[:] = r.randn(*arr.shape).astype(np.float32) * 0.2
    for name, arr in sorted(exe.aux_dict.items()):
        if name.endswith("moving_var"):
            arr[:] = np.abs(r.randn(*arr.shape).astype(np.float32)) + 0.5
        else:
            arr[:] = r.randn(*arr.shape).astype(np.float32) * 0.1
    outs = exe.forward(is_train=train, **feeds)
    outs = [o.asnumpy().copy() for o in outs]
    grads = {}
    if train:
        exe.backward()
        grads = {k: v.asnumpy().copy() for k, v in exe.grad_dict.items()
                 if v is not None}
    return outs, grads, exe


def assert_equivalent(sym, shapes, passes="", seed=0, train=False,
                      rtol=1e-6, atol=1e-6):
    o_off, g_off, _ = _bind_and_run(sym, shapes, "off", seed, train)
    o_on, g_on, exe = _bind_and_run(sym, shapes, passes, seed, train)
    for a, b in zip(o_off, o_on):
        np.testing.assert_allclose(a, b, rtol=rtol, atol=atol)
    assert set(g_off) == set(g_on)
    for k in g_off:
        np.testing.assert_allclose(g_off[k], g_on[k], rtol=rtol,
                                   atol=atol, err_msg="grad %s" % k)
    return exe


# ---------------------------------------------------------------------------
# randomized whole-pipeline laws
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_random_conv_graph_equivalent_eval(seed):
    sym, shapes = random_conv_graph(seed)
    exe = assert_equivalent(sym, shapes, seed=seed, train=False)
    assert exe._graph_report is not None


@pytest.mark.parametrize("seed", [0, 1])
def test_random_conv_graph_equivalent_train_with_grads(seed):
    sym, shapes = random_conv_graph(seed)
    assert_equivalent(sym, shapes, seed=seed, train=True)


@pytest.mark.parametrize("seed", [0, 1])
def test_random_transformer_graph_equivalent(seed):
    sym, shapes = random_transformer_graph(seed)
    assert_equivalent(sym, shapes, seed=seed, train=True)


def resnet_tower_graph(blocks=8, filters=16):
    """Conv→BN→ReLU residual tower with a BN'd stem and a dense head:
    every unit is the pattern the fuse pass targets."""
    def unit(x, name, act=True):
        x = mx.sym.Convolution(x, kernel=(3, 3), pad=(1, 1), no_bias=True,
                               num_filter=filters, name=name + "_conv")
        x = mx.sym.BatchNorm(x, fix_gamma=False, name=name + "_bn")
        return mx.sym.Activation(x, act_type="relu",
                                 name=name + "_relu") if act else x

    net = unit(mx.sym.Variable("data"), "stem")
    for i in range(blocks):
        inner = unit(unit(net, "b%d_u1" % i), "b%d_u2" % i, act=False)
        net = mx.sym.Activation(net + inner, act_type="relu",
                                name="b%d_out" % i)
    net = mx.sym.Pooling(net, global_pool=True, pool_type="avg",
                         name="gap")
    net = mx.sym.FullyConnected(net, num_hidden=64, name="head_fc")
    net = mx.sym.Activation(net, act_type="relu", name="head_relu")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="logits")
    return mx.sym.SoftmaxOutput(net, name="softmax"), \
        {"data": (8, 3, 16, 16), "softmax_label": (8,)}


def gpt_stack_graph(layers=4, units=64, heads=4, seq=128, vocab=128):
    """Post-LN transformer stack whose causal mask is built
    SYMBOLICALLY inside every block (arange → reshape → compare →
    scale), the per-layer redundancy an op-by-op frontend emits: folding
    evaluates each chain once at bind, CSE merges the copies, and
    LayerNorm(x + sublayer) is the fused-epilogue pattern."""
    d = units // heads

    def causal_bias(name):
        q = mx.sym.Reshape(mx.sym._arange(start=0, stop=seq,
                                          name=name + "_qpos"),
                           shape=(seq, 1))
        k = mx.sym.Reshape(mx.sym._arange(start=0, stop=seq,
                                          name=name + "_kpos"),
                           shape=(1, seq))
        return (mx.sym.broadcast_greater_equal(q, k) - 1.0) * 1e9

    def head_rows(qkv, i):
        return mx.sym.Reshape(
            mx.sym.slice_axis(qkv, axis=0, begin=i, end=i + 1),
            shape=(-1, seq, d))

    def block(x, name):
        qkv = mx.sym.FullyConnected(x, num_hidden=3 * units, flatten=False,
                                    name=name + "_qkv")
        qkv = mx.sym.transpose(
            mx.sym.Reshape(qkv, shape=(-1, seq, 3, heads, d)),
            axes=(2, 0, 3, 1, 4))
        q, k, v = (head_rows(qkv, i) for i in range(3))
        scores = mx.sym.batch_dot(q, k, transpose_b=True) * (d ** -0.5)
        scores = mx.sym.broadcast_add(scores, causal_bias(name))
        att = mx.sym.batch_dot(mx.sym.softmax(scores, axis=-1), v)
        att = mx.sym.transpose(
            mx.sym.Reshape(att, shape=(-1, heads, seq, d)),
            axes=(0, 2, 1, 3))
        att = mx.sym.FullyConnected(
            mx.sym.Reshape(att, shape=(-1, seq, units)),
            num_hidden=units, flatten=False, name=name + "_proj")
        x = mx.sym.LayerNorm(x + att, name=name + "_ln1")
        h = mx.sym.FullyConnected(x, num_hidden=4 * units, flatten=False,
                                  name=name + "_fc1")
        h = mx.sym.Activation(h, act_type="gelu", name=name + "_gelu")
        h = mx.sym.FullyConnected(h, num_hidden=units, flatten=False,
                                  name=name + "_fc2")
        return mx.sym.LayerNorm(x + h, name=name + "_ln2")

    h = mx.sym.Embedding(mx.sym.Variable("data"), input_dim=vocab,
                         output_dim=units, name="wte")
    pos = mx.sym.Embedding(mx.sym._arange(start=0, stop=seq,
                                          name="pos_ids"),
                           input_dim=seq, output_dim=units, name="wpe")
    h = mx.sym.broadcast_add(h, mx.sym.expand_dims(pos, axis=0))
    for i in range(layers):
        h = block(h, "h%d" % i)
    h = mx.sym.FullyConnected(h, num_hidden=vocab, flatten=False,
                              name="lm_head")
    return mx.sym.SoftmaxOutput(h, preserve_shape=True, name="softmax"), \
        {"data": (2, seq), "softmax_label": (2, seq)}


@pytest.mark.parametrize("builder,least_cut", [
    (resnet_tower_graph, 0.15), (gpt_stack_graph, 0.14)])
def test_pipeline_cuts_lowered_hlo_instructions(builder, least_cut):
    """What the graph stage hands XLA: the eval forward of a conv tower
    lowers to >= 15% fewer HLO instructions with the pipeline on (352 ->
    299 under jax 0.9.0), that of a transformer stack to >= 14% fewer
    (655 -> 563), counted in the module ``jit(...).lower()`` gives
    before the backend optimizes; both compute the same outputs (1e-6
    relative)."""
    import re
    import jax

    sym, shapes = builder()
    r = np.random.RandomState(3)
    data = (r.randint(0, 128, shapes["data"]) if builder is gpt_stack_graph
            else r.randn(*shapes["data"])).astype(np.float32)
    count, outs = {}, {}
    for passes in ("off", ""):
        outs[passes], _, exe = _bind_and_run(sym, shapes, passes, 7, False,
                                             data=data)
        args = {k: v._data for k, v in exe.arg_dict.items()}
        aux = {k: v._data for k, v in exe.aux_dict.items()}
        plan, key = exe._plan, jax.random.PRNGKey(0)
        text = jax.jit(lambda a, x: plan(a, x, key, False)[0]).lower(
            args, aux).as_text()
        count[passes] = len(re.findall(r"^\s+\S+ = ", text, re.M))
    assert count[""] <= (1 - least_cut) * count["off"], count
    for a, b in zip(outs["off"], outs[""]):
        assert np.max(np.abs(a - b) / np.maximum(np.abs(a), 1e-6)) <= 1e-6


@pytest.mark.parametrize("passname", ["fuse", "fold", "cse", "dce"])
def test_each_pass_alone_is_equivalent(passname):
    """Every pass individually preserves semantics, not just the
    default composition."""
    for builder in (random_conv_graph, random_transformer_graph):
        sym, shapes = builder(0)
        assert_equivalent(sym, shapes, passes=passname, seed=0,
                          train=True)


def test_train_mode_fused_regions_bit_exact():
    """In training the fused conv→bn→act region IS the unfused
    composition (same jnp calls): outputs and gradients bit-identical,
    and the moving-stat (aux) updates too."""
    sym, shapes = random_conv_graph(0)
    o_off, g_off, exe_off = _bind_and_run(sym, shapes, "off", 0, True)
    o_on, g_on, exe_on = _bind_and_run(sym, shapes, "", 0, True)
    for a, b in zip(o_off, o_on):
        np.testing.assert_array_equal(a, b)
    for k in g_off:
        np.testing.assert_array_equal(g_off[k], g_on[k])
    for k in exe_off.aux_dict:
        np.testing.assert_array_equal(exe_off.aux_dict[k].asnumpy(),
                                      exe_on.aux_dict[k].asnumpy())


def test_pipeline_idempotent():
    """optimize(optimize(sym)) == optimize(sym): second run fires no
    rewrites and keeps the node count."""
    for builder in (random_conv_graph, random_transformer_graph):
        sym, _ = builder(1)
        once, rep1 = G.optimize(sym)
        twice, rep2 = G.optimize(once)
        assert rep1["rewrites"], "pipeline fired nothing on %s" % builder
        assert not rep2["rewrites"], rep2
        assert rep2["nodes_after"] == rep1["nodes_after"]
        assert twice is once  # no rewrites → same symbol handed back


def test_pipeline_leaves_original_symbol_untouched():
    """Passes are pure: the input symbol's graph is structurally
    unchanged by optimize()."""
    sym, shapes = random_conv_graph(0)
    before = [(n.name, None if n.op is None else n.op.name)
              for n in sym._topo_nodes()]
    G.optimize(sym)
    after = [(n.name, None if n.op is None else n.op.name)
             for n in sym._topo_nodes()]
    assert before == after
    # and the original still binds/runs
    with pipeline_env("off"):
        exe = sym.simple_bind(mx.cpu(), grad_req="null", **shapes)
    exe.forward(is_train=False,
                data=np.zeros(shapes["data"], np.float32))


# ---------------------------------------------------------------------------
# per-pass unit laws
# ---------------------------------------------------------------------------

def test_dce_removes_only_unreachable():
    a = mx.sym.Variable("a")
    live = mx.sym.Activation(a, act_type="relu", name="live")
    dead = mx.sym.Activation(a, act_type="tanh", name="dead")
    g = Graph.from_symbol(live)
    # splice the dead node into the node list (reachable graph + orphan)
    g.nodes.append(dead._outputs[0][0])
    out, stats = run_pass("dce", g)
    assert stats["removed"] == 1
    names = {n.name for n in out.nodes}
    assert "live" in names and "dead" not in names
    # a second run removes nothing
    out2, stats2 = run_pass("dce", out)
    assert stats2["removed"] == 0
    assert len(out2.nodes) == len(out.nodes)


def test_fold_evaluates_param_free_subgraph():
    T = 5
    q = mx.sym.Reshape(mx.sym._arange(start=0, stop=T), shape=(T, 1))
    k = mx.sym.Reshape(mx.sym._arange(start=0, stop=T), shape=(1, T))
    mask = (mx.sym.broadcast_greater_equal(q, k) - 1.0) * 1e9
    x = mx.sym.Variable("x")
    out = mx.sym.broadcast_add(x, mask)
    opt, report = G.optimize(out, passes=("fold", "dce"))
    ops = [n.op.name for n in opt._topo_nodes() if not n.is_var]
    assert "_graph_constant" in ops
    assert "_arange" not in ops
    xin = np.random.RandomState(0).randn(T, T).astype(np.float32)
    with pipeline_env("off"):
        ref = out.bind(mx.cpu(), args={"x": nd.array(xin)},
                       grad_req="null").forward()[0].asnumpy()
    got = opt.bind(mx.cpu(), args={"x": nd.array(xin)},
                   grad_req="null").forward()[0].asnumpy()
    np.testing.assert_array_equal(ref, got)


def test_fold_skips_rng_and_stateful_ops():
    """RNG draws and train-dependent/aux-mutating ops never fold, even
    when parameter-free."""
    u = mx.sym._random_uniform(low=0.0, high=1.0, shape=(3, 3))
    d = mx.sym.Dropout(u, p=0.5)
    out = d + 1.0
    opt, report = G.optimize(out, passes=("fold", "dce"))
    ops = [n.op.name for n in opt._topo_nodes() if not n.is_var]
    assert "_random_uniform" in ops
    assert "Dropout" in ops
    assert report["rewrites"].get("constants", 0) == 0


def test_fold_respects_size_cap():
    prev = os.environ.get("MXTPU_GRAPH_FOLD_MAX_BYTES")
    os.environ["MXTPU_GRAPH_FOLD_MAX_BYTES"] = "8"
    try:
        big = mx.sym._arange(start=0, stop=64)  # 256B > 8B cap
        out = mx.sym.broadcast_add(mx.sym.Variable("x"), big)
        opt, report = G.optimize(out, passes=("fold", "dce"))
        ops = [n.op.name for n in opt._topo_nodes() if not n.is_var]
        assert "_arange" in ops
        assert "_graph_constant" not in ops
    finally:
        if prev is None:
            os.environ.pop("MXTPU_GRAPH_FOLD_MAX_BYTES", None)
        else:
            os.environ["MXTPU_GRAPH_FOLD_MAX_BYTES"] = prev


def test_cse_merges_identical_subexpressions():
    x = mx.sym.Variable("x")
    a = mx.sym.sin(x, name="s1")
    b = mx.sym.sin(x, name="s2")
    out = a * b
    opt, report = G.optimize(out, passes=("cse", "dce"))
    ops = [n.op.name for n in opt._topo_nodes() if not n.is_var]
    assert ops.count("sin") == 1
    assert report["rewrites"]["merged"] == 1
    xin = np.random.RandomState(0).randn(2, 2).astype(np.float32)
    got = opt.bind(mx.cpu(), args={"x": nd.array(xin)},
                   grad_req="null").forward()[0].asnumpy()
    np.testing.assert_allclose(got, np.sin(xin) ** 2, rtol=1e-6)


def test_cse_never_merges_rng_ops():
    x = mx.sym.Variable("x")
    d1 = mx.sym.Dropout(x, p=0.5, name="d1")
    d2 = mx.sym.Dropout(x, p=0.5, name="d2")
    out = d1 + d2
    opt, report = G.optimize(out, passes=("cse", "dce"))
    ops = [n.op.name for n in opt._topo_nodes() if not n.is_var]
    assert ops.count("Dropout") == 2
    assert report["rewrites"].get("merged", 0) == 0


def test_fuse_defers_interior_to_longest_chain():
    """conv→bn→relu fuses as ONE region (not conv→bn plus an orphan
    act), and a BN consumed twice keeps the conv unfused."""
    x = mx.sym.Variable("data")
    y = mx.sym.Convolution(x, kernel=(1, 1), num_filter=4, name="c")
    y = mx.sym.BatchNorm(y, name="b")
    y = mx.sym.Activation(y, act_type="relu", name="r")
    opt, report = G.optimize(y, passes=("fuse", "dce"))
    ops = [n.op.name for n in opt._topo_nodes() if not n.is_var]
    assert ops == ["_fused_conv_bn_act"]
    assert report["rewrites"]["conv_bn_act"] == 1

    # bn output used twice → act chain can't absorb it; conv+bn still fuse
    x = mx.sym.Variable("data")
    y = mx.sym.Convolution(x, kernel=(1, 1), num_filter=4, name="c2")
    b = mx.sym.BatchNorm(y, name="b2")
    out = mx.sym.Activation(b, act_type="relu", name="r2") + b
    opt, report = G.optimize(out, passes=("fuse", "dce"))
    ops = sorted(n.op.name for n in opt._topo_nodes() if not n.is_var)
    assert "_fused_conv_bn_act" in ops      # conv→bn (no act) fused
    assert "Activation" in ops              # act stays separate


def test_fused_region_node_attrs_name_constituents():
    x = mx.sym.Variable("data")
    y = mx.sym.Convolution(x, kernel=(1, 1), num_filter=4, name="c")
    y = mx.sym.BatchNorm(y, name="b")
    y = mx.sym.Activation(y, act_type="relu", name="r")
    opt, _ = G.optimize(y, passes=("fuse",))
    node = [n for n in opt._topo_nodes()
            if not n.is_var and n.op.name == "_fused_conv_bn_act"][0]
    assert node.attrs["__fused_ops__"] == "Convolution+BatchNorm+Activation"
    assert node.attrs["__fused_names__"] == "c,b,r"
    assert node.name == "r"  # tail name → output names preserved


def test_fused_batch_dot_bit_exact():
    r = np.random.RandomState(0)
    a = r.randn(2, 3, 4).astype(np.float32)
    b = r.randn(2, 5, 4).astype(np.float32)
    la, lb = mx.sym.Variable("a"), mx.sym.Variable("b")
    out = mx.sym.batch_dot(la, lb, transpose_b=True)
    ref = out.bind(mx.cpu(), args={"a": nd.array(a), "b": nd.array(b)},
                   grad_req="null").forward()[0].asnumpy()
    opt, report = G.optimize(out, passes=("fuse", "dce"))
    assert report["rewrites"]["batch_dot"] == 1
    got = opt.bind(mx.cpu(), args={"a": nd.array(a), "b": nd.array(b)},
                   grad_req="null").forward()[0].asnumpy()
    np.testing.assert_array_equal(ref, got)


def test_pallas_layer_norm_kernel_matches_oracle():
    """The Pallas fused LN+residual kernel (interpret mode on CPU) vs
    the jnp oracle — forward and every gradient.  Clean subprocess: the
    flash_attention_driver.py pattern (pallas' checkify import chain
    breaks inside the contaminated pytest process)."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable,
         os.path.join(repo, "tests", "graph_pallas_driver.py")],
        env=env, capture_output=True, timeout=420)
    out = r.stdout.decode() + r.stderr.decode()
    assert r.returncode == 0, out[-2000:]
    assert "GRAPH_LN_OK" in out


# ---------------------------------------------------------------------------
# configuration / identity
# ---------------------------------------------------------------------------

def test_env_selects_passes_and_off_disables():
    sym, shapes = random_conv_graph(0)
    with pipeline_env("dce"):
        assert G.pipeline_config() == ("dce",)
        exe = sym.simple_bind(mx.cpu(), grad_req="null", **shapes)
        assert [p["name"] for p in exe._graph_report["passes"]] == ["dce"]
        assert not exe._graph_report["rewrites"].get("conv_bn_act")
    with pipeline_env("off"):
        assert G.pipeline_config() == ()
        assert not G.enabled()
        exe = sym.simple_bind(mx.cpu(), grad_req="null", **shapes)
        assert exe._graph_report is None
    with pipeline_env("fuse,nonsense,dce"):
        # unknown names warn and are skipped, never crash the bind
        assert G.pipeline_config() == ("fuse", "dce")


def test_aot_fingerprint_folds_pipeline_config():
    """The pass-pipeline config is program identity: fingerprints (and
    therefore every AOT cache key) differ between pipeline-on and
    pipeline-off processes, so a rewritten graph can never replay a
    pre-rewrite executable."""
    from mxnet_tpu import aot_cache
    with pipeline_env(""):
        fp_on = aot_cache.fingerprint()
        assert G.pipeline_fingerprint() in fp_on
    with pipeline_env("off"):
        fp_off = aot_cache.fingerprint()
    with pipeline_env("fuse"):
        fp_fuse = aot_cache.fingerprint()
    assert len({fp_on, fp_off, fp_fuse}) == 3


def test_tojson_schema_stamp_and_roundtrip():
    sym, _ = random_conv_graph(0)
    import json
    doc = json.loads(sym.tojson())
    assert doc["attrs"]["mxtpu_json_schema"] == \
        [

            "int", mx.sym.Symbol.JSON_SCHEMA_VERSION]
    back = mx.sym.load_json(sym.tojson())
    assert back.list_arguments() == sym.list_arguments()
    assert back.list_outputs() == sym.list_outputs()


def test_graph_report_in_telemetry_and_cost_doc():
    from mxnet_tpu import telemetry
    sym, shapes = random_conv_graph(0)
    with pipeline_env(""):
        exe = sym.simple_bind(mx.cpu(), grad_req="null", **shapes)
    rep = telemetry.report()
    assert rep["gauges"].get("graph.nodes_before", 0) > 0
    assert rep["gauges"].get("graph.nodes_after", 0) > 0
    # the pass report rides the executor's compile-attribution doc
    doc = exe._analyze_compiled(object()) or {}
    assert doc.get("graph") == exe._graph_report


# ---------------------------------------------------------------------------
# module / gluon integration
# ---------------------------------------------------------------------------

def _fusable_module(passes, seed=0):
    r = np.random.RandomState(seed)
    X = r.randn(16, 3, 6, 6).astype(np.float32)
    y = r.randint(0, 3, 16).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=4, shuffle=False,
                           label_name="softmax_label")
    net = mx.sym.Variable("data")
    net = mx.sym.Convolution(net, kernel=(3, 3), pad=(1, 1), num_filter=4,
                             no_bias=True, name="c1")
    net = mx.sym.BatchNorm(net, fix_gamma=False, name="bn1")
    net = mx.sym.Activation(net, act_type="relu", name="r1")
    net = mx.sym.FullyConnected(net, num_hidden=8, name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="fa1")
    net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
    s = mx.sym.SoftmaxOutput(net, name="softmax")
    with pipeline_env(passes):
        mod = mx.mod.Module(s, context=mx.cpu())
        mod.bind(data_shapes=it.provide_data,
                 label_shapes=it.provide_label)
        mod.init_params(mx.initializer.Uniform(0.1))
        mod.init_optimizer(kvstore=None, optimizer="sgd",
                           optimizer_params=(("learning_rate", 0.05),
                                             ("momentum", 0.9)))
    return mod, list(it)


def test_module_fused_fit_equivalent_and_single_dispatch():
    """N fused train steps with the pipeline on == off (bit-exact:
    train-mode regions are compositions), still 1.0 dispatch/step."""
    from mxnet_tpu import profiler

    mod_off, batches = _fusable_module("off")
    mod_on, _ = _fusable_module("")
    assert mod_on.graph_report is not None
    assert mod_on.graph_report["rewrites"].get("conv_bn_act") == 1
    # identical starting point: copy the off module's init into the on
    # module (initializers draw from an unseeded stream)
    a0, x0 = mod_off.get_params()
    mod_on.init_params(arg_params={k: v.copy() for k, v in a0.items()},
                       aux_params={k: v.copy() for k, v in x0.items()},
                       force_init=True)
    with pipeline_env("off"):
        for b in batches + batches:
            mod_off.fit_step(b)
    with pipeline_env(""):
        for b in batches:
            mod_on.fit_step(b)
        profiler.reset_step_stats()
        for b in batches:  # same total step count as the off module
            mod_on.fit_step(b)
        stats = profiler.step_stats()
    assert stats["dispatch_count"] == len(batches)
    assert stats["compile_count"] == 0
    a_off, x_off = mod_off.get_params()
    a_on, x_on = mod_on.get_params()
    for k in a_off:
        np.testing.assert_array_equal(a_off[k].asnumpy(),
                                      a_on[k].asnumpy(), err_msg=k)
    for k in x_off:
        np.testing.assert_array_equal(x_off[k].asnumpy(),
                                      x_on[k].asnumpy(), err_msg=k)


def test_gluon_hybridize_lowers_through_pipeline():
    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(4, kernel_size=3, padding=1))
        net.add(nn.BatchNorm())
        net.add(nn.Activation("relu"))
        net.add(nn.Dense(8, activation="relu"))
        net.add(nn.Dense(3))
    net.initialize(mx.initializer.Uniform(0.1))
    x = nd.array(np.random.RandomState(0).randn(2, 3, 6, 6)
                 .astype(np.float32))
    eager = net(x).asnumpy()
    with pipeline_env(""):
        net.hybridize()
        hyb = net(x).asnumpy()
    assert net._cached_graph_report is not None
    assert net._cached_graph_report["rewrites"].get("conv_bn_act") == 1
    np.testing.assert_allclose(eager, hyb, rtol=1e-6, atol=1e-6)


def test_gluon_unsymbolizable_block_falls_back():
    """A block whose hybrid_forward needs concrete shapes cannot trace
    symbolically — hybridize must silently keep the jnp CachedOp."""
    from mxnet_tpu.gluon.block import HybridBlock

    class ShapeUser(HybridBlock):
        def hybrid_forward(self, F, x):
            b = x.shape[0]  # Symbol has no .shape → symbolic trace fails
            return F.Reshape(x, shape=(b, -1))

    net = ShapeUser()
    net.initialize()
    x = nd.array(np.ones((2, 3, 4), np.float32))
    with pipeline_env(""):
        net.hybridize()
        out = net(x)
    assert out.shape == (2, 12)
    assert net._cached_graph_report is None


def test_visualization_renders_fused_regions():
    from mxnet_tpu.visualization import _node_label, print_summary

    x = mx.sym.Variable("data")
    y = mx.sym.Convolution(x, kernel=(3, 3), pad=(1, 1), num_filter=4,
                           name="c")
    y = mx.sym.BatchNorm(y, name="b")
    y = mx.sym.Activation(y, act_type="relu", name="r")
    y = mx.sym.FullyConnected(y, num_hidden=2, name="fc")
    opt, _ = G.optimize(y)
    node = [n for n in opt._topo_nodes()
            if not n.is_var and n.op.name == "_fused_conv_bn_act"][0]
    label = _node_label(node)
    assert "Convolution+BatchNorm+Activation" in label
    total = print_summary(opt, shape={"data": (1, 3, 6, 6)})
    assert total > 0  # fused regions summarized, not crashed


def test_predictor_path_routes_through_pipeline(tmp_path):
    """The deployment path (Predictor.simple_bind) rewrites too — the
    serving-prefill half of the routing contract."""
    from mxnet_tpu.predictor import Predictor

    sym, shapes = random_conv_graph(0)
    with pipeline_env(""):
        pred = Predictor(sym.tojson(), None,
                         {"data": shapes["data"]})
    assert pred._exec._graph_report is not None
    assert pred._exec._graph_report["rewrites"]
    out = pred.predict(np.zeros(shapes["data"], np.float32))
    assert out.shape[0] == shapes["data"][0]
