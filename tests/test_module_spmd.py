"""Module(context=[N devices]) → one SPMD program over a dp mesh.

The reference ran one executor per GPU and sliced every batch in Python
(/root/reference/python/mxnet/module/executor_group.py:296-378,
module.py:751), reducing gradients through KVStore.  The TPU-native Module
instead dp-shards the whole batch into ONE compiled step; these tests assert
(a) shards actually land on all devices, (b) the multi-device run is
numerically identical to single-device, and (c) `--kv-store device` keeps
working unmodified on top of it.
"""
import numpy as np
import jax
import pytest

import mxnet_tpu as mx


def _problem(n=256, d=16, k=4, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    W = rng.randn(d, k).astype(np.float32)
    Y = (X @ W).argmax(1).astype(np.float32)
    return X, Y


def _mlp():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _fit(ctx, X, Y, batch_size=64, num_epoch=3, kv="device"):
    np.random.seed(42)
    mx.random.seed(42)
    train = mx.io.NDArrayIter(X, Y, batch_size=batch_size)
    mod = mx.mod.Module(_mlp(), context=ctx)
    mod.fit(train, optimizer="sgd", kvstore=kv,
            optimizer_params={"learning_rate": 0.5, "momentum": 0.9},
            initializer=mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                       magnitude=2),
            num_epoch=num_epoch)
    return mod


def test_spmd_shards_on_all_devices():
    assert jax.device_count() >= 8, "conftest must force 8 CPU devices"
    X, Y = _problem()
    ctx = [mx.cpu(i) for i in range(8)]
    train = mx.io.NDArrayIter(X, Y, batch_size=64)
    mod = mx.mod.Module(_mlp(), context=ctx)
    mod.bind(data_shapes=train.provide_data,
             label_shapes=train.provide_label)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(kvstore="device", optimizer="sgd")
    batch = next(iter(train))
    mod.forward_backward(batch)
    mod.update()

    # the batch input is dp-sharded across all 8 devices...
    data_arr = mod._exec.arg_dict["data"]._data
    assert len(data_arr.sharding.device_set) == 8
    # ...one shard per device, 1/8th of the batch each
    shard_shapes = {s.data.shape for s in data_arr.addressable_shards}
    assert shard_shapes == {(8, 16)}
    # parameters + their gradients are replicated over the same mesh
    w = mod._exec.arg_dict["fc1_weight"]._data
    g = mod._exec.grad_dict["fc1_weight"]._data
    assert len(w.sharding.device_set) == 8
    assert len(g.sharding.device_set) == 8
    assert w.sharding.is_fully_replicated
    assert g.sharding.is_fully_replicated


def test_spmd_matches_single_device():
    X, Y = _problem()
    mod1 = _fit(mx.cpu(0), X, Y)
    mod8 = _fit([mx.cpu(i) for i in range(8)], X, Y)
    args1, _ = mod1.get_params()
    args8, _ = mod8.get_params()
    for name in args1:
        np.testing.assert_allclose(args1[name].asnumpy(),
                                   args8[name].asnumpy(),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg="param %s diverged" % name)
    score = mod8.score(mx.io.NDArrayIter(X, Y, batch_size=64), "acc")
    assert score[0][1] > 0.9


def test_spmd_batch_not_divisible_raises():
    X, Y = _problem(n=60)
    train = mx.io.NDArrayIter(X, Y, batch_size=60)
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(8)])
    with pytest.raises(mx.base.MXNetError, match="not divisible"):
        mod.bind(data_shapes=train.provide_data,
                 label_shapes=train.provide_label)


def test_spmd_duplicate_context_raises():
    X, Y = _problem()
    train = mx.io.NDArrayIter(X, Y, batch_size=64)
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(0), mx.cpu(0)])
    with pytest.raises(mx.base.MXNetError, match="duplicate"):
        mod.bind(data_shapes=train.provide_data,
                 label_shapes=train.provide_label)


def test_spmd_grad_req_add():
    X, Y = _problem()
    train = mx.io.NDArrayIter(X, Y, batch_size=64)
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(8)])
    mod.bind(data_shapes=train.provide_data,
             label_shapes=train.provide_label, grad_req="add")
    mod.init_params(mx.init.Xavier())
    batch = next(iter(train))
    mod.forward_backward(batch)
    g1 = mod._exec.grad_dict["fc1_weight"].asnumpy().copy()
    mod.forward_backward(batch)
    g2 = mod._exec.grad_dict["fc1_weight"].asnumpy()
    np.testing.assert_allclose(g2, 2 * g1, rtol=1e-5, atol=1e-6)


def test_spmd_forward_only_inference():
    X, Y = _problem()
    ctx = [mx.cpu(i) for i in range(8)]
    mod8 = _fit(ctx, X, Y, num_epoch=1)
    val = mx.io.NDArrayIter(X, None, batch_size=64)
    preds = mod8.predict(val)
    assert preds.shape == (256, 4)


# ---------------------------------------------------------------------------
# Mesh-native fused step: partition rules + ZeRO-1 sharded weight update
# ---------------------------------------------------------------------------

def _fit_steps(ctx, steps=10, optimizer="sgd",
               opt_params={"learning_rate": 0.5, "momentum": 0.9},
               symbol=None):
    """Deterministic fit_step loop (same seeds, same batch order) so the
    dp=8 ZeRO-1 run and the single-device fused run see identical data."""
    np.random.seed(42)
    mx.random.seed(42)
    X, Y = _problem()
    train = mx.io.NDArrayIter(X, Y, batch_size=64)
    mod = mx.mod.Module(symbol if symbol is not None else _mlp(),
                        context=ctx)
    mod.bind(data_shapes=train.provide_data,
             label_shapes=train.provide_label)
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2))
    mod.init_optimizer(kvstore=None, optimizer=optimizer,
                       optimizer_params=opt_params)
    it = iter(train)
    n = 0
    while n < steps:
        try:
            batch = next(it)
        except StopIteration:
            train.reset()
            it = iter(train)
            continue
        mod.fit_step(batch)
        n += 1
    return mod


def _state_leaves(mod):
    out = {}
    for name, sub in mod._fused["state"].items():
        out[name] = jax.tree_util.tree_leaves(sub)
    return out


def test_zero1_opt_state_sharded(monkeypatch):
    """MXTPU_ZERO=1 on a dp=8 mesh: every shardable optimizer-state leaf
    holds 1/8 per device; the indivisible fc2_bias (4,) falls back to
    replication and is COUNTED, not silent."""
    from mxnet_tpu import telemetry
    monkeypatch.setenv("MXTPU_ZERO", "1")
    mod = _fit_steps([mx.cpu(i) for i in range(8)], steps=2)
    leaves = _state_leaves(mod)
    # fc1_weight (32,16) momentum: dim0 sharded 8 ways, (4,16) per device
    (mom,) = leaves["fc1_weight"]
    assert len(mom.addressable_shards) == 8
    assert {s.data.shape for s in mom.addressable_shards} == {(4, 16)}
    assert not mom.sharding.is_fully_replicated
    # fc2_weight (4,32): dim0 indivisible, dim1 sharded -> (4,4) shards
    (mom2,) = leaves["fc2_weight"]
    assert {s.data.shape for s in mom2.addressable_shards} == {(4, 4)}
    # fc2_bias (4,): nothing divides 8 -> replicated fallback
    (momb,) = leaves["fc2_bias"]
    assert momb.sharding.is_fully_replicated
    # params themselves stay replicated (ZeRO-1, not FSDP)
    w = mod._exec.arg_dict["fc1_weight"]._data
    assert w.sharding.is_fully_replicated
    # the fallback is visible on the telemetry counter, and the gauges
    # carry the 1/N economics
    rep = telemetry.report()
    assert rep["counters"].get("sharding.fallbacks", 0) >= 1
    assert rep["gauges"].get("sharding.zero_stage") == 1
    total = sum(
        int(np.prod(l.shape)) * l.dtype.itemsize
        for leaves in _state_leaves(mod).values() for l in leaves)
    per_dev = rep["gauges"]["sharding.opt_state_bytes_per_device"]
    # fc2_bias (16 bytes) is replicated; everything else is 1/8
    assert per_dev < total / 4


@pytest.mark.parametrize("optimizer,opt_params", [
    ("sgd", {"learning_rate": 0.5, "momentum": 0.9}),
    ("adam", {"learning_rate": 0.05}),
])
def test_zero1_matches_single_device(monkeypatch, optimizer, opt_params):
    """10 ZeRO-1 steps on the dp=8 host mesh track the single-device
    fused step bit-tolerantly (reduce-scatter + sharded update +
    all-gather reassociates float sums, so exact bitwise equality is not
    the contract — 1e-5 relative is)."""
    mod1 = _fit_steps(mx.cpu(0), optimizer=optimizer,
                      opt_params=opt_params)
    monkeypatch.setenv("MXTPU_ZERO", "1")
    mod8 = _fit_steps([mx.cpu(i) for i in range(8)], optimizer=optimizer,
                      opt_params=opt_params)
    args1, _ = mod1.get_params()
    args8, _ = mod8.get_params()
    for name in args1:
        np.testing.assert_allclose(
            args1[name].asnumpy(), args8[name].asnumpy(),
            rtol=1e-5, atol=1e-6,
            err_msg="param %s diverged under ZeRO-1 (%s)"
                    % (name, optimizer))


def test_zero1_one_dispatch_per_step(monkeypatch):
    """The sharded update stays INSIDE the one donated program: steady
    state is exactly 1 dispatch and 0 compiles per step on the dp=8
    mesh."""
    from mxnet_tpu import profiler
    monkeypatch.setenv("MXTPU_ZERO", "1")
    mod = _fit_steps([mx.cpu(i) for i in range(8)], steps=2)  # warm
    X, Y = _problem()
    train = mx.io.NDArrayIter(X, Y, batch_size=64)
    batches = list(train)
    profiler.reset_step_stats()
    for b in batches:
        mod.fit_step(b)
    stats = profiler.step_stats()
    # profiler steps count INTERVALS (first note_step arms the clock);
    # the dispatch contract is per fit_step call
    assert stats["dispatch_count"] == len(batches)
    assert stats["dispatch_count"] / len(batches) == 1.0
    assert stats["compile_count"] == 0


def test_zero1_divergence_guard_inside_sharded_program(monkeypatch):
    """A NaN batch under ZeRO-1 skips tree-wide: params and sharded
    opt-state pass through unchanged, skipped_steps ticks, t rolls
    back — same contract as the single-device guard, same one
    program."""
    from mxnet_tpu import profiler
    monkeypatch.setenv("MXTPU_ZERO", "1")
    mod = _fit_steps([mx.cpu(i) for i in range(8)], steps=3)
    args_before, _ = mod.get_params()
    args_before = {k: v.asnumpy().copy() for k, v in args_before.items()}
    mom_before = {k: np.asarray(v[0]) for k, v in
                  _state_leaves(mod).items()}
    t_before = dict(mod._optimizer._index_update_count)
    X, Y = _problem()
    X[:] = np.nan
    bad = mx.io.NDArrayIter(X, Y, batch_size=64)
    skipped0 = profiler.step_stats()["skipped_steps"]
    mod.fit_step(next(iter(bad)))
    assert profiler.step_stats()["skipped_steps"] == skipped0 + 1
    assert dict(mod._optimizer._index_update_count) == t_before
    args_after, _ = mod.get_params()
    for name in args_before:
        np.testing.assert_array_equal(args_before[name],
                                      args_after[name].asnumpy())
    for name, m0 in mom_before.items():
        np.testing.assert_array_equal(
            m0, np.asarray(_state_leaves(mod)[name][0]))


def test_zero1_save_reshard_load_roundtrip(monkeypatch, tmp_path):
    """save(ZeRO-1, dp=8) -> manifest carries the sharding stamp, the
    .states payload is full-size (all-gathered) -> a fresh dp=8 module
    reshards it back onto 1/N slices at load and training state is
    preserved exactly."""
    import json
    monkeypatch.setenv("MXTPU_ZERO", "1")
    ctx = [mx.cpu(i) for i in range(8)]
    mod = _fit_steps(ctx, steps=5)
    prefix = str(tmp_path / "zck")
    mod.save_checkpoint(prefix, 1, save_optimizer_states=True)
    manifest = json.loads(
        (tmp_path / "zck-0001.manifest.json").read_text())
    stamp = manifest["sharding"]
    assert stamp["zero_stage"] == 1
    assert stamp["mesh"]["dp"] == 8
    assert stamp["opt_state"] == "gathered"
    assert "fc1_weight" in stamp["specs"]
    mom_saved = {k: np.asarray(v[0]) for k, v in
                 _state_leaves(mod).items()}

    mod2 = mx.mod.Module.load(prefix, 1, load_optimizer_states=True,
                              context=ctx)
    X, Y = _problem()
    train = mx.io.NDArrayIter(X, Y, batch_size=64)
    mod2.bind(data_shapes=train.provide_data,
              label_shapes=train.provide_label)
    mod2.init_params()
    mod2.init_optimizer(kvstore=None, optimizer="sgd",
                        optimizer_params={"learning_rate": 0.5,
                                          "momentum": 0.9})
    mod2.fit_step(next(iter(train)))  # forces _fused_setup + reshard
    leaves = _state_leaves(mod2)
    (mom,) = leaves["fc1_weight"]
    assert {s.data.shape for s in mom.addressable_shards} == {(4, 16)}
    # loaded momentum must be the SAVED momentum advanced by exactly the
    # one post-load step; cheaper and tighter: compare the pre-step
    # loaded state by reloading into a module we don't step
    mod3 = mx.mod.Module.load(prefix, 1, load_optimizer_states=True,
                              context=ctx)
    mod3.bind(data_shapes=train.provide_data,
              label_shapes=train.provide_label)
    mod3.init_params()
    mod3.init_optimizer(kvstore=None, optimizer="sgd",
                        optimizer_params={"learning_rate": 0.5,
                                          "momentum": 0.9})
    fused = mod3._fused_setup()
    for name, m0 in mom_saved.items():
        got = np.asarray(jax.tree_util.tree_leaves(fused["state"][name])[0])
        np.testing.assert_array_equal(m0, got,
                                      err_msg="state %s changed across "
                                              "save->reshard->load" % name)


def test_zero1_aot_cache_mesh_keyed(monkeypatch, tmp_path):
    """The AOT key is mesh-keyed and the CPU SPMD-deserialize hazard is
    quarantined: (a) a same-process module rebuild warm-starts from the
    in-process memo with 0 foreground compiles; (b) the SAME model on a
    dp=4 mesh over the same device pool gets its own key (compiles,
    never collides with dp=8 — the PR-6 topology-clobber class of bug);
    (c) NO mesh entry is written to disk on this backend — a replayed
    (deserialized) SPMD executable flakily corrupts the heap or
    deadlocks its collectives even donation-free (ROBUSTNESS.md §8), so
    cross-process CPU mesh restarts pay one compile by design while the
    memo covers rebinds/reconfigs.  On TPU-class backends the disk path
    stays on (deserialized_spmd_safe)."""
    from mxnet_tpu import aot_cache, profiler, telemetry
    monkeypatch.setenv("MXTPU_ZERO", "1")
    monkeypatch.setenv("MXTPU_AOT_CACHE_DIR", str(tmp_path))
    sym = _mlp()

    def build(ctx):
        np.random.seed(42)
        mx.random.seed(42)
        X, Y = _problem()
        train = mx.io.NDArrayIter(X, Y, batch_size=64)
        mod = mx.mod.Module(sym, context=ctx)
        mod.bind(data_shapes=train.provide_data,
                 label_shapes=train.provide_label)
        mod.init_params(mx.init.Xavier())
        mod.init_optimizer(kvstore=None, optimizer="sgd",
                           optimizer_params={"learning_rate": 0.5,
                                             "momentum": 0.9})
        return mod, next(iter(train))

    ctx8 = [mx.cpu(i) for i in range(8)]
    mod, batch = build(ctx8)
    mod.fit_step(batch)
    assert aot_cache.drain(60)
    # hazard quarantine: nothing on disk for a CPU mesh program
    assert not [p for p in tmp_path.iterdir()
                if p.suffix == ".aotx"], \
        "CPU mesh fused step must never be serialized to disk"

    # warm rebuild in-process: memo tier, zero foreground compiles
    memo0 = telemetry.report()["counters"].get("aot.memo_hits", 0)
    mod2, batch2 = build(ctx8)
    profiler.reset_step_stats()
    mod2.fit_step(batch2)
    mod2.fit_step(batch2)
    stats = profiler.step_stats()
    assert stats["compile_count"] == 0, "warm mesh rebuild compiled"
    assert stats["dispatch_count"] == 2
    assert telemetry.report()["counters"]["aot.memo_hits"] == memo0 + 1

    # same devices, different mesh shape: MUST be a different key —
    # dp=4 compiles its own program instead of hitting dp=8's memo
    mod4, batch4 = build([mx.cpu(i) for i in range(4)])
    profiler.reset_step_stats()
    mod4.fit_step(batch4)
    assert profiler.step_stats()["compile_count"] == 1

    # ...and dp=8 still hits its own memo afterwards
    mod8b, batch8b = build(ctx8)
    profiler.reset_step_stats()
    mod8b.fit_step(batch8b)
    assert profiler.step_stats()["compile_count"] == 0


def test_partition_rules_thread_through_bind():
    """Executor._build_shardings resolves the bind's partition rules over
    the named arg/aux tree (match_partition_rules) — batch names get
    batch_spec, ruled params their spec, everything else replicated."""
    from mxnet_tpu.parallel.sharding import PartitionRule
    from jax.sharding import PartitionSpec as P
    X, Y = _problem()
    train = mx.io.NDArrayIter(X, Y, batch_size=64)
    sym = _mlp()
    ctx = [mx.cpu(i) for i in range(8)]
    from mxnet_tpu.parallel.mesh import dp_mesh_from_ctx
    mesh = dp_mesh_from_ctx(ctx)
    from mxnet_tpu.executor import Executor
    exe = sym.simple_bind(
        ctx[0], grad_req="write", mesh=mesh,
        batch_names=["data", "softmax_label"],
        partition_rules=[PartitionRule(r"fc\d_weight$", P("dp", None), 2)],
        data=(64, 16), softmax_label=(64,))
    assert exe.param_spec("fc1_weight") == P("dp", None)
    assert exe.param_spec("fc1_bias") == P()
    assert exe.param_spec("data") == P("dp", None)


def test_partition_rules_unknown_axis_falls_back():
    """The SCALING.md cookbook shares one rule set across mesh shapes:
    a tp rule on a dp-only Module bind must replicate (counted +
    warned), never KeyError at bind."""
    from mxnet_tpu.parallel.sharding import PartitionRule
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu import telemetry
    X, Y = _problem()
    train = mx.io.NDArrayIter(X, Y, batch_size=64)
    before = telemetry.report()["counters"].get("sharding.fallbacks", 0)
    mod = mx.mod.Module(
        _mlp(), context=[mx.cpu(i) for i in range(8)],
        partition_rules=[(r"fc\d_weight$", P("tp", None), 2)])
    mod.bind(data_shapes=train.provide_data,
             label_shapes=train.provide_label)
    assert mod._exec.param_spec("fc1_weight") == P()
    assert telemetry.report()["counters"]["sharding.fallbacks"] > before
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(kvstore=None, optimizer="sgd")
    mod.fit_step(next(iter(train)))  # trains, just unsharded


def test_spmd_with_gradient_compression():
    """SPMD Module + 2-bit gradient compression (the --gpus + --gc-type
    combination fit.py now wires): the quantized update rule applies on
    the mesh-replicated merged gradients and training still learns."""
    X, Y = _problem()
    ctx = [mx.cpu(i) for i in range(4)]
    np.random.seed(42)
    mx.random.seed(42)
    train = mx.io.NDArrayIter(X, Y, batch_size=64)
    mod = mx.mod.Module(_mlp(), context=ctx)
    kv = mx.kv.create("device")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.2})
    mod.fit(train, optimizer="sgd", kvstore=kv,
            optimizer_params={"learning_rate": 0.5},
            initializer=mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2),
            num_epoch=20)
    score = mod.score(mx.io.NDArrayIter(X, Y, batch_size=64),
                      mx.metric.Accuracy())
    acc = dict(score)["accuracy"]
    assert acc > 0.5, acc  # 4 classes; compressed training must learn
    # the compressor really ran: residuals exist only after quantization
    assert kv._compressor is not None and kv._compressor._residuals
