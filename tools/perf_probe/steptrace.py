"""Per-step dispatch/compile trace for the train hot path (PERF.md,
"Fused train step").  Runs the same MLP fit loop through the fused
Module.fit_step (one donated XLA program per batch) and the split
forward_backward()+update() pair (one program + one update kernel per
parameter), printing profiler.step_stats() for each so dispatch-count
regressions are visible at a glance.

Usage: JAX_PLATFORMS=cpu python tools/perf_probe/steptrace.py
Prints one JSON object: {"fused": {...}, "fused_async_ckpt": {...},
"unfused": {...}} where each side carries steady-state
dispatches_per_step, compile_count and step_time_ema_ms — the
fused_async_ckpt trace runs a per-epoch MXTPU_ASYNC_CKPT=1 checkpoint
inside the loop (tests/test_async_ckpt.py asserts the save path adds
zero dispatches).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def build_module(batch=64, dim=32, classes=4, hidden=64, depth=2,
                 n_batches=8, ctx=None, optimizer="sgd",
                 opt_params=(("learning_rate", 0.05), ("momentum", 0.9))):
    """The probes' and tests' MLP fit-loop fixture: ``depth-1`` hidden
    relu layers + a softmax head.  ``ctx`` may be a device list —
    ``run_spmd`` passes the whole 8-device host mesh."""
    import numpy as np
    import mxnet_tpu as mx

    rs = np.random.RandomState(0)
    X = rs.randn(n_batches * batch, dim).astype(np.float32)
    y = rs.randint(0, classes, size=n_batches * batch).astype(np.float32)
    train = mx.io.NDArrayIter(X, y, batch_size=batch, shuffle=False,
                              label_name="softmax_label")
    net = mx.sym.Variable("data")
    for i in range(1, depth):
        net = mx.sym.FullyConnected(net, num_hidden=hidden,
                                    name="fc%d" % i)
        net = mx.sym.Activation(net, act_type="relu")
    out = mx.sym.FullyConnected(net, num_hidden=classes,
                                name="fc%d" % depth)
    s = mx.sym.SoftmaxOutput(out, name="softmax")
    mod = mx.mod.Module(s, context=mx.cpu() if ctx is None else ctx)
    mod.bind(data_shapes=train.provide_data,
             label_shapes=train.provide_label)
    mod.init_params(mx.initializer.Uniform(0.1))
    mod.init_optimizer(kvstore=None, optimizer=optimizer,
                       optimizer_params=opt_params)
    return mod, train


def trace(step_fn, batches, epochs=3):
    """Warm one epoch, then measure steady state (profiler counters AND
    the always-on telemetry phase histograms, reset together)."""
    from mxnet_tpu import profiler, telemetry
    for b in batches:
        step_fn(b)
    # the probe VERIFIES telemetry/step_stats consistency, so recording
    # must be on even under MXTPU_TELEMETRY_OFF=1 in the environment
    telemetry.set_enabled(True)
    profiler.reset_step_stats()
    telemetry.reset()
    t0 = time.perf_counter()
    n = 0
    for _ in range(epochs):
        for b in batches:
            step_fn(b)
            n += 1
    dt = time.perf_counter() - t0
    stats = profiler.step_stats()
    rep = telemetry.report()
    ema = stats["step_time_ema_s"]
    return {
        "steps": n,
        "dispatches_per_step": stats["dispatch_count"] / n,
        "compile_count": stats["compile_count"],
        "skipped_steps": stats["skipped_steps"],
        "step_time_ema_ms": round(ema * 1e3, 3) if ema else None,
        "wall_ms_per_step": round(dt / n * 1e3, 3),
        "phase_counts": {name: p["count"]
                         for name, p in rep["phases"].items()},
        "flight_len": rep["flight"]["len"],
        "flight_maxlen": rep["flight"]["maxlen"],
    }


def run():
    import shutil
    import tempfile

    mod, train = build_module()
    batches = list(train)

    fused = trace(mod.fit_step, batches)

    mod2, _ = build_module()

    def split_step(b):
        from mxnet_tpu import profiler
        mod2.forward_backward(b)
        mod2.update()
        profiler.note_step()  # the fused path notes its own steps

    unfused = trace(split_step, batches)
    n_params = len(mod._param_names)

    # fused loop WITH async checkpointing live: a save per epoch, the
    # write overlapping the following steps.  The snapshot (host fetch +
    # owned copies) and enqueue must add ZERO compiled-program
    # dispatches (tests/test_async_ckpt.py
    # test_async_saves_in_the_loop_add_no_dispatch).
    from mxnet_tpu import checkpoint as _ckpt
    mod3, _ = build_module()
    ckdir = tempfile.mkdtemp(prefix="steptrace-ckpt-")
    prev = os.environ.get("MXTPU_ASYNC_CKPT")
    os.environ["MXTPU_ASYNC_CKPT"] = "1"
    seen = [0]

    def fused_ckpt_step(b):
        mod3.fit_step(b)
        seen[0] += 1
        if seen[0] % len(batches) == 0:  # one checkpoint per epoch
            mod3.save_checkpoint(os.path.join(ckdir, "ck"),
                                 seen[0] // len(batches),
                                 save_optimizer_states=True)

    try:
        fused_async = trace(fused_ckpt_step, batches)
        _ckpt.flush_async()
    finally:
        if prev is None:
            os.environ.pop("MXTPU_ASYNC_CKPT", None)
        else:
            os.environ["MXTPU_ASYNC_CKPT"] = prev
        shutil.rmtree(ckdir, ignore_errors=True)
    # the telemetry layer must agree with the profiler's step counters:
    # every fused dispatch produced exactly one fit_step.dispatch /
    # fit_step.sync phase record and one flight-recorder entry (the 1.0
    # dispatch/step contract, cross-checked against the per-phase
    # counters; tests/test_fused_step.py asserts the dispatch rate)
    n = fused["steps"]
    for phase in ("fit_step.dispatch", "fit_step.sync"):
        got = fused["phase_counts"].get(phase, 0)
        assert got == n, (
            "telemetry phase %r recorded %d entries for %d fused steps — "
            "per-phase counters diverged from profiler.step_stats()"
            % (phase, got, n))
    assert fused["flight_len"] == min(n, fused["flight_maxlen"]), (
        "flight recorder held %d records for %d fused steps (ring cap %d)"
        % (fused["flight_len"], n, fused["flight_maxlen"]))

    return {"fused": fused, "fused_async_ckpt": fused_async,
            "unfused": unfused, "n_params": n_params}


def run_spmd(n_dev=8):
    """The ZeRO-1 fused step on an n_dev host mesh (``STEPTRACE_SPMD=1``).
    Returns per-step dispatch stats plus the sharded-state economics
    (opt-state bytes per device vs total, the estimated per-step
    collective bytes, fallback count); tests/test_module_spmd.py and
    tests/test_job_report.py assert the 1.0 dispatch/step and
    1/N-state contracts."""
    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    if jax.device_count() < n_dev:
        raise RuntimeError(
            "steptrace.run_spmd needs %d devices (run under "
            "--xla_force_host_platform_device_count=%d or on real "
            "chips); have %d" % (n_dev, n_dev, jax.device_count()))
    prev = os.environ.get("MXTPU_ZERO")
    os.environ["MXTPU_ZERO"] = "1"
    try:
        ctx = [mx.cpu(i) for i in range(n_dev)]
        # adam: two state leaves per param — the sharpest 1/N contrast
        mod, train = build_module(ctx=ctx, optimizer="adam",
                                  opt_params=(("learning_rate", 0.01),))
        batches = list(train)
        spmd = trace(mod.fit_step, batches)

        fused = mod._fused
        assert fused["zero"] is not None, \
            "MXTPU_ZERO=1 on a mesh bind must engage ZeRO-1"
        # trace() resets telemetry after warmup, wiping the setup-time
        # sharding + cost-attribution gauges — republish both for the
        # report below
        mod._exec._note_sharding_telemetry(
            tuple(fused["update_names"]), fused["state"], fused["zero"])
        mod._exec.publish_cost_telemetry()

        def per_device_bytes(leaf):
            shards = {s.data.shape for s in leaf.addressable_shards}
            return int(np.prod(next(iter(shards)))) * leaf.dtype.itemsize

        total = 0
        per_device = 0
        sharded_leaves = 0
        leaves = 0
        for name, sub in fused["state"].items():
            for leaf in jax.tree_util.tree_leaves(sub):
                leaves += 1
                nb = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                total += nb
                per_device += per_device_bytes(leaf)
                if not leaf.sharding.is_fully_replicated:
                    sharded_leaves += 1
        # every OTHER fused-step input, per device, from the live
        # arrays' actual shard shapes: params/data/label/aux.  Together
        # with the 1/N state this is what the compiled program's
        # xla.memory.argument_bytes must agree with (±20%,
        # tests/test_job_report.py) — the measured cross-check of the ZeRO-1
        # state economics (scalars/rng are a few tens of bytes, inside
        # the tolerance).
        expected_args = per_device
        exe = mod._exec
        for d in (exe.arg_dict, exe.aux_dict):
            for name, arr in d.items():
                expected_args += per_device_bytes(arr._data)
        rep = telemetry.report()
        spmd.update({
            "n_devices": n_dev,
            "opt_state_total_bytes": total,
            "opt_state_bytes_per_device": per_device,
            "opt_state_leaves": leaves,
            "opt_state_leaves_sharded": sharded_leaves,
            "expected_argument_bytes_per_device": expected_args,
            "gauge_opt_state_bytes_per_device":
                rep["gauges"].get("sharding.opt_state_bytes_per_device"),
            "gauge_collective_bytes_per_step":
                rep["gauges"].get("sharding.collective_bytes_per_step"),
            "gauge_collective_bytes_modeled":
                rep["gauges"].get("sharding.collective_bytes_modeled"),
            "gauge_xla_memory_argument_bytes":
                rep["gauges"].get("xla.memory.argument_bytes"),
            "gauge_xla_cost_flops":
                rep["gauges"].get("xla.cost.flops_per_step"),
            "collective_ops":
                (mod._exec._cost_doc or {}).get("collectives", {})
                .get("ops"),
            "sharding_fallbacks":
                rep["counters"].get("sharding.fallbacks", 0),
        })
        return spmd
    finally:
        if prev is None:
            os.environ.pop("MXTPU_ZERO", None)
        else:
            os.environ["MXTPU_ZERO"] = prev


if __name__ == "__main__":
    if os.environ.get("STEPTRACE_SPMD") == "1":
        print(json.dumps(run_spmd()))
    else:
        print(json.dumps(run()))
