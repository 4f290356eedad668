"""Runner ``train_module``: ``Module.fit_step`` on a symbol from the
repo's own examples, for ``--seconds``.

``chip_smoke.py``'s ``resnet_module`` / ``phase_train_resnet`` (PR 21,
proven on the chip) with the handful of steps replaced by a timed
window: the fused donated program of ``Executor.make_fit_step``, SGD
with momentum, a fresh batch each step from a seeded ring, the loss read
from the step's own softmax output (the host fetch is the barrier).
"""
import importlib
import os
import sys
import time

import numpy as np

import common
import trafficgen

sys.path.insert(0, os.path.join(common.ROOT, "example",
                                "image-classification"))


def loss_of(mod, label):
    """Cross-entropy of the SoftmaxOutput head; the host fetch is the
    step's completion barrier."""
    probs = mod.get_outputs()[0].asnumpy().astype(np.float64)
    return float(-np.log(probs[np.arange(len(label)), label] + 1e-30)
                 .mean())


def run(ctx):
    import mxnet_tpu as mx
    from mxnet_tpu import profiler

    cell, cfg, mix, watch = ctx.cell, ctx.config, ctx.traffic, ctx.watch
    job = cell["job"]
    symbols = importlib.import_module("symbols." + cfg["model"]["symbol"])
    image = tuple(cfg["image_shape"])
    sym = symbols.get_symbol(
        num_classes=cfg["num_classes"], num_layers=cfg["num_layers"],
        image_shape=",".join(str(d) for d in image))
    ring = trafficgen.image_batches(mix, ctx.seed, image,
                                    cfg["num_classes"])
    rows = ring[0][0].shape[0]
    mod = mx.mod.Module(sym, context=mx.tpu(0))
    mod.bind(data_shapes=[("data", (rows,) + image)],
             label_shapes=[("softmax_label", (rows,))])
    mx.random.seed(int(ctx.seed) & 0x7FFFFFFF)
    mod.init_params(mx.initializer.Xavier(magnitude=2.0))
    mod.init_optimizer(kvstore=None, optimizer=job["optimizer"],
                       optimizer_params=job["optimizer_params"])
    batches = [(mx.io.DataBatch([mx.nd.array(d)], [mx.nd.array(l)]),
                l.astype(np.int64)) for d, l in ring]

    losses = []

    def step(i):
        batch, label = batches[i % len(batches)]
        mod.fit_step(batch)
        losses.append(loss_of(mod, label))

    t0 = time.perf_counter()
    step(0)                           # AOT-compiles the fused step
    common.say("first_step", seconds=time.perf_counter() - t0,
               compile_cache=dict(watch.cache))
    watch.on_device([a._data for a in mod._exec.arg_dict.values()],
                    "parameters")
    programs = [common.program_memory(mod._fused["step"].__wrapped__)]
    for i in range(int(job.get("warm_steps", 2))):
        step(1 + i)

    profiler.reset_step_stats()
    steps, window_s, records = common.step_window(
        ctx, lambda: step(len(losses)))
    stats = profiler.step_stats()
    compiles = watch.compiles - ctx.compiles_at_open
    finite, falling = common.loss_checks(losses)
    one_dispatch = stats["dispatch_count"] == steps and \
        stats["compile_count"] == 0
    common.say("window", seconds=window_s, steps=steps,
               step_s=window_s / max(1, steps), first_loss=losses[0],
               last_losses=losses[-5:], finite=finite, falling=falling,
               dispatches=stats["dispatch_count"],
               program_compiles=stats["compile_count"],
               compiles_in_window=compiles)
    correct = bool(finite and falling and one_dispatch and compiles == 0)
    return {"correct": correct, "attempted": steps,
            "failed": 0 if finite else steps,
            "end_to_end": {"train_samples_s": steps * rows / window_s},
            "counters": {"steps": steps, "batch": rows,
                         "window_s": window_s,
                         "dispatches": stats["dispatch_count"]},
            "span_records": records, "programs": programs,
            "why_not_correct": None if correct else {
                "finite": finite, "falling": falling,
                "dispatches": stats["dispatch_count"], "steps": steps,
                "compiles": compiles}}
