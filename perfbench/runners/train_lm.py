"""Runner ``train_lm``: language-model training steps on the mesh the
cell names, for ``--seconds``.

``chip_smoke.py``'s ``gpt_train`` (PR 21, proven on one chip and on a
dp2 x tp2 mesh) with the handful of steps replaced by a timed window:
``functionalize`` + ``parallel.gpt_spmd.make_train_step``, a fresh batch
each step from a seeded ring, every step's loss fetched (the barrier).
"""
import gc
import time

import numpy as np

import common
import trafficgen
from reference import gpt2 as reference


def reference_loss(net, batch, rows):
    """The plain reference's loss on ``batch``, ``rows`` sequences at a
    time (equal parts, so the mean of the parts is the batch's mean)."""
    import jax
    w, n_head = reference.weights_from_net(net)
    fn = jax.jit(reference.loss, static_argnums=3)
    x, y = batch["x"], batch["y"]
    parts = [float(fn(w, x[i:i + rows], y[i:i + rows], n_head))
             for i in range(0, len(x), rows)]
    del w
    gc.collect()
    return float(np.mean(parts))


def run(ctx):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import parallel as par
    from mxnet_tpu.gluon.block import functionalize
    from mxnet_tpu.gluon.model_zoo import gpt
    from mxnet_tpu.parallel import gpt_spmd
    from mxnet_tpu.parallel.ring_attention import default_attention_impl

    cell, cfg, mix, watch = ctx.cell, ctx.config, ctx.traffic, ctx.watch
    job = cell["job"]
    impl = default_attention_impl()
    if watch.want == "tpu":
        assert impl == "flash", \
            "the Pallas kernel was not picked on a TPU: %r" % impl
    net = getattr(gpt, cfg["model"]["factory"])(
        max_len=cfg["n_positions"], vocab_size=cfg["vocab_size"])
    common.seeded_gpt_weights(net, ctx.seed, keep_grads=True)
    vocab_real = cfg["vocab_size"] if ctx.tiny else 50257
    ring = trafficgen.token_batches(mix, ctx.seed, vocab_real)
    batch_rows, seq = ring[0]["x"].shape
    check = cell["correct"]
    want_loss = reference_loss(net, ring[0], check["reference_rows"])

    mesh = par.make_mesh(devices=jax.devices()[:ctx.chips], **job["mesh"])
    fn, params = functionalize(net, jnp.asarray(ring[0]["x"]), train=True)
    init_fn, step_fn = gpt_spmd.make_train_step(
        fn, mesh, lr=job["lr"], compute_dtype=jnp.dtype(job["compute_dtype"]))
    ps, opt = init_fn(params)
    del params
    watch.on_device(ps, "GPT parameters")
    rng = common.seed_key(ctx.seed)
    t0 = time.perf_counter()
    compiled = step_fn.lower(ps, opt, ring[0], rng).compile()
    common.say("compiled", seconds=time.perf_counter() - t0,
               compile_cache=dict(watch.cache))
    programs = [common.program_memory(compiled)]
    if watch.want == "tpu":
        assert common.has_kernel(compiled), \
            "no Mosaic call in the lowered train step"
    del compiled

    losses = []

    def step(i):
        nonlocal ps, opt
        ps, opt, loss = step_fn(ps, opt, ring[i % len(ring)], rng)
        losses.append(float(loss))      # scalar fetch ends the step

    for i in range(1 + int(job.get("warm_steps", 2))):
        step(i)
    got_loss = losses[0]
    ok_ref = abs(got_loss - want_loss) <= check["loss_tol"]
    common.say("reference", loss_system=got_loss, loss_reference=want_loss,
               tol=check["loss_tol"], ok=ok_ref, attention_impl=impl)

    steps, window_s, records = common.step_window(
        ctx, lambda: step(len(losses)))
    compiles = watch.compiles - ctx.compiles_at_open
    finite, falling = common.loss_checks(losses)
    common.say("window", seconds=window_s, steps=steps,
               step_s=window_s / max(1, steps), first_loss=losses[0],
               last_losses=losses[-5:], finite=finite, falling=falling,
               compiles_in_window=compiles)
    correct = bool(ok_ref and finite and falling and compiles == 0)
    return {"correct": correct, "attempted": steps,
            "failed": 0 if finite else steps,
            "end_to_end": {"train_samples_s": steps * batch_rows / window_s},
            "counters": {"steps": steps, "batch": batch_rows, "seq": seq,
                         "window_s": window_s, "chips": ctx.chips},
            "span_records": records, "programs": programs,
            "why_not_correct": None if correct else {
                "loss_system": got_loss, "loss_reference": want_loss,
                "finite": finite, "falling": falling, "compiles": compiles}}
