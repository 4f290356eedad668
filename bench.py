"""Headline benchmark: model-zoo training throughput (img/s) on one chip.

Baseline (BASELINE.md): MXNet v0.11 ResNet-50 ImageNet at batch 32 on one
K80 = 109 img/s (/root/reference/example/image-classification/README.md:147-157);
the NETWORKS table below carries every per-family K80 row from that README.
Default: gluon model_zoo ResNet-50 v1 compiled to one XLA program —
forward, softmax-CE loss, backward, SGD+momentum update — per step,
images 224x224x3.  BENCH_NETWORK selects any other family.

Timing: host clock around steps that end in ``block_until_ready`` on the
last step's loss — every step's loss depends on the previous step's
(donated) params, so waiting for the last one waits for the whole chain.

Two kinds of mode live here (the split into a cell runner and tests is
ROADMAP S1/D4).  DEVICE-METRIC modes ('' = model-zoo training,
'attention', 'transformer', 'generate') time a program on the chip:
without a TPU they FAIL — they never shrink the problem to the CPU and
print under the same metric name — and a device kind missing from
PEAK_FLOPS is an error, not a dropped MFU.  COUNT-CONTRACT modes
('steptrace', 'spmd', 'telemetry', 'restart', 'serve', 'graph',
'stream', 'pipeline') assert dispatch counts, recompiles, ratios and
host-side rates on whatever backend is there; the ones that start
child processes ('serve', 'restart') pin every child to the CPU, so a
parent that holds the chip never starts a child that needs it, and
their times are CPU times, not device times.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""
import functools
import json
import os
import sys
import time

# per-network reference baselines (1x K80 img/s) and fwd GMACs at 224²
# (299² for inception_v3) — reference example/image-classification/
# README.md:147-157,357; GMACs are the standard published counts
NETWORKS = {
    "resnet18_v1": (185.0, 1.82),
    "resnet34_v1": (172.0, 3.67),
    "resnet50_v1": (109.0, 4.089),
    "resnet101_v1": (78.0, 7.80),
    "resnet152_v1": (57.0, 11.51),
    "inception_v3": (30.0, 5.73),
    "alexnet": (457.0, 0.71),
    "vgg16": (None, 15.47),
    "densenet121": (None, 2.83),
    "squeezenet1_0": (None, 0.82),
}

def _network_metric(network):
    """'resnet50_v1' -> 'resnet50_train_images_per_sec' (the name the
    driver has tracked since round 1).  Only the '_v1' family default is
    stripped — 'inception_v3' keeps its version so the metric name
    round-trips to the BENCH_NETWORK value (ADVICE r3)."""
    if network.endswith("_v1"):
        network = network[:-3]
    return "%s_train_images_per_sec" % network


# nominal dense bf16 peak FLOP/s by device kind (for the MFU report;
# vendor datasheets — v5e: Google Cloud documentation, "TPU v5e")
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
}


def _require_chip(mode):
    """The device a DEVICE-METRIC mode measures, or a hard failure: a
    number from a CPU run is never printed under a device metric's
    name.  Also places the persistent compile cache before the first
    compile."""
    import jax
    from mxnet_tpu import aot_cache
    aot_cache.enable_persistent_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            "bench.py: BENCH_MODE=%r measures a device metric and found "
            "no TPU (jax.devices()[0].platform == %r) — refusing to "
            "shrink to the CPU and print under the same metric name"
            % (mode or "", dev.platform))
    return dev


def _peak_flops(device_kind):
    if device_kind not in PEAK_FLOPS:
        raise SystemExit(
            "bench.py: no peak FLOP/s on record for device kind %r — add "
            "it to PEAK_FLOPS with its source; MFU is not silently "
            "dropped" % device_kind)
    return PEAK_FLOPS[device_kind]


def _count_contract_note():
    """Stamp for the count-contract modes that start child processes:
    what ran where, so their times are never read as device metrics."""
    import jax
    dev = jax.devices()[0]
    return ("count-contract mode: every child process pinned to "
            "JAX_PLATFORMS=cpu, parent on %s (%s) — counts and ratios "
            "are the contract, times are not device times"
            % (dev.platform, dev.device_kind))


def _perf_probe_path():
    """Put tools/perf_probe on sys.path once (steptrace/restart_probe
    imports for the probe-backed bench modes)."""
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "tools", "perf_probe")
    if d not in sys.path:
        sys.path.insert(0, d)


def _tier1_margin_gate():
    """Post-suite wall-margin assertion (ISSUE 17 satellite): with
    MXTPU_TIER1_LOG pointing at a captured tier-1 pytest log, the
    bench run refuses to pass when the suite overran the CI wall
    (MXTPU_TIER1_WALL, default 870 s) — the wall is discovered by this
    gate, never by the harness's kill.  Unset/missing log = skip: the
    gate only speaks when a suite actually ran."""
    path = os.environ.get("MXTPU_TIER1_LOG")
    if not path or not os.path.exists(path):
        return
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "tools")
    if d not in sys.path:
        sys.path.insert(0, d)
    import tier1_margin
    wall = float(os.environ.get("MXTPU_TIER1_WALL", "870"))
    with open(path) as f:
        elapsed, m = tier1_margin.margin(f.read(), wall)
    if elapsed is None:
        print("tier1-margin: no pytest summary in %s — the suite "
              "died before reporting; failing the bench run" % path,
              file=sys.stderr, flush=True)
        sys.exit(5)
    print("tier1-margin: suite %.1fs, wall %.0fs, margin %+.1fs"
          % (elapsed, wall, m), file=sys.stderr, flush=True)
    if m < 0:
        print("tier1-margin: tier-1 OVERRAN the wall; failing the "
              "bench run", file=sys.stderr, flush=True)
        sys.exit(5)


def bench_attention():
    """BENCH_MODE=attention: Pallas flash-attention step vs chip peak.

    Times fwd+bwd of the fused kernel on [B,H,T,D] = (4, 16, 4096, 128)
    — ~O(T) memory where the einsum oracle would hold a 4096² score
    matrix per head.  Attention FLOPs: 4·B·H·T²·D per fwd, ×3.5 for
    fwd+bwd (dq, dk, dv re-use the two matmuls plus recompute).
    """
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention

    b, h, t, d = (int(os.environ.get("BENCH_ATTN_" + k, v)) for k, v in
                  (("B", 4), ("H", 16), ("T", 4096), ("D", 128)))
    steps = max(1, int(os.environ.get("BENCH_STEPS", "20")))
    dev = _require_chip("attention")
    platform, device_kind = dev.platform, dev.device_kind

    key = jax.random.PRNGKey(0)
    dt = jnp.bfloat16
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                 (b, h, t, d), dt) for i in range(3))

    @jax.jit
    def step(q, k, v):
        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True).astype(
                jnp.float32).sum()
        l, (dq, dk, dv) = jax.value_and_grad(loss, argnums=(0, 1, 2))(
            q, k, v)
        # reduce grads to ONE scalar output: keeps the backward live
        # (returning l alone lets XLA dead-code-eliminate it) without
        # 48 MB of gradient outputs per step
        gs = (dq.astype(jnp.float32).sum() + dk.astype(jnp.float32).sum()
              + dv.astype(jnp.float32).sum())
        return l, gs

    l, gs = step(q, k, v)
    gs.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(steps):
        l, gs = step(q, k, v)
    gs.block_until_ready()
    dtime = time.perf_counter() - t0
    # causal halves the score matrix work
    flops = 3.5 * 4 * b * h * t * t * d / 2 * steps
    result = {
        "metric": "flash_attention_train_tflops",
        "value": round(flops / dtime / 1e12, 2),
        "unit": "TFLOP/s (B%d H%d T%d D%d causal %s fwd+bwd, 1 %s)"
                % (b, h, t, d, jnp.dtype(dt).name, platform),
        "vs_baseline": 0.0,  # no reference counterpart (2017, pre-attention)
        "ms_per_step": round(dtime / steps * 1e3, 2),
        "mfu": round(flops / dtime / _peak_flops(device_kind), 3),
    }
    print(json.dumps(result))


GPT_CONFIGS = {"tiny": (2, 128, 4), "small": (12, 768, 12),
               "medium": (24, 1024, 16)}


def _gpt_metric(kind="train"):
    cfg_name = os.environ.get("BENCH_GPT", "small")
    if cfg_name not in GPT_CONFIGS:
        raise ValueError("BENCH_GPT must be one of %s, got %r"
                         % (sorted(GPT_CONFIGS), cfg_name))
    return cfg_name, "gpt2_%s_%s_tokens_per_sec" % (cfg_name, kind)


def bench_generate():
    """BENCH_MODE=generate: GPT flagship INFERENCE throughput.

    Times gpt.generate (prefill + jitted KV-cache decode scan): one
    batched causal pass over the prompt, then n_new sequential decode
    steps.  Metric is decoded tokens/s (batch * n_new / wall) with the
    prompt prefill amortized in — the serving-path number next to the
    training MFU headline.
    """
    import numpy as np
    import jax

    cfg_name, metric = _gpt_metric("generate")
    n_layer, d_model, n_head = GPT_CONFIGS[cfg_name]
    device_kind = _require_chip("generate").device_kind
    prompt_len = int(os.environ.get("BENCH_PROMPT", "512"))
    n_new = int(os.environ.get("BENCH_NEW", "128"))
    batch = int(os.environ.get("BENCH_BATCH", "8"))
    steps = max(1, int(os.environ.get("BENCH_STEPS", "10")))
    vocab = 50304

    from mxnet_tpu.gluon.model_zoo import gpt
    net = gpt.GPTLM(vocab, n_layer, d_model, n_head,
                    max_len=prompt_len + n_new)
    net.initialize()
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, vocab, (batch, prompt_len)).astype(np.int32)

    # warm up the SAME (sampling) runner the timed loop uses — greedy
    # and sampling compile different scans (static cache key)
    gpt.generate(net, prompt, n_new, temperature=0.8, seed=-1)
    t0 = time.perf_counter()
    for i in range(steps):
        out = gpt.generate(net, prompt, n_new, temperature=0.8,
                           seed=i)
    dt = (time.perf_counter() - t0) / steps
    assert out.shape == (batch, prompt_len + n_new)
    tok_s = batch * n_new / dt
    print(json.dumps({
        "metric": metric,
        "value": round(tok_s, 1),
        "unit": "tok/s (B%d prompt %d +%d new, %d %s)" % (
            batch, prompt_len, n_new, len(jax.devices()), device_kind),
        "vs_baseline": 0.0,
        "ms_per_step": round(dt * 1000, 2),
    }), flush=True)


def bench_transformer():
    """BENCH_MODE=transformer: GPT flagship training MFU.

    Times the full causal-LM training step (fwd, softmax-CE over the
    padded vocab, bwd, SGD+momentum, bf16 compute / fp32 master) of a
    model-zoo GPT config.  This is the workload class TPUs are bought
    for: MFU is the headline, tokens/s the throughput.  FLOPs: matmul
    params contribute 6·N_matmul per token (fwd 2N + bwd 4N); attention
    adds 3.5 · 4·T²·H·D / 2 (causal) per layer per sequence.
    """
    import jax
    import jax.numpy as jnp

    cfg_name, metric = _gpt_metric()
    n_layer, d_model, n_head = GPT_CONFIGS[cfg_name]

    dev = _require_chip("transformer")
    platform, device_kind = dev.platform, dev.device_kind
    seq = int(os.environ.get("BENCH_SEQ", "2048"))
    batch = int(os.environ.get("BENCH_BATCH", "8"))
    steps = max(1, int(os.environ.get("BENCH_STEPS", "20")))
    warmup = max(1, int(os.environ.get("BENCH_WARMUP", "3")))
    vocab = 50304

    from mxnet_tpu.gluon.model_zoo import gpt
    from mxnet_tpu.gluon.block import functionalize

    # BENCH_REMAT=1: per-block rematerialisation (memory for FLOPs —
    # lets T or batch grow past HBM; MFU denominator stays the same)
    net = gpt.GPTLM(vocab, n_layer, d_model, n_head, max_len=seq,
                    remat=os.environ.get("BENCH_REMAT") == "1")
    net.initialize()
    toks0 = jnp.zeros((batch, seq), jnp.int32)
    fn, params = functionalize(net, toks0, train=True)
    mom = [jnp.zeros_like(p) for p in params]

    bench_dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    if bench_dtype not in ("bfloat16", "float32"):
        raise ValueError("BENCH_DTYPE must be bfloat16 or float32, got %r"
                         % bench_dtype)
    cdt = jnp.bfloat16 if bench_dtype == "bfloat16" else jnp.float32

    def loss_fn(ps, x, y):
        cps = [p.astype(cdt) for p in ps]
        (logits,), _ = fn(cps, x)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, y[..., None], axis=-1).mean()

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(ps, mom, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(ps, x, y)
        new_mom = [0.9 * m - 3e-4 * g.astype(jnp.float32)
                   for m, g in zip(mom, grads)]
        new_ps = [p + m for p, m in zip(ps, new_mom)]
        return new_ps, new_mom, loss

    key = jax.random.PRNGKey(0)
    x = jax.random.randint(key, (batch, seq), 0, vocab)
    y = jnp.roll(x, -1, axis=1)

    # analytic per-step training FLOPs: 6 FLOPs per matmul param per
    # token (embedding/position tables do no matmul work; the tied head
    # DOES matmul — count d·V once) + flash-attention score FLOPs
    n_matmul = n_layer * 12 * d_model * d_model + d_model * vocab
    attn = n_layer * 3.5 * 4 * seq * seq * d_model / 2
    step_flops = (6 * n_matmul * seq + attn) * batch

    for _ in range(warmup):
        params, mom, loss = train_step(params, mom, x, y)
    loss.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(steps):
        params, mom, loss = train_step(params, mom, x, y)
    loss.block_until_ready()
    dt = time.perf_counter() - t0

    tok_s = batch * seq * steps / dt
    result = {
        "metric": metric,
        "value": round(tok_s, 1),
        "unit": "tok/s (bs %d, T %d, vocab %d, %s, 1 %s device)" % (
            batch, seq, vocab, bench_dtype, platform),
        "vs_baseline": None,  # no reference counterpart (2017, pre-attention)
        "tflops": round(step_flops * steps / dt / 1e12, 1),
        "mfu": round(step_flops * steps / dt / _peak_flops(device_kind),
                     3),
    }
    print(json.dumps(result))


def _synthetic_rec(n_images, edge, path):
    """Write an ImageNet-shaped synthetic .rec (JPEG-encoded random
    images) once; reruns reuse it.  Plays tools/im2rec.py's role without
    needing an image folder."""
    import numpy as np
    from mxnet_tpu import recordio

    if os.path.exists(path):
        return path
    from PIL import Image
    import io as pyio
    rng = np.random.RandomState(0)
    # write to a temp name, rename only on completion — an interrupted
    # generation must not leave a truncated .rec a later run benchmarks
    rec_tmp = path + ".partial"
    idx_final = path[:-4] + ".idx"
    idx_tmp = idx_final + ".partial"
    rec = recordio.MXIndexedRecordIO(idx_tmp, rec_tmp, "w")
    try:
        for i in range(n_images):
            img = rng.randint(0, 256, (edge, edge, 3), np.uint8)
            buf = pyio.BytesIO()
            Image.fromarray(img).save(buf, format="JPEG", quality=90)
            header = recordio.IRHeader(0, float(i % 1000), i, 0)
            rec.write_idx(i, recordio.pack(header, buf.getvalue()))
        rec.close()
        os.replace(rec_tmp, path)
        os.replace(idx_tmp, idx_final)
    except BaseException:
        rec.close()
        for f in (rec_tmp, idx_tmp):
            if os.path.exists(f):
                os.remove(f)
        raise
    return path


def bench_pipeline():
    """BENCH_MODE=pipeline: native input-pipeline throughput.

    Measures the C++ decode+augment pipeline (src/mxtpu/image_iter.cc)
    standalone — JPEG decode, 224 random crop, mirror, mean/std — the
    denominator for 'does IO sustain training' (PERF.md; the reference
    benchmarked the same via `--test-io 1`, example/image-classification/
    common/fit.py)."""
    import time as _time
    import numpy as np
    import jax
    import mxnet_tpu as mx

    jax.devices()  # backend init is the hang risk; prove it then disarm

    n_images = int(os.environ.get("BENCH_PIPE_IMAGES", "2000"))
    batch = int(os.environ.get("BENCH_BATCH", "128"))
    threads = int(os.environ.get("BENCH_PIPE_THREADS", "8"))
    epochs = int(os.environ.get("BENCH_PIPE_EPOCHS", "3"))
    cache = os.environ.get("BENCH_PIPE_REC",
                           "/tmp/mxtpu_bench_synth_%d.rec" % n_images)
    _synthetic_rec(n_images, 256, cache)

    it = mx.io.ImageRecordIter(
        path_imgrec=cache, data_shape=(3, 224, 224), batch_size=batch,
        shuffle=True, rand_crop=True, rand_mirror=True,
        mean_r=123.68, mean_g=116.78, mean_b=103.94,
        std_r=58.4, std_g=57.12, std_b=57.38,
        preprocess_threads=threads, prefetch_buffer=8)
    # warm epoch (thread pool spin-up, file cache)
    n = 0
    for b in it:
        n += batch
    t0 = _time.perf_counter()
    total = 0
    for _ in range(epochs):
        it.reset()
        for b in it:
            np.asarray(b.data[0]._data[0, 0, 0])  # pull one value
            total += batch
    dt = _time.perf_counter() - t0
    img_s = total / dt
    train_img_s = float(os.environ.get("BENCH_PIPE_TRAIN_IMG_S", "2235"))
    print(json.dumps({
        "metric": "input_pipeline_images_per_sec",
        "value": round(img_s, 2),
        "unit": "img/s (jpeg decode + 224 crop/mirror/norm, %d threads, "
                "bs %d)" % (threads, batch),
        "vs_baseline": round(img_s / train_img_s, 3),
    }))


def bench_steptrace():
    """BENCH_MODE=steptrace: per-step XLA dispatch/compile counts of the
    fused Module.fit_step vs the split forward_backward+update pair on a
    small MLP fit loop — the regression tail for BENCH_*.json (the fused
    path must stay at exactly 1 dispatch/step, 0 steady-state compiles;
    see PERF.md, "Fused train step")."""
    import jax
    _perf_probe_path()
    import steptrace as _steptrace

    jax.devices()
    result = _steptrace.run()
    fused = result["fused"]
    unfused = result["unfused"]
    # the divergence guard rides INSIDE the fused program — folding it in
    # must not cost a dispatch.  Fail the bench loudly if it ever does.
    if fused["dispatches_per_step"] != 1.0:
        raise AssertionError(
            "guarded fused step dispatched %.3f programs/step (contract: "
            "exactly 1.0 — the divergence guard must stay inside the "
            "fused program)" % fused["dispatches_per_step"])
    fused_async = result["fused_async_ckpt"]
    if fused_async["dispatches_per_step"] != 1.0:
        raise AssertionError(
            "fused step with async checkpointing dispatched %.3f "
            "programs/step (contract: the snapshot+enqueue save path "
            "adds ZERO dispatches)" % fused_async["dispatches_per_step"])
    print(json.dumps({
        "metric": "fused_step_dispatches_per_step",
        "value": round(fused["dispatches_per_step"], 3),
        "unit": "dispatches/step (steady state; unfused=%s; %d params)"
                % (round(unfused["dispatches_per_step"], 3),
                   result["n_params"]),
        # 1.0 == the fused-path contract; anything above is a regression
        "vs_baseline": round(fused["dispatches_per_step"] / 1.0, 3),
        "steptrace": result,
    }))


def bench_spmd():
    """BENCH_MODE=spmd: the mesh-native ZeRO-1 fused step on an 8-device
    host mesh (tools/perf_probe/steptrace.run_spmd).  Hard contracts:

    - exactly 1.0 dispatch/step — the reduce-scatter, sharded update and
      all-gather all live INSIDE the one donated program;
    - 0 steady-state compiles;
    - opt-state bytes/device ~= 1/N of the total (replicated fallbacks
      for indivisible leaves get a small tolerance).
    """
    import jax
    _perf_probe_path()
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags and \
            jax.device_count() < 8:
        raise RuntimeError(
            "BENCH_MODE=spmd: fewer than 8 devices and no "
            "--xla_force_host_platform_device_count in XLA_FLAGS")
    import steptrace as _steptrace

    jax.devices()
    result = _steptrace.run_spmd()
    n = result["n_devices"]
    if result["dispatches_per_step"] != 1.0:
        raise AssertionError(
            "ZeRO-1 fused step dispatched %.3f programs/step (contract: "
            "exactly 1.0 — reduce-scatter/update/all-gather must stay "
            "inside the one donated program)"
            % result["dispatches_per_step"])
    if result["compile_count"] != 0:
        raise AssertionError(
            "ZeRO-1 fused step recompiled %d time(s) in steady state"
            % result["compile_count"])
    ratio = result["opt_state_total_bytes"] / \
        max(1, result["opt_state_bytes_per_device"])
    # the MLP's (4,) softmax bias state replicates (nothing divides 8);
    # everything else must be 1/N — so the aggregate factor sits just
    # under N but far above N/2
    if ratio < n / 2:
        raise AssertionError(
            "opt-state bytes/device %d vs total %d (factor %.2f): state "
            "is not sharded ~1/%d across the mesh"
            % (result["opt_state_bytes_per_device"],
               result["opt_state_total_bytes"], ratio, n))
    # compile-time attribution cross-check (OBSERVABILITY.md §8): the
    # compiled program's OWN per-device argument accounting
    # (xla.memory.argument_bytes) must agree ±20% with the bytes the
    # live arrays' shard shapes say each device holds — 1/N opt-state +
    # replicated params + 1/N batch.  An unsharded state tree would blow
    # this by ~2.4x (adam: two full state leaves vs two 1/N shards), so
    # the ZeRO economics are now asserted from the executable, not from
    # the placement model.
    arg_bytes = result["gauge_xla_memory_argument_bytes"]
    expected = result["expected_argument_bytes_per_device"]
    if not arg_bytes:
        raise AssertionError(
            "xla.memory.argument_bytes gauge not populated — the fused "
            "step's compile-time attribution is missing")
    if abs(arg_bytes - expected) > 0.2 * expected:
        raise AssertionError(
            "compiled per-device argument bytes %d vs %d expected from "
            "the sharded live arrays (>20%% apart): the program's "
            "memory accounting disagrees with the ZeRO-1 placement"
            % (arg_bytes, expected))
    if not result["gauge_collective_bytes_per_step"]:
        raise AssertionError(
            "sharding.collective_bytes_per_step gauge not populated "
            "from the compiled program's collective ops")
    print(json.dumps({
        "metric": "zero1_opt_state_shard_factor",
        "value": round(ratio, 3),
        "unit": "x smaller per device (n=%d, %d/%d leaves sharded, "
                "1.0 dispatch/step)"
                % (n, result["opt_state_leaves_sharded"],
                   result["opt_state_leaves"]),
        "vs_baseline": round(ratio / n, 3),
        "spmd": result,
    }))


def bench_telemetry():
    """BENCH_MODE=telemetry: always-on telemetry cost + phase breakdown.

    Runs the steptrace MLP fused fit loop with telemetry recording on
    (the production default) and with the hot path disabled
    (telemetry.set_enabled(False) — same switch as MXTPU_TELEMETRY_OFF)
    in many short alternating paired segments; the reported overhead is
    the median of the per-pair deltas, which cancels the slow drift that
    dwarfs a couple-of-µs effect on a ~0.3 ms CPU step.  Also reports
    the phase-time breakdown (fit_step.dispatch / fit_step.sync
    histograms).  Contract (OBSERVABILITY.md): overhead < 1% of the
    fused step, dispatch rate untouched at exactly 1.0/step."""
    import jax
    _perf_probe_path()
    import steptrace as _steptrace
    from mxnet_tpu import profiler, telemetry

    jax.devices()
    mod, train = _steptrace.build_module()
    batches = list(train)
    steps = max(1, int(os.environ.get("BENCH_STEPS", "200")))
    pairs = max(3, int(os.environ.get("BENCH_PAIRS", "12")))
    for _ in range(2):  # warm: trace + compile + allocator steady state
        for b in batches:
            mod.fit_step(b)

    def loop(n):
        t0 = time.perf_counter()
        for i in range(n):
            mod.fit_step(batches[i % len(batches)])
        return (time.perf_counter() - t0) / n

    deltas, offs = [], []
    try:
        for i in range(pairs):
            # alternate which side runs first so per-pair warmup/drift
            # doesn't systematically land on one side
            if i % 2:
                telemetry.set_enabled(True)
                on = loop(steps)
                telemetry.set_enabled(False)
                off = loop(steps)
            else:
                telemetry.set_enabled(False)
                off = loop(steps)
                telemetry.set_enabled(True)
                on = loop(steps)
            offs.append(off)
            deltas.append(on - off)
    finally:
        telemetry.set_enabled(True)

    telemetry.reset()
    profiler.reset_step_stats()
    measured = loop(steps)
    stats = profiler.step_stats()
    rep = telemetry.report()
    if stats["dispatch_count"] != steps:
        raise AssertionError(
            "telemetry run dispatched %d programs over %d steps "
            "(contract: exactly 1.0/step)" % (stats["dispatch_count"],
                                              steps))
    deltas.sort()
    offs.sort()
    delta = deltas[len(deltas) // 2]
    off = offs[len(offs) // 2]
    on = off + delta
    overhead_pct = delta / off * 100.0
    # the absolute per-step budget (OBSERVABILITY.md §8): the rank-
    # stamped hot path — one tuple append + the amortized batched
    # drain; job-scope identity/clock stamping is paid per report()
    # line, never per step — must stay within the ~2 µs always-on
    # budget.  Asserted on an ISOLATED microbench of the recording call
    # itself: the A/B fit-loop delta above is the honest end-to-end
    # number but carries several µs of scheduler noise on a shared box
    # (the seed measures ~10 µs of "overhead" by that method on a busy
    # machine), which would make an absolute gate on it meaningless.
    # The gate defaults to 2x the budget for interpreter jitter.
    telemetry.reset()
    iters = 20000
    base = time.perf_counter_ns()
    t0 = time.perf_counter()
    for i in range(iters):
        telemetry.note_train_step(base + i * 1000,
                                  base + i * 1000 + 500,
                                  base + i * 1000 + 800, False, None)
    hot_us = (time.perf_counter() - t0) / iters * 1e6
    telemetry.reset()
    budget_us = float(os.environ.get("MXTPU_TELEMETRY_BUDGET_US", "4"))
    if hot_us > budget_us:
        raise AssertionError(
            "telemetry hot path costs %.2f us/step isolated (budget "
            "%.1f us, ~2 us contract + headroom): the always-on "
            "per-step recording path regressed" % (hot_us, budget_us))
    phases = {
        name: {"count": p["count"],
               "mean_ms": round(1e3 * p["sum"] / p["count"], 4),
               "p50_ms": round(1e3 * p["p50"], 4),
               "p99_ms": round(1e3 * p["p99"], 4)}
        for name, p in rep["phases"].items() if p["count"]}
    print(json.dumps({
        "metric": "telemetry_overhead_pct",
        "value": round(overhead_pct, 3),
        "unit": "%% of fused CPU MLP step (median-paired on %.4f ms vs "
                "off %.4f ms, %d pairs x %d steps; budget 1%%)"
                % (on * 1e3, off * 1e3, pairs, steps),
        # vs the 1% always-on budget: <1.0 is within contract
        "vs_baseline": round(overhead_pct / 1.0, 3),
        "wall_ms_per_step": round(measured * 1e3, 4),
        "hot_path_us_per_step": round(hot_us, 3),
        "phases": phases,
        "flight": rep["flight"],
    }))


def bench_serve():
    """BENCH_MODE=serve: production inference serving (PERF.md §6).

    tools/perf_probe/serve_probe.py: an open-loop Poisson workload of
    mixed prompt/output lengths through the continuous-batching paged-KV
    ServingEngine vs the sequential per-request predictor baseline (one
    fixed-shape full forward per token — today's Predictor.forward
    discipline).  Hard contracts:

    - exactly 1.0 decode dispatch per token step (ALL resident
      sequences advance inside the one donated program);
    - 0 steady-state recompiles across request join/leave churn;
    - both servers emit bit-identical greedy tokens (asserted inside
      the probe);
    - continuous batching >= 2x the sequential baseline's tokens/s;
    - an AOT-warm replica reaches its first token with 0 foreground
      serving-program compiles (two subprocesses sharing a cache dir);
    - **degraded mode** (ISSUE 11): with one of two router replicas
      killed mid-probe (serve.replica.lost), every accepted request
      still completes with BIT-identical tokens to the unfaulted run,
      and the replacement replica spins up AOT-warm (0 foreground
      compiles) — with per-VERDICT deltas pinned (0 failed, exactly
      the killed replica's in-flight count retried);
    - **request-scope observability** (ISSUE 13): the per-decode-step
      tracing cost stays within MXTPU_SERVE_TRACE_BUDGET_US (default
      2 µs, isolated microbench), goodput == raw tokens on the
      unfaulted run, and serve_report run on the degraded drill's REAL
      artifact tree reconstructs every lifecycle (one terminal verdict
      each), links failovers across replicas by trace id, names the
      killed replica in the blame section, emits a single loadable
      merged chrome trace, and reconciles traced tokens with the
      serving.tokens counter bit-exactly;
    - **partition drill** (ISSUE 17): over a fleet sharing NO run dir
      (private per-worker tmp dirs, addr-pinned proxies), heartbeat-only
      loss raises suspicion with ZERO failovers and completes every
      request; a real partition confirms the typed `fence_expiry`
      reason, fails over, and fences the zombie's late completions —
      0 double-delivered, >= 1 fenced result, tokens bit-identical;
    - **telemetry plane** (ISSUE 18): the partition drill's router
      host assembles fleet telemetry ONLY via telemetry_pull and
      serve_report over that pull-only tree is green (lawful
      lifecycles, bit-exact token accounting, >= 1 default alert rule
      fired and rendered), fleet_top returns a complete live matrix,
      and a pull per engine step leaves 1.0 decode dispatch/step with
      0 recompiles, the steady-state pull itself under
      MXTPU_TELEMETRY_PULL_BUDGET (default 2000 us, isolated);
    - **speculative decoding** (ISSUE 16): on the acceptance-friendly
      workload spec-on reaches >= 1.5x spec-off tokens/s with > 1.3
      tokens per slot step, still exactly 1.0 decode dispatch/step and
      0 steady-state recompiles, greedy tokens bit-identical to
      spec-off, drafted == accepted + rejected, decode tokens ==
      slot_steps + accepted - discarded, and mixed greedy/sampled
      streams reproduce bit-exactly both on a re-run and across a
      router failover re-decode;
    - **quantized KV pages** (ISSUE 20): int8 pages + per-page-per-KV-
      head fp32 absmax scales vs bf16 pools — >= 1.8x residents in the
      same pool bytes, greedy token match-rate >= 0.99 vs the fp
      reference, kernel-vs-oracle dequant error <= 1e-5, and the hot
      path keeps 1.0 decode dispatch/step with 0 steady-state
      recompiles (quantize-on-scatter and dequant live INSIDE the one
      donated program);
    - **streamed delivery** (ISSUE 19): cursor-pull streaming delivers
      every accepted request's tokens EXACTLY ONCE — in-process
      (streamed TTFT p50 < 0.5x the unary completion p50, polling
      leaves 1.0 dispatch/step and 0 recompiles), across a real
      SIGKILL failover mid-stream (no gap, no duplicate, bit-identical
      to unfaulted; a blackholed poll reply recovered by an idempotent
      re-poll at the same cursor), under cancellation (typed
      `cancelled` verdict mid-decode AND queued, slot + KV pages back,
      survivors unperturbed), and under client vanish (the abandon
      sweep reclaims orphans with the typed `abandoned` verdict, page
      conservation green, the `orphan_reclaim` default alert fires).
    """
    import jax
    _perf_probe_path()
    import serve_probe

    jax.devices()
    result = serve_probe.run()
    cont = result["continuous"]
    trace_us = result["trace_overhead_us"]
    trace_budget = float(os.environ.get("MXTPU_SERVE_TRACE_BUDGET_US",
                                        "2"))
    if trace_us > trace_budget:
        raise AssertionError(
            "per-decode-step request tracing costs %.3f us isolated "
            "(budget %.1f us): the one-batched-event hot path "
            "regressed" % (trace_us, trace_budget))
    if not (cont["goodput_counter"] == cont["tokens_counter"]
            == cont["traced_tokens"] == cont["total_tokens"]):
        raise AssertionError(
            "unfaulted run accounting diverged: goodput=%d "
            "tokens_counter=%d traced=%d produced=%d (contract: all "
            "equal when nothing expires or fails)"
            % (cont["goodput_counter"], cont["tokens_counter"],
               cont["traced_tokens"], cont["total_tokens"]))
    if cont["decode_dispatches_per_step"] != 1.0:
        raise AssertionError(
            "serving decode dispatched %.3f programs/step (contract: "
            "exactly 1.0 — every resident sequence advances inside ONE "
            "donated program)" % cont["decode_dispatches_per_step"])
    if cont["steady_state_compiles"] != 0:
        raise AssertionError(
            "serving loop recompiled %d time(s) under request churn "
            "(contract: join/leave never changes a program shape)"
            % cont["steady_state_compiles"])
    spin = result["spinup"]
    if spin["warm_serve_compiles"] != 0:
        raise AssertionError(
            "AOT-warm replica spin-up compiled %d serving program(s) in "
            "the foreground (contract: 0 — first token comes off the "
            "deserialized executable)" % spin["warm_serve_compiles"])
    speedup = result["speedup_tokens_per_sec"]
    if speedup < 2.0:
        raise AssertionError(
            "continuous batching reached only %.2fx the sequential "
            "predictor baseline (contract: >= 2x tokens/s on the same "
            "mixed-length workload)" % speedup)
    pfx = result["prefix"]
    if pfx["hit_rate"] <= 0:
        raise AssertionError(
            "prefix-heavy workload produced a 0 hit-rate (contract: "
            "shared system prompts MUST hit the prefix cache)")
    if pfx["prefill_token_reduction"] < 0.30:
        raise AssertionError(
            "prefix caching cut prefill tokens by only %.1f%% on the "
            "system-prompt workload (%d -> %d; contract: >= 30%% fewer "
            "prefill tokens than cache-off on the same workload)"
            % (100 * pfx["prefill_token_reduction"],
               pfx["prefill_tokens_off"], pfx["prefill_tokens_on"]))
    if not pfx["tokens_match_cache_off"]:
        raise AssertionError(
            "cache-on tokens diverged from cache-off on the same "
            "workload (contract: prefix sharing changes capacity and "
            "prefill cost, NEVER tokens — greedy and sampled alike)")
    if pfx["decode_dispatches_per_step"] != 1.0:
        raise AssertionError(
            "with prefix cache + sampling enabled the decode loop "
            "dispatched %.3f programs/step (contract: exactly 1.0 — "
            "both multipliers ride the one-donated-program step)"
            % pfx["decode_dispatches_per_step"])
    if pfx["steady_state_compiles"] != 0:
        raise AssertionError(
            "prefix+sampling serving recompiled %d time(s) under churn "
            "(contract: per-request sampling params are program INPUTS, "
            "never a recompile)" % pfx["steady_state_compiles"])
    if pfx["sampling_requests"] < 1:
        raise AssertionError(
            "the prefix workload exercised no sampled requests — the "
            "sampling half of the contract is vacuous")
    gqa = result["gqa"]
    if gqa["kernel_max_err"] >= 1e-5:
        raise AssertionError(
            "GQA paged kernel diverged from the oracle at K_kv=%d "
            "(max err %.2e; contract: kernel-vs-oracle equivalence at "
            "mixed lengths)" % (gqa["kv_heads"], gqa["kernel_max_err"]))
    if gqa["pool_bytes_gqa"] > gqa["pool_bytes_mha"]:
        raise AssertionError(
            "GQA page pools used MORE bytes (%d) than the multi-head "
            "pools (%d) — the capacity comparison is unsound"
            % (gqa["pool_bytes_gqa"], gqa["pool_bytes_mha"]))
    if gqa["resident_multiplier"] < 1.5:
        raise AssertionError(
            "GQA at K_kv = H/2 fit only %.2fx residents in the same "
            "page-pool bytes (%d -> %d; contract: >= 1.5x)"
            % (gqa["resident_multiplier"], gqa["residents_mha"],
               gqa["residents_gqa"]))
    kvq = result["kvq"]
    if kvq["dequant_max_err"] > 1e-5:
        raise AssertionError(
            "quantized paged kernel diverged from the dequantizing "
            "oracle on the SAME int8 pools + scales (max err %.2e; "
            "contract: <= 1e-5 — in-kernel dequant is exact up to fp "
            "reassociation)" % kvq["dequant_max_err"])
    if kvq["pool_bytes_int8"] > kvq["pool_bytes_bf16"]:
        raise AssertionError(
            "int8 page pools used MORE bytes (%d) than the bf16 pools "
            "(%d) — the capacity comparison is unsound"
            % (kvq["pool_bytes_int8"], kvq["pool_bytes_bf16"]))
    if kvq["resident_multiplier"] < 1.8:
        raise AssertionError(
            "int8 KV pages fit only %.2fx residents in the same pool "
            "bytes as bf16 (%d -> %d; contract: >= 1.8x — payload "
            "halves, scale rows cost ~8*K_kv bytes/page)"
            % (kvq["resident_multiplier"], kvq["residents_bf16"],
               kvq["residents_int8"]))
    if kvq["token_match_rate"] < 0.99:
        raise AssertionError(
            "int8 greedy tokens matched the fp reference at only "
            "%.4f (contract: >= 0.99 — quantized greedy is pinned to "
            "itself, the match-rate gate pins its drift from fp)"
            % kvq["token_match_rate"])
    if kvq["decode_dispatches_per_step"] != 1.0:
        raise AssertionError(
            "with int8 KV pages the decode loop dispatched %.3f "
            "programs/step (contract: exactly 1.0 — quantize-on-"
            "scatter and in-kernel dequant ride the ONE donated "
            "program)" % kvq["decode_dispatches_per_step"])
    if kvq["steady_state_compiles"] != 0:
        raise AssertionError(
            "int8 serving recompiled %d time(s) under churn "
            "(contract: the page dtype is baked at engine build, "
            "never a steady-state shape change)"
            % kvq["steady_state_compiles"])
    spec = result["spec"]
    if spec["speedup_tokens_per_sec"] < 1.5:
        raise AssertionError(
            "speculative decoding reached only %.2fx spec-off tokens/s "
            "on the acceptance-friendly workload (contract: >= 1.5x — "
            "verified drafts must multiply tokens per dispatch)"
            % spec["speedup_tokens_per_sec"])
    if not spec["tokens_match_spec_off"]:
        raise AssertionError(
            "spec-on greedy tokens diverged from spec-off on the same "
            "workload (contract: acceptance emits the greedy chain "
            "itself — speculation changes throughput, NEVER tokens)")
    if spec["tokens_per_slot_step"] <= 1.3:
        raise AssertionError(
            "speculative decode committed only %.2f tokens per slot "
            "participation (contract: > 1.3 — a non-speculative slot "
            "step is exactly 1.0)" % spec["tokens_per_slot_step"])
    if spec["decode_dispatches_per_step"] != 1.0:
        raise AssertionError(
            "with speculation enabled the decode loop dispatched %.3f "
            "programs/step (contract: exactly 1.0 — draft + verify + "
            "accept ride the ONE donated program)"
            % spec["decode_dispatches_per_step"])
    if spec["steady_state_compiles"] != 0:
        raise AssertionError(
            "speculative serving recompiled %d time(s) under churn "
            "(contract: draft length is a MASK, never a shape)"
            % spec["steady_state_compiles"])
    if not spec["counter_identity_draft"] or \
            not spec["counter_identity_tokens"]:
        raise AssertionError(
            "spec counters do not reconcile (drafted=%d accepted=%d "
            "rejected=%d; contract: drafted == accepted + rejected AND "
            "decode tokens == slot_steps + accepted - discarded)"
            % (spec["draft_tokens"], spec["accepted"],
               spec["rejected"]))
    if spec["spec_off_drafted"] != 0:
        raise AssertionError(
            "the spec-off arm drafted %d token(s) (contract: spec_k=0 "
            "means the drafter never runs)" % spec["spec_off_drafted"])
    if not spec["sampled_repro_match"]:
        raise AssertionError(
            "a mixed greedy/sampled spec-on run did not repeat "
            "bit-identically (contract: per-request functional PRNG — "
            "same seed, same stream)")
    if spec["failover_completed"] != spec["requests"] or \
            spec["failover_failovers"] < 1 or \
            not spec["failover_tokens_match"]:
        raise AssertionError(
            "spec-on router failover broke determinism (%d/%d "
            "completed, %d failover(s), tokens_match=%s; contract: "
            "sampled AND greedy streams survive the replacement "
            "replica's re-decode bit-exactly)"
            % (spec["failover_completed"], spec["requests"],
               spec["failover_failovers"],
               spec["failover_tokens_match"]))
    deg = result["degraded"]
    if deg["dropped"] != 0:
        raise AssertionError(
            "degraded mode dropped %d accepted request(s) after a "
            "replica kill (contract: the router completes every "
            "accepted request exactly once)" % deg["dropped"])
    if not deg["tokens_match_unfaulted"]:
        raise AssertionError(
            "degraded-mode tokens diverged from the unfaulted run "
            "(contract: failover re-decode is bit-identical greedy)")
    if deg["failovers"] < 1:
        raise AssertionError(
            "degraded mode observed no failover — the replica kill "
            "never landed; the contract was not exercised")
    if deg["replacement_foreground_compiles"] != 0:
        raise AssertionError(
            "replacement replica compiled %d serving program(s) in the "
            "foreground (contract: AOT/memo-warm spin-up)"
            % deg["replacement_foreground_compiles"])
    if deg["failed"] != 0:
        raise AssertionError(
            "degraded mode left %d request(s) with verdict `failed` "
            "(contract: 0 — a replica kill retries, never fails)"
            % deg["failed"])
    if deg["retried"] != deg["expected_retried"]:
        raise AssertionError(
            "degraded mode retried %s request(s) but the killed "
            "replica held exactly %s in flight (contract: the retry "
            "set IS the victim's in-flight set — verdict accounting, "
            "not just totals)" % (deg["retried"],
                                  deg["expected_retried"]))
    rep = deg["report"]
    if not rep["lifecycle_ok"]:
        raise AssertionError(
            "serve_report on the degraded artifact tree found "
            "lifecycle violations %s + %d open trace(s) (contract: "
            "every accepted request reconstructs with exactly one "
            "terminal verdict)" % (rep["violations"],
                                   rep["open_traces"]))
    if rep["arcs"] < 1 or rep["linked_arcs"] != rep["arcs"]:
        raise AssertionError(
            "serve_report linked %d of %d failover arc(s) across "
            "replicas by trace id (contract: every failed-over "
            "request links victim -> survivor)"
            % (rep["linked_arcs"], rep["arcs"]))
    if not rep["killed_replica_blamed"]:
        raise AssertionError(
            "serve_report's blame section did not name the killed "
            "replica %r" % rep["killed_replica"])
    if rep["trace_file_events"] < 1:
        raise AssertionError(
            "the merged serve chrome trace did not round-trip as one "
            "loadable JSON document")
    if not rep["token_accounting_exact"]:
        raise AssertionError(
            "traced token events (%s) did not reconcile bit-exactly "
            "with the serving.tokens counter (%s) on the degraded "
            "drill" % (rep["traced_tokens"], rep["tokens_counter"]))
    fleet = result["fleet"]
    if fleet["dropped"] != 0:
        raise AssertionError(
            "fleet drill dropped %d accepted request(s) after the "
            "replica-process SIGKILL (contract: the router completes "
            "every accepted request exactly once across real process "
            "death)" % fleet["dropped"])
    if not fleet["tokens_match_unfaulted"]:
        raise AssertionError(
            "fleet-drill tokens diverged from the unfaulted run "
            "(contract: the out-of-process failover re-decode is "
            "bit-identical greedy)")
    if fleet["failovers"] < 1:
        raise AssertionError(
            "fleet drill observed no failover — the "
            "serve.replica.sigkill never landed; the contract was "
            "not exercised")
    if fleet["replacement_spawns"] < 1:
        raise AssertionError(
            "the fleet drill never spawned a replacement process — "
            "the AOT-warm-replacement contract was not exercised "
            "(Router tolerates spawn failures on survivors; the DRILL "
            "must not)")
    if fleet["replacement_foreground_compiles"] != 0:
        raise AssertionError(
            "the replacement replica PROCESS compiled %d serving "
            "program(s) in the foreground (contract: 0 — it "
            "deserializes the fleet's shared AOT cache)"
            % fleet["replacement_foreground_compiles"])
    br = fleet["breaker"]
    if br["trips"] < 1 or not br["recovered"] or \
            br["final_state"] != "closed":
        raise AssertionError(
            "circuit breaker did not trip and recover under rpc.drop "
            "(trips=%s, final=%s; contract: consecutive timeouts trip "
            "it open, the half-open probe closes it once the replica "
            "heals)" % (br["trips"], br["final_state"]))
    if br["completed"] != br["requests"]:
        raise AssertionError(
            "breaker drill completed %d of %d requests (contract: a "
            "tripped breaker re-routes intake, it never strands a "
            "request)" % (br["completed"], br["requests"]))
    if br["served_by_b_after_recovery"] < 1:
        raise AssertionError(
            "no post-recovery request was served by the healed "
            "replica (contract: a closed breaker restores placement)")
    part = result["partition"]
    pha = part["phase_a"]
    if pha["suspicions"] < 1:
        raise AssertionError(
            "heartbeat-only loss raised no suspicion (contract: a cut "
            "control plane is OBSERVED — rpc.suspicions counts it)")
    if pha["failovers"] != 0 or pha["confirm_reason"] is not None:
        raise AssertionError(
            "heartbeat-only loss caused %d failover(s) (reason=%s; "
            "contract: suspicion NEVER fails over a replica whose "
            "data plane still makes progress)"
            % (pha["failovers"], pha["confirm_reason"]))
    if pha["completed"] != pha["requests"]:
        raise AssertionError(
            "heartbeat-only loss completed %d of %d requests "
            "(contract: a suspected-but-working replica serves on)"
            % (pha["completed"], pha["requests"]))
    if not pha["suspect_cleared"]:
        raise AssertionError(
            "suspicion did not clear after the control plane healed "
            "(contract: suspicion is reversible, confirmation is not)")
    if part["failovers"] <= pha["failovers"] or \
            part["confirm_reason"] != "fence_expiry" or \
            part["confirmations_fence_expiry"] < 1:
        raise AssertionError(
            "the partition drill never confirmed fence_expiry "
            "(failovers=%d, reason=%r; contract: heartbeat AND "
            "progress silence past the lease is the typed partition "
            "verdict)" % (part["failovers"], part["confirm_reason"]))
    if part["dropped"] != 0 or part["double_delivered"] != 0:
        raise AssertionError(
            "partition drill dropped %d / double-delivered %d "
            "request(s) (contract: exactly-once — one terminal "
            "journal line per rid, fenced zombies rejected)"
            % (part["dropped"], part["double_delivered"]))
    if part["fenced_results"] < 1 or \
            part["fenced_journal_lines"] < 1:
        raise AssertionError(
            "the zombie's late completions were never fenced "
            "(fenced_results=%d, journal lines=%d; contract: the "
            "healed partition's write-backs are observed and "
            "REJECTED, never silently unread)"
            % (part["fenced_results"], part["fenced_journal_lines"]))
    if not part["tokens_match_unfaulted"]:
        raise AssertionError(
            "partition-drill tokens diverged from the unfaulted run "
            "(contract: the fenced failover re-decode is bit-identical "
            "greedy)")
    coll = result["collector"]
    pull_budget = float(os.environ.get("MXTPU_TELEMETRY_PULL_BUDGET",
                                       "2000"))
    if coll["decode_dispatches_per_step"] != 1.0 or \
            coll["steady_state_compiles"] != 0:
        raise AssertionError(
            "a telemetry pull per engine step broke the hot path "
            "(%.3f dispatch/step, %d recompile(s); contract: the "
            "collector NEVER forces a dispatch or a recompile)"
            % (coll["decode_dispatches_per_step"],
               coll["steady_state_compiles"]))
    if coll["pull_us"] > pull_budget:
        raise AssertionError(
            "a steady-state telemetry pull costs %.1f us isolated "
            "(MXTPU_TELEMETRY_PULL_BUDGET %.0f us): the pull_snapshot "
            "path regressed" % (coll["pull_us"], pull_budget))
    tel = part["telemetry"]
    if not (tel["lifecycle_ok"] and tel["accounting_exact"]):
        raise AssertionError(
            "serve_report on the PULL-ONLY partition tree was not "
            "green (lifecycle_ok=%s accounting_exact=%s tokens=%s "
            "traced=%s; contract: the router host's telemetry_pull "
            "collector assembles the complete fleet record — no "
            "shared-filesystem reads)"
            % (tel["lifecycle_ok"], tel["accounting_exact"],
               tel["tokens"], tel["traced_tokens"]))
    if tel["alerts_fired"] < 1 or not tel["report_renders"]:
        raise AssertionError(
            "no default alert rule fired/rendered during the "
            "partition drill (fired=%d rules=%s renders=%s; contract: "
            "an open breaker or a fence confirmation trips the "
            "default rules and the alerts lane shows it)"
            % (tel["alerts_fired"], tel["alert_rules"],
               tel["report_renders"]))
    if tel["fleet_top"]["rows"] != 2 or \
            not tel["fleet_top"]["complete"]:
        raise AssertionError(
            "fleet_top's live matrix was incomplete on the drill "
            "fleet (%s; contract: one complete row per live worker "
            "via status + telemetry_pull alone)" % (tel["fleet_top"],))
    stream = result["stream"]
    sm = stream["streamed"]
    if not sm["exactly_once"]:
        raise AssertionError(
            "in-process streaming broke exactly-once assembly "
            "(contract: the cursor-pull chunks concatenate to the "
            "engine's token list — no gap, no duplicate)")
    if sm["decode_dispatches_per_step"] != 1.0 or \
            sm["steady_state_compiles"] != 0:
        raise AssertionError(
            "polling the stream broke the hot path (%.3f "
            "dispatch/step, %d recompile(s); contract: poll reads a "
            "host-side buffer — it NEVER touches the donated program)"
            % (sm["decode_dispatches_per_step"],
               sm["steady_state_compiles"]))
    if sm["ttft_vs_unary_ratio"] >= 0.5:
        raise AssertionError(
            "streamed TTFT p50 (%.1fms) is %.2fx the unary completion "
            "p50 (%.1fms) on the mixed-length workload (contract: "
            "< 0.5x — the first chunk must beat the full reply)"
            % (sm["streamed_ttft_p50_ms"], sm["ttft_vs_unary_ratio"],
               sm["unary_completion_p50_ms"]))
    can = stream["cancel"]
    if can["mid_decode_verdict"] != "cancelled" or \
            can["queued_verdict"] != "cancelled" or \
            not can["idempotent"]:
        raise AssertionError(
            "cancel did not land the typed terminal verdict "
            "(mid_decode=%r queued=%r idempotent=%s; contract: "
            "`cancelled` between decode steps, for queued requests, "
            "and a repeat cancel is a no-op)"
            % (can["mid_decode_verdict"], can["queued_verdict"],
               can["idempotent"]))
    if not (can["survivors_completed"] and can["survivor_tokens_match"]
            and can["pages_restored"] and can["conservation_ok"]):
        raise AssertionError(
            "cancellation perturbed the batch (survivors_completed=%s "
            "tokens_match=%s pages_restored=%s conservation=%s; "
            "contract: a cancel frees slot + KV pages and the "
            "survivors' greedy streams are untouched)"
            % (can["survivors_completed"],
               can["survivor_tokens_match"], can["pages_restored"],
               can["conservation_ok"]))
    van = stream["vanish"]
    if van["orphans"] < 1 or not van["abandoned_verdicts"] or \
            van["abandoned_counter"] < van["orphans"]:
        raise AssertionError(
            "the serve.client.vanish drill reclaimed no orphan "
            "(orphans=%s verdicts_ok=%s counter=%s; contract: a "
            "stream unpolled past MXTPU_SERVE_ABANDON_S lands the "
            "typed `abandoned` verdict + counter)"
            % (van["orphans"], van["abandoned_verdicts"],
               van["abandoned_counter"]))
    if not (van["pages_restored"] and van["conservation_ok"]
            and van["survivors_completed"]
            and van["survivor_streams_exact"]):
        raise AssertionError(
            "orphan reclamation leaked (pages_restored=%s "
            "conservation=%s survivors_completed=%s survivors_exact=%s"
            "; contract: reclaim returns every page to the free pool "
            "with the conservation audit green and live pollers "
            "unperturbed)"
            % (van["pages_restored"], van["conservation_ok"],
               van["survivors_completed"],
               van["survivor_streams_exact"]))
    if not van["alert_fired"]:
        raise AssertionError(
            "the orphan_reclaim default alert did not fire on the "
            "vanish drill (contract: abandoned-counter movement trips "
            "the default rule)")
    sf = stream["fleet"]
    if sf["dropped"] != 0 or not sf["exactly_once"]:
        raise AssertionError(
            "the kill-mid-stream fleet drill broke exactly-once "
            "delivery (dropped=%d exactly_once=%s; contract: every "
            "accepted request's tokens arrive exactly once across a "
            "real SIGKILL failover — no gap, no duplicate)"
            % (sf["dropped"], sf["exactly_once"]))
    if not sf["tokens_match_unfaulted"]:
        raise AssertionError(
            "streamed fleet tokens diverged from the unfaulted "
            "reference (contract: the survivor's re-decode is "
            "bit-identical, so the cursor stays valid across the "
            "kill)")
    if sf["failovers"] < 1 or not sf["killed_mid_stream"] or \
            sf["streams_resumed_across_kill"] < 1:
        raise AssertionError(
            "the SIGKILL never landed mid-stream (failovers=%d "
            "mid_stream=%s resumed=%d; contract: >= 1 stream with a "
            "non-zero cursor at kill time resumes on the replacement "
            "with no client-visible gap)"
            % (sf["failovers"], sf["killed_mid_stream"],
               sf["streams_resumed_across_kill"]))
    if sf["drop_blackholed_replies"] < 1 or \
            not sf["drop_repoll_contiguous"]:
        raise AssertionError(
            "the serve.stream.drop site never bit, or the re-poll "
            "tore the stream (blackholed=%d contiguous=%s; contract: "
            "a dropped poll reply is recovered by an idempotent "
            "re-poll at the SAME cursor)"
            % (sf["drop_blackholed_replies"],
               sf["drop_repoll_contiguous"]))
    if sf["replacement_spawns"] < 1:
        raise AssertionError(
            "the streamed fleet drill never spawned a replacement — "
            "the resume-across-failover contract was not exercised")
    print(json.dumps({
        "metric": "serving_tokens_per_sec",
        "value": cont["tokens_per_sec"],
        "unit": "tok/s (%d reqs Poisson, %d slots busy %.1f avg, ttft "
                "p50 %.1fms p99 %.1fms, tpot p50 %.2fms; sequential "
                "baseline %.1f tok/s; warm spin-up %.2fs/%d compiles)"
                % (cont["requests"], cont["num_slots"],
                   cont["mean_batch_occupancy"],
                   cont["ttft_p50_ms"], cont["ttft_p99_ms"],
                   cont["tpot_p50_ms"],
                   result["sequential"]["tokens_per_sec"],
                   spin["warm_ttfb_s"], spin["warm_serve_compiles"]),
        "note": _count_contract_note(),
        # the >=2x continuous-batching contract; >=1.0 is within it
        "vs_baseline": round(speedup / 2.0, 3),
        "speedup": speedup,
        "trace_overhead_us": trace_us,
        "collector_pull_us": coll["pull_us"],
        "partition_alerts_fired": tel["alerts_fired"],
        "prefix_prefill_token_reduction":
            pfx["prefill_token_reduction"],
        "prefix_hit_rate": pfx["hit_rate"],
        "gqa_resident_multiplier": gqa["resident_multiplier"],
        "kvq_resident_multiplier": kvq["resident_multiplier"],
        "kvq_token_match_rate": kvq["token_match_rate"],
        "kvq_dequant_max_err": kvq["dequant_max_err"],
        "spec_speedup": spec["speedup_tokens_per_sec"],
        "spec_tokens_per_slot_step": spec["tokens_per_slot_step"],
        "spec_acceptance_rate": spec["acceptance_rate"],
        "streamed_ttft_p50_ms": sm["streamed_ttft_p50_ms"],
        "streamed_ttft_vs_unary": sm["ttft_vs_unary_ratio"],
        "stream_orphans_reclaimed": van["orphans"],
        "stream_kill_resumed": sf["streams_resumed_across_kill"],
        "serve": result,
    }))


def bench_graph():
    """BENCH_MODE=graph: the graph rewrite pipeline's contract
    (PERF.md §6, tools/perf_probe/graph_probe.py).  Hard contracts:

    - >= 15% fewer lowered-HLO instructions with the pipeline on vs off
      on BOTH bench graphs (the ResNet conv→bn→relu tower and the
      post-LN GPT stack) — the instruction-count contract is measured
      on the pre-optimization module the graph stage hands XLA;
    - pipeline-on outputs equivalent to pipeline-off (rtol 1e-6);
    - steptrace invariants with the pipeline enabled: exactly 1.0
      dispatch/step, 0 steady-state recompiles on a fused fit loop over
      a fusable (conv→bn→relu) net.

    The measured forward step-time ratio is reported alongside (the
    headline unit string carries it)."""
    import jax
    _perf_probe_path()
    import graph_probe

    jax.devices()
    result = graph_probe.run()
    contract = result["hlo_contract"]
    for name in ("resnet", "gpt"):
        side = result[name]
        if side["lowered_reduction"] < contract:
            raise AssertionError(
                "%s bench graph: pipeline cut lowered-HLO instructions "
                "by only %.1f%% (%d -> %d; contract >= %.0f%%)"
                % (name, side["lowered_reduction"] * 100,
                   side["lowered_instructions_off"],
                   side["lowered_instructions_on"], contract * 100))
        if side["max_rel_err"] > 1e-6:
            raise AssertionError(
                "%s bench graph: pipeline-on output diverged from "
                "pipeline-off (max rel err %.3g > 1e-6)"
                % (name, side["max_rel_err"]))
    st = result["steptrace"]
    if st["dispatches_per_step"] != 1.0:
        raise AssertionError(
            "fused fit loop with the pipeline enabled dispatched %.3f "
            "programs/step (contract: exactly 1.0)"
            % st["dispatches_per_step"])
    if st["compile_count"] != 0:
        raise AssertionError(
            "fused fit loop with the pipeline enabled recompiled %d "
            "time(s) in steady state (contract: 0)" % st["compile_count"])
    worst = min(result["resnet"]["lowered_reduction"],
                result["gpt"]["lowered_reduction"])
    print(json.dumps({
        "metric": "graph_pipeline_hlo_reduction",
        "value": round(worst * 100, 2),
        "unit": "%% fewer lowered-HLO instructions (worst graph; resnet "
                "%.1f%% %d->%d fwd x%.2f, gpt %.1f%% %d->%d fwd "
                "x%.2f; 1.0 dispatch/step, 0 recompiles)" % (
                    result["resnet"]["lowered_reduction"] * 100,
                    result["resnet"]["lowered_instructions_off"],
                    result["resnet"]["lowered_instructions_on"],
                    result["resnet"]["fwd_speedup"],
                    result["gpt"]["lowered_reduction"] * 100,
                    result["gpt"]["lowered_instructions_off"],
                    result["gpt"]["lowered_instructions_on"],
                    result["gpt"]["fwd_speedup"]),
        "vs_baseline": round(worst / contract, 3),
        "graph": result,
    }))


def bench_restart():
    """BENCH_MODE=restart: fault tolerance off the hot path.

    Two numbers (tools/perf_probe/restart_probe.py, CPU micro-bench):
    per-checkpoint step stall sync vs async (p50/p99 of the wall time
    save_checkpoint blocks the step loop; contract ≥5× lower async) and
    restart time-to-first-step cold vs warm (fresh subprocesses sharing
    one AOT executable cache, the launch.py restart setup; contract ≥2×
    faster warm).  Headline value is the p50 stall ratio;
    vs_baseline is that ratio against the 5× contract."""
    import jax
    _perf_probe_path()
    import restart_probe

    jax.devices()
    result = restart_probe.run()
    stall = result["stall"]
    ttfs = result["ttfs"]
    print(json.dumps({
        "metric": "ckpt_stall_sync_over_async",
        "value": stall["ratio_p50"],
        "unit": "x lower per-ckpt step stall (sync p50 %.2fms p99 %.2fms"
                " -> async p50 %.2fms p99 %.2fms; warm restart"
                " time-to-first-step %.2fx: cold %.2fs -> warm %.2fs,"
                " warm compiles %d)" % (
                    stall["sync"]["p50_ms"], stall["sync"]["p99_ms"],
                    stall["async"]["p50_ms"], stall["async"]["p99_ms"],
                    ttfs["speedup"], ttfs["cold_s"], ttfs["warm_s"],
                    ttfs["warm_fit_step_compiles"]),
        "note": _count_contract_note(),
        # the ≥5x async-stall contract; ≥1.0 is within it
        "vs_baseline": round(stall["ratio_p50"] / 5.0, 3),
        "warm_ttfs_speedup": ttfs["speedup"],
        "restart": result,
    }))


def bench_stream():
    """BENCH_MODE=stream: streaming ingest vs the in-memory DataLoader
    (tools/perf_probe/stream_probe.py).  Hard contracts (DATA.md):

    - steady-state fused-step time from disk shards within
      MXTPU_STREAM_BENCH_MAX_RATIO (default 1.10x) of the in-memory
      DataLoader on the same data — decode hidden by the worker pool;
    - io.queue_wait p99 bounded below one in-memory step;
    - exactly 1.0 dispatch/step, 0 steady-state recompiles.
    """
    import jax
    _perf_probe_path()
    import stream_probe as _stream_probe

    jax.devices()
    result = _stream_probe.run()
    _stream_probe.check(result)
    print(json.dumps({
        "metric": "stream_vs_inmem_step_ratio",
        "value": result["ratio_stream_vs_mem"],
        "unit": "x in-memory step (median of %d pairs; queue-wait p99 "
                "%.3f ms; 1.0 dispatch/step)"
                % (len(result["ratio_pairs"]),
                   result["io_queue_wait_p99_ms"]),
        # 1.0 == parity with in-memory; the contract ceiling is 1.10
        "vs_baseline": round(result["ratio_stream_vs_mem"], 3),
        "stream": result,
    }))


def main():
    mode = os.environ.get("BENCH_MODE")
    network = os.environ.get("BENCH_NETWORK", "resnet50_v1")
    if network not in NETWORKS:
        raise ValueError("BENCH_NETWORK must be one of %s, got %r"
                         % (sorted(NETWORKS), network))
    # a failure is a failure: the traceback and a non-zero exit, never a
    # zero-valued row under the metric's name
    _run_mode(mode, network)
    _tier1_margin_gate()


def _run_mode(mode, network):
    if mode == "attention":
        bench_attention()
        return
    if mode == "pipeline":
        bench_pipeline()
        return
    if mode == "transformer":
        bench_transformer()
        return
    if mode == "generate":
        bench_generate()
        return
    if mode == "steptrace":
        bench_steptrace()
        return
    if mode == "spmd":
        bench_spmd()
        return
    if mode == "telemetry":
        bench_telemetry()
        return
    if mode == "restart":
        bench_restart()
        return
    if mode == "serve":
        bench_serve()
        return
    if mode == "graph":
        bench_graph()
        return
    if mode == "stream":
        bench_stream()
        return
    # bs 128 is the measured single-chip sweet spot on v5e (PERF.md:
    # 2379 img/s vs 2263 at bs 256, 2114 at bs 512)
    batch = int(os.environ.get("BENCH_BATCH", "128"))
    steps = max(1, int(os.environ.get("BENCH_STEPS", "20")))
    warmup = max(1, int(os.environ.get("BENCH_WARMUP", "3")))
    default_image = "299" if network == "inception_v3" else "224"
    image = int(os.environ.get("BENCH_IMAGE", default_image))

    import jax
    import jax.numpy as jnp

    dev = _require_chip(mode)
    platform, device_kind = dev.platform, dev.device_kind

    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.gluon.block import functionalize

    net = getattr(vision, network)(classes=1000)
    net.initialize()
    x0 = jnp.zeros((batch, 3, image, image), jnp.float32)
    fn, params = functionalize(net, x0, train=True)
    n_aux = fn.num_aux
    n_diff = len(params) - n_aux
    diff_params = params[:n_diff]
    aux_params = params[n_diff:]
    mom = [jnp.zeros_like(p) for p in diff_params]

    # mixed precision: bf16 activations/weights on the MXU, fp32 master
    # weights + fp32 update (the reference's mp_sgd fp16 recipe,
    # src/operator/optimizer_op.cc; BENCH_DTYPE=float32 opts out)
    bench_dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    if bench_dtype not in ("bfloat16", "float32"):
        raise ValueError("BENCH_DTYPE must be bfloat16 or float32, got %r"
                         % bench_dtype)
    cdt = jnp.bfloat16 if bench_dtype == "bfloat16" else jnp.float32

    def loss_fn(diff, aux, x, y, rng):
        cdiff = [p.astype(cdt) for p in diff]
        (logits,), new_aux = fn(cdiff + list(aux), x.astype(cdt), rng=rng)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        loss = -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()
        return loss, new_aux

    # donate params/aux/momentum: the step updates them in place in HBM
    # (PlanMemory's inplace discipline, done by XLA buffer donation)
    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(diff, aux, mom, x, y, rng):
        (loss, new_aux), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(diff, aux, x, y, rng)
        new_mom = [0.9 * m - 0.05 * g.astype(jnp.float32)
                   for m, g in zip(mom, grads)]
        new_diff = [p + m for p, m in zip(diff, new_mom)]
        return new_diff, list(new_aux), new_mom, loss

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (batch, 3, image, image), jnp.float32)
    y = jax.random.randint(key, (batch,), 0, 1000)

    # Per-step training FLOPs for the MFU report.  Analytic by default:
    # ResNet-50 forward at 224² is 4.089 GMACs (stem+4 stages+fc, standard
    # count) → 8.18 GFLOPs; training ≈ 3× forward (one fwd + two bwd
    # matmul passes) = 24.5 GFLOPs/img, scaled by the spatial area.
    # BENCH_COST_ANALYSIS=1 uses XLA's own count instead (an AOT
    # lower().compile() beside the jit's own; XLA counts ~22.5
    # GFLOPs/img for this program, 8% under the analytic figure).
    if os.environ.get("BENCH_COST_ANALYSIS") == "1":
        ca = train_step.lower(diff_params, aux_params, mom, x, y,
                              key).compile().cost_analysis()
        ca = ca[0] if isinstance(ca, list) else ca
        step_flops = float(ca.get("flops", 0.0)) or None
    else:
        base_image = 299.0 if network == "inception_v3" else 224.0
        gmacs = NETWORKS[network][1]
        step_flops = 3 * 2 * gmacs * 1e9 * batch * (image / base_image) ** 2

    for i in range(warmup):
        diff_params, aux_params, mom, loss = train_step(
            diff_params, aux_params, mom, x, y, jax.random.fold_in(key, i))
    loss.block_until_ready()

    # BENCH_PROFILE=<dir>: capture an xplane/trace of the timed loop for
    # tensorboard / xprof analysis (the profiler story for perf work)
    profile_dir = os.environ.get("BENCH_PROFILE")
    if profile_dir:
        jax.profiler.start_trace(profile_dir)
    try:
        t0 = time.perf_counter()
        for i in range(steps):
            diff_params, aux_params, mom, loss = train_step(
                diff_params, aux_params, mom, x, y,
                jax.random.fold_in(key, i))
        loss.block_until_ready()  # the whole donated-param chain
        dt = time.perf_counter() - t0
    finally:
        if profile_dir:
            jax.profiler.stop_trace()  # flush even when a step dies

    img_s = batch * steps / dt
    baseline = NETWORKS[network][0]
    result = {
        "metric": _network_metric(network),
        "value": round(img_s, 2),
        "unit": "img/s (bs %d, %dx%d, %s, 1 %s device)" % (
            batch, image, image, bench_dtype, platform),
        # null (not 0.0 — the watchdog's failure sentinel) when the
        # reference README published no number for this network
        "vs_baseline": round(img_s / baseline, 3) if baseline else None,
    }
    if step_flops:
        tflops = step_flops * steps / dt / 1e12
        result["tflops"] = round(tflops, 1)
        result["mfu"] = round(
            step_flops * steps / dt / _peak_flops(device_kind), 3)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
