#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full width of models the repo supports (seeded random weights, nothing
read from a file outside git):

- train: ResNet-50 (224x224, batch 128) through ``Module.fit_step`` — the
  fused donated program of ``Executor.make_fit_step`` — and GPT-2 medium
  (24 x 1024, 16 heads, T = 2048) through
  ``parallel.gpt_spmd.make_train_step`` on a one-device mesh with the
  Pallas flash kernel as the attention path;
- serve: a ``ServingEngine`` over GPT-2 medium with the KV pool filling
  most of the HBM the weights leave, in the three page formats (fp32,
  bf16, int8) with ``spec_k`` 0 and 4, greedy tokens checked against the
  dense-cache ``gpt.generate`` and spec-on against spec-off.

Every phase prints one JSON line (seconds to compile, seconds per step,
peak device bytes, which kernels the compiled program holds): orientation
for the benchmark that follows, never a benchmark.  The LAST line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
Any failing phase — or no TPU — ends the run with ``"ok": false`` and a
non-zero exit; no phase's exception is passed over.

``--tiny`` is the CPU rehearsal of the same control flow at toy sizes
(Pallas in interpreter mode): it says ``"platform": "cpu"`` and its
``"ok"`` is about the rehearsal only.  ``--chips 4`` runs ONLY what exists
across chips — GPT-2 medium on a dp=2 x tp=2 mesh against the same steps
on one device, and one ``Module`` dp=4 ZeRO-1 step on ResNet-50.

One process, start to finish: a chip belongs to one process at a time,
so this script starts no child that imports JAX.  It does not touch the
native library (``mxnet_tpu/_native.py`` returns None without a built
``.so`` and callers take the Python path): every input is a synthetic
array.
"""
import argparse
import gc
import json
import os
import sys
import time
import traceback
from statistics import median

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "example", "image-classification"))


def emit(phase, **fields):
    print(json.dumps(dict(phase=phase, **fields)), flush=True)


def device_doc():
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


class Sizes:
    """What --tiny cuts; widths of the real run are the published ones."""

    def __init__(self, tiny):
        self.tiny = tiny
        # ResNet through Module.fit_step
        self.resnet_layers = 20 if tiny else 50
        self.image = 28 if tiny else 224
        self.classes = 10 if tiny else 1000
        self.resnet_batch = 8 if tiny else 128
        # GPT through gpt_spmd.make_train_step
        self.gpt = "gpt2_tiny" if tiny else "gpt2_medium"
        self.seq = 64 if tiny else 2048
        self.gpt_batch = 4
        self.train_steps = 3 if tiny else 4
        # serving
        self.max_len = 128 if tiny else 2048
        self.slots = 3 if tiny else 8
        self.page = 8 if tiny else 16
        self.prefill = 32 if tiny else 256
        self.prefix = 16 if tiny else 48
        self.max_new = 6 if tiny else 12
        # the periodic request runs on: a random-weight model's greedy
        # stream takes a few dozen tokens to start repeating itself,
        # and only then can the n-gram drafter be right
        self.max_new_periodic = 12 if tiny else 40
        self.spec_k = 4


# ---------------------------------------------------------------------------
# what the device and the compile cache say
# ---------------------------------------------------------------------------

class Watch:
    """Device placement, memory and compile-cache counters."""

    def __init__(self, want_platform):
        import jax
        from mxnet_tpu import aot_cache
        self.want = want_platform
        self.cache_dir = aot_cache.enable_persistent_cache()
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event.endswith("/compilation_cache/cache_hits"):
            self.cache["hits"] += 1
        elif event.endswith("/compilation_cache/cache_misses"):
            self.cache["misses"] += 1

    def on_device(self, tree, what):
        """Every array of ``tree`` lives on the platform this run is
        about — Context('tpu') quietly resolves to a CPU device where
        there is no accelerator, and the chip path has to KNOW."""
        import jax
        for leaf in jax.tree_util.tree_leaves(tree):
            got = {d.platform for d in leaf.devices()}
            assert got == {self.want}, \
                "%s lives on %s, want %s" % (what, got, self.want)

    @staticmethod
    def memory(device=None):
        import jax
        stats = (device or jax.devices()[0]).memory_stats() or {}
        return {k: stats.get(k) for k in
                ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


def has_kernel(compiled):
    return "tpu_custom_call" in compiled.as_text()


def collectives(compiled):
    import re
    text = compiled.as_text()
    return {op: len(re.findall(r"\b%s(?:-start)?\(" % op, text))
            for op in ("all-reduce", "all-gather", "reduce-scatter",
                       "all-to-all", "collective-permute")}


# ---------------------------------------------------------------------------
# train: ResNet through Module.fit_step
# ---------------------------------------------------------------------------

def resnet_module(sz, ctx, seed):
    import numpy as np
    import mxnet_tpu as mx
    from symbols import resnet

    sym = resnet.get_symbol(
        num_classes=sz.classes, num_layers=sz.resnet_layers,
        image_shape="3,%d,%d" % (sz.image, sz.image))
    mod = mx.mod.Module(sym, context=ctx)
    shape = (sz.resnet_batch, 3, sz.image, sz.image)
    mod.bind(data_shapes=[("data", shape)],
             label_shapes=[("softmax_label", (sz.resnet_batch,))])
    mx.random.seed(seed)
    mod.init_params(mx.initializer.Xavier(magnitude=2.0))
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    rs = np.random.RandomState(seed)
    data = rs.uniform(-1, 1, shape).astype(np.float32)
    label = rs.randint(0, sz.classes, sz.resnet_batch).astype(np.float32)
    batch = mx.io.DataBatch([mx.nd.array(data)], [mx.nd.array(label)])
    return mod, batch, label.astype(np.int64)


def resnet_loss(mod, label):
    """Cross-entropy of the SoftmaxOutput head; the host fetch is the
    step's completion barrier."""
    import numpy as np
    probs = mod.get_outputs()[0].asnumpy().astype(np.float64)
    return float(-np.log(probs[np.arange(len(label)), label] + 1e-30)
                 .mean())


def phase_train_resnet(sz, watch, seed):
    import mxnet_tpu as mx
    from mxnet_tpu import profiler

    mod, batch, label = resnet_module(sz, mx.tpu(0), seed)
    t0 = time.perf_counter()
    mod.fit_step(batch)              # AOT-compiles the fused step
    losses = [resnet_loss(mod, label)]
    first_s = time.perf_counter() - t0
    watch.on_device([a._data for a in mod._exec.arg_dict.values()],
                    "ResNet parameters")
    profiler.reset_step_stats()
    times = []
    for _ in range(sz.train_steps):
        t0 = time.perf_counter()
        mod.fit_step(batch)
        losses.append(resnet_loss(mod, label))
        times.append(time.perf_counter() - t0)
    stats = profiler.step_stats()
    assert all(l == l and abs(l) < 1e30 for l in losses), losses
    assert losses[-1] < losses[0], "ResNet loss not falling: %r" % losses
    assert stats["dispatch_count"] == sz.train_steps, stats
    assert stats["compile_count"] == 0, stats
    emit("train_resnet", model="resnet%d" % sz.resnet_layers,
         entry="Module.fit_step", batch=sz.resnet_batch, image=sz.image,
         param_dtype="float32", first_step_s=first_s,
         step_s=median(times), losses=losses,
         dispatches_per_step=stats["dispatch_count"] / sz.train_steps,
         recompiles=stats["compile_count"],
         tpu_custom_call=has_kernel(mod._fused["step"].__wrapped__),
         memory=watch.memory())


# ---------------------------------------------------------------------------
# train: GPT through gpt_spmd.make_train_step
# ---------------------------------------------------------------------------

def gpt_net(sz, seed, **kw):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import gpt
    mx.random.seed(seed)
    net = getattr(gpt, sz.gpt)(**kw)
    net.initialize()
    return net


def gpt_batch(sz, net, seed):
    import numpy as np
    rs = np.random.RandomState(seed)
    toks = rs.randint(0, net._vocab, (sz.gpt_batch, sz.seq + 1))
    return {"x": toks[:, :-1].astype(np.int32),
            "y": toks[:, 1:].astype(np.int32)}


def gpt_train(sz, watch, seed, mesh, label):
    """A few steps of the SPMD recipe on ``mesh``; returns the losses
    and the phase's fields."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import telemetry
    from mxnet_tpu.gluon.block import functionalize
    from mxnet_tpu.parallel import gpt_spmd
    from mxnet_tpu.parallel.ring_attention import default_attention_impl

    impl = default_attention_impl()
    if watch.want == "tpu":
        assert impl == "flash", \
            "the Pallas kernel was not picked on a TPU: %r" % impl
    net = gpt_net(sz, seed, max_len=sz.seq)
    batch = gpt_batch(sz, net, seed)
    fn, params = functionalize(net, jnp.asarray(batch["x"]), train=True)
    init_fn, step_fn = gpt_spmd.make_train_step(
        fn, mesh, lr=0.01, compute_dtype=jnp.bfloat16)
    ps, opt = init_fn(params)
    del params
    watch.on_device(ps, "GPT parameters")
    rng = jax.random.PRNGKey(seed)
    t0 = time.perf_counter()
    compiled = step_fn.lower(ps, opt, batch, rng).compile()
    compile_s = time.perf_counter() - t0
    if impl == "flash" and watch.want == "tpu":
        assert has_kernel(compiled), \
            "no Mosaic call in the lowered GPT train step"
    losses, times = [], []
    compiles0 = None
    for i in range(sz.train_steps + 1):
        t0 = time.perf_counter()
        ps, opt, loss = step_fn(ps, opt, batch, rng)
        losses.append(float(loss))      # scalar fetch ends the step
        times.append(time.perf_counter() - t0)
        if compiles0 is None:           # the first call compiles
            compiles0 = telemetry.xla_compile_events()
    recompiles = telemetry.xla_compile_events() - compiles0
    # sampled while params and optimizer state are alive: code that has
    # only ever met virtual CPU devices may put everything on device 0
    used = [Watch.memory(d)["bytes_in_use"] for d in mesh.devices.flat]
    if watch.want == "tpu":
        assert all(b > (1 << 26) for b in used), used
    assert all(l == l and abs(l) < 1e30 for l in losses), losses
    assert losses[-1] < losses[0], "GPT loss not falling: %r" % losses
    assert recompiles == 0, recompiles
    fields = dict(
        model=sz.gpt, entry="parallel.gpt_spmd.make_train_step",
        mesh=dict(mesh.shape), batch=sz.gpt_batch, seq=sz.seq,
        compute_dtype="bfloat16", attention_impl=impl,
        compile_s=compile_s, first_step_s=times[0],
        step_s=median(times[1:]), losses=losses,
        programs_per_step=1, recompiles=recompiles,
        tpu_custom_call=has_kernel(compiled),
        collectives=collectives(compiled),
        bytes_in_use_per_device=used)
    emit(label, **fields)
    return losses


def phase_train_gpt(sz, watch, seed):
    import jax
    from mxnet_tpu import parallel as par
    mesh = par.make_mesh(dp=1, tp=1, devices=jax.devices()[:1])
    gpt_train(sz, watch, seed, mesh, "train_gpt")
    emit("train_gpt_memory", memory=watch.memory())


# ---------------------------------------------------------------------------
# serve: ServingEngine in three page formats, spec_k 0 and 4
# ---------------------------------------------------------------------------

def kernel_vs_oracle(sz, seed):
    """The paged kernels against their jnp oracles on a small input at
    the real head shape, in every page format, one and five query
    positions per slot."""
    import numpy as np
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.paged_attention import (
        paged_attention_multi, paged_attention_multi_reference)

    rs = np.random.RandomState(seed)
    s_n, h, d, page, n_pages, mp = 4, 16, 64, sz.page, 32, 4
    bt = (rs.permutation(n_pages - 1)[:s_n * mp] + 1) \
        .reshape(s_n, mp).astype(np.int32)
    errs = {}
    for fmt in ("fp32", "bf16", "int8"):
        # pools as the engine stores them: [num_pages, page, K_kv * D]
        kv = [rs.randn(n_pages, page, h * d).astype(np.float32)
              for _ in range(2)]
        scales = {}
        if fmt == "int8":
            # one absmax scale for each page and KV head
            heads = [x.reshape(n_pages, page, h, d) for x in kv]
            sc = [np.abs(x).max(axis=(1, 3)) / 127.0 for x in heads]
            kv = [jnp.asarray(np.round(x / s[:, None, :, None])
                              .reshape(n_pages, page, h * d), jnp.int8)
                  for x, s in zip(heads, sc)]
            scales = dict(k_scales=jnp.asarray(sc[0]),
                          v_scales=jnp.asarray(sc[1]))
        else:
            dt = jnp.bfloat16 if fmt == "bf16" else jnp.float32
            kv = [jnp.asarray(x, dt) for x in kv]
        for n_q in (1, 5):
            q = jnp.asarray(rs.randn(s_n, n_q, h, d), jnp.float32)
            ctx = np.minimum(
                rs.randint(0, mp * page - n_q, (s_n, 1))
                + np.arange(1, n_q + 1)[None], mp * page)
            ctx[-1] = 0                      # an empty slot emits zeros
            ctx = jnp.asarray(ctx, jnp.int32)
            got = np.asarray(paged_attention_multi(
                q, kv[0], kv[1], bt, ctx, **scales))
            want = np.asarray(paged_attention_multi_reference(
                q, kv[0], kv[1], bt, ctx, **scales))
            assert np.isfinite(got).all() and (got[-1] == 0).all()
            errs["%s/q%d" % (fmt, n_q)] = float(np.abs(got - want).max())
    assert max(errs.values()) < 2e-3, errs
    return errs


def serve_requests(sz, vocab, seed):
    """``(prompts, new tokens for each, pair)``: mixed prompt lengths
    with a shared page-aligned prefix among most of them (``pair``
    share one LENGTH: the two the dense reference is run on, in one
    batch), and last a periodic prompt the n-gram drafter can propose
    on."""
    import numpy as np
    rs = np.random.RandomState(seed)
    prefix = rs.randint(0, vocab, sz.prefix)

    def tail(n):
        return rs.randint(0, vocab, n)
    prompts = [np.concatenate([prefix, tail(8)]),
               np.concatenate([prefix, tail(8)]),
               np.concatenate([prefix, tail(sz.prefill // 4)]),
               tail(sz.prefill // 8),
               tail(sz.prefill - 3),
               np.resize(tail(3), sz.prefill // 2)]
    news = [sz.max_new] * (len(prompts) - 1) + [sz.max_new_periodic]
    return [p.astype(np.int32) for p in prompts], news, (0, 1)


def same_greedy(got, want, logits, tol=1e-3):
    """Two greedy streams agree up to numerical ties: where they first
    part, the stream that owns ``logits`` must hold the other's token
    within ``tol`` of its own maximum (past that point the contexts
    differ and nothing more can be compared)."""
    if len(got) != len(want):
        return False
    for i, (g, w) in enumerate(zip(got, want)):
        if g != w:
            return float(logits[i].max() - logits[i][w]) <= tol
    return True


def run_engine(sz, watch, net, prompts, news, kv_dtype, spec_k,
               pool_bytes):
    """Build one engine, answer ``prompts``; returns (requests, fields)."""
    from mxnet_tpu import profiler, telemetry
    from mxnet_tpu.serving import ServingEngine
    from mxnet_tpu.serving.kv_cache import PagedKVAllocator

    def count(name):
        return telemetry.counter(name).value

    heads = net.blocks._children[0].attn._num_heads
    head_dim = net._units // heads
    max_seq = sz.max_len - sz.spec_k
    kw = dict(num_slots=sz.slots, page_size=sz.page,
              max_prefill_len=sz.prefill, max_seq_len=max_seq,
              kv_dtype=kv_dtype, spec_k=spec_k, record_logits=True)
    page_bytes = len(net.blocks._children) * PagedKVAllocator(
        2, sz.page, kv_dtype=kv_dtype).page_bytes(heads, head_dim)
    num_pages = None if pool_bytes is None else \
        max(2, pool_bytes // page_bytes)
    t0 = time.perf_counter()
    eng = ServingEngine(net, num_pages=num_pages, **kw)
    build_s = time.perf_counter() - t0
    watch.on_device(eng._kv, "KV pools")
    for prog in (eng._decode, eng._prefill):
        assert hasattr(prog.__wrapped__, "as_text"), \
            "a serving program fell back to lazy jit: %r" % prog
    decode_kernel = has_kernel(eng._decode.__wrapped__)
    if watch.want == "tpu":
        assert decode_kernel, "no Mosaic call in the decode program"
    c0 = {n: count(n) for n in (
        "serving.prefix.hits", "serving.spec.draft_tokens",
        "serving.spec.accepted", "serving.spec.rejected")}
    profiler.reset_step_stats()
    # staggered joins: two requests, a step, the rest, then drain
    reqs = [eng.submit(p, n) for p, n in zip(prompts[:2], news)]
    step_times = []

    def step():
        t0 = time.perf_counter()
        eng.step()
        step_times.append(time.perf_counter() - t0)
    step()
    reqs += [eng.submit(p, n) for p, n in zip(prompts[2:], news[2:])]
    for _ in range(10000):
        if all(r.done for r in reqs):
            break
        step()
    stats = profiler.step_stats()
    for r, n in zip(reqs, news):
        assert r.done and len(r.tokens) == n, \
            (r.state, r.verdict, r.error, r.tokens)
    d = {n: count(n) - v for n, v in c0.items()}
    assert stats["compile_count"] == 0, stats
    assert stats["dispatch_count"] == eng.decode_steps + eng.prefills, \
        (stats, eng.decode_steps, eng.prefills)
    assert d["serving.prefix.hits"] > 0, d
    if spec_k:
        assert d["serving.spec.draft_tokens"] > 0, d
        assert d["serving.spec.accepted"] + d["serving.spec.rejected"] \
            == d["serving.spec.draft_tokens"], d
    else:
        assert d["serving.spec.draft_tokens"] == 0, d
    pool = eng.alloc.num_pages * page_bytes
    fields = dict(
        kv_dtype=kv_dtype, spec_k=spec_k, build_s=build_s,
        num_pages=eng.alloc.num_pages, pool_bytes=pool,
        pool_tokens=eng.alloc.num_pages * sz.page,
        decode_steps=eng.decode_steps, prefills=eng.prefills,
        decode_step_s=median(step_times),
        tokens=sum(len(r.tokens) for r in reqs),
        tokens_per_decode_step=(sum(len(r.tokens) - 1 for r in reqs)
                                / max(1, eng.decode_steps)),
        counters=d, recompiles=stats["compile_count"],
        tpu_custom_call=decode_kernel, memory=watch.memory())
    return reqs, fields


def phase_serve(sz, watch, seed):
    import jax
    import numpy as np
    from mxnet_tpu.gluon.model_zoo import gpt

    # greedy streams of two differently-ordered programs are compared
    # token by token: at the TPU's default (one bf16 pass) the logits of
    # equal math differ in the third digit and the argmax of a
    # random-weight model flips every few tokens.  The repo's own
    # numeric checks run at full fp32 (tests/conftest.py); so does this
    # phase, and its decode times are for that precision.
    jax.config.update("jax_default_matmul_precision", "float32")
    kernel_errs = kernel_vs_oracle(sz, seed)
    emit("serve_kernels", max_abs_err=kernel_errs)

    net = gpt_net(sz, seed, max_len=sz.max_len)
    watch.on_device([p.data()._data
                     for p in net.collect_params().values()],
                    "serving weights")
    prompts, news, pair = serve_requests(sz, net._vocab, seed)
    t0 = time.perf_counter()
    dense = gpt.generate(net, np.stack([prompts[i] for i in pair]),
                         sz.max_new)
    dense = {i: [int(t) for t in row[len(prompts[i]):]]
             for i, row in zip(pair, dense)}
    emit("serve_dense_reference", entry="gpt.generate",
         requests=list(pair), prompt_len=len(prompts[pair[0]]),
         new_tokens=sz.max_new, seconds=time.perf_counter() - t0)
    gc.collect()

    pool_bytes = None
    mem = watch.memory()
    if not sz.tiny:
        # most of what the weights leave; the rest is for the prefill
        # program's temporaries (~2 GB at this width, compile rehearsal)
        # and the allocator's slack
        free = mem["bytes_limit"] - mem["bytes_in_use"]
        pool_bytes = int(0.65 * free)
    emit("serve_pool", bytes_limit=mem["bytes_limit"],
         bytes_in_use_with_weights=mem["bytes_in_use"],
         pool_bytes_target=pool_bytes)
    for kv_dtype in ("fp32", "bf16", "int8"):
        streams = {}
        for spec_k in (0, sz.spec_k):
            reqs, fields = run_engine(sz, watch, net, prompts, news,
                                      kv_dtype, spec_k, pool_bytes)
            streams[spec_k] = reqs
            match = [r.tokens == dense[i] for i, r in enumerate(reqs)
                     if i in dense]
            emit("serve", model=sz.gpt, matmul_precision="float32",
                 dense_match=match, **fields)
            del reqs
            gc.collect()
        off, on = streams[0], streams[sz.spec_k]
        for i, (a, b) in enumerate(zip(off, on)):
            assert same_greedy(b.tokens, a.tokens, b.logits_trace), \
                ("spec-on != spec-off", kv_dtype, i, a.tokens, b.tokens)
        if kv_dtype == "fp32":
            # quantized pages are pinned to themselves, not to fp32
            for spec_k, reqs in streams.items():
                for i, want in dense.items():
                    assert same_greedy(reqs[i].tokens, want,
                                       reqs[i].logits_trace), \
                        ("engine != dense generate", spec_k, i,
                         reqs[i].tokens, want)
    jax.config.update("jax_default_matmul_precision", None)


# ---------------------------------------------------------------------------
# four chips: dp x tp GPT against one device, and a ZeRO-1 Module step
# ---------------------------------------------------------------------------

def phase_four_chips(sz, watch, seed):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import parallel as par
    from mxnet_tpu import profiler

    four = jax.devices()[:4]
    one = par.make_mesh(dp=1, tp=1, devices=four[:1])
    ref = gpt_train(sz, watch, seed, one, "gpt_one_of_four")
    gc.collect()
    mesh = par.make_mesh(dp=2, tp=2, devices=four)
    got = gpt_train(sz, watch, seed, mesh, "gpt_dp2_tp2")
    gc.collect()
    # bf16 compute, fp32 accumulation in another order: the trajectories
    # agree to one bf16 ulp at the loss's magnitude
    tol = 2.0 ** -8 * max(abs(l) for l in ref)
    assert all(abs(a - b) <= tol for a, b in zip(ref, got)), (ref, got)
    emit("gpt_dp2_tp2_vs_one", losses_one=ref, losses_mesh=got, tol=tol,
         persistent_cache_enabled=bool(
             jax.config.jax_enable_compilation_cache))

    os.environ["MXTPU_ZERO"] = "1"
    mod, batch, label = resnet_module(
        sz, [mx.tpu(i) for i in range(4)], seed)
    mod.fit_step(batch)
    first = resnet_loss(mod, label)
    profiler.reset_step_stats()
    mod.fit_step(batch)
    second = resnet_loss(mod, label)
    stats = profiler.step_stats()
    fused = mod._fused
    assert fused["zero"] is not None, "ZeRO-1 did not engage on dp=4"
    factors = set()
    for leaf in jax.tree_util.tree_leaves(fused["state"]):
        if leaf.size >= 4096:
            factors.add(leaf.size // int(
                __import__("numpy").prod(
                    leaf.sharding.shard_shape(leaf.shape))))
    assert first == first and second < first, (first, second)
    assert stats["dispatch_count"] == 1 and stats["compile_count"] == 0, \
        stats
    assert factors == {4}, factors
    compiled = fused["step"].__wrapped__
    used = [Watch.memory(d)["bytes_in_use"] for d in jax.devices()[:4]]
    if watch.want == "tpu":
        assert all(b > (1 << 26) for b in used), used
    emit("module_zero1_dp4", model="resnet%d" % sz.resnet_layers,
         entry="Module.fit_step", batch=sz.resnet_batch,
         losses=[first, second],
         opt_state_shard_factors=sorted(factors),
         dispatches_per_step=stats["dispatch_count"],
         collectives=collectives(compiled),
         bytes_in_use_per_device=used,
         persistent_cache_enabled=bool(
             jax.config.jax_enable_compilation_cache))


# ---------------------------------------------------------------------------

def run(args):
    import jax
    dev = device_doc()
    want = "cpu" if args.tiny else "tpu"
    if dev["platform"] != want:
        raise SystemExit(
            "chip_smoke.py: found platform %r, this run is about %r "
            "(--tiny is the CPU rehearsal; without it a TPU is required)"
            % (dev["platform"], want))
    if dev["count"] < args.chips:
        raise SystemExit("chip_smoke.py: --chips %d but JAX reports %d "
                         "device(s)" % (args.chips, dev["count"]))
    sz = Sizes(args.tiny)
    watch = Watch(want)
    emit("start", device=dev, tiny=args.tiny, chips=args.chips,
         seed=args.seed, jax=jax.__version__,
         compile_cache_dir=watch.cache_dir, memory=watch.memory())
    t0 = time.perf_counter()
    if args.chips == 4:
        phases = [phase_four_chips]
    else:
        phases = [phase_train_resnet, phase_train_gpt, phase_serve]
    for phase in phases:
        t1 = time.perf_counter()
        phase(sz, watch, args.seed)
        gc.collect()
        emit("phase_done", name=phase.__name__,
             seconds=time.perf_counter() - t1,
             compile_cache=dict(watch.cache))
    emit("done", seconds=time.perf_counter() - t0,
         compile_cache=dict(watch.cache), memory=watch.memory())
    return dev


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at toy sizes (JAX_PLATFORMS=cpu)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the mesh path and what it is compared "
                    "with (dp2 x tp2 GPT vs one device, ZeRO-1 dp=4)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    try:
        dev = run(args)
    except BaseException as e:
        traceback.print_exc()
        sys.stderr.flush()
        try:
            dev = device_doc()
        except Exception:
            dev = None
        print(json.dumps({"ok": False, "device": dev,
                          "error": "%s: %s" % (type(e).__name__, e)}),
              flush=True)
        sys.exit(1)
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
