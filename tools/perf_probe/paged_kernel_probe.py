"""Time ``paged_attention_multi`` alone on the chip at a serving cell's
shape: one jitted program of ``--calls`` kernel launches (a decode step's
24 layers), the context lengths drawn like the cell's traffic.

    chiprun -- python tools/perf_probe/paged_kernel_probe.py \
        [--parent-file .parent/mxnet_tpu/ops/pallas/paged_attention.py]

Prints one JSON line a case: microseconds a call, the share of the HBM
roofline (live K and V bytes once, as ``perfbench/shapes.py`` counts
them), the error against the jnp oracle.  ``--block-tokens`` times the
kernel at other block sizes than the one it derives (module constant
``_BLOCK_TOKENS``); ``--parent-file`` times another checkout's kernel
on the same inputs.
"""
import argparse
import importlib
import importlib.util
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..")
sys.path.insert(0, ROOT)


def contexts(rng, slots, live, kind):
    ctx = np.zeros(slots, np.int64)
    n = slots if live is None else live
    if kind == "backlog":
        c = np.clip(rng.lognormal(np.log(256), 0.7, n), 16, 512) \
            + rng.randint(0, 64, n)
    else:
        c = rng.randint(200, 700, n)
    ctx[rng.permutation(slots)[:n]] = c.astype(np.int64)
    return ctx


def build(rng, slots, heads, kv_heads, d, page, pages, per_seq, n_q,
          kv_dtype, ctx):
    import jax.numpy as jnp
    width = kv_heads * d
    q = rng.randn(slots, n_q, heads, d).astype(np.float32)
    perm = rng.permutation(pages - 1) + 1
    bt = np.zeros((slots, per_seq), np.int32)
    at = 0
    for s in range(slots):
        need = -(-int(ctx[s] + n_q) // page)
        bt[s, :need] = perm[at:at + need]
        at += need
    ctxs = np.where(ctx[:, None] > 0,
                    ctx[:, None] + np.arange(n_q)[None], 0).astype(np.int32)
    dt = {"fp32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}[
        kv_dtype]
    if kv_dtype == "int8":
        kp = jnp.asarray(rng.randint(-127, 128, (pages, page, width)),
                         jnp.int8)
        vp = jnp.asarray(rng.randint(-127, 128, (pages, page, width)),
                         jnp.int8)
        ks = jnp.asarray(rng.rand(pages, kv_heads) / 64 + 0.001,
                         jnp.float32)
        vs = jnp.asarray(rng.rand(pages, kv_heads) / 64 + 0.001,
                         jnp.float32)
        scales = dict(k_scales=ks, v_scales=vs)
    else:
        kp = jnp.asarray(rng.randn(pages, page, width), dt)
        vp = jnp.asarray(rng.randn(pages, page, width), dt)
        scales = {}
    return jnp.asarray(q), kp, vp, jnp.asarray(bt), jnp.asarray(ctxs), scales


def timed(fn, args, calls, reps=5):
    import jax

    @jax.jit
    def many(q, kp, vp, bt, ctx, scales):
        acc = 0.0
        for i in range(calls):
            # a data dependence keeps the launches in order and alive
            acc = acc + fn(q + acc * 0.0, kp, vp, bt, ctx, **scales) \
                .astype("float32")
        return acc
    out = many(*args)
    out.block_until_ready()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        many(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / calls, out / calls


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent-file")
    ap.add_argument("--block-tokens", default="")
    ap.add_argument("--calls", type=int, default=24)
    ap.add_argument("--slots", type=int, default=128)
    ap.add_argument("--pages", type=int, default=5711)
    ap.add_argument("--per-seq", type=int, default=64)
    ap.add_argument("--page", type=int, default=16)
    ap.add_argument("--cases", default="bf16:1:16:backlog:,bf16:1:16:chat:16")
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    import jax
    cur = importlib.import_module("mxnet_tpu.ops.pallas.paged_attention")
    kernels = {"change": cur.paged_attention_multi}
    if a.parent_file:
        spec = importlib.util.spec_from_file_location(
            "mxnet_tpu.ops.pallas.paged_attention_parent", a.parent_file)
        par = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(par)
        kernels["parent"] = par.paged_attention_multi
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.platform, "kind": dev.device_kind}),
          flush=True)
    with open(os.path.join(ROOT, "perfbench", "peaks.json")) as f:
        # off the chip the share printed is of a v5e's peak, and says
        # nothing
        peaks = json.load(f)
    hbm = peaks.get(dev.device_kind, peaks["TPU v5 lite"])["hbm_bytes_per_s"]
    heads, d = 16, 64
    for case in a.cases.split(","):
        kv_dtype, n_q, kv_heads, kind, live = case.split(":")
        n_q, kv_heads = int(n_q), int(kv_heads)
        rng = np.random.RandomState(a.seed)
        ctx = np.minimum(
            contexts(rng, a.slots, int(live) if live else None, kind),
            a.per_seq * a.page - n_q)
        args = build(rng, a.slots, heads, kv_heads, d, a.page, a.pages,
                     a.per_seq, n_q, kv_dtype, ctx)
        item = {"fp32": 4, "bf16": 2, "int8": 1}[kv_dtype]
        nbytes = 2 * int(ctx.sum()) * kv_heads * d * item
        ref = np.asarray(cur.paged_attention_multi_reference(
            *args[:5], **args[5]))
        variants = [("parent", None)] if "parent" in kernels else []
        variants += [("change", None)] + [
            ("change", int(t)) for t in a.block_tokens.split(",") if t]
        for name, tokens in variants:
            derived = cur._BLOCK_TOKENS
            if tokens:
                cur._BLOCK_TOKENS = tokens
            try:
                fn = kernels[name]
                sec, out = timed(
                    lambda *x, **kw: fn(*x, **kw), args, a.calls)
                doc = {"case": case, "kernel": name,
                       "block_tokens": tokens or (
                           derived if name == "change" else a.page),
                       "us_per_call": sec * 1e6,
                       "roofline_pct": 100 * nbytes / hbm / sec,
                       "live_tokens": int(ctx.sum()),
                       "max_abs_err": float(np.abs(
                           np.asarray(out) - ref).max())}
            except Exception as e:          # a refused shape is a result
                doc = {"case": case, "kernel": name,
                       "block_tokens": tokens,
                       "error": "%s: %s" % (type(e).__name__,
                                            str(e)[:300])}
            finally:
                cur._BLOCK_TOKENS = derived
            print(json.dumps(doc), flush=True)


if __name__ == "__main__":
    main()
