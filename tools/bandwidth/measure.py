#!/usr/bin/env python
"""Measure all-reduce bandwidth over the device mesh.

Port of /root/reference/tools/bandwidth/measure.py: the reference timed
KVStore push+pull of ResNet-sized gradient arrays across GPUs
(README.md:33-67, ~11 GB/s on 2 GPUs).  TPU-native, the gradient
all-reduce is ``jax.lax.psum`` over the mesh's data axis riding ICI; this
tool times exactly that collective and reports per-chip algorithm
bandwidth, the number BASELINE.json tracks.

busbw = algbw * 2 * (n-1) / n   (ring all-reduce traffic factor)

Usage:
  python tools/bandwidth/measure.py                 # all local devices
  python tools/bandwidth/measure.py --test-gpus 4   # first 4 devices
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python tools/bandwidth/measure.py             # 8 fake devices
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def measure(num_devices=0, size_mb=256.0, num_arrays=30, iters=10,
            warmup=3, dtype="float32"):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    devs = jax.devices()
    n = min(num_devices, len(devs)) if num_devices else len(devs)
    devs = devs[:n]
    mesh = Mesh(np.array(devs), ("dp",))

    itemsize = jnp.dtype(dtype).itemsize
    per_array = int(size_mb * 1e6 / num_arrays / itemsize)
    per_array = max(per_array - per_array % n, n)
    arrays = [jnp.ones((per_array,), dtype) for _ in range(num_arrays)]

    @jax.jit
    def allreduce(xs):
        def f(*xs):
            return tuple(jax.lax.psum(x, "dp") for x in xs)
        return shard_map(f, mesh=mesh, in_specs=(P("dp"),) * len(xs),
                         out_specs=(P(None),) * len(xs))(*xs)

    total_bytes = sum(a.nbytes for a in arrays)
    for _ in range(warmup):
        out = allreduce(tuple(arrays))
        jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = allreduce(tuple(arrays))
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    t = min(times)
    algbw = total_bytes / t / 1e9
    busbw = algbw * 2 * (n - 1) / n
    return {"devices": n, "size_mb": total_bytes / 1e6, "time_s": t,
            "algbw_GBps": algbw, "busbw_GBps": busbw}


def measure_kvstore(kv_type="dist_sync", size_mb=64.0, num_arrays=10,
                    iters=10, warmup=2, dtype="float32",
                    gc_type="none", gc_threshold=0.5):
    """Time KVStore push+pull per key batch — the user-facing path the
    reference README benchmarked (push grads, pull weights, ~11 GB/s on
    2 GPUs).  Run under tools/launch.py -n 2 for the dist path.
    gc_type='2bit' measures the quantized push path (pull still moves
    uncompressed weights; single-process stores quantize semantics only
    — the wire numbers are meaningful for dist stores)."""
    import numpy as np
    import mxnet_tpu as mx

    kv = mx.kv.create(kv_type)
    if gc_type != "none":
        kv.set_gradient_compression({"type": gc_type,
                                     "threshold": gc_threshold})
    itemsize = np.dtype(dtype).itemsize
    per_array = max(1, int(size_mb * 1e6 / num_arrays / itemsize))
    keys = [str(i) for i in range(num_arrays)]
    vals = [mx.nd.ones((per_array,), dtype=dtype) for _ in keys]
    outs = [mx.nd.zeros((per_array,), dtype=dtype) for _ in keys]
    for k, v in zip(keys, vals):
        kv.init(k, v)
    total_bytes = sum(v._data.nbytes for v in vals)

    def roundtrip():
        kv.push(keys, [[v] for v in vals])
        kv.pull(keys, [[o] for o in outs])
        for o in outs:
            np.asarray(o._data[-1])  # completion barrier

    for _ in range(warmup):
        roundtrip()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        roundtrip()
        times.append(time.perf_counter() - t0)
    t = min(times)
    res = {"kv_type": kv_type, "workers": kv.num_workers,
           "num_keys": num_arrays, "total_mb": total_bytes / 1e6,
           "time_s": t, "GBps": total_bytes / t / 1e9,
           "per_key_GBps": total_bytes / num_arrays / t / 1e9}
    if gc_type != "none":
        res["gc_type"] = gc_type
        # the push wire carries 2-bit codes packed PER KEY: each key
        # contributes ceil(elements/4) bytes, independent of the
        # uncompressed dtype's width
        res["wire_bytes_per_push"] = num_arrays * (-(-per_array // 4))
    return res


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="all-reduce bandwidth over the mesh "
        "(reference tools/bandwidth/measure.py)")
    parser.add_argument("--test-gpus", "--test-devices", dest="devices",
                        type=int, default=0,
                        help="number of devices (0 = all)")
    parser.add_argument("--image-shape", default=None,
                        help="ignored (CLI compat)")
    parser.add_argument("--network", default=None,
                        help="ignored (CLI compat); sizes come from "
                        "--size-mb")
    parser.add_argument("--size-mb", type=float, default=256.0,
                        help="total gradient bytes per all-reduce")
    parser.add_argument("--num-arrays", type=int, default=30,
                        help="number of gradient arrays (ResNet-ish ~30 "
                        "large tensors)")
    parser.add_argument("--iters", type=int, default=10)
    parser.add_argument("--dtype", default="float32",
                        choices=["float32", "bfloat16"])
    parser.add_argument("--kv-store", default=None,
                        help="measure through the KVStore API instead of "
                        "the raw mesh psum (e.g. 'device', 'dist_sync'; "
                        "run dist under tools/launch.py -n 2)")
    parser.add_argument("--gc-type", default="none",
                        help="gradient compression for the KVStore path "
                        "(none or 2bit)")
    parser.add_argument("--gc-threshold", type=float, default=0.5,
                        help="2bit compression threshold")
    args = parser.parse_args(argv)
    if args.kv_store:
        res = measure_kvstore(args.kv_store, args.size_mb,
                              args.num_arrays, args.iters,
                              dtype=args.dtype, gc_type=args.gc_type,
                              gc_threshold=args.gc_threshold)
        extra = " gc=%s push-wire=%.1f MB" % (
            res["gc_type"], res["wire_bytes_per_push"] / 1e6) \
            if args.gc_type != "none" else ""
        print("kv=%s workers=%d keys=%d total=%.1f MB time=%.4f s "
              "agg=%.2f GB/s per-key=%.3f GB/s%s"
              % (res["kv_type"], res["workers"], res["num_keys"],
                 res["total_mb"], res["time_s"], res["GBps"],
                 res["per_key_GBps"], extra))
        return res
    res = measure(args.devices, args.size_mb, args.num_arrays, args.iters,
                  dtype=args.dtype)
    print("devices=%d total=%.1f MB time=%.4f s algbw=%.2f GB/s "
          "busbw=%.2f GB/s"
          % (res["devices"], res["size_mb"], res["time_s"],
             res["algbw_GBps"], res["busbw_GBps"]))
    return res


if __name__ == "__main__":
    main()
