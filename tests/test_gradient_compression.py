"""2-bit gradient compression: oracle, residual carry, kvstore paths.

The v0.11 reference has no compression implementation (the API landed
upstream immediately after); semantics here follow the upstream 2-bit
scheme: quantize to {-threshold, 0, +threshold} with per-key residual
feedback.  Oracle is a literal numpy transcription of that rule.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def oracle_quantize(grad, residual, threshold):
    v = grad + residual
    out = np.zeros_like(v)
    out[v >= threshold] = threshold
    out[v <= -threshold] = -threshold
    return out, v - out


def test_compress_decompress_matches_oracle():
    from mxnet_tpu.gradient_compression import TwoBitCompression
    rng = np.random.RandomState(7)
    comp = TwoBitCompression(threshold=0.5)
    res = np.zeros(37, np.float32)
    for _ in range(4):  # several rounds so residuals actually carry
        g = rng.uniform(-1.2, 1.2, size=37).astype(np.float32)
        want, res = oracle_quantize(g, res, 0.5)
        packed = comp.compress("w", __import__("jax").numpy.asarray(g))
        got = np.asarray(comp.decompress(packed, (37,), np.float32))
        np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(np.asarray(comp._residuals["w"]), res,
                               atol=1e-5)


def test_packed_wire_is_16x_smaller():
    from mxnet_tpu.gradient_compression import TwoBitCompression
    import jax.numpy as jnp
    comp = TwoBitCompression(threshold=0.5)
    packed = comp.compress("k", jnp.ones(1024, jnp.float32))
    assert packed.dtype == jnp.uint8 and packed.shape == (256,)


def test_residual_accumulates_small_gradients():
    from mxnet_tpu.gradient_compression import TwoBitCompression
    import jax.numpy as jnp
    comp = TwoBitCompression(threshold=0.5)
    g = jnp.full((4,), 0.2, jnp.float32)
    sent = [np.asarray(comp.decompress(comp.compress("k", g), (4,),
                                       np.float32))
            for _ in range(3)]
    # 0.2, 0.4 stay under threshold; third step v=0.6 fires +0.5
    assert not sent[0].any() and not sent[1].any()
    np.testing.assert_allclose(sent[2], 0.5)
    np.testing.assert_allclose(np.asarray(comp._residuals["k"]), 0.1,
                               atol=1e-6)


def test_kvstore_local_compressed_push():
    import mxnet_tpu as mx
    kv = mx.kv.create("device")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv.init("w", mx.nd.zeros((4,)))
    kv.push("w", mx.nd.full((4,), 0.8))
    out = mx.nd.zeros((4,))
    kv.pull("w", out=out)
    # no updater installed: store holds the merged (quantized) gradient
    np.testing.assert_allclose(out.asnumpy(), 0.5)
    # residual 0.3 carries: next push of 0.3 fires (0.3+0.3 >= 0.5)
    kv.push("w", mx.nd.full((4,), 0.3))
    kv.pull("w", out=out)
    np.testing.assert_allclose(out.asnumpy(), 0.5)


def test_unsupported_compression_type_raises():
    import mxnet_tpu as mx
    kv = mx.kv.create("device")
    with pytest.raises(ValueError):
        kv.set_gradient_compression({"type": "fp8"})


@pytest.mark.elastic
def test_world_change_invalidates_residuals_and_allreduce_caches(
        monkeypatch):
    """The elastic bugfix: an in-process world-size change (elastic
    restart rejoin) must drop every world-coupled KVStore cache — the
    error-feedback residuals encode quantization error against a sum
    over the OLD worker set (replaying them would silently corrupt the
    first post-reshard push), and the cached worker mesh / jitted
    allreduce / decode-sum programs bake the old device set into their
    shardings."""
    import mxnet_tpu as mx
    from mxnet_tpu.kvstore import KVStore
    kv = mx.kv.create("dist_sync")  # no coordinator: world is 1
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    comp = kv._compressor
    kv.init("w", mx.nd.zeros((4,)))
    kv.push("w", mx.nd.full((4,), 0.3))  # residual 0.3 accumulates
    assert np.allclose(np.asarray(comp._residuals["w"]), 0.3)
    # plant sentinels for the world-coupled jit/mesh caches
    kv._allreduce_jit = object()
    kv._worker_mesh = object()
    comp._decode_sum_jit = object()
    # same world: idempotent re-set keeps the live compressor AND its
    # residuals (the ADVICE-r3 contract, still intact)
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    assert kv._compressor is comp and "w" in comp._residuals
    # world changes 1 -> 2: every cache drops
    monkeypatch.setattr(KVStore, "num_workers",
                        property(lambda self: 2))
    kv._check_world()
    assert kv._allreduce_jit is None and kv._worker_mesh is None
    assert comp._residuals == {} and comp._decode_sum_jit is None
    assert kv._cached_world == 2
    from mxnet_tpu import telemetry
    assert telemetry.counter("kv.world_changes").value >= 1


@pytest.mark.elastic
def test_set_gradient_compression_world_aware(monkeypatch):
    """Re-calling set_gradient_compression with identical params after
    a world change must NOT keep the stale residual stream (the bug:
    the idempotence early-return ignored the world)."""
    import mxnet_tpu as mx
    from mxnet_tpu.kvstore import KVStore
    kv = mx.kv.create("dist_sync")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    comp = kv._compressor
    kv.init("w", mx.nd.zeros((4,)))
    kv.push("w", mx.nd.full((4,), 0.3))
    assert "w" in comp._residuals
    monkeypatch.setattr(KVStore, "num_workers",
                        property(lambda self: 3))
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    assert comp._residuals == {}  # stale stream dropped, not carried


COMPRESSED_WORKER = """
import os, sys
sys.path.insert(0, %(repo)r)
import numpy as np
import mxnet_tpu as mx

kv = mx.kv.create("dist_sync")
rank, n = kv.rank, kv.num_workers
assert n == 2
kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
kv.init("w", mx.nd.zeros((6,)))
# worker 0 pushes 0.9 (-> +0.5, residual 0.4); worker 1 pushes -0.7
# (-> -0.5, residual -0.2); quantized sum = 0.0 on both workers
kv.push("w", mx.nd.full((6,), 0.9 if rank == 0 else -0.7))
out = mx.nd.zeros((6,))
kv.pull("w", out=out)
assert np.allclose(out.asnumpy(), 0.0), out.asnumpy()
# second push: worker 0 residual 0.4 + 0.2 -> +0.5; worker 1 residual
# -0.2 + 0.2 -> 0; sum = 0.5
kv.push("w", mx.nd.full((6,), 0.2))
kv.pull("w", out=out)
assert np.allclose(out.asnumpy(), 0.5), out.asnumpy()
kv.barrier()
open(os.path.join(%(tmp)r, "gc_ok_%%d" %% rank), "w").write("1")
"""


@pytest.mark.slow
def test_dist_compressed_two_processes(tmp_path):
    script = tmp_path / "gc_worker.py"
    script.write_text(COMPRESSED_WORKER % {"repo": REPO,
                                           "tmp": str(tmp_path)})
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--cpu-fake-devices", sys.executable, str(script)],
        env=env, capture_output=True, timeout=300)
    assert r.returncode == 0, (r.stdout.decode()[-2000:] +
                               r.stderr.decode()[-2000:])
    assert (tmp_path / "gc_ok_0").exists() and (tmp_path / "gc_ok_1").exists()
