"""Per-conv layout probe (PERF.md §6) — NOTE: per-op timings are
dispatch-bound; use resnet_probe.py for whole-step numbers."""
import time, functools
import jax, jax.numpy as jnp
from jax import lax

B = 256
ITERS = 50
cases = [
    (56, 64, 64, 3, 1),
    (56, 256, 64, 1, 1),
    (28, 128, 128, 3, 1),
    (14, 256, 256, 3, 1),
    (7, 512, 512, 3, 1),
]
key = jax.random.PRNGKey(0)

def run(layout, H, Ci, Co, k, s):
    pad = [(k // 2, k // 2)] * 2
    if layout == "NCHW":
        x = jax.random.normal(key, (B, Ci, H, H), jnp.bfloat16)
        w = jax.random.normal(key, (Co, Ci, k, k), jnp.bfloat16)
        dn = ("NCHW", "OIHW", "NCHW")
    else:
        x = jax.random.normal(key, (B, H, H, Ci), jnp.bfloat16)
        w = jax.random.normal(key, (k, k, Ci, Co), jnp.bfloat16)
        dn = ("NHWC", "HWIO", "NHWC")
    dnn = lax.conv_dimension_numbers(x.shape, w.shape, dn)
    conv = functools.partial(lax.conv_general_dilated, window_strides=(s, s),
                             padding=pad, dimension_numbers=dnn)
    # chain ITERS convs so one dispatch measures pure device time; output
    # feeds back (same shape when Ci==Co and s==1; else re-use x)
    @jax.jit
    def loop(x, w):
        def body(i, acc):
            # perturb the input by the running sum so the conv depends on
            # the loop carry — else XLA hoists a loop-invariant conv out
            # (LICM) and the probe reports ITERS-times-too-fast numbers
            xi = acc[0] if Ci == Co and s == 1 else \
                x + acc[1].astype(x.dtype)
            y = conv(xi, w)
            return (y if Ci == Co and s == 1 else acc[0],
                    acc[1] + y.mean().astype(jnp.float32))
        return lax.fori_loop(0, ITERS, body, (x, jnp.float32(0)))
    o = loop(x, w); jax.block_until_ready(o)
    t0 = time.perf_counter()
    o = loop(x, w); jax.block_until_ready(o)
    dt = (time.perf_counter() - t0) / ITERS
    Ho = -(-H // s)
    fl = 2 * B * Ho * Ho * Co * Ci * k * k
    return dt, fl / dt / 1e12

for H, Ci, Co, k, s in cases:
    t1, tf1 = run("NCHW", H, Ci, Co, k, s)
    t2, tf2 = run("NHWC", H, Ci, Co, k, s)
    print("H%-4dCi%-4dCo%-4dk%d  NCHW %7.3fms %6.1fTF/s | NHWC %7.3fms %6.1fTF/s"
          % (H, Ci, Co, k, t1 * 1e3, tf1, t2 * 1e3, tf2))
