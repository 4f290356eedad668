"""Raw-JAX ResNet-50 train-step ceiling probe: NCHW vs NHWC on one chip."""
import functools, time, sys
import jax, jax.numpy as jnp
from jax import lax
import numpy as np

LAYOUT = sys.argv[1] if len(sys.argv) > 1 else "NHWC"
B = int(sys.argv[2]) if len(sys.argv) > 2 else 256
nhwc = LAYOUT == "NHWC"
dn = ("NHWC", "HWIO", "NHWC") if nhwc else ("NCHW", "OIHW", "NCHW")
caxis = -1 if nhwc else 1

rng = np.random.RandomState(0)
params = []

def conv_w(k, ci, co):
    w = rng.randn(*( (k, k, ci, co) if nhwc else (co, ci, k, k) )).astype(np.float32) * 0.05
    params.append(w)
    return len(params) - 1

def bn_w(c):
    params.append(np.ones((c,), np.float32))
    params.append(np.zeros((c,), np.float32))
    return len(params) - 2

# resnet50 v1: stem + [3,4,6,3] bottleneck stages
stages = [(3, 64, 256, 1), (4, 128, 512, 2), (6, 256, 1024, 2), (3, 512, 2048, 2)]
arch = {"stem_conv": conv_w(7, 3, 64), "stem_bn": bn_w(64)}
blocks = []
cin = 64
for n, mid, cout, stride in stages:
    for i in range(n):
        s = stride if i == 0 else 1
        blk = {
            "c1": conv_w(1, cin, mid), "b1": bn_w(mid),
            "c2": conv_w(3, mid, mid), "b2": bn_w(mid),
            "c3": conv_w(1, mid, cout), "b3": bn_w(cout),
            "stride": s,
        }
        if cin != cout or s != 1:
            blk["down"] = conv_w(1, cin, cout)
            blk["down_bn"] = bn_w(cout)
        blocks.append(blk)
        cin = cout
fc_w = rng.randn(2048, 1000).astype(np.float32) * 0.01
params.append(fc_w)
FC = len(params) - 1

def conv(x, w, stride=1, k=1):
    p = k // 2
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(p, p), (p, p)],
        dimension_numbers=lax.conv_dimension_numbers(x.shape, w.shape, dn))

def bn(x, g, b):
    axes = tuple(i for i in range(4) if i != (3 if nhwc else 1))
    m = x.mean(axes, keepdims=True)
    v = ((x - m) ** 2).mean(axes, keepdims=True)
    sh = (1, 1, 1, -1) if nhwc else (1, -1, 1, 1)
    return (x - m) * lax.rsqrt(v + 1e-5) * g.reshape(sh) + b.reshape(sh)

def fwd(p, x):
    x = conv(x, p[arch["stem_conv"]], 2, 7)
    x = jax.nn.relu(bn(x, p[arch["stem_bn"]], p[arch["stem_bn"] + 1]))
    wdims = (1, 1) if nhwc else (2, 3)
    x = lax.reduce_window(x, -jnp.inf, lax.max,
                          tuple(3 if i in wdims else 1 for i in range(4)),
                          tuple(2 if i in wdims else 1 for i in range(4)),
                          [(0, 0) if i not in wdims else (1, 1) for i in range(4)])
    for blk in blocks:
        idn = x
        y = jax.nn.relu(bn(conv(x, p[blk["c1"]]), p[blk["b1"]], p[blk["b1"] + 1]))
        y = jax.nn.relu(bn(conv(y, p[blk["c2"]], blk["stride"], 3), p[blk["b2"]], p[blk["b2"] + 1]))
        y = bn(conv(y, p[blk["c3"]]), p[blk["b3"]], p[blk["b3"] + 1])
        if "down" in blk:
            idn = bn(conv(x, p[blk["down"]], blk["stride"]), p[blk["down_bn"]], p[blk["down_bn"] + 1])
        x = jax.nn.relu(y + idn)
    x = x.mean((1, 2) if nhwc else (2, 3))
    return x @ p[FC]

def loss_fn(p, x, y):
    pb = [q.astype(jnp.bfloat16) for q in p]
    logits = fwd(pb, x.astype(jnp.bfloat16)).astype(jnp.float32)
    lp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(lp, y[:, None], 1).mean()

@functools.partial(jax.jit, donate_argnums=(0, 1))
def step(p, mom, x, y):
    l, g = jax.value_and_grad(loss_fn)(p, x, y)
    mom = [0.9 * m - 0.05 * gg for m, gg in zip(mom, g)]
    p = [w + m for w, m in zip(p, mom)]
    return p, mom, l

ps = [jnp.asarray(w) for w in params]
mom = [jnp.zeros_like(w) for w in ps]
key = jax.random.PRNGKey(0)
shape = (B, 224, 224, 3) if nhwc else (B, 3, 224, 224)
x = jax.random.normal(key, shape, jnp.float32)
y = jax.random.randint(key, (B,), 0, 1000)

for _ in range(3):
    ps, mom, l = step(ps, mom, x, y)
l.block_until_ready()
t0 = time.perf_counter()
N = 20
for _ in range(N):
    ps, mom, l = step(ps, mom, x, y)
l.block_until_ready()  # the last loss waits for the whole chain
dt = time.perf_counter() - t0
imgs = B * N / dt
print("%s bs%d: %.1f img/s  (%.1f ms/step, loss %.3f)"
      % (LAYOUT, B, imgs, dt / N * 1e3, float(l)))
