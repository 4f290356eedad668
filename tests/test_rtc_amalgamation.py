"""mx.rtc runtime kernels + the single-file amalgamation bundle."""
import os
import subprocess
import sys

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_rtc_kernel_compiles_and_runs():
    x = nd.array(np.linspace(-1, 1, 12).astype(np.float32))
    a = nd.array(np.full(12, 3.0, np.float32))
    y = nd.zeros((12,))
    rtc = mx.rtc.Rtc("axpy", [("x", x), ("a", a)], [("y", y)],
                     "y = a * x + jnp.sin(x)")
    rtc.push([x, a], [y])
    want = 3.0 * x.asnumpy() + np.sin(x.asnumpy())
    np.testing.assert_allclose(y.asnumpy(), want, rtol=1e-6)
    # grid/block accepted for reference-signature parity
    rtc.push([x, a], [y], grid_dims=(1, 1, 1), block_dims=(12, 1, 1))
    np.testing.assert_allclose(y.asnumpy(), want, rtol=1e-6)


def test_rtc_multiple_outputs_and_missing_output_error():
    x = nd.array(np.arange(6, dtype=np.float32))
    s = nd.zeros((6,))
    c = nd.zeros((6,))
    rtc = mx.rtc.Rtc("sincos", [("x", x)], [("s", s), ("c", c)],
                     "s = jnp.sin(x)\nc = jnp.cos(x)")
    rtc.push([x], [s, c])
    np.testing.assert_allclose(s.asnumpy(), np.sin(x.asnumpy()), rtol=1e-6)
    np.testing.assert_allclose(c.asnumpy(), np.cos(x.asnumpy()), rtol=1e-6)

    bad = mx.rtc.Rtc("bad", [("x", x)], [("nope", s)], "tmp = x * 2")
    try:
        bad.push([x], [s])
    except mx.MXNetError as e:
        assert "nope" in str(e)
    else:
        raise AssertionError("missing output did not raise")


PALLAS_RTC_DRIVER = """
import sys
sys.path.insert(0, %(repo)r)
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import nd

src = '''
def kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0 + 1.0
'''
k = mx.rtc.PallasRtc("double_plus", src)
x = nd.array(np.arange(8, dtype=np.float32).reshape(2, 4))
y = k(x)
np.testing.assert_allclose(y.asnumpy(), x.asnumpy() * 2 + 1, rtol=1e-6)
print("PALLAS_RTC_OK")
"""


def test_pallas_rtc_kernel(tmp_path):
    """The user-written Pallas kernel through mx.rtc, in a subprocess
    like test_flash_attention."""
    driver = tmp_path / "pallas_rtc_driver.py"
    driver.write_text(PALLAS_RTC_DRIVER % {"repo": REPO})
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, str(driver)], capture_output=True,
                       env=env, timeout=300)
    out = r.stdout.decode() + r.stderr.decode()
    assert r.returncode == 0, out[-1500:]
    assert "PALLAS_RTC_OK" in out


AMALG_DRIVER = """
import sys
sys.path.insert(0, %(bundle_dir)r)
import mxnet_tpu_amalgamation  # registers the in-memory loader
import mxnet_tpu as mx
from mxnet_tpu import nd
import numpy as np

assert "<amalgamated:" in repr(mx.__spec__.origin), mx.__spec__.origin

# train a tiny gluon net end-to-end from the bundle
net = mx.gluon.nn.Sequential()
with net.name_scope():
    net.add(mx.gluon.nn.Dense(8, activation="tanh"))
    net.add(mx.gluon.nn.Dense(1))
net.collect_params().initialize(ctx=mx.cpu())
trainer = mx.gluon.Trainer(net.collect_params(), "adam",
                           {"learning_rate": 0.05})
rng = np.random.RandomState(0)
X = rng.randn(32, 4).astype(np.float32)
Y = X.sum(1, keepdims=True).astype(np.float32)
first = last = None
for step in range(150):
    with mx.autograd.record():
        loss = ((net(nd.array(X)) - nd.array(Y)) ** 2).mean()
    loss.backward()
    trainer.step(32)
    v = float(loss.asnumpy())
    first = v if first is None else first
    last = v
assert last < 0.1 * first, (first, last)
print("AMALG OK", first, last)
"""


def test_amalgamation_single_file_runs_standalone(tmp_path):
    """Build the bundle, then import + train in a subprocess whose ONLY
    path entry for the framework is the bundle file (the real package
    directory is not importable there)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import amalgamation
    out = tmp_path / "mxnet_tpu_amalgamation.py"
    path, n_modules, _ = amalgamation.amalgamate(str(out))
    assert n_modules > 50
    driver = tmp_path / "drive.py"
    driver.write_text(AMALG_DRIVER % {"bundle_dir": str(tmp_path)})
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = ""  # the repo must NOT be importable
    r = subprocess.run([sys.executable, str(driver)], capture_output=True,
                       cwd=str(tmp_path), env=env, timeout=300)
    assert r.returncode == 0, (r.stdout.decode() + r.stderr.decode())[-1500:]
    assert b"AMALG OK" in r.stdout
