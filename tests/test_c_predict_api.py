"""C predict ABI (include/mxtpu/c_predict_api.h, libmxtpu_predict.so).

Two hosts, matching the reference's deployment modes
(reference include/mxnet/c_predict_api.h):
- this Python process loading the .so via ctypes (attached-GIL path);
- a standalone C program linked against the .so (embedded-interpreter
  path) — the "any language with a C FFI" story.
"""
import ctypes
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(REPO, "mxnet_tpu", "native", "libmxtpu_predict.so")


def _build_lib():
    if not os.path.exists(LIB):
        r = subprocess.run(["make", "predict"],
                           cwd=os.path.join(REPO, "src"),
                           capture_output=True)
        if r.returncode != 0:
            pytest.skip("libmxtpu_predict.so build failed: %s"
                        % r.stderr.decode()[-500:])
    return LIB


def _save_checkpoint(tmp_path):
    """A small MLP checkpoint: prefix-symbol.json + prefix-0000.params."""
    data = mx.sym.Variable("data")
    y = mx.sym.FullyConnected(data, name="fc1", num_hidden=8)
    y = mx.sym.Activation(y, act_type="tanh", name="act1")
    y = mx.sym.FullyConnected(y, name="fc2", num_hidden=3)
    y = mx.sym.softmax(y, name="prob")
    exe = y.simple_bind(mx.cpu(), grad_req="null", data=(2, 5))
    rng = np.random.RandomState(0)
    args = {k: nd.array(rng.randn(*v.shape).astype(np.float32) * 0.3)
            for k, v in exe.arg_dict.items() if k != "data"}
    exe.copy_params_from(args)
    prefix = str(tmp_path / "mlp")
    y.save("%s-symbol.json" % prefix)
    nd.save("%s-0000.params" % prefix,
            {"arg:%s" % k: v for k, v in args.items()})
    return prefix, y, args


def _declare(lib):
    c = ctypes
    u = c.c_uint32
    lib.MXPredGetLastError.restype = c.c_char_p
    lib.MXPredCreate.restype = c.c_int
    lib.MXPredCreate.argtypes = [
        c.c_char_p, c.c_void_p, c.c_int, c.c_int, c.c_int, u,
        c.POINTER(c.c_char_p), c.POINTER(u), c.POINTER(u),
        c.POINTER(c.c_void_p)]
    lib.MXPredSetInput.restype = c.c_int
    lib.MXPredSetInput.argtypes = [c.c_void_p, c.c_char_p,
                                   c.POINTER(c.c_float), u]
    lib.MXPredForward.restype = c.c_int
    lib.MXPredForward.argtypes = [c.c_void_p]
    lib.MXPredGetOutputShape.restype = c.c_int
    lib.MXPredGetOutputShape.argtypes = [c.c_void_p, u,
                                         c.POINTER(c.POINTER(u)),
                                         c.POINTER(u)]
    lib.MXPredGetOutput.restype = c.c_int
    lib.MXPredGetOutput.argtypes = [c.c_void_p, u, c.POINTER(c.c_float), u]
    lib.MXPredFree.restype = c.c_int
    lib.MXPredFree.argtypes = [c.c_void_p]
    lib.MXPredReshape.restype = c.c_int
    lib.MXPredReshape.argtypes = [u, c.POINTER(c.c_char_p), c.POINTER(u),
                                  c.POINTER(u), c.c_void_p,
                                  c.POINTER(c.c_void_p)]
    return lib


def test_c_predict_ctypes_roundtrip(tmp_path):
    _build_lib()
    prefix, sym, args = _save_checkpoint(tmp_path)
    lib = _declare(ctypes.CDLL(LIB))

    with open("%s-symbol.json" % prefix, "rb") as f:
        sym_json = f.read()
    with open("%s-0000.params" % prefix, "rb") as f:
        params = f.read()

    u = ctypes.c_uint32
    keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (u * 2)(0, 2)
    shape = (u * 2)(2, 5)
    handle = ctypes.c_void_p()
    rc = lib.MXPredCreate(sym_json, params, len(params), 1, 0, 1, keys,
                          indptr, shape, ctypes.byref(handle))
    assert rc == 0, lib.MXPredGetLastError().decode()

    # output shape available straight after create (inferred, no forward)
    sdata = ctypes.POINTER(u)()
    sndim = u()
    rc = lib.MXPredGetOutputShape(handle, 0, ctypes.byref(sdata),
                                  ctypes.byref(sndim))
    assert rc == 0, lib.MXPredGetLastError().decode()
    out_shape = tuple(sdata[i] for i in range(sndim.value))
    assert out_shape == (2, 3)

    x = np.random.RandomState(1).randn(2, 5).astype(np.float32)
    xc = np.ascontiguousarray(x)
    rc = lib.MXPredSetInput(
        handle, b"data",
        xc.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), x.size)
    assert rc == 0, lib.MXPredGetLastError().decode()
    rc = lib.MXPredForward(handle)
    assert rc == 0, lib.MXPredGetLastError().decode()

    out = np.zeros(6, np.float32)
    rc = lib.MXPredGetOutput(
        handle, 0, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out.size)
    assert rc == 0, lib.MXPredGetLastError().decode()

    # oracle: the Python Predictor on the same checkpoint
    pred = mx.Predictor.from_checkpoint(prefix, 0, {"data": (2, 5)},
                                        ctx=mx.cpu())
    want = pred.predict(x)
    np.testing.assert_allclose(out.reshape(2, 3), want, rtol=1e-5,
                               atol=1e-6)

    # wrong size reports, not crashes
    bad = np.zeros(4, np.float32)
    rc = lib.MXPredGetOutput(
        handle, 0, bad.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        bad.size)
    assert rc != 0 and b"mismatch" in lib.MXPredGetLastError()

    # reshape returns a NEW handle for batch 4; the old handle must stay
    # fully usable at batch 2 (reference MXPredReshape semantics)
    shape4 = (u * 2)(4, 5)
    handle4 = ctypes.c_void_p()
    rc = lib.MXPredReshape(1, keys, indptr, shape4, handle,
                           ctypes.byref(handle4))
    assert rc == 0, lib.MXPredGetLastError().decode()
    x4 = np.random.RandomState(2).randn(4, 5).astype(np.float32)
    rc = lib.MXPredSetInput(
        handle4, b"data",
        x4.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), x4.size)
    assert rc == 0, lib.MXPredGetLastError().decode()
    assert lib.MXPredForward(handle4) == 0
    out4 = np.zeros(12, np.float32)
    assert lib.MXPredGetOutput(
        handle4, 0, out4.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out4.size) == 0
    np.testing.assert_allclose(
        out4.reshape(4, 3),
        mx.Predictor.from_checkpoint(prefix, 0, {"data": (4, 5)},
                                     ctx=mx.cpu()).predict(x4),
        rtol=1e-5, atol=1e-6)
    # old handle: re-run batch 2 and get the same answer as before
    rc = lib.MXPredSetInput(
        handle, b"data",
        xc.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), x.size)
    assert rc == 0, lib.MXPredGetLastError().decode()
    assert lib.MXPredForward(handle) == 0
    out2 = np.zeros(6, np.float32)
    assert lib.MXPredGetOutput(
        handle, 0, out2.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out2.size) == 0
    np.testing.assert_allclose(out2, out, rtol=1e-6)
    # same-shape reshape must not alias buffers: staging input on the
    # clone then re-running the old handle must reproduce its old output
    same = ctypes.c_void_p()
    assert lib.MXPredReshape(1, keys, indptr, shape, handle,
                             ctypes.byref(same)) == 0
    other = np.full((2, 5), 9.0, np.float32)
    assert lib.MXPredSetInput(
        same, b"data",
        other.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        other.size) == 0
    assert lib.MXPredForward(handle) == 0  # old handle, old staged input
    out_again = np.zeros(6, np.float32)
    assert lib.MXPredGetOutput(
        handle, 0, out_again.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out_again.size) == 0
    np.testing.assert_allclose(out_again, out2, rtol=1e-6)
    lib.MXPredFree(same)
    lib.MXPredFree(handle4)
    lib.MXPredFree(handle)


C_DRIVER = r"""
#include <stdio.h>
#include <stdlib.h>
#include "mxtpu/c_predict_api.h"

static char *slurp(const char *path, long *size) {
  FILE *f = fopen(path, "rb");
  if (!f) { fprintf(stderr, "open %s failed\n", path); exit(2); }
  fseek(f, 0, SEEK_END); *size = ftell(f); fseek(f, 0, SEEK_SET);
  char *buf = (char *)malloc((size_t)*size + 1);
  if (fread(buf, 1, (size_t)*size, f) != (size_t)*size) exit(2);
  buf[*size] = 0; fclose(f);
  return buf;
}

int main(int argc, char **argv) {
  long sym_size, param_size;
  char *sym_json = slurp(argv[1], &sym_size);
  char *params = slurp(argv[2], &param_size);
  const char *keys[1] = {"data"};
  uint32_t indptr[2] = {0, 2};
  uint32_t shape[2] = {2, 5};
  PredictorHandle h = NULL;
  if (MXPredCreate(sym_json, params, (int)param_size, 1, 0, 1, keys,
                   indptr, shape, &h) != 0) {
    fprintf(stderr, "create: %s\n", MXPredGetLastError());
    return 1;
  }
  float x[10];
  for (int i = 0; i < 10; ++i) x[i] = (float)i * 0.1f - 0.5f;
  if (MXPredSetInput(h, "data", x, 10) != 0 || MXPredForward(h) != 0) {
    fprintf(stderr, "fwd: %s\n", MXPredGetLastError());
    return 1;
  }
  float out[6];
  if (MXPredGetOutput(h, 0, out, 6) != 0) {
    fprintf(stderr, "out: %s\n", MXPredGetLastError());
    return 1;
  }
  double total = 0;
  for (int i = 0; i < 6; ++i) { printf("%.6f ", out[i]); total += out[i]; }
  printf("\n");
  MXPredFree(h);
  /* softmax rows each sum to 1 */
  return (total > 1.99 && total < 2.01) ? 0 : 1;
}
"""


CPP_DRIVER = r"""
#include <fstream>
#include <iostream>
#include <sstream>
#include "mxtpu/predictor.hpp"

static std::string slurp(const char *path) {
  std::ifstream f(path, std::ios::binary);
  std::ostringstream ss;
  ss << f.rdbuf();
  return ss.str();
}

int main(int argc, char **argv) {
  (void)argc;
  mxtpu::Predictor pred(slurp(argv[1]), slurp(argv[2]),
                        {{"data", {2, 5}}});
  std::vector<float> x(10);
  for (int i = 0; i < 10; ++i) x[i] = 0.1f * i - 0.5f;
  pred.SetInput("data", x);
  pred.Forward();
  auto shape = pred.GetOutputShape(0);
  if (shape != mxtpu::Predictor::Shape{2, 3}) return 1;
  auto out = pred.GetOutput(0);
  double total = 0;
  for (float v : out) { std::cout << v << " "; total += v; }
  std::cout << std::endl;

  // Reshape: new handle at batch 4; old keeps working
  auto big = pred.Reshape({{"data", {4, 5}}});
  big.SetInput("data", std::vector<float>(20, 0.25f));
  big.Forward();
  if (big.GetOutputShape(0) != mxtpu::Predictor::Shape{4, 3}) return 1;
  pred.Forward();

  // error surfaces as an exception, not a crash
  try {
    pred.SetInput("nope", x);
    return 1;
  } catch (const mxtpu::Error &e) {
    if (std::string(e.what()).find("nope") == std::string::npos) return 1;
  }
  return (total > 1.99 && total < 2.01) ? 0 : 1;
}
"""


@pytest.mark.slow
def test_cpp_package_wrapper(tmp_path):
    """The cpp-package analogue: RAII C++ wrapper (predictor.hpp) over
    the C ABI, compiled and run standalone."""
    _build_lib()
    prefix, _, _ = _save_checkpoint(tmp_path)
    src = tmp_path / "driver.cpp"
    src.write_text(CPP_DRIVER)
    exe = tmp_path / "cppdriver"
    r = subprocess.run(
        ["g++", "-std=c++17", str(src), "-I", os.path.join(REPO, "include"),
         "-L", os.path.dirname(LIB), "-lmxtpu_predict",
         "-Wl,-rpath," + os.path.dirname(LIB), "-o", str(exe)],
        capture_output=True)
    assert r.returncode == 0, r.stderr.decode()[-800:]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXTPU_HOME"] = REPO
    r = subprocess.run(
        [str(exe), "%s-symbol.json" % prefix, "%s-0000.params" % prefix],
        capture_output=True, env=env, timeout=300)
    assert r.returncode == 0, (r.stdout.decode() + r.stderr.decode())[-800:]


@pytest.mark.slow
def test_c_predict_embedded_interpreter(tmp_path):
    """Compile a real C program against the ABI and run it standalone —
    the interpreter is embedded by the library, not provided by pytest."""
    _build_lib()
    prefix, _, _ = _save_checkpoint(tmp_path)
    csrc = tmp_path / "driver.c"
    csrc.write_text(C_DRIVER)
    exe = tmp_path / "driver"
    r = subprocess.run(
        ["gcc", str(csrc), "-I", os.path.join(REPO, "include"),
         "-L", os.path.dirname(LIB), "-lmxtpu_predict",
         "-Wl,-rpath," + os.path.dirname(LIB), "-o", str(exe)],
        capture_output=True)
    assert r.returncode == 0, r.stderr.decode()[-800:]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXTPU_HOME"] = REPO
    r = subprocess.run(
        [str(exe), "%s-symbol.json" % prefix, "%s-0000.params" % prefix],
        capture_output=True, env=env, timeout=300)
    assert r.returncode == 0, (r.stdout.decode() + r.stderr.decode())[-800:]
    vals = [float(v) for v in r.stdout.split()]
    assert len(vals) == 6 and abs(sum(vals) - 2.0) < 1e-2
