"""Predictor (c_predict_api analogue) + env-flag config registry."""
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import config, nd


def _train_and_save(tmp_path, prefix="model"):
    np.random.seed(0)
    mx.random.seed(0)
    X = np.random.randn(64, 8).astype(np.float32)
    Y = (X.sum(1) > 0).astype(np.float32)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    it = mx.io.NDArrayIter(X, Y, batch_size=32)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, optimizer="sgd", optimizer_params={"learning_rate": 0.5},
            initializer=mx.init.Xavier(), num_epoch=5)
    p = str(tmp_path / prefix)
    mod.save_checkpoint(p, 5)
    return p, X, Y, mod


def test_predictor_from_checkpoint(tmp_path):
    prefix, X, Y, mod = _train_and_save(tmp_path)
    pred = mx.Predictor.from_checkpoint(prefix, 5,
                                        {"data": (32, 8)})
    probs = pred.predict(X[:32])
    assert probs.shape == (32, 2)
    acc = (probs.argmax(1) == Y[:32]).mean()
    assert acc > 0.9, acc
    # matches the training module's own forward
    val = mx.io.NDArrayIter(X[:32], None, batch_size=32)
    ref = mod.predict(val).asnumpy()
    np.testing.assert_allclose(probs, ref, rtol=1e-5, atol=1e-6)


def test_predictor_buffer_signature(tmp_path):
    """MXPredCreate-shaped: JSON string + params bytes, not files."""
    prefix, X, _, _ = _train_and_save(tmp_path, "buf")
    sym_json = open(prefix + "-symbol.json").read()
    param_bytes = open(prefix + "-0005.params", "rb").read()
    pred = mx.Predictor(sym_json, param_bytes, {"data": (8, 8)})
    out = pred.predict(X[:8])
    assert out.shape == (8, 2)
    np.testing.assert_allclose(out.sum(1), 1.0, rtol=1e-5)


def test_predictor_set_input_forward_get_output(tmp_path):
    prefix, X, _, _ = _train_and_save(tmp_path, "stepwise")
    pred = mx.Predictor.from_checkpoint(prefix, 5, {"data": (4, 8)})
    pred.set_input("data", X[:4])
    pred.forward()
    out = pred.get_output(0)
    assert out.shape == (4, 2)
    import pytest
    with pytest.raises(mx.base.MXNetError):
        pred.set_input("nonexistent", X[:4])


def test_predictor_reshape(tmp_path):
    prefix, X, _, _ = _train_and_save(tmp_path, "reshape")
    pred = mx.Predictor.from_checkpoint(prefix, 5, {"data": (4, 8)})
    a = pred.predict(X[:4])
    pred.reshape({"data": (16, 8)})
    b = pred.predict(X[:16])
    assert b.shape == (16, 2)
    np.testing.assert_allclose(a, b[:4], rtol=1e-5, atol=1e-6)


def test_config_flag_resolution(monkeypatch):
    assert config.flag("MXTPU_NUM_WORKERS") == 1
    monkeypatch.setenv("MXTPU_NUM_WORKERS", "64")
    assert config.flag("MXTPU_NUM_WORKERS") == 64
    # alias name resolves too
    monkeypatch.setenv("MXTPU_PROFILER_AUTOSTART", "1")
    assert config.flag("MXNET_PROFILER_AUTOSTART") == 1
    import pytest
    with pytest.raises(KeyError):
        config.flag("MXTPU_NOT_A_FLAG")
    text = config.describe()
    assert "MXTPU_ATTENTION_IMPL" in text
    assert "MXNET_BACKWARD_DO_MIRROR" in text  # absorbed table present


def test_config_drives_attention_impl(monkeypatch):
    from mxnet_tpu.parallel.ring_attention import default_attention_impl
    monkeypatch.setenv("MXTPU_ATTENTION_IMPL", "xla")
    assert default_attention_impl() == "xla"
    monkeypatch.setenv("MXTPU_ATTENTION_IMPL", "flash")
    assert default_attention_impl() == "flash"
