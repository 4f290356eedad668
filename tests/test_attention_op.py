"""Attention op + gluon layer through the in-process (xla-impl) path.

The Pallas-kernel impl of the same op is exercised by
tests/flash_attention_driver.py check_op_and_layer_flash.
"""
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd


def _rand(shape, seed):
    return np.random.RandomState(seed).uniform(-1, 1, shape).astype(
        np.float32)


def _oracle(q, k, v, causal):
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        t = q.shape[2]
        s = np.where(np.tril(np.ones((t, t), bool)), s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, v)


def test_attention_op_matches_oracle():
    q, k, v = (_rand((2, 2, 16, 8), i) for i in range(3))
    for causal in (False, True):
        out = getattr(nd, "_contrib_flash_attention")(
            nd.array(q), nd.array(k), nd.array(v), causal=causal)
        np.testing.assert_allclose(out.asnumpy(),
                                   _oracle(q, k, v, causal),
                                   rtol=1e-5, atol=1e-5)


def test_attention_symbol_and_alias():
    qs, ks, vs = (mx.sym.Variable(n) for n in "qkv")
    out = mx.sym.flash_attention(qs, ks, vs, causal=True)
    exe = out.simple_bind(mx.cpu(), grad_req="null",
                          q=(1, 2, 8, 4), k=(1, 2, 8, 4), v=(1, 2, 8, 4))
    assert exe.forward()[0].shape == (1, 2, 8, 4)


def test_flash_self_attention_layer_trains():
    np.random.seed(0)
    mx.random.seed(0)
    layer = gluon.nn.FlashSelfAttention(units=16, num_heads=4, causal=True)
    layer.initialize(mx.init.Xavier())
    x = nd.array(_rand((2, 12, 16), 9))
    trainer = gluon.Trainer(layer.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    with autograd.record():
        y = layer(x)
        loss = (y * y).sum()
    loss.backward()
    trainer.step(2)
    assert y.shape == (2, 12, 16)
    g = list(layer.collect_params().values())[0].grad()
    assert float(np.abs(g.asnumpy()).sum()) > 0
