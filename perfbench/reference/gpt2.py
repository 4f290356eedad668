"""Plain GPT-2 (Radford et al. 2019): the forward pass and the
next-token loss in straightforward ``jax.numpy``, float32, matmul
precision "highest".  No kernel, no cache, no batching tricks: the
yardstick that decides ``correct``.

Weights are a dict in the published layout (``c_attn`` as separate q, k
and v matrices, every matrix ``[out, in]``).  ``weights_from_net`` is the
one place that knows how the program under test stores the same numbers
(a fused, head-major qkv matrix); the only departure from the published
model is the vocabulary the configuration file states (padded rows take
part in the softmax exactly as they do in the program).
"""
import jax
import jax.numpy as jnp


def _ln(x, g, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def forward(w, tokens, n_head):
    """tokens int[B, T] -> logits float32[B, T, V]."""
    with jax.default_matmul_precision("highest"):
        b, t = tokens.shape
        x = w["wte"][tokens] + w["wpe"][:t]
        d = x.shape[-1] // n_head
        mask = jnp.tril(jnp.ones((t, t), bool))
        for blk in w["blocks"]:
            h = _ln(x, blk["ln1_g"], blk["ln1_b"])

            def heads(name):
                y = h @ blk[name + "_w"].T + blk[name + "_b"]
                return y.reshape(b, t, n_head, d).transpose(0, 2, 1, 3)
            q, k, v = heads("q"), heads("k"), heads("v")
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(float(d))
            s = jnp.where(mask, s, -jnp.inf)
            a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
            a = a.transpose(0, 2, 1, 3).reshape(b, t, -1)
            x = x + a @ blk["proj_w"].T + blk["proj_b"]
            h = _ln(x, blk["ln2_g"], blk["ln2_b"])
            h = _gelu_new(h @ blk["fc_w"].T + blk["fc_b"])
            x = x + h @ blk["fc2_w"].T + blk["fc2_b"]
        x = _ln(x, w["lnf_g"], w["lnf_b"])
        return x @ w["wte"].T


def loss(w, x, y, n_head):
    """Mean next-token cross-entropy of targets ``y`` given ``x``."""
    logp = jax.nn.log_softmax(forward(w, x, n_head), -1)
    return -jnp.take_along_axis(logp, y[..., None], -1).mean()


def weights_from_net(net):
    """The reference's weight dict from a ``gluon.model_zoo.gpt.GPTLM``
    (float32 copies of the live parameter values)."""
    def g(p):
        return jnp.asarray(p.data()._data, jnp.float32)

    n_head = net.blocks._children[0].attn._num_heads
    blocks = []
    for blk in net.blocks._children:
        qkv_w, qkv_b = g(blk.attn.qkv.weight), g(blk.attn.qkv.bias)
        c = qkv_w.shape[1]
        d = c // n_head
        qkv_w = qkv_w.reshape(n_head, 3, d, c)
        qkv_b = qkv_b.reshape(n_head, 3, d)
        layer = {"ln1_g": g(blk.ln1.gamma), "ln1_b": g(blk.ln1.beta),
                 "ln2_g": g(blk.ln2.gamma), "ln2_b": g(blk.ln2.beta),
                 "proj_w": g(blk.attn.out_proj.weight),
                 "proj_b": g(blk.attn.out_proj.bias),
                 "fc_w": g(blk.fc1.weight), "fc_b": g(blk.fc1.bias),
                 "fc2_w": g(blk.fc2.weight), "fc2_b": g(blk.fc2.bias)}
        for i, name in enumerate("qkv"):
            layer[name + "_w"] = qkv_w[:, i].reshape(n_head * d, c)
            layer[name + "_b"] = qkv_b[:, i].reshape(n_head * d)
        blocks.append(layer)
    return {"wte": g(net.wte), "wpe": g(net.wpe),
            "lnf_g": g(net.ln_f.gamma), "lnf_b": g(net.ln_f.beta),
            "blocks": blocks}, n_head
