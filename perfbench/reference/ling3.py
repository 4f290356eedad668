"""Plain reference of the language model of Ling-3.0-flash-VL
(https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/blob/main/config.json):
the forward pass in straightforward ``jax.numpy``, float32, matmul
precision "highest".  No kernel, no cache, no chunking, no batching: KDA
runs token by token, MLA in its plain (non-absorbed) form, the experts
in a loop.  The yardstick that decides ``correct``.

Layer (pre-norm residual, eps 1e-6)::

    h = x + Mix_l(RMSNorm(x));  y = h + FFN_l(RMSNorm(h))

``Mix_l`` is MLA where ``(l + 1) % layer_group_size == 0``, else KDA;
``FFN_l`` is a dense SwiGLU for ``l < first_k_dense_replace``, else the
routed experts plus one shared expert.  Final RMSNorm, untied head.

**The share.**  ``cfg["experts_held"] = [first, count]``: the router
scores, groups and normalises over ALL ``num_experts``; only the terms
of held experts are added (their weights stay as normalised over all 8
chosen), the shared expert is added in full.  ``cfg["layers"]`` lists
the published layer indices that are kept.  A sliced vocabulary is a
smaller vocabulary.

**Readings of keys the published config leaves open** (the file's
``assumed`` lists the same):

- attention kind: MLA at ``(l + 1) % 6 == 0`` (last layer of a group);
- expert bias ``b``: zeros (it is learned by load balancing, not by
  the loss); selection on ``s + b``, weights from ``s``;
- a group's score is the sum of its top 2 ``s + b`` (DeepSeek-V3 form);
- MLA: ``use_qk_norm`` is a per-head RMSNorm over the 192 query values
  before RoPE; the key side has the latent's RMSNorm only (which keeps
  decode absorbable); the output gate is one sigmoid scalar a head
  (``gated_attention_proj_granularity_type`` "head_wise"); RoPE pairs
  are (i, i + 32) of the 64 rope dims (rotate-half), theta 6e6;
- KDA: 32 heads, key and value size 128 (``head_dim``); decay
  ``log a = kda_lower_bound * sigmoid(exp(A_h) * (W_f x + d))`` with
  ``kda_lower_bound`` -5 (the FLA / Kimi ``gate_lower_bound`` form);
  ``beta = sigmoid(w_b . x)`` a head; L2 norm with eps 1e-6 inside the
  root; the short convolution has no bias and is followed by SiLU;
  output RMSNorm a head (``group_norm_size`` 1) with one gain vector,
  then the head-wise sigmoid gate;
- weights: normal(0, 0.02), norm gains 1, ``A_h`` and ``d`` normal(0,
  0.02) like every other weight, stored bfloat16; this file upcasts the
  same rounded values.

**Departures from the published model**: no vision tower (the catalog's
config holds the language model only), no multi-token-prediction head,
and the swiglu limits are 0 (no clamp) in every layer that is kept.

**Routing near ties.**  bfloat16 activations can flip the 8th and 9th
expert against this file's float32 scores.  ``forward`` takes the
system's chosen experts (``sys_experts``); where they differ from its
own choice it adopts them ONLY if every expert of the difference is
ambiguous under its own scores: within ``delta`` of the 8th selection
score, or in a group within ``delta`` of the group boundary.  Any other
difference is reported (``routing_mismatch``) and fails the comparison.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6


#: set while a layer is traced with ``compute_as``: every matrix and
#: every matmul's input is rounded to that type first (the reading "in
#: the nearest precision below" that a cell's limits have to refuse)
_ROUND = [None]


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _dot(x, w):
    """``x @ w`` in float32; under ``compute_as`` both operands are
    rounded to that type first."""
    w = jnp.asarray(w)
    if _ROUND[0] is not None:
        x, w = x.astype(_ROUND[0]), w.astype(_ROUND[0])
    return _f32(x) @ _f32(w)


def _rms(x, g):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + EPS) * g


def _rope(x, pos, theta):
    """Rotate-half RoPE on the last axis (64); ``pos`` [T]."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]      # [T, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if x.ndim == 3:                                            # [T, H, 64]
        cos, sin = cos[:, None, :], sin[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(x, gu_w, down_w):
    gu = _dot(x, gu_w)
    half = gu.shape[-1] // 2
    return _dot(jax.nn.silu(gu[..., :half]) * gu[..., half:], down_w)


def mla(lp, x, cfg):
    """Plain multi-head latent attention over a whole sequence [T, C]."""
    t = x.shape[0]
    h = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    pos = jnp.arange(t)
    q = _dot(x, lp["q_w"]).reshape(t, h, dn + dr)
    q = _rms(q, _f32(lp["q_norm_g"]))
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], pos, cfg["rope_theta"])
    kva = _dot(x, lp["kva_w"])
    c = _rms(kva[:, :rank], _f32(lp["kv_norm_g"]))
    k_rope = _rope(kva[:, rank:], pos, cfg["rope_theta"])      # [T, 64]
    kv = _dot(c, lp["kvb_w"]).reshape(t, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
         + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)) / float(np.sqrt(dn + dr))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    gate = jax.nn.sigmoid(_dot(x, lp["g_w"]))                  # [T, H]
    return _dot((o * gate[..., None]).reshape(t, h * dv), lp["o_w"])


def kda(lp, x, cfg):
    """Kimi delta attention, token by token, over a sequence [T, C]."""
    t = x.shape[0]
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    width = cfg["short_conv_kernel_size"]
    qkv = _dot(x, lp["qkv_w"])                                # [T, 3HD]
    padded = jnp.concatenate(
        [jnp.zeros((width - 1, qkv.shape[1]), jnp.float32), qkv])
    conv_w = _f32(lp["conv_w"])                                # [width, 3HD]
    qkv = jax.nn.silu(sum(padded[i:i + t] * conv_w[i]
                          for i in range(width)))
    q, k, v = (a.reshape(t, h, d) for a in jnp.split(qkv, 3, -1))
    q = q * jax.lax.rsqrt((q * q).sum(-1, keepdims=True) + EPS) / float(np.sqrt(d))
    k = k * jax.lax.rsqrt((k * k).sum(-1, keepdims=True) + EPS)
    f = (_dot(x, lp["f_w"]) + _f32(lp["f_b"])).reshape(t, h, d)
    log_a = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(_f32(lp["a_log"]))[None, :, None] * f)
    a = jnp.exp(log_a)                                         # [T, H, D]
    beta = jax.nn.sigmoid(_dot(x, lp["b_w"]))                 # [T, H]

    def step(state, inp):                                      # [H, Dk, Dv]
        q_t, k_t, v_t, a_t, b_t = inp
        state = state * a_t[:, :, None]
        resid = v_t - jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + jnp.einsum("hk,hv->hkv", k_t,
                                   b_t[:, None] * resid)
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((h, d, d), jnp.float32),
                        (q, k, v, a, beta))
    o = _rms(o, _f32(lp["o_norm_g"]))
    gate = jax.nn.sigmoid(_dot(x, lp["g_w"]))
    return _dot((o * gate[..., None]).reshape(t, h * d), lp["o_w"])


def route(scores, bias, cfg):
    """The published router on sigmoid scores ``[T, E]``: returns the
    chosen experts ``[T, k]`` and, per token, what the near-tie rule
    needs (selection scores masked to the kept groups, the 8th of them,
    group scores and their 4th and 5th)."""
    t, e = scores.shape
    n_group, topk_group, k = (cfg["n_group"], cfg["topk_group"],
                              cfg["num_experts_per_tok"])
    sel = scores + bias
    per = sel.reshape(t, n_group, e // n_group)
    gs = jax.lax.top_k(per, 2)[0].sum(-1)                      # [T, G]
    g_sorted = jnp.sort(gs, -1)[:, ::-1]
    g4, g5 = g_sorted[:, topk_group - 1], g_sorted[:, topk_group]
    kept = jnp.zeros((t, n_group), bool).at[
        jnp.arange(t)[:, None], jax.lax.top_k(gs, topk_group)[1]].set(True)
    masked = jnp.where(jnp.repeat(kept, e // n_group, -1), sel, -jnp.inf)
    top, idx = jax.lax.top_k(masked, k + 1)
    return idx[:, :k], {"sel": sel, "t8": top[:, k - 1], "gs": gs,
                        "g4": g4, "g5": g5,
                        "margin": jnp.minimum(top[:, k - 1] - top[:, k],
                                              g4 - g5)}


def _adopt(idx, info, sys_idx, cfg, delta):
    """The near-tie rule (module docstring): the system's choice where
    it differs only by ambiguous experts.  Returns ``(chosen [T, k],
    adopted [T] bool, mismatch [T] bool)``."""
    t, e = info["sel"].shape
    per_group = e // cfg["n_group"]
    hot = lambda i: jnp.zeros((t, e), bool).at[
        jnp.arange(t)[:, None], i].set(True)
    diff = hot(idx) ^ hot(sys_idx)
    amb_expert = jnp.abs(info["sel"] - info["t8"][:, None]) <= delta
    edge = (info["g4"] - info["g5"] <= delta)[:, None] & (
        (jnp.abs(info["gs"] - info["g4"][:, None]) <= delta)
        | (jnp.abs(info["gs"] - info["g5"][:, None]) <= delta))
    ambiguous = amb_expert | jnp.repeat(edge, per_group, -1)
    differs = diff.any(-1)
    ok = ~(diff & ~ambiguous).any(-1)
    adopted = differs & ok
    # the smallest delta that would have adopted this position's
    # difference (0 where the choices agree): what delta is set from
    by_group = jnp.maximum(
        (info["g4"] - info["g5"])[:, None],
        jnp.minimum(jnp.abs(info["gs"] - info["g4"][:, None]),
                    jnp.abs(info["gs"] - info["g5"][:, None])))
    need = jnp.minimum(jnp.abs(info["sel"] - info["t8"][:, None]),
                       jnp.repeat(by_group, per_group, -1))
    need = jnp.where(diff, need, 0.0).max(-1)
    return (jnp.where(adopted[:, None], sys_idx, idx), adopted,
            differs & ~ok, need)


def moe(lp, x, cfg, sys_idx=None, delta=0.0, block=8):
    """Routed experts (this chip's share) + the shared expert over
    tokens ``[T, C]``.  Returns ``(y, routing doc)``."""
    t = x.shape[0]
    e = cfg["num_experts"]
    first, count = cfg["experts_held"]
    scores = jax.nn.sigmoid(x @ _f32(lp["router_w"]))          # [T, E]
    idx, info = route(scores, _f32(lp["router_b"]), cfg)
    adopted = mismatch = jnp.zeros((t,), bool)
    need = jnp.zeros((t,), jnp.float32)
    if sys_idx is not None:
        idx, adopted, mismatch, need = _adopt(idx, info, sys_idx, cfg,
                                              delta)
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / w.sum(-1, keepdims=True) * cfg["routed_scaling_factor"]
    dense = jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], idx].set(w)[:, first:first + count]

    block = min(block, count)
    def one_block(y, b):
        # `block` experts at a time: the upcast weights of one block are
        # all that is held beside the activations
        gu = jax.lax.dynamic_slice_in_dim(lp["gu_w"], b * block, block)
        dn = jax.lax.dynamic_slice_in_dim(lp["down_w"], b * block, block)
        wb = jax.lax.dynamic_slice_in_dim(dense, b * block, block, 1)
        for j in range(block):                      # a loop over experts
            y = y + wb[:, j:j + 1] * _swiglu(x, gu[j], dn[j])
        return y, None

    assert count % block == 0, (count, block)
    y, _ = jax.lax.scan(one_block, jnp.zeros_like(x),
                        jnp.arange(count // block))
    y = y + _swiglu(x, lp["sh_gu_w"], lp["sh_down_w"])
    return y, {"experts": idx, "adopted": adopted, "mismatch": mismatch,
               "need": need,
               "margin": info["margin"]}


@functools.partial(jax.jit, static_argnames=("kind", "cfg_key", "delta",
                                             "compute_as"))
def _layer(lp, x, sys_idx, kind, cfg_key, delta, compute_as=None):
    cfg = _CFGS[cfg_key]
    mix, ffn = kind
    _ROUND[0] = compute_as
    try:
        return _layer_body(lp, x, sys_idx, mix, ffn, cfg, delta)
    finally:
        _ROUND[0] = None


def _layer_body(lp, x, sys_idx, mix, ffn, cfg, delta):
    with jax.default_matmul_precision("highest"):
        h = _rms(x, _f32(lp["ln1_g"]))
        x = x + (mla(lp["mla"], h, cfg) if mix == "mla"
                 else kda(lp["kda"], h, cfg))
        h = _rms(x, _f32(lp["ln2_g"]))
        if ffn == "dense":
            return x + _swiglu(h, lp["mlp"]["gu_w"],
                               lp["mlp"]["down_w"]), None
        y, doc = moe(lp["moe"], h, cfg, sys_idx, delta)
        return x + y, doc


_CFGS = {}


def layer_kinds(cfg):
    """``(mix, ffn)`` of every kept layer, by its PUBLISHED index."""
    return [("mla" if (l + 1) % cfg["layer_group_size"] == 0 else "kda",
             "dense" if l < cfg["first_k_dense_replace"] else "moe")
            for l in cfg["layers"]]


def forward(w, tokens, cfg, sys_experts=None, delta=0.0, rows=None,
            compute_as=None):
    """tokens int[T] -> ``(logits float32[T or len(rows), V], routing)``.

    ``w`` is the parameter tree as the program stores it (bfloat16
    leaves are upcast here, layer by layer and, for the experts, block
    by block, so that at the published widths the reference never holds
    more than one layer's activations and one block of experts in
    float32).  ``sys_experts``: the system's chosen experts, one
    ``[T, k]`` array per MoE layer, for the near-tie rule; ``rows``:
    the positions whose logits are wanted (all by default).
    ``routing`` is one doc per MoE layer.  ``compute_as``: a dtype name
    every matrix and every matmul's input is rounded to first (the
    router, the embedding and the head stay float32): the same forward
    computed in a lower precision."""
    key = repr(sorted((k, repr(v)) for k, v in cfg.items()))
    _CFGS[key] = cfg
    x = _f32(w["wte"][jnp.asarray(tokens)])
    routing, m = [], 0
    for lp, kind in zip(w["layers"], layer_kinds(cfg)):
        sys_idx = None
        if kind[1] == "moe" and sys_experts is not None:
            sys_idx = jnp.asarray(sys_experts[m], jnp.int32)
        x, doc = _layer(lp, x, sys_idx, kind, key, float(delta),
                        compute_as)
        if doc is not None:
            routing.append(doc)
            m += 1
    with jax.default_matmul_precision("highest"):
        x = _rms(x, _f32(w["lnf_g"]))
        if rows is not None:
            x = x[jnp.asarray(rows)]
        return x @ _f32(w["head"]).T, routing
