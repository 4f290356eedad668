"""Plain reference of GigaChat3.5's language model, ``model_type``
``gigachat3_5``
(https://huggingface.co/ai-sage/GigaChat3.5-432B-A28B/blob/main/config.json):
the forward pass in straightforward ``jax.numpy``, float32, matmul
precision "highest".  No kernel, no cache, no batching: the gated delta
rule runs TOKEN BY TOKEN (not in the chunked form the program uses),
latent attention in its plain form (keys and values expanded from the
latent, not absorbed), the experts in a loop.  The yardstick that decides
``correct``.

A layer's norms and the final one are gated RMSNorm ``N(x; w) = x /
rms(x) 2 sigmoid(w)`` (``norm_type`` "ZeroCenteredGatedNorm", the factor
``layernorm_gating_weight`` 2); the latent's query and key norms and the
delta rule's output norm are zero-centred, ``x / rms(x) (1 + w)``; eps
1e-6.  Layer ``l`` (``layernorm_type`` "pre_post")::

    h = x + N_2(Mix_l(N_1(x)));   y = h + N_4(FFN_l(N_3(h)))

- ``Mix_l`` for ``l`` in ``full_attention_layers`` (latent attention):
  ``q = W_qb N(W_qa x)`` [64, 128 + 64], ``[c | k_r] = W_kva x`` (``c``
  normed), ``[k_nope | v] = W_kvb c``; rotary positions on the last 64
  query values and on ``k_r`` in interleaved pairs ``(2i, 2i + 1)`` at
  YaRN's frequencies (theta 1e5, factor 8, original 32,768, beta 32 / 1);
  ``a = softmax(q . [k_nope | k_r] 192^-1/2 m^2)`` causal, ``m = 0.1 ln 8
  + 1``; ``out = W_o [(a v) * sigmoid(W_g x)]``;
- otherwise the gated delta rule (``GigaChat35GatedDeltaNet``): ``raw =
  x W_qkv`` -> depthwise causal convolution of width 4 (no bias) -> SiLU
  -> ``q`` (32 x 128), ``k`` (32 x 128), ``v`` (64 x 128); ``q``, ``k``
  L2-normalised, ``q`` times 128^-1/2; ``beta_h = sigmoid(x . b_h)``,
  ``g_h = -exp(A_h) softplus(x . a_h + dt_h)``; value head ``h`` with key
  head ``j = h // 2``, one token at a time::

      S_h <- exp(g_h) S_h;  S_h <- S_h + k_j (beta_h (v_h - S_h^T k_j))^T;
      o_h = S_h^T q_j

  ``out = W_o [(o_h / rms(o_h) (1 + w_o)) 2 sigmoid(z_h)]``, ``z = x
  W_z``;
- ``FFN_l``: a dense SwiGLU of width 18,432 for ``l < 3``, else one
  shared expert (no gate) plus the routed experts: ``s = sigmoid(x W_r)``,
  the 8 largest ``s + b`` chosen, weights ``2.5 s_e / sum of the chosen
  s``.  Every SwiGLU is ``silu(min(gate, 10)) * clip(up, -10, 10)``.

After the last layer a norm and the untied head.

**The share.**  ``cfg["experts_held"] = [first, count]``: the router
scores all ``num_experts``; only the held experts' terms are added, the
shared expert in full.  ``cfg["layers"]`` lists the published layers
kept.  A sliced vocabulary is a smaller vocabulary.

**Readings of what config.json leaves open** (the configuration file's
``assumed`` lists the same): the gated ``2 sigmoid(w)`` gains of the
layer norms and the zero-centred ``(1 + w)`` ones of the others; the
latent layer's gate a value, on the normed input; the sigmoid router
with a correction bias ``b`` (zeros) that chooses only; the SwiGLU clamp
in every SwiGLU (experts, shared and dense).  No multi-token-prediction
module.  Weights: normal(0, 0.02) for every matrix, ``A_h`` and ``dt_h``;
norm gains 0 (a gain of 1); stored bfloat16, upcast here a block of
columns or an expert at a time.

**In blocks, so that 32k positions fit beside a loaded engine.**  The
residual stream stays on the host; a layer runs ``TIME_BLOCK`` positions
a call, and a delta-rule layer carries its state and its convolution's
last inputs from one call to the next (the recurrence is still one token
at a time).  Latent attention scores ``QUERY_ROWS`` queries and
``HEAD_GROUP`` heads at a time against every key.  A short sequence is
one block.

**Near ties.**  bfloat16 activations can flip the 8th and 9th expert.
``forward`` takes the system's choices (``sys_experts``) where every
expert of the difference lies within ``route_delta`` of the 8th selection
score under its OWN scores; any other difference is a mismatch.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

#: a weight matrix is upcast at most this many elements at a time
BLOCK_ELEMS = 48 * 1024 * 1024
#: rows of a SwiGLU's hidden activation at a time
ROW_BLOCK = 512
#: positions a call of a layer
TIME_BLOCK = 2048
#: query rows and heads of one score block of the latent attention
QUERY_ROWS = 256
HEAD_GROUP = 8

#: set while a layer is traced with ``compute_as``: every matrix and
#: every matmul's input is rounded to that type first (the reading "in
#: the nearest precision below" that a cell's limits have to refuse)
_ROUND = [None]
_CFGS = {}


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rounded(a):
    return a if _ROUND[0] is None else a.astype(_ROUND[0])


def _dot(x, w):
    """``x @ w`` in float32, the matrix upcast a block of columns at a
    time; under ``compute_as`` both operands are rounded first."""
    w = jnp.asarray(w)
    x = _f32(_rounded(x))
    rows, cols = w.shape
    n = 1
    while rows * (cols // n) > BLOCK_ELEMS or cols % n:
        n += 1

    def one(i):
        wb = jax.lax.dynamic_slice_in_dim(w, i * (cols // n), cols // n, 1)
        return x @ _f32(_rounded(wb))

    if n == 1:
        return one(0)
    out = jax.lax.map(one, jnp.arange(n))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], cols)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * (1.0 + _f32(w))


def _layer_norm(x, w, cfg):
    """A layer's norm or the final one: ``x / rms(x) *
    layernorm_gating_weight * sigmoid(w)``."""
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True)
                             + cfg["rms_norm_eps"]) \
        * (cfg["layernorm_gating_weight"] * jax.nn.sigmoid(_f32(w)))


def yarn_inv_freq(cfg):
    """YaRN's rotary frequencies for the 64 rotary values: the plain
    ``theta ** (-i / 32)`` where a pair turns more than ``beta_fast``
    times over the original context, divided by ``factor`` where fewer
    than ``beta_slow``, a linear ramp between."""
    rs = cfg["rope_scaling"]
    dim, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    orig = rs["original_max_position_embeddings"]
    half = dim // 2
    base = theta ** (-np.arange(half, dtype=np.float64) / half)

    def dim_of(turns):
        return dim * np.log(orig / (turns * 2 * np.pi)) / (2 * np.log(theta))

    low = max(np.floor(dim_of(rs["beta_fast"])), 0)
    high = min(np.ceil(dim_of(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return (base * (1 - ramp) + base / rs["factor"] * ramp).astype(np.float32)


def _rope(x, pos, cfg):
    """Interleaved rotary positions on the last axis of ``x``: pair
    ``(2i, 2i + 1)`` turns by ``pos * inv_freq[i]``."""
    ang = pos.astype(jnp.float32)[:, None] \
        * jnp.asarray(yarn_inv_freq(cfg))[None, :]
    if x.ndim == 3:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1) \
        .reshape(x.shape)


def softmax_scale(cfg):
    rs = cfg["rope_scaling"]
    m = 0.1 * rs["mscale_all_dim"] * np.log(rs["factor"]) + 1.0
    return float((cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
                 * m * m)


def _swiglu(x, gu_w, down_w, limit):
    def rows(xb):
        gu = _dot(xb, gu_w)
        half = gu.shape[-1] // 2
        gate, up = gu[..., :half], gu[..., half:]
        if limit is not None:
            gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
        return _dot(jax.nn.silu(gate) * up, down_w)

    t = x.shape[0]
    r = min(ROW_BLOCK, t)
    n = -(-t // r)
    if n == 1:
        return rows(x)
    xp = jnp.pad(x, ((0, n * r - t), (0, 0)))
    return jax.lax.map(rows, xp.reshape(n, r, -1)).reshape(n * r, -1)[:t]


# -- the gated delta rule, token by token -------------------------------------

def delta_rule(q, k, v, g, beta, state):
    """The recurrence one token at a time: ``q, k`` [T, Hk, D], ``v`` [T,
    Hv, Dv], ``g, beta`` [T, Hv], ``state`` [Hv, D, Dv].  Returns ``(o
    [T, Hv, Dv], final state)``."""
    group = v.shape[1] // q.shape[1]

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        kk, qq = jnp.repeat(k_t, group, 0), jnp.repeat(q_t, group, 0)
        s = s * jnp.exp(g_t)[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, kk))
        s = s + kk[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qq)

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def gated_delta_net(lp, x, state, tail, cfg):
    """The mixer over a block of positions ``x`` [T, C] (already normed)
    from ``state`` and the raw inputs ``tail`` [3, C_qkv] before it.
    Returns ``(out [T, C], state, new tail)``."""
    t = x.shape[0]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    width = cfg["linear_conv_kernel_dim"]
    raw = jnp.concatenate([tail, _dot(x, lp["qkv_w"])])
    conv_w = _f32(lp["conv_w"])
    qkv = jax.nn.silu(sum(raw[i:i + t] * conv_w[i] for i in range(width)))
    q = qkv[:, :hk * dk].reshape(t, hk, dk)
    k = qkv[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)
    v = qkv[:, 2 * hk * dk:].reshape(t, hv, dv)
    q = q * jax.lax.rsqrt((q * q).sum(-1, keepdims=True) + 1e-6) \
        * float(dk ** -0.5)
    k = k * jax.lax.rsqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    q, k, v = (_f32(_rounded(a)) for a in (q, k, v))
    beta = jax.nn.sigmoid(_dot(x, lp["b_w"]))
    g = -jnp.exp(_f32(lp["a_log"])) * jax.nn.softplus(
        _dot(x, lp["a_w"]) + _f32(lp["dt_bias"]))
    o, state = delta_rule(q, k, v, g, beta, state)
    z = _dot(x, lp["z_w"]).reshape(t, hv, dv)
    o = _norm(o, lp["o_norm_g"], cfg["linear_attn_o_norm_eps"]) \
        * cfg["linear_sigmoid_gate_scale"] * jax.nn.sigmoid(z)
    return _dot(o.reshape(t, hv * dv), lp["o_w"]), state, raw[t:]


# -- latent attention ---------------------------------------------------------

def latent(lp, x, pos, cfg):
    """What a position leaves for the keys: the normed latent ``c`` [T,
    rank] and the rotated ``k_r`` [T, 64]."""
    rank = cfg["kv_lora_rank"]
    kva = _dot(x, lp["kva_w"])
    return (_norm(kva[:, :rank], lp["kv_norm_g"], cfg["rms_norm_eps"]),
            _rope(kva[:, rank:], pos, cfg))


def latent_attention(lp, x, pos, c_all, kr_all, cfg):
    """Queries of the block ``x`` [T, C] at positions ``pos`` over the
    keys of every position (``c_all``, ``kr_all`` [N, .]), causal: keys
    and values expanded from the latent a group of heads at a time, the
    queries ``QUERY_ROWS`` at a time.  Returns ``out`` [T, C]."""
    t = x.shape[0]
    n = c_all.shape[0]
    nh = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps = cfg["rms_norm_eps"]
    q = _dot(_norm(_dot(x, lp["q_a_w"]), lp["q_a_norm_g"], eps),
             lp["q_b_w"]).reshape(t, nh, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, cfg)], -1)
    q = _f32(_rounded(q))
    kr = _f32(_rounded(kr_all))
    hg = min(HEAD_GROUP, nh)
    assert nh % hg == 0, (nh, hg)
    rows = next(r for r in range(min(QUERY_ROWS, t), 0, -1) if t % r == 0)
    kvb = jnp.asarray(lp["kvb_w"]).reshape(-1, nh, dn + dv)
    scale = softmax_scale(cfg)

    def group(j):
        w = jax.lax.dynamic_slice_in_dim(kvb, j * hg, hg, 1)
        kv = _dot(c_all, w.reshape(w.shape[0], -1)).reshape(n, hg, dn + dv)
        k_nope, v = _f32(_rounded(kv[..., :dn])), _f32(_rounded(kv[..., dn:]))
        qg = jax.lax.dynamic_slice_in_dim(q, j * hg, hg, 1)

        def block(b):
            qb = jax.lax.dynamic_slice_in_dim(qg, b * rows, rows, 0)
            pb = jax.lax.dynamic_slice_in_dim(pos, b * rows, rows, 0)
            s = (jnp.einsum("qhd,khd->hqk", qb[..., :dn], k_nope)
                 + jnp.einsum("qhd,kd->hqk", qb[..., dn:], kr)) * scale
            s = jnp.where((jnp.arange(n)[None, :] <= pb[:, None])[None],
                          s, -jnp.inf)
            p = _f32(_rounded(jax.nn.softmax(s, -1)))
            return jnp.einsum("hqk,khd->qhd", p, v)

        o = jax.lax.map(block, jnp.arange(t // rows))    # [b, rows, hg, dv]
        return o.reshape(t, hg, dv)

    o = jax.lax.map(group, jnp.arange(nh // hg))        # [g, T, hg, dv]
    o = jnp.moveaxis(o, 0, 1).reshape(t, nh * dv)
    gate = jax.nn.sigmoid(_dot(x, lp["g_w"]))
    return _dot(o * gate, lp["o_w"])


# -- routed experts -----------------------------------------------------------

def route(scores, bias, k):
    """The router on sigmoid scores ``[T, E]``: the ``k`` largest of
    ``s + b``.  Returns ``(chosen [T, k], s + b, its k-th largest)``."""
    sel = scores + bias
    top, idx = jax.lax.top_k(sel, k)
    return idx, sel, top[:, k - 1]


def _adopt(idx, sel, kth, sys_idx, delta):
    """The near-tie rule: the system's choice where every expert of the
    difference lies within ``delta`` of the k-th selection score.
    Returns ``(chosen, adopted, mismatch, need)``."""
    t, e = sel.shape
    hot = lambda i: jnp.zeros((t, e), bool).at[
        jnp.arange(t)[:, None], i].set(True)
    theirs = hot(jnp.clip(sys_idx, 0, e - 1))
    bad = ((sys_idx < 0) | (sys_idx >= e)).any(-1) \
        | (theirs.sum(-1) != idx.shape[1])
    diff = hot(idx) ^ theirs
    need = jnp.where(diff, jnp.abs(sel - kth[:, None]), 0.0).max(-1)
    differs = diff.any(-1)
    ok = (need <= delta) & ~bad
    adopted = differs & ok
    return (jnp.where(adopted[:, None], sys_idx, idx), adopted,
            (differs & ~ok) | bad, jnp.where(bad, jnp.inf, need))


def moe(lp, x, cfg, sys_idx=None, delta=0.0):
    """Routed experts (this chip's share) + the shared expert over
    tokens ``[T, C]``, an expert at a time.  Returns ``(y, doc)``."""
    t = x.shape[0]
    e = cfg["num_experts"]
    first, count = cfg["experts_held"]
    limit = cfg["swiglu_limit"]
    scores = jax.nn.sigmoid(x @ _f32(lp["router_w"]))
    idx, sel, kth = route(scores, _f32(lp["router_b"]),
                          cfg["num_experts_per_tok"])
    adopted = mismatch = jnp.zeros((t,), bool)
    need = jnp.zeros((t,), jnp.float32)
    if sys_idx is not None:
        idx, adopted, mismatch, need = _adopt(idx, sel, kth, sys_idx, delta)
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / w.sum(-1, keepdims=True) * cfg["routed_scaling_factor"]
    dense = jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], idx].set(w)[:, first:first + count]

    def one_expert(y, j):
        gu = jax.lax.dynamic_index_in_dim(lp["gu_w"], j, keepdims=False)
        dn = jax.lax.dynamic_index_in_dim(lp["down_w"], j, keepdims=False)
        wj = jax.lax.dynamic_slice_in_dim(dense, j, 1, 1)
        return y + wj * _swiglu(x, gu, dn, limit), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), jnp.arange(count))
    y = y + _swiglu(x, lp["sh_gu_w"], lp["sh_down_w"], limit)
    return y, {"experts": idx, "adopted": adopted, "mismatch": mismatch,
               "need": need}


def _ffn(lp, h, cfg, ffn, sys_idx, delta):
    x = _layer_norm(h, lp["ln2_g"], cfg)
    if ffn == "dense":
        f, doc = _swiglu(x, lp["mlp"]["gu_w"], lp["mlp"]["down_w"],
                         cfg["swiglu_limit"]), None
    else:
        f, doc = moe(lp["moe"], x, cfg, sys_idx, delta)
    return h + _layer_norm(f, lp["post"]["ffn_g"], cfg), doc


# -- one call: a layer over a block of positions ------------------------------

@functools.partial(jax.jit, static_argnames=(
    "ffn", "cfg_key", "route_delta", "compute_as"))
def _gdn_block(lp, x, state, tail, sys_idx, ffn, cfg_key, route_delta,
               compute_as):
    cfg = _CFGS[cfg_key]
    _ROUND[0] = compute_as
    try:
        with jax.default_matmul_precision("highest"):
            y, state, tail = gated_delta_net(
                lp["gdn"], _layer_norm(x, lp["ln1_g"], cfg), state, tail,
                cfg)
            h = x + _layer_norm(y, lp["post"]["mix_g"], cfg)
            out, doc = _ffn(lp, h, cfg, ffn, sys_idx, route_delta)
            return out, state, tail, doc
    finally:
        _ROUND[0] = None


@functools.partial(jax.jit, static_argnames=("cfg_key", "compute_as"))
def _latent_block(lp, x, pos, cfg_key, compute_as):
    cfg = _CFGS[cfg_key]
    _ROUND[0] = compute_as
    try:
        with jax.default_matmul_precision("highest"):
            return latent(lp["attn"], _layer_norm(x, lp["ln1_g"], cfg), pos,
                          cfg)
    finally:
        _ROUND[0] = None


@functools.partial(jax.jit, static_argnames=(
    "ffn", "cfg_key", "route_delta", "compute_as"))
def _mla_block(lp, x, pos, c_all, kr_all, sys_idx, ffn, cfg_key,
               route_delta, compute_as):
    cfg = _CFGS[cfg_key]
    _ROUND[0] = compute_as
    try:
        with jax.default_matmul_precision("highest"):
            y = latent_attention(lp["attn"], _layer_norm(x, lp["ln1_g"], cfg),
                                 pos, c_all, kr_all, cfg)
            h = x + _layer_norm(y, lp["post"]["mix_g"], cfg)
            return _ffn(lp, h, cfg, ffn, sys_idx, route_delta)
    finally:
        _ROUND[0] = None


def layer_kinds(cfg):
    return [("mla" if l in cfg["full_attention_layers"] else "gdn",
             "dense" if l < cfg["first_k_dense_replace"] else "moe")
            for l in cfg["layers"]]


def forward(w, tokens, cfg, sys_experts=None, route_delta=0.0, rows=None,
            compute_as=None):
    """tokens int[T] -> ``(logits float32[T or len(rows), V], routing)``.

    ``w`` is the parameter tree as the program stores it (bfloat16 leaves
    are upcast here, a block at a time).  ``sys_experts``: the system's
    chosen experts, one ``[T, k]`` array per MoE layer; ``rows``: the
    positions whose logits are wanted.  ``routing`` is one doc per MoE
    layer (``adopted``, ``mismatch``, ``need`` per position).
    ``compute_as``: a dtype name every matrix and every matmul's input is
    rounded to first (the router, the recurrence's own arithmetic, the
    embedding and the head stay float32)."""
    key = repr(sorted((k, repr(v)) for k, v in cfg.items()))
    _CFGS[key] = cfg
    tokens = np.asarray(tokens)
    t = tokens.size
    step = min(TIME_BLOCK, t)
    n_blocks = -(-t // step)
    padded = n_blocks * step
    x = np.zeros((padded, cfg["hidden_size"]), np.float32)
    x[:t] = np.asarray(_f32(w["wte"][jnp.asarray(tokens)]))
    pos = jnp.arange(padded, dtype=jnp.int32)
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    width = 2 * hk * dk + hv * dv
    routing, m = [], 0
    for lp, (mix, ffn) in zip(w["layers"], layer_kinds(cfg)):
        sys_idx = None
        if ffn == "moe" and sys_experts is not None:
            sys_idx = np.zeros((padded, cfg["num_experts_per_tok"]),
                               np.int32)
            sys_idx[:t] = np.asarray(sys_experts[m], np.int32)
            # a pad row takes the reference's own choice: its doc is cut
            sys_idx[t:] = -1
            m += 1
        out, docs = np.empty_like(x), []
        if mix == "mla":
            lat = [_latent_block(lp, jnp.asarray(x[b:b + step]),
                                 pos[b:b + step], key, compute_as)
                   for b in range(0, padded, step)]
            c_all = jnp.concatenate([a for a, _ in lat])
            kr_all = jnp.concatenate([b for _, b in lat])
            del lat
        else:
            state = jnp.zeros((hv, dk, dv), jnp.float32)
            tail = jnp.zeros((cfg["linear_conv_kernel_dim"] - 1, width),
                             jnp.float32)
        for b in range(0, padded, step):
            xb = jnp.asarray(x[b:b + step])
            sb = None if sys_idx is None else jnp.asarray(sys_idx[b:b + step])
            if mix == "mla":
                yb, doc = _mla_block(lp, xb, pos[b:b + step], c_all, kr_all,
                                     sb, ffn, key, float(route_delta),
                                     compute_as)
            else:
                yb, state, tail, doc = _gdn_block(
                    lp, xb, state, tail, sb, ffn, key, float(route_delta),
                    compute_as)
            out[b:b + step] = np.asarray(yb)
            docs.append(doc)
        x = out
        if docs[0] is not None:
            routing.append({k: jnp.concatenate([d[k] for d in docs])[:t]
                            for k in docs[0]})
    with jax.default_matmul_precision("highest"):
        want = np.arange(t) if rows is None else np.asarray(rows)
        h = _layer_norm(jnp.asarray(x[want]), w["lnf_g"], cfg)
        return h @ _f32(w["head"]).T, routing
