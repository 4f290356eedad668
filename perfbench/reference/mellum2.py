"""Plain reference of Mellum 2's language model, ``model_type`` ``mellum``
(https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json):
the forward pass in straightforward ``jax.numpy``, float32, matmul
precision "highest".  No kernel, no cache, no chunking, no batching:
attention over the WHOLE sequence under the causal mask (a full layer)
or the banded one (a sliding layer), the experts in a loop.  The
yardstick that decides ``correct``.

Layer on the residual stream ``x`` (RMSNorm eps 1e-6, the norm on each
sub-layer's INPUT)::

    u = RMSNorm(x) g1
    q_t,i = rot(RMSNorm_128(u_t Wq,i) gq)     (32 heads)
    k_t,j = rot(RMSNorm_128(u_t Wk,j) gk)     (4 heads)
    v_t,j = u_t Wv,j
    a_t,i,s = softmax over the visible s of q_t,i . k_s,j(i) 128^-1/2
    h = x + [o_t,1..32] Wo,   o_t,i = sum_s a_t,i,s v_s,j(i)
    y = h + sum over the 8 chosen e of w_e SwiGLU_e(RMSNorm(h) g2)

with ``j(i) = i // 8`` and key ``s`` visible to query ``t`` iff ``0 <= t -
s`` and, on a sliding layer, ``t - s < sliding_window`` (1,024 keys, the
query's own among them).  ``rot``: ``x cos + rotate_half(x) sin`` over
all 128 values (pairs ``(i, i + 64)``) at the layer kind's
``rope_parameters``: plain powers of theta on a sliding layer; on a full
layer YaRN's frequencies (transformers' ``_compute_yarn_parameters``:
the correction range floored and ceiled), with ``attention_factor``
multiplying cos and sin.  Router: ``p = softmax(h Wr)`` over all 64
experts, the 8 largest chosen, ``w_e = p_e / sum of the chosen p``; no
shared expert.  After the last layer RMSNorm and the untied head.

**The share.**  ``cfg["experts_held"] = [first, count]``: the router
scores all ``num_experts``; only the held experts' terms are added.
``cfg["layers"]`` lists the published layers kept.

**Readings of what config.json leaves open** (the configuration file's
``assumed`` lists the same): RMSNorm on ``q`` and ``k`` per head, the
norm on each sub-layer's input, the softmax router (the family of
``qwen3_moe``, whose keys the config carries).  No multi-token
prediction.  Weights: normal(0, 0.02), norm gains 1, stored bfloat16;
this file upcasts the same rounded values, a block of columns or an
expert at a time, and scores a block of query rows and one group of
heads at a time, so that 12k positions fit beside a loaded engine.

**Near ties.**  bfloat16 activations can flip the 8th and 9th expert
against this file's float32 probabilities.  ``forward`` takes the
system's choices (``sys_experts``); where they differ from its own it
adopts them ONLY if every expert of the difference lies within
``route_delta`` of the 8th probability under its OWN scores.  Any other
difference is reported as a mismatch and fails the comparison.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

FULL, SLIDING = "full_attention", "sliding_attention"
#: a weight matrix is upcast at most this many elements at a time
BLOCK_ELEMS = 48 * 1024 * 1024
#: query rows scored at a time (a block's scores are [heads of one
#: group, rows, keys] float32), and rows of a SwiGLU's hidden activation
ROW_BLOCK = 512

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
    time; under ``compute_as`` both operands are rounded to that type
    first."""
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
    out = jax.lax.map(one, jnp.arange(n))               # [n, T, cols / n]
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], cols)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def inv_freq(rp, dim):
    """Rotary frequencies (float64 [dim / 2]) and the factor on cos and
    sin of one entry of ``rope_parameters``, as transformers computes
    them ("default" and "yarn")."""
    base = float(rp["rope_theta"])
    plain = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rp["rope_type"] == "default":
        return plain, 1.0
    assert rp["rope_type"] == "yarn", rp
    factor = rp["factor"]
    orig = rp["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * np.log(orig / (rotations * 2 * np.pi)) \
            / (2 * np.log(base))

    low = max(np.floor(correction_dim(rp["beta_fast"])), 0)
    high = min(np.ceil(correction_dim(rp["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extrapolation = 1 - ramp
    return (plain / factor * (1 - extrapolation)
            + plain * extrapolation), float(rp["attention_factor"])


def _rope(x, pos, rp):
    """``x cos + rotate_half(x) sin`` over the whole last axis of ``x``
    [T, H, D] at the frequencies of ``rp``, cos and sin times its
    factor."""
    freqs, factor = inv_freq(rp, x.shape[-1])
    ang = pos.astype(jnp.float32)[:, None, None] \
        * jnp.asarray(freqs, jnp.float32)[None, None, :]
    ang = jnp.concatenate([ang, ang], -1)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def visible(q_pos, k_pos, kind, window):
    """Key at ``k_pos`` visible to the query at ``q_pos`` (broadcast)."""
    gap = q_pos - k_pos
    mask = (k_pos >= 0) & (gap >= 0)
    if kind == SLIDING:
        mask &= gap < window
    return mask


def _row_blocks(t):
    """``(blocks, rows)``: ``t`` rows padded to whole blocks of at most
    ``ROW_BLOCK``."""
    rows = min(ROW_BLOCK, t)
    return -(-t // rows), rows


def _swiglu(x, gu_w, down_w):
    def rows(xb):
        gu = _dot(xb, gu_w)
        half = gu.shape[-1] // 2
        return _dot(jax.nn.silu(gu[..., :half]) * gu[..., half:], down_w)

    t = x.shape[0]
    n, r = _row_blocks(t)
    if n == 1:
        return rows(x)
    xp = jnp.pad(x, ((0, n * r - t), (0, 0)))
    return jax.lax.map(rows, xp.reshape(n, r, -1)).reshape(n * r, -1)[:t]


def attention(lp, u, kind, cfg):
    """Grouped-query attention over a whole sequence ``u`` [T, C] (the
    layer's normed input) under the causal (full layer) or banded
    (sliding layer) mask: keys and values of every row first, then one
    block of query rows and one group of heads at a time, each against
    every key a row of the block can see.  Returns ``[o] Wo`` [T, C]."""
    t = u.shape[0]
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    g = h // kv
    eps = cfg["rms_norm_eps"]
    window = cfg["sliding_window"]
    rp = cfg["rope_parameters"][kind]
    w_q, w_kv = lp["qkv_w"][:, :h * d], lp["qkv_w"][:, h * d:]
    kv_rows = _dot(u, w_kv)
    k = _rms(kv_rows[:, :kv * d].reshape(t, kv, d), _f32(lp["k_norm_g"]),
             eps)
    v = kv_rows[:, kv * d:].reshape(t, kv, d)
    k = _rope(k, jnp.arange(t), rp)
    k, v = _f32(_rounded(k)), _f32(_rounded(v))
    n, rows = _row_blocks(t)
    # a block of query rows sees the keys from ``reach`` before its
    # first row to its last: everything (full) or a window (sliding)
    reach = n * rows if kind == FULL else window
    span = reach + rows
    front = lambda a: jnp.pad(a, ((reach, n * rows - t), (0, 0), (0, 0)))
    kp, vp = front(k), front(v)

    def block(args):
        ub, b = args                                           # [rows, C]
        q_pos = b * rows + jnp.arange(rows)
        q = _rms(_dot(ub, w_q).reshape(rows, h, d), _f32(lp["q_norm_g"]),
                 eps)
        q = _f32(_rounded(_rope(q, q_pos, rp))).reshape(rows, kv, g, d)
        kb = jax.lax.dynamic_slice_in_dim(kp, b * rows, span, 0)
        vb = jax.lax.dynamic_slice_in_dim(vp, b * rows, span, 0)
        k_pos = b * rows - reach + jnp.arange(span)
        mask = visible(q_pos[:, None], k_pos[None, :], kind, window)

        def group(j):
            s = jnp.einsum("qgd,sd->gqs", q[:, j], kb[:, j]) \
                * float(d ** -0.5)
            p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), -1)
            return jnp.einsum("gqs,sd->qgd", _f32(_rounded(p)), vb[:, j])

        # [kv, rows, g, d] -> [rows, kv * g * d]
        o = jnp.moveaxis(jax.lax.map(group, jnp.arange(kv)), 0, 1)
        return _dot(o.reshape(rows, h * d), lp["o_w"])

    up = jnp.pad(u, ((0, n * rows - t), (0, 0))).reshape(n, rows, -1)
    return jax.lax.map(block, (up, jnp.arange(n))).reshape(n * rows, -1)[:t]


def route(probs, k):
    """The router on softmax probabilities ``[T, E]``: the ``k``
    largest.  Returns ``(chosen [T, k], probs, its k-th largest)``."""
    top, idx = jax.lax.top_k(probs, k)
    return idx, probs, top[:, k - 1]


def _adopt(idx, sel, kth, sys_idx, delta):
    """The near-tie rule: the system's choice where every expert of the
    difference lies within ``delta`` of the k-th probability.  Returns
    ``(chosen, adopted, mismatch, need)``."""
    t, e = sel.shape
    hot = lambda i: jnp.zeros((t, e), bool).at[
        jnp.arange(t)[:, None], i].set(True)
    theirs = hot(jnp.clip(sys_idx, 0, e - 1))
    # an entry out of range or given twice leaves the count short
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
    """Routed experts (this chip's share) over tokens ``[T, C]`` (the
    normed stream), an expert at a time.  Returns ``(y, doc)``."""
    t = x.shape[0]
    e = cfg["num_experts"]
    first, count = cfg["experts_held"]
    probs = jax.nn.softmax(x @ _f32(lp["router_w"]), -1)       # [T, E]
    idx, sel, kth = route(probs, cfg["num_experts_per_tok"])
    adopted = mismatch = jnp.zeros((t,), bool)
    need = jnp.zeros((t,), jnp.float32)
    if sys_idx is not None:
        idx, adopted, mismatch, need = _adopt(idx, sel, kth, sys_idx, delta)
    w = jnp.take_along_axis(probs, idx, -1)
    w = w / w.sum(-1, keepdims=True)
    dense = jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], idx].set(w)[:, first:first + count]

    def one_expert(y, j):
        gu = jax.lax.dynamic_index_in_dim(lp["gu_w"], j, keepdims=False)
        dn = jax.lax.dynamic_index_in_dim(lp["down_w"], j, keepdims=False)
        wj = jax.lax.dynamic_slice_in_dim(dense, j, 1, 1)
        return y + wj * _swiglu(x, gu, dn), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), jnp.arange(count))
    return y, {"experts": idx, "adopted": adopted, "mismatch": mismatch,
               "need": need}


@functools.partial(jax.jit, static_argnames=(
    "kind", "cfg_key", "route_delta", "compute_as"))
def _layer(lp, x, sys_idx, kind, cfg_key, route_delta, compute_as):
    cfg = _CFGS[cfg_key]
    eps = cfg["rms_norm_eps"]
    _ROUND[0] = compute_as
    try:
        with jax.default_matmul_precision("highest"):
            h = x + attention(lp["attn"], _rms(x, _f32(lp["ln1_g"]), eps),
                              kind, cfg)
            f, doc = moe(lp["moe"], _rms(h, _f32(lp["ln2_g"]), eps), cfg,
                         sys_idx, route_delta)
            return h + f, doc
    finally:
        _ROUND[0] = None


def layer_kinds(cfg):
    return [cfg["layer_types"][l] for l in cfg["layers"]]


def forward(w, tokens, cfg, sys_experts=None, route_delta=0.0, rows=None,
            compute_as=None):
    """tokens int[T] -> ``(logits float32[T or len(rows), V], routing)``.

    ``w`` is the parameter tree as the program stores it (bfloat16
    leaves are upcast here, a block at a time; the query, key and value
    projections side by side).  ``sys_experts``: the system's chosen
    experts, one ``[T, k]`` array per layer; ``rows``: the positions
    whose logits are wanted.  ``routing`` is one doc per layer
    (``adopted``, ``mismatch``, ``need`` per position).  ``compute_as``:
    a dtype name every matrix and every matmul's input is rounded to
    first (the router, the embedding and the head stay float32)."""
    key = repr(sorted((k, repr(v)) for k, v in cfg.items()))
    _CFGS[key] = cfg
    x = _f32(w["wte"][jnp.asarray(tokens)])
    routing = []
    for m, (lp, kind) in enumerate(zip(w["layers"], layer_kinds(cfg))):
        sys_idx = None if sys_experts is None \
            else jnp.asarray(sys_experts[m], jnp.int32)
        x, doc = _layer(lp, x, sys_idx, kind, key, float(route_delta),
                        compute_as)
        routing.append(doc)
    with jax.default_matmul_precision("highest"):
        x = _rms(x, _f32(w["lnf_g"]), cfg["rms_norm_eps"])
        if rows is not None:
            x = x[jnp.asarray(rows)]
        return _logits(x, w["head"]), routing


@jax.jit
def _logits(x, head):
    """``x @ head.T`` with the head [V, C] upcast a block of rows at a
    time (the whole vocabulary in float32 would be 0.9 GB)."""
    v, c = head.shape
    n = 1
    while (v // n) * c > BLOCK_ELEMS // 2 or v % n:
        n += 1
    with jax.default_matmul_precision("highest"):
        out = jax.lax.map(lambda hb: x @ _f32(hb).T,
                          head.reshape(n, v // n, c))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], v)
