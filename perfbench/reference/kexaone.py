"""Plain reference of K-EXAONE's language model, ``model_type``
``exaone_moe``
(https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/config.json):
the forward pass in straightforward ``jax.numpy``, float32, matmul
precision "highest".  No kernel, no cache, no chunking, no batching:
attention over the WHOLE sequence under the causal mask (a full layer)
or the banded one (a sliding layer), the experts in a loop.  The
yardstick that decides ``correct``.

Layer on the residual stream ``x`` (RMSNorm eps 1e-5, the norm on each
sub-layer's OUTPUT)::

    q_t,i = RMSNorm_128(x_t Wq,i) gq     (64 heads; rotated on a sliding layer)
    k_t,j = RMSNorm_128(x_t Wk,j) gk     (8 heads;  rotated on a sliding layer)
    v_t,j = x_t Wv,j
    a_t,i,s = softmax over the visible s of q_t,i . k_s,j(i) 128^-1/2
    o_t,i = sum_s a_t,i,s v_s,j(i);  attn = [o_t,1..64] Wo
    h = x + RMSNorm(attn) g1;   y = h + RMSNorm(FFN(h)) g2

with ``j(i) = i // 8`` and key ``s`` visible to query ``t`` iff ``0 <= t -
s`` and, on a sliding layer, ``t - s < sliding_window`` (128 keys, the
query's own among them).  Rotary: theta 1e6 over all 128 values, pairs
``(i, i + 64)``.  FFN: a dense SwiGLU for published layers below
``first_k_dense_replace``, else ``shared SwiGLU + sum over the 8 chosen e
of w_e SwiGLU_e``: ``p = sigmoid(h Wr)`` over all experts, chosen the 8
largest of ``p + b``, ``w_e = 2.5 p_e / sum of the chosen p``.  After
the last layer RMSNorm and the head.

**The share.**  ``cfg["experts_held"] = [first, count]``: the router
scores all ``num_experts``; only the held experts' terms are added, the
shared expert in full.  ``cfg["layers"]`` lists the published layers
kept.  A sliced vocabulary is a smaller vocabulary.

**Readings of what config.json leaves open** (the configuration file's
``assumed`` lists the same): RMSNorm on ``q`` and ``k`` per head; rotary
positions on sliding layers only; the norm on each sub-layer's output;
the router's correction bias ``b`` (zeros), used to choose only.  No
multi-token-prediction module.  Weights: normal(0, 0.02), norm gains 1,
biases 0, stored bfloat16; this file upcasts the same rounded values, a
block of columns or an expert at a time, and scores a block of query
rows and one group of heads at a time, so that at the published widths
16k positions fit beside a loaded engine.

**Near ties.**  bfloat16 activations can flip the 8th and 9th expert
against this file's float32 scores.  ``forward`` takes the system's
choices (``sys_experts``); where they differ from its own it adopts
them ONLY if every expert of the difference lies within ``route_delta``
of the 8th selection score under its OWN scores.  Any other difference
is reported as a mismatch and fails the comparison.
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


def _rope(x, pos, theta):
    """Rotary embedding over the whole last axis of ``x`` [T, H, D],
    pairs ``(i, i + D / 2)``."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
    ang = pos.astype(jnp.float32)[:, None, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


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


def attention(lp, x, kind, cfg):
    """Grouped-query attention over a whole sequence ``x`` [T, C] under
    the causal (full layer) or banded (sliding layer) mask: keys and
    values of every row first, then one block of query rows and one
    group of heads at a time, each against every key a row of the block
    can see.  Returns ``attn`` [T, C]."""
    t = x.shape[0]
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    g = h // kv
    eps = cfg["rms_norm_eps"]
    window = cfg["sliding_window"]
    theta = float(cfg["rope_theta"])
    w_q, w_kv = lp["qkv_w"][:, :h * d], lp["qkv_w"][:, h * d:]
    kv_rows = _dot(x, w_kv)
    k = _rms(kv_rows[:, :kv * d].reshape(t, kv, d), _f32(lp["k_norm_g"]),
             eps)
    v = kv_rows[:, kv * d:].reshape(t, kv, d)
    if kind == SLIDING:
        k = _rope(k, jnp.arange(t), theta)
    k, v = _f32(_rounded(k)), _f32(_rounded(v))
    n, rows = _row_blocks(t)
    # a block of query rows sees the keys from ``reach`` before its
    # first row to its last: everything (full) or a window (sliding)
    reach = n * rows if kind == FULL else window
    span = reach + rows
    front = lambda a: jnp.pad(a, ((reach, n * rows - t), (0, 0), (0, 0)))
    kp, vp = front(k), front(v)

    def block(args):
        xb, b = args                                           # [rows, C]
        q_pos = b * rows + jnp.arange(rows)
        q = _rms(_dot(xb, w_q).reshape(rows, h, d), _f32(lp["q_norm_g"]),
                 eps)
        if kind == SLIDING:
            q = _rope(q, q_pos, theta)
        q = _f32(_rounded(q)).reshape(rows, kv, g, d)
        kb = jax.lax.dynamic_slice_in_dim(kp, b * rows, span, 0)
        vb = jax.lax.dynamic_slice_in_dim(vp, b * rows, span, 0)
        k_pos = b * rows - reach + jnp.arange(span)
        gap = q_pos[:, None] - k_pos[None, :]
        mask = (k_pos[None, :] >= 0) & (gap >= 0)
        if kind == SLIDING:
            mask &= gap < window

        def group(j):
            s = jnp.einsum("qgd,sd->gqs", q[:, j], kb[:, j]) \
                * float(d ** -0.5)
            p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), -1)
            return jnp.einsum("gqs,sd->qgd", _f32(_rounded(p)), vb[:, j])

        # [kv, rows, g, d] -> [rows, kv * g * d]
        o = jnp.moveaxis(jax.lax.map(group, jnp.arange(kv)), 0, 1)
        return _dot(o.reshape(rows, h * d), lp["o_w"])

    xp = jnp.pad(x, ((0, n * rows - t), (0, 0))).reshape(n, rows, -1)
    return jax.lax.map(block, (xp, jnp.arange(n))).reshape(n * rows, -1)[:t]


def route(scores, bias, k):
    """The router on sigmoid scores ``[T, E]``: the ``k`` largest of
    ``p + b``.  Returns ``(chosen [T, k], p + b, its k-th largest)``."""
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
    """Routed experts (this chip's share) + the shared expert over
    tokens ``[T, C]``, an expert at a time.  Returns ``(y, doc)``."""
    t = x.shape[0]
    e = cfg["num_experts"]
    first, count = cfg["experts_held"]
    scores = jax.nn.sigmoid(x @ _f32(lp["router_w"]))          # [T, E]
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
        return y + wj * _swiglu(x, gu, dn), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), jnp.arange(count))
    y = y + _swiglu(x, lp["sh_gu_w"], lp["sh_down_w"])
    return y, {"experts": idx, "adopted": adopted, "mismatch": mismatch,
               "need": need}


@functools.partial(jax.jit, static_argnames=(
    "kind", "ffn", "cfg_key", "route_delta", "compute_as"))
def _layer(lp, x, sys_idx, kind, ffn, cfg_key, route_delta, compute_as):
    cfg = _CFGS[cfg_key]
    eps = cfg["rms_norm_eps"]
    _ROUND[0] = compute_as
    try:
        with jax.default_matmul_precision("highest"):
            h = x + _rms(attention(lp["attn"], x, kind, cfg),
                         _f32(lp["ln1_g"]), eps)
            if ffn == "dense":
                f, doc = _swiglu(h, lp["mlp"]["gu_w"],
                                 lp["mlp"]["down_w"]), None
            else:
                f, doc = moe(lp["moe"], h, cfg, sys_idx, route_delta)
            return h + _rms(f, _f32(lp["ln2_g"]), eps), doc
    finally:
        _ROUND[0] = None


def layer_kinds(cfg):
    return [cfg["layer_types"][l] for l in cfg["layers"]]


def ffn_kinds(cfg):
    """"dense" or "moe" of every kept layer, by its PUBLISHED index."""
    return ["dense" if l < cfg["first_k_dense_replace"] else "moe"
            for l in cfg["layers"]]


def forward(w, tokens, cfg, sys_experts=None, route_delta=0.0, rows=None,
            compute_as=None):
    """tokens int[T] -> ``(logits float32[T or len(rows), V], routing)``.

    ``w`` is the parameter tree as the program stores it (bfloat16
    leaves are upcast here, a block at a time; the query, key and value
    projections side by side).  ``sys_experts``: the system's chosen
    experts, one ``[T, k]`` array per MoE layer; ``rows``: the positions
    whose logits are wanted.  ``routing`` is one doc per MoE layer
    (``adopted``, ``mismatch``, ``need`` per position).  ``compute_as``:
    a dtype name every matrix and every matmul's input is rounded to
    first (the router, the embedding and the head stay float32)."""
    key = repr(sorted((k, repr(v)) for k, v in cfg.items()))
    _CFGS[key] = cfg
    x = _f32(w["wte"][jnp.asarray(tokens)])
    routing, m = [], 0
    for lp, kind, ffn in zip(w["layers"], layer_kinds(cfg), ffn_kinds(cfg)):
        sys_idx = None
        if ffn == "moe" and sys_experts is not None:
            sys_idx = jnp.asarray(sys_experts[m], jnp.int32)
        x, doc = _layer(lp, x, sys_idx, kind, ffn, key, float(route_delta),
                        compute_as)
        if doc is not None:
            routing.append(doc)
            m += 1
    with jax.default_matmul_precision("highest"):
        x = _rms(x, _f32(w["lnf_g"]), cfg["rms_norm_eps"])
        if rows is not None:
            x = x[jnp.asarray(rows)]
        return x @ _f32(w["head"]).T, routing
