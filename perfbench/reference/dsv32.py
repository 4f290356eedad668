"""Plain reference of DeepSeek-V3.2's language model
(https://huggingface.co/deepseek-ai/DeepSeek-V3.2/blob/main/config.json):
the forward pass in straightforward ``jax.numpy``, float32, matmul
precision "highest".  No kernel, no cache, no chunking: latent attention
in its plain (non-absorbed) form over the WHOLE sequence with the
indexer's selection as a mask, the experts in a loop.  The yardstick
that decides ``correct``.

Layer (pre-norm residual, RMSNorm eps 1e-6), for the normed input
``h_t`` at position ``t``::

    cQ_t   = RMSNorm(h_t W_DQ)                       (q_lora_rank)
    q_t,i  = cQ_t W_UQ,i = [qC (128) ; qR (64)]      (qR rotated)
    [cKV_t ; kR_t] = h_t W_DKV                       (cKV RMSNormed, kR rotated)
    qI_t,j = cQ_t W_IQ,j                             (64 x 128, first 64 rotated)
    kI_s   = LayerNorm(h_s W_IK)                     (128, first 64 rotated)
    w_t,j  = (h_t W_Iw)_j 64^-1/2 128^-1/2
    I_t,s  = sum_j w_t,j ReLU(qI_t,j . kI_s)         (s <= t)
    S_t    = the index_topk largest of I_t,.         (all s <= t while t < index_topk)
    a_t,i,s = softmax over S_t of (qC.kC_s,i + qR.kR_s) 192^-1/2 m^2
    o_t,i  = sum_s a_t,i,s v_s,i;   out = [o_t,1..H] W_O

with ``[kC_s,i ; v_s,i] = cKV_s W_UKV,i`` and ``m = 0.1 ln(factor) +
1`` (YaRN's softmax scale).  FFN: a dense SwiGLU for published layers
below ``first_k_dense_replace``, else ``shared SwiGLU + 2.5 x sum over
the 8 chosen e of g_e SwiGLU_e``: sigmoid scores over all experts,
chosen by ``s + b`` inside the 4 of 8 groups with the largest sum of
their top two, ``g_e = s_e / sum of the chosen s``.

**The share.**  ``cfg["experts_held"] = [first, count]``: the router
scores all ``num_experts``; only the held experts' terms are added, the
shared expert in full.  ``cfg["layers"]`` lists the published layers
kept.  A sliced vocabulary is a smaller vocabulary.

**Departures from the published model, and readings of what config.json
leaves open** (the configuration file's ``assumed`` lists the same):

- rotary pairs: interleaved ``(2i, 2i + 1)`` in attention, halves ``(i,
  i + 32)`` in the indexer, as the family's published inference code;
- the indexer's keys and queries are bfloat16 values here, not float8
  after a Hadamard rotation: the rotation is orthogonal and cancels in
  ``q . k``; float8 is a precision, not mathematics;
- YaRN frequencies are applied at every length (the published code
  applies them once the served length exceeds the original 4096, which
  every cell's does);
- the expert bias ``b`` is zeros; no multi-token-prediction module;
- weights: normal(0, 0.02), norm gains 1, biases 0, stored bfloat16;
  this file upcasts the same rounded values, a block of columns or a
  block of experts at a time, so that at the published widths it fits
  beside a loaded engine.

**Near ties.**  bfloat16 activations can flip the 8th and 9th expert,
and the 2,048th and 2,049th row, against this file's float32 scores.
``forward`` takes the system's choices (``sys_experts``,
``sys_selected``); where they differ from its own it adopts them ONLY
if every entry of the difference lies within a stated delta of the
boundary score (8th expert: ``route_delta``; ``index_topk``-th row:
``select_delta``) under its OWN scores.  Any other difference is
reported as a mismatch and fails the comparison.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6
#: a weight matrix is upcast at most this many elements at a time
BLOCK_ELEMS = 48 * 1024 * 1024
#: attention heads scored at a time (scores are [heads, T, T] float32)
HEAD_BLOCK = 2

#: set while a layer is traced with ``compute_as``: every matrix and
#: every matmul's input is rounded to that type first (the reading "in
#: the nearest precision below" that a cell's limits have to refuse)
_ROUND = [None]


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _dot(x, w):
    """``x @ w`` in float32, the matrix upcast a block of columns at a
    time; under ``compute_as`` both operands are rounded to that type
    first."""
    w = jnp.asarray(w)
    if _ROUND[0] is not None:
        x = x.astype(_ROUND[0])
    x = _f32(x)
    rows, cols = w.shape
    n = 1
    while rows * (cols // n) > BLOCK_ELEMS or cols % n:
        n += 1

    def one(i):
        wb = jax.lax.dynamic_slice_in_dim(w, i * (cols // n), cols // n, 1)
        if _ROUND[0] is not None:
            wb = wb.astype(_ROUND[0])
        return x @ _f32(wb)

    if n == 1:
        return one(0)
    out = jax.lax.map(one, jnp.arange(n))               # [n, T, cols / n]
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], cols)


def _rms(x, g):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + EPS) * g


def _layer_norm(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS) * g + b


def yarn_inv_freq(cfg):
    """YaRN's rotary frequencies for the 64 rope values (numpy float32
    [32]): plain where a pair turns more than ``beta_fast`` times over
    the original context, divided by ``factor`` where fewer than
    ``beta_slow``, a linear blend between."""
    rs = cfg["rope_scaling"]
    dim, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    half = dim // 2
    freqs = 1.0 / theta ** (np.arange(half, dtype=np.float64) / half)

    def correction_dim(turns):
        return dim * np.log(rs["original_max_position_embeddings"]
                            / (turns * 2 * np.pi)) / (2 * np.log(theta))

    low = max(np.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(np.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    smooth = 1 - np.clip((np.arange(half) - low) / (high - low), 0, 1)
    return (freqs / rs["factor"] * (1 - smooth) + freqs * smooth) \
        .astype(np.float32)


def softmax_scale(cfg):
    rs = cfg["rope_scaling"]
    m = 0.1 * rs["mscale_all_dim"] * np.log(rs["factor"]) + 1.0
    return float((cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
                 * m * m)


def _rope(x, pos, inv_freq, interleaved):
    """Rotary embedding on the last axis (64) of ``x`` [T, 64] or [T, H,
    64]: pairs ``(2i, 2i + 1)`` if ``interleaved`` else ``(i, i + 32)``."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if x.ndim == 3:
        cos, sin = cos[:, None, :], sin[:, None, :]
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         -1).reshape(x.shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


#: a SwiGLU's hidden activations are held for at most this many rows
ROW_BLOCK = 1024


def _swiglu(x, gu_w, down_w):
    def rows(xb):
        gu = _dot(xb, gu_w)
        half = gu.shape[-1] // 2
        return _dot(jax.nn.silu(gu[..., :half]) * gu[..., half:], down_w)

    t = x.shape[0]
    if t <= ROW_BLOCK:
        return rows(x)
    n = -(-t // ROW_BLOCK)
    xp = jnp.pad(x, ((0, n * ROW_BLOCK - t), (0, 0)))
    out = jax.lax.map(rows, xp.reshape(n, ROW_BLOCK, x.shape[1]))
    return out.reshape(n * ROW_BLOCK, -1)[:t]


def _index_keys(lp, h, pos, cfg, inv_freq):
    """The indexer's cached rows ``kI`` [T, 128] of the normed inputs
    ``h`` at positions ``pos``."""
    dr = cfg["qk_rope_head_dim"]
    k = _layer_norm(_dot(h, lp["k_w"]), _f32(lp["k_norm_g"]),
                    _f32(lp["k_norm_bias"]))
    return jnp.concatenate([_rope(k[:, :dr], pos, inv_freq, False),
                            k[:, dr:]], -1)


def _index_queries(lp, h, c_q, pos, cfg, inv_freq):
    """The indexer's queries ``qI`` [T, n, 128] and head weights ``w``
    [T, n] of the rows at positions ``pos``."""
    n, d, dr = (cfg["index_n_heads"], cfg["index_head_dim"],
                cfg["qk_rope_head_dim"])
    q = _dot(c_q, lp["q_w"]).reshape(-1, n, d)
    q = jnp.concatenate([_rope(q[..., :dr], pos, inv_freq, False),
                         q[..., dr:]], -1)
    return q, _dot(h, lp["w_w"]) * float(n ** -0.5 * d ** -0.5)


def _index_sum(q, k, w):
    """``sum_j w_t,j ReLU(qI_t,j . kI_s)`` [queries, keys], heads a
    block at a time."""
    if _ROUND[0] is not None:
        q, k = q.astype(_ROUND[0]), k.astype(_ROUND[0])
    q, k = _f32(q), _f32(k)
    n = q.shape[1]
    blk = min(8, n)
    assert n % blk == 0, (n, blk)

    def some_heads(acc, j):
        qj = jax.lax.dynamic_slice_in_dim(q, j * blk, blk, 1)
        wj = jax.lax.dynamic_slice_in_dim(w, j * blk, blk, 1)
        s = jax.nn.relu(jnp.einsum("tjd,sd->tjs", qj, k))
        return acc + jnp.einsum("tjs,tj->ts", s, wj), None

    scores, _ = jax.lax.scan(
        some_heads, jnp.zeros((q.shape[0], k.shape[0]), jnp.float32),
        jnp.arange(n // blk))
    return scores


def index_scores(lp, h, c_q, cfg, inv_freq):
    """The lightning indexer's scores ``I`` [T, T] (``-inf`` above the
    diagonal)."""
    t = h.shape[0]
    pos = jnp.arange(t)
    q, w = _index_queries(lp, h, c_q, pos, cfg, inv_freq)
    scores = _index_sum(q, _index_keys(lp, h, pos, cfg, inv_freq), w)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)


#: rows of a long sequence whose indexer keys are computed at a time
KEY_BLOCK = 4096


@functools.partial(jax.jit, static_argnames=("cfg_key", "compute_as"))
def _first_layer_scores(wte, lp, tokens, queries, cfg_key, compute_as):
    cfg = _CFGS[cfg_key]
    _ROUND[0] = compute_as
    try:
        with jax.default_matmul_precision("highest"):
            inv_freq = yarn_inv_freq(cfg)
            normed = lambda toks: _rms(_f32(wte[toks]), _f32(lp["ln1_g"]))
            t = tokens.shape[0]
            n = t // KEY_BLOCK
            k = jax.lax.map(
                lambda blk: _index_keys(lp["idx"], normed(blk[0]), blk[1],
                                        cfg, inv_freq),
                (tokens.reshape(n, -1), jnp.arange(t).reshape(n, -1)))
            h = normed(tokens[queries])
            c_q = _rms(_dot(h, lp["attn"]["q_a_w"]),
                       _f32(lp["attn"]["q_a_norm_g"]))
            q, w = _index_queries(lp["idx"], h, c_q, queries, cfg, inv_freq)
            scores = _index_sum(q, k.reshape(t, -1), w)
            return jnp.where(jnp.arange(t)[None, :] <= queries[:, None],
                             scores, -jnp.inf)
    finally:
        _ROUND[0] = None


def first_layer_index_scores(w, tokens, cfg, queries, pad_to=None,
                             compute_as=None):
    """The FIRST kept layer's index scores of the positions ``queries``
    over the sequence ``tokens`` int[T], float32 [len(queries), T or
    pad_to] (``-inf`` past a query's own position).  That layer's input
    is the embedding, so the scores need the tokens alone and no layer
    below: what a long context can afford beside a loaded engine, its
    keys computed ``KEY_BLOCK`` rows at a time.  ``pad_to``: a fixed
    length (one compiled shape for every context); ``compute_as`` as in
    :func:`forward`."""
    key = repr(sorted((k, repr(v)) for k, v in cfg.items()))
    _CFGS[key] = cfg
    tokens = np.asarray(tokens, np.int32)
    size = -(-max(pad_to or tokens.size, tokens.size) // KEY_BLOCK) \
        * KEY_BLOCK
    padded = np.zeros(size, np.int32)
    padded[:tokens.size] = tokens
    return _first_layer_scores(w["wte"], w["layers"][0], padded,
                               jnp.asarray(queries, jnp.int32), key,
                               compute_as)


def select(scores, topk, sys_rows=None, delta=0.0, positions=None):
    """The selection ``S`` as a mask [T, T] from the indexer's scores,
    with the near-tie rule (module docstring) when the system's chosen
    rows ``sys_rows`` int32 [T, topk] are given (entries past
    ``min(t + 1, topk)`` are ignored).  ``positions``: the positions of
    the rows of ``scores`` [Q, T] where they are not ``0..T-1``.
    Returns ``(mask, doc)``."""
    q, t = scores.shape
    pos = jnp.arange(q) if positions is None else jnp.asarray(positions)
    k = min(topk, t)
    # exactly k rows: equal scores go to the lower position, as the
    # sort has them (a score is exactly 0 where no head's ReLU fires)
    top, idx = jax.lax.top_k(scores, k)
    kth = top[:, -1]                                      # -inf while t < k
    own = jnp.zeros((q, t), bool).at[jnp.arange(q)[:, None], idx].set(
        top > -jnp.inf)
    zeros = jnp.zeros((q,), bool)
    doc = {"adopted": zeros, "mismatch": zeros,
           "need": jnp.zeros((q,), jnp.float32)}
    if sys_rows is None:
        return own, doc
    n_valid = jnp.minimum(pos + 1, topk)
    valid = jnp.arange(sys_rows.shape[1])[None, :] < n_valid[:, None]
    rows = jnp.where(valid, jnp.clip(sys_rows, 0, t - 1), t)
    theirs = jnp.zeros((q, t + 1), bool).at[
        jnp.arange(q)[:, None], rows].set(True)[:, :t]
    # an entry out of range, above the diagonal or given twice leaves
    # the count short or sits on a -inf score: both are mismatches
    bad = (jnp.where(valid, (sys_rows < 0) | (sys_rows >= t), False).any(-1)
           | (theirs.sum(-1) != n_valid))
    diff = own ^ theirs
    need = jnp.where(diff, jnp.abs(scores - kth[:, None]), 0.0).max(-1)
    differs = diff.any(-1)
    ok = (need <= delta) & ~bad
    adopted = differs & ok
    doc = {"adopted": adopted, "mismatch": (differs & ~ok) | bad,
           "need": jnp.where(bad, jnp.inf, need)}
    return jnp.where(adopted[:, None], theirs, own), doc


def attention(lp, ip, h, cfg, sys_rows=None, select_delta=0.0):
    """Latent attention over a whole sequence ``h`` [T, C] under the
    indexer's selection.  Returns ``(y, selection doc)``."""
    t = h.shape[0]
    n_h = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    inv_freq = yarn_inv_freq(cfg)
    pos = jnp.arange(t)
    c_q = _rms(_dot(h, lp["q_a_w"]), _f32(lp["q_a_norm_g"]))
    kva = _dot(h, lp["kva_w"])
    c = _rms(kva[:, :rank], _f32(lp["kv_norm_g"]))
    k_rope = _rope(kva[:, rank:], pos, inv_freq, True)        # [T, 64]
    mask, doc = select(index_scores(ip, h, c_q, cfg, inv_freq),
                       cfg["index_topk"], sys_rows, select_delta)
    scale = softmax_scale(cfg)
    blk = min(HEAD_BLOCK, n_h)
    assert n_h % blk == 0, (n_h, blk)
    q_w = jnp.asarray(lp["q_b_w"]).reshape(-1, n_h, dn + dr)
    kv_w = jnp.asarray(lp["kvb_w"]).reshape(rank, n_h, dn + dv)

    def some_heads(i):
        # a block of heads at a time: its queries, keys and values are
        # expanded here, its scores are [heads, T, T]
        sl = lambda w: jax.lax.dynamic_slice_in_dim(w, i * blk, blk, 1)
        q = _dot(c_q, sl(q_w).reshape(-1, blk * (dn + dr))) \
            .reshape(t, blk, dn + dr)
        kv = _dot(c, sl(kv_w).reshape(rank, blk * (dn + dv))) \
            .reshape(t, blk, dn + dv)
        s = (jnp.einsum("qhd,khd->hqk", q[..., :dn], kv[..., :dn])
             + jnp.einsum("qhd,kd->hqk",
                          _rope(q[..., dn:], pos, inv_freq, True), k_rope)) \
            * scale
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", p, kv[..., dn:])

    o = jax.lax.map(some_heads, jnp.arange(n_h // blk))   # [n, T, blk, dv]
    o = jnp.moveaxis(o, 0, 1).reshape(t, n_h * dv)
    return _dot(o, lp["o_w"]), doc


def route(scores, bias, cfg):
    """The published router on sigmoid scores ``[T, E]``: the chosen
    experts ``[T, k]`` and what the near-tie rule needs."""
    t, e = scores.shape
    n_group, topk_group, k = (cfg["n_group"], cfg["topk_group"],
                              cfg["num_experts_per_tok"])
    sel = scores + bias
    per = sel.reshape(t, n_group, e // n_group)
    gs = jax.lax.top_k(per, 2)[0].sum(-1)                      # [T, G]
    g_sorted = jnp.sort(gs, -1)[:, ::-1]
    g4 = g_sorted[:, topk_group - 1]
    g5 = g_sorted[:, topk_group] if topk_group < n_group \
        else jnp.full((t,), -jnp.inf)
    kept = jnp.zeros((t, n_group), bool).at[
        jnp.arange(t)[:, None], jax.lax.top_k(gs, topk_group)[1]].set(True)
    masked = jnp.where(jnp.repeat(kept, e // n_group, -1), sel, -jnp.inf)
    top, idx = jax.lax.top_k(masked, k)
    return idx, {"sel": sel, "t8": top[:, k - 1], "gs": gs,
                 "g4": g4, "g5": g5}


def _adopt(idx, info, sys_idx, cfg, delta):
    """The near-tie rule for routing: the system's choice where it
    differs only by ambiguous experts (within ``delta`` of the 8th
    selection score, or in a group within ``delta`` of the group
    boundary).  Returns ``(chosen, adopted, mismatch, need)``."""
    t, e = info["sel"].shape
    per_group = e // cfg["n_group"]
    hot = lambda i: jnp.zeros((t, e), bool).at[
        jnp.arange(t)[:, None], i].set(True)
    diff = hot(idx) ^ hot(sys_idx)
    by_group = jnp.maximum(
        (info["g4"] - info["g5"])[:, None],
        jnp.minimum(jnp.abs(info["gs"] - info["g4"][:, None]),
                    jnp.abs(info["gs"] - info["g5"][:, None])))
    need = jnp.minimum(jnp.abs(info["sel"] - info["t8"][:, None]),
                       jnp.repeat(by_group, per_group, -1))
    need = jnp.where(diff, need, 0.0).max(-1)
    differs = diff.any(-1)
    ok = need <= delta
    adopted = differs & ok
    return (jnp.where(adopted[:, None], sys_idx, idx), adopted,
            differs & ~ok, need)


def moe(lp, x, cfg, sys_idx=None, delta=0.0):
    """Routed experts (this chip's share) + the shared expert over
    tokens ``[T, C]``, an expert at a time.  Returns ``(y, doc)``."""
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
    "ffn", "cfg_key", "route_delta", "select_delta", "compute_as"))
def _layer(lp, x, sys_idx, sys_rows, ffn, cfg_key, route_delta,
           select_delta, compute_as=None):
    cfg = _CFGS[cfg_key]
    _ROUND[0] = compute_as
    try:
        with jax.default_matmul_precision("highest"):
            h = _rms(x, _f32(lp["ln1_g"]))
            y, sel_doc = attention(lp["attn"], lp["idx"], h, cfg, sys_rows,
                                   select_delta)
            x = x + y
            h = _rms(x, _f32(lp["ln2_g"]))
            if ffn == "dense":
                return x + _swiglu(h, lp["mlp"]["gu_w"],
                                   lp["mlp"]["down_w"]), None, sel_doc
            y, doc = moe(lp["moe"], h, cfg, sys_idx, route_delta)
            return x + y, doc, sel_doc
    finally:
        _ROUND[0] = None


_CFGS = {}


def ffn_kinds(cfg):
    """"dense" or "moe" of every kept layer, by its PUBLISHED index."""
    return ["dense" if l < cfg["first_k_dense_replace"] else "moe"
            for l in cfg["layers"]]


def forward(w, tokens, cfg, sys_experts=None, sys_selected=None,
            route_delta=0.0, select_delta=0.0, rows=None, compute_as=None):
    """tokens int[T] -> ``(logits float32[T or len(rows), V], routing,
    selection)``.

    ``w`` is the parameter tree as the program stores it (bfloat16
    leaves are upcast here, a block at a time).  ``sys_experts``: the
    system's chosen experts, one ``[T, k]`` array per MoE layer;
    ``sys_selected``: its selected rows, one int32 ``[T, index_topk]``
    array per layer (position ``t``'s first ``min(t + 1, index_topk)``
    entries count); ``rows``: the positions whose logits are wanted.
    ``routing`` is one doc per MoE layer, ``selection`` one per layer
    (``adopted``, ``mismatch``, ``need`` per position).  ``compute_as``:
    a dtype name every matrix and every matmul's input is rounded to
    first (the router, the embedding and the head stay float32)."""
    key = repr(sorted((k, repr(v)) for k, v in cfg.items()))
    _CFGS[key] = cfg
    x = _f32(w["wte"][jnp.asarray(tokens)])
    routing, selection, m = [], [], 0
    for i, (lp, ffn) in enumerate(zip(w["layers"], ffn_kinds(cfg))):
        sys_idx = sys_rows = None
        if ffn == "moe" and sys_experts is not None:
            sys_idx = jnp.asarray(sys_experts[m], jnp.int32)
        if sys_selected is not None:
            sys_rows = jnp.asarray(sys_selected[i], jnp.int32)
        x, doc, sel_doc = _layer(lp, x, sys_idx, sys_rows, ffn, key,
                                 float(route_delta), float(select_delta),
                                 compute_as)
        selection.append(sel_doc)
        if doc is not None:
            routing.append(doc)
            m += 1
    with jax.default_matmul_precision("highest"):
        x = _rms(x, _f32(w["lnf_g"]))
        if rows is not None:
            x = x[jnp.asarray(rows)]
        return x @ _f32(w["head"]).T, routing, selection
