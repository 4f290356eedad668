"""What the served decoder models of this zoo share: the small layer
functions over a parameter tree (``ling3.py``, ``deepseek_v32.py``,
``exaone_moe.py``) and the page-at-a-time write of a slot's rows
(``gpt.py`` too).

Every function takes arrays, not a net: bfloat16 weights as stored,
float32 residual stream, float32 accumulation.  A rotary embedding takes
its FREQUENCIES as an argument (plain powers of theta, or YaRN's blend),
so a model states its own and the rotation is one function.
"""
from __future__ import annotations

import numpy as _np

from ...telemetry import device_scope

EPS = 1e-6
#: a latent row is padded to a multiple of this many lanes
LATENT_ALIGN = 128

#: the decode program's last output: a float32 vector of these counts,
#: summed over the expert layers of one step
MOE_STATS = ("experts_hit", "local_assignments",
             "max_tokens_per_expert", "assignments", "held_experts",
             "expert_layers", "weight_tiles")


def latent_width(cfg):
    w = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return -(-w // LATENT_ALIGN) * LATENT_ALIGN


def init_seeded(net, inits, seed):
    """Seeded values in the stored type, made on the device one
    parameter at a time (a share's expert stack is gigabytes):
    normal(0, 0.02), norm gains 1, biases 0.  ``inits``: parameter name
    -> "normal" / "ones" / "zeros"; ``seed``: a whole number or a PRNG
    key."""
    import jax
    import jax.numpy as jnp
    from ...ndarray import NDArray

    def make(key, shape, dtype, init):
        if init == "normal":
            return (0.02 * jax.random.normal(key, shape, jnp.float32)) \
                .astype(dtype)
        return (jnp.ones if init == "ones" else jnp.zeros)(shape, dtype)

    make = jax.jit(make, static_argnums=(1, 2, 3))
    key = seed if hasattr(seed, "shape") \
        else jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)
    for i, p in enumerate(net.collect_params().values()):
        p.set_data(NDArray(make(
            jax.random.fold_in(key, i), tuple(p.shape),
            jnp.dtype(p.dtype).name, inits[p.name])))
    return net


def param_tree(shapes, n_layers, groups, leaf):
    """The parameter tree the programs take, by layer, from the flat
    ``{path: (shape, init)}``: ``l<i>_<group>_<name>`` lands at
    ``layers[i][group][name]`` (``gamma`` shortened to ``g``,
    ``router_bias`` to ``router_b``), with ``leaf(path, shape)`` at
    every parameter (the net's live arrays, or shapes for a compile
    without weights)."""
    def g(path):
        return leaf(path, shapes[path][0])

    layers = []
    for i in range(n_layers):
        pre = "l%d_" % i
        names = [k[len(pre):] for k in shapes if k.startswith(pre)]
        lp = {"ln1_g": g(pre + "ln1_gamma"), "ln2_g": g(pre + "ln2_gamma")}
        for group in groups:
            sub = {n[len(group) + 1:].replace("gamma", "g")
                   .replace("router_bias", "router_b"): g(pre + n)
                   for n in names if n.startswith(group + "_")}
            if sub:
                lp[group] = sub
        layers.append(lp)
    return {"wte": g("wte"), "head": g("head"), "lnf_g": g("lnf_gamma"),
            "layers": layers}


def mm(x, w):
    """``x @ w`` with the activation in the weight's stored type and
    float32 accumulation."""
    import jax.numpy as jnp
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def rms(x, g, eps=EPS):
    import jax
    import jax.numpy as jnp
    with device_scope("norm"):
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
            * g.astype(jnp.float32)


def rope_inv_freq(theta, half):
    """Plain rotary frequencies: ``theta ** (-i / half)``."""
    import jax.numpy as jnp
    return jnp.float32(theta) ** (
        -jnp.arange(half, dtype=jnp.float32) / half)


def yarn_inv_freq(dim, theta, factor, original_max, beta_fast, beta_slow):
    """YaRN's frequencies for ``dim`` rotary values (float32 [dim / 2]):
    the plain ones where a pair turns more than ``beta_fast`` times over
    the original context, divided by ``factor`` where it turns fewer
    than ``beta_slow`` times, a linear blend between."""
    half = dim // 2
    freqs = 1.0 / theta ** (_np.arange(half, dtype=_np.float64) / half)

    def correction_dim(turns):
        return dim * _np.log(original_max / (turns * 2 * _np.pi)) \
            / (2 * _np.log(theta))

    low = max(_np.floor(correction_dim(beta_fast)), 0)
    high = min(_np.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    smooth = 1 - _np.clip((_np.arange(half) - low) / (high - low), 0, 1)
    return (freqs / factor * (1 - smooth) + freqs * smooth) \
        .astype(_np.float32)


def rope(x, pos, inv_freq, interleaved=False):
    """Rotary embedding on the last axis at frequencies ``inv_freq``
    [last / 2]; ``pos`` indexes the first axis.  Pairs are ``(i, i +
    half)`` (rotate-half) or, ``interleaved``, ``(2i, 2i + 1)``."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if x.ndim == 3:
        cos, sin = cos[:, None, :], sin[:, None, :]
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         -1).reshape(x.shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def head(x, w):
    """Logits over the vocabulary slice: ``x @ w.T`` for ``w`` stored
    ``[vocab, units]``."""
    import jax.numpy as jnp
    from jax import lax
    return lax.dot_general(x.astype(w.dtype), w, (((x.ndim - 1,), (1,)),
                                                  ((), ())),
                           preferred_element_type=jnp.float32)


def swiglu(x, gu_w, down_w):
    import jax
    gu = mm(x, gu_w)
    half = gu.shape[-1] // 2
    return mm(jax.nn.silu(gu[..., :half]) * gu[..., half:], down_w)


def moe(lp, x, cfg, valid=None):
    """Routed experts (held share) + shared expert.  ``valid`` bool [T]
    marks the rows that are tokens: a pad row (a chunk's tail, an empty
    slot) is routed like any row and given to NO held expert, so what it
    would have chosen costs no expert a row and counts in no statistic
    but ``assignments``.  Returns ``(y, experts [T, k], stats)``."""
    import jax.numpy as jnp
    from ...parallel import moe as _moe
    with device_scope("moe"):
        with device_scope("moe.route"):
            experts, weights = _moe.grouped_topk_route(
                x, lp["router_w"], lp["router_b"], cfg["n_group"],
                cfg["topk_group"], cfg["num_experts_per_tok"],
                cfg["routed_scaling_factor"])
            local = experts if valid is None \
                else jnp.where(valid[:, None], experts, -1)
        y, stats = _moe.held_experts_ffn(
            x, local, weights, lp["gu_w"], lp["down_w"],
            cfg["experts_held"][0], lp["router_w"].shape[1])
        with device_scope("moe.shared"):
            return y + swiglu(x, lp["sh_gu_w"], lp["sh_down_w"]), \
                experts, stats


def moe_stats_vector(stats, n_assign, held):
    import jax.numpy as jnp
    if not stats:
        return jnp.zeros(len(MOE_STATS), jnp.float32)
    return jnp.stack([
        sum(s["experts_hit"] for s in stats),
        sum(s["local_assignments"] for s in stats),
        sum(s["max_tokens_per_expert"] for s in stats),
        jnp.float32(n_assign * len(stats)),
        jnp.float32(held * len(stats)), jnp.float32(len(stats)),
        sum(s["weight_tiles"] for s in stats)])


def latent_rows(c, k_rope, width, dtype):
    """The cached row of a latent-attention layer: ``[c | k_rope |
    zeros]`` in ``width`` lanes."""
    import jax.numpy as jnp
    rows = jnp.concatenate([c, k_rope], -1)
    return jnp.pad(rows, ((0, 0), (0, width - rows.shape[1]))).astype(dtype)


def rows_per_block(t, want):
    """The largest divisor of ``t`` that is at most ``want``: the rows
    a block of a chunk's attention takes at a time."""
    r = min(want, t)
    while t % r:
        r -= 1
    return r


def page_scatter(pool, block_table_row, rows, start, n_rows):
    """Write ONE slot's consecutive rows into a full-precision page
    pool with one update a PAGE, not one a row.

    ``pool``: [num_pages, page_size, K_kv * D]; ``rows``: [T, K_kv, D],
    the slot's positions ``start .. start + T`` in order, of which the
    first ``n_rows`` are real (both traced scalars); ``block_table_row``:
    int32 [max_pages_per_seq].  The rows are laid out as the slot's
    consecutive pages (shifted down by ``start % page_size`` into a
    zeroed buffer of ``ceil((T + page_size - 1) / page_size)`` pages,
    block ``j`` being the page that holds position ``(start //
    page_size + j) * page_size``) and the pool takes one scatter of
    whole pages: a page is a whole number of the chip's tiles, a row a
    sublane of one, and the scatter costs by the update.

    - a block with no real row (all past ``start + n_rows``, which
      includes every block past the block table's end) goes to scratch
      page 0, as zeros;
    - the head page keeps its rows below ``start % page_size`` (the
      prefix rows of a copy-on-write page): they are read back from the
      pool and written again as they are;
    - the tail page's rows past ``start + n_rows`` are written as
      ZEROS: the page is this slot's alone, no kernel reads a row at or
      past a slot's context, and the decode steps write them one by one.

    Every real row lands bit for bit where ``pool.at[phys, offs].set``
    put it.  Returns the new pool."""
    import jax.numpy as jnp
    from jax import lax
    t, page_size = rows.shape[0], pool.shape[1]
    shift = start % page_size
    n_blocks = -(-(t + page_size - 1) // page_size)
    block = jnp.arange(n_blocks)
    pages = jnp.where(
        block * page_size < shift + n_rows,
        jnp.take(block_table_row, start // page_size + block, mode="clip"),
        0)
    x = jnp.where((jnp.arange(t) < n_rows)[:, None], rows.reshape(t, -1),
                  0).astype(pool.dtype)
    blocks = lax.dynamic_update_slice_in_dim(
        jnp.zeros((n_blocks * page_size, x.shape[1]), pool.dtype),
        x, shift, 0).reshape(n_blocks, page_size, -1)
    head = jnp.where((jnp.arange(page_size) < shift)[:, None],
                     pool[pages[0]], blocks[0])
    return pool.at[pages].set(blocks.at[0].set(head))
