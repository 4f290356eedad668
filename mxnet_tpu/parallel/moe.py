"""Mixture-of-experts with expert parallelism (``ep`` mesh axis).

No MoE exists in the reference; this is the TPU-native capability the task
brief requires (EP via ``lax.all_to_all`` routing).  GShard-style dense
dispatch: top-k gating with a capacity bound produces a dispatch tensor,
one all_to_all moves token slots to their expert's device, each device runs
its local experts as one batched matmul (MXU-friendly — no gather loops),
and a second all_to_all brings results home for the weighted combine.

Beside it, the expert layer of a SHARE (:func:`grouped_topk_route` or
:func:`softmax_topk_route`, :func:`held_experts_ffn`): top-k over ALL
experts with no capacity and no dropped token, computed for the
contiguous range of experts this chip holds, at a cost that follows the
tokens each held expert got.  It is what expert parallelism asks of one
chip; the exchange that would bring other chips' tokens here is not part
of it.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from ._shard_map import shard_map

from .collectives import axis_size
from .mesh import AXIS_EP
from ..telemetry import device_scope


def top1_gating(logits, capacity):
    """Top-1 gating with capacity. logits [T, E] → (combine, dispatch).

    combine: [T, E, C] float weights; dispatch: [T, E, C] bool mask.
    Tokens overflowing an expert's capacity are dropped (GShard semantics).
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)                     # [T]
    gate = jnp.take_along_axis(probs, expert[:, None], 1)[:, 0]
    onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)   # [T, E]
    pos = jnp.cumsum(onehot, axis=0) - onehot               # position in queue
    pos_in_expert = jnp.sum(pos * onehot, axis=-1)          # [T]
    keep = pos_in_expert < capacity
    gate = gate * keep
    cap_onehot = jax.nn.one_hot(pos_in_expert.astype(jnp.int32), capacity,
                                dtype=jnp.float32)          # [T, C]
    dispatch = onehot[:, :, None] * cap_onehot[:, None, :] * keep[:, None, None]
    combine = dispatch * gate[:, None, None]
    return combine, dispatch


def moe_dense(x, gate_w, w1, b1, w2, b2, capacity_factor=2.0,
              act=jax.nn.relu):
    """Single-device MoE FFN (no collectives): the same GShard top-1
    gating + capacity math as ``_moe_local`` with every expert local —
    the flagship's MoE blocks use this off-mesh, and it equals the
    ep-sharded form exactly when capacity doesn't bind (e.g.
    ``capacity_factor >= num_experts``).  x [T, D] -> [T, D]."""
    t, d = x.shape
    e = w1.shape[0]
    capacity = max(1, int(capacity_factor * t / e))
    logits = jnp.dot(x, gate_w, preferred_element_type=jnp.float32)
    combine, dispatch = top1_gating(logits, capacity)
    slots = jnp.einsum("tec,td->ecd", dispatch, x)
    h = jnp.einsum("ecd,edf->ecf", slots, w1,
                   preferred_element_type=jnp.float32) + b1[:, None, :]
    h = act(h)
    y = jnp.einsum("ecf,efd->ecd", h, w2,
                   preferred_element_type=jnp.float32) + b2[:, None, :]
    return jnp.einsum("tec,ecd->td", combine, y).astype(x.dtype)


def _moe_local(x, gate_w, w1, b1, w2, b2, axis, capacity_factor, act):
    """Inside shard_map.  x: [T_local, D]; experts sharded: w1 [E_local,...]."""
    n = axis_size(axis)
    t, d = x.shape
    e_local = w1.shape[0]
    e = e_local * n
    capacity = max(1, int(capacity_factor * t / e))

    logits = jnp.dot(x, gate_w, preferred_element_type=jnp.float32)  # [T, E]
    combine, dispatch = top1_gating(logits, capacity)

    # [T, E, C] x [T, D] → [E, C, D]: expert-major slots for this shard
    slots = jnp.einsum("tec,td->ecd", dispatch, x)
    # all_to_all: split expert dim across devices, concat their slots —
    # afterwards each device holds [E_local, C*n, D]: every device's slots
    # for MY experts.
    slots = slots.reshape(n, e_local * capacity, d)
    recv = lax.all_to_all(slots, axis, split_axis=0, concat_axis=0,
                          tiled=True)                    # [n*E_local*C, D]
    recv = recv.reshape(n, e_local, capacity, d)
    recv = recv.transpose(1, 0, 2, 3).reshape(e_local, n * capacity, d)

    # batched expert FFN — one big MXU matmul per projection
    h = jnp.einsum("egd,edf->egf", recv, w1,
                   preferred_element_type=jnp.float32) + b1[:, None, :]
    h = act(h)
    y = jnp.einsum("egf,efd->egd", h, w2,
                   preferred_element_type=jnp.float32) + b2[:, None, :]

    # route back: inverse of the dispatch all_to_all
    y = y.reshape(e_local, n, capacity, d).transpose(1, 0, 2, 3)
    y = y.reshape(n * e_local * capacity, d)
    back = lax.all_to_all(y.reshape(n, e_local * capacity, d), axis,
                          split_axis=0, concat_axis=0, tiled=True)
    back = back.reshape(e, capacity, d)
    return jnp.einsum("tec,ecd->td", combine, back).astype(x.dtype)


def moe_apply(x, gate_w, w1, b1, w2, b2, mesh=None, axis=AXIS_EP,
              capacity_factor=2.0, act=jax.nn.relu, batch_axis=None):
    """MoE FFN. Global shapes: x [T, D]; gate_w [D, E]; w1 [E, D, F];
    b1 [E, F]; w2 [E, F, D]; b2 [E, D].  Tokens sharded over ``axis``
    (and ``batch_axis`` when composing with dp), experts over ``axis``."""
    if mesh is None:
        return _moe_local(x, gate_w, w1, b1, w2, b2, axis, capacity_factor,
                          act)
    fn = functools.partial(_moe_local, axis=axis,
                           capacity_factor=capacity_factor, act=act)
    tok = (batch_axis, axis) if batch_axis else axis
    return shard_map(
        fn, mesh=mesh,
        in_specs=(P(tok, None), P(None, None), P(axis, None, None),
                  P(axis, None), P(axis, None, None), P(axis, None)),
        out_specs=P(tok, None), check_rep=False)(
            x, gate_w, w1, b1, w2, b2)


class MoELayer:
    """Parameter container + init for `moe_apply` (functional style)."""

    def __init__(self, dim, hidden, num_experts, capacity_factor=2.0):
        self.dim, self.hidden = dim, hidden
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor

    def init(self, key):
        kg, k1, k2 = jax.random.split(key, 3)
        scale = self.dim ** -0.5
        return {
            "gate_w": jax.random.normal(kg, (self.dim, self.num_experts)) * scale,
            "w1": jax.random.normal(k1, (self.num_experts, self.dim,
                                         self.hidden)) * scale,
            "b1": jnp.zeros((self.num_experts, self.hidden)),
            "w2": jax.random.normal(k2, (self.num_experts, self.hidden,
                                         self.dim)) * (self.hidden ** -0.5),
            "b2": jnp.zeros((self.num_experts, self.dim)),
        }

    def __call__(self, params, x, mesh=None, axis=AXIS_EP):
        return moe_apply(x, params["gate_w"], params["w1"], params["b1"],
                         params["w2"], params["b2"], mesh=mesh, axis=axis,
                         capacity_factor=self.capacity_factor)


# ---------------------------------------------------------------------------
# the share of one chip: route over all experts, compute the held ones
# ---------------------------------------------------------------------------

def grouped_topk_route(x, router_w, router_b, n_group, topk_group, k,
                       scale):
    """Sigmoid router with group-limited top-k (DeepSeek-V3 form).

    ``x`` [T, C]; ``router_w`` [C, E]; ``router_b`` [E] is the expert
    bias that steers SELECTION only.  Scores ``s = sigmoid(x W)`` in
    float32; the ``E`` experts form ``n_group`` equal groups, a group's
    score is the sum of its top 2 ``s + b``, the best ``topk_group``
    groups are kept and the top ``k`` experts taken inside them.  The
    weights are ``s`` (without ``b``) of the chosen, normalised to sum
    1, times ``scale``.

    Returns ``(experts int32 [T, k], weights float32 [T, k])``.  No
    token is dropped: every token gets exactly ``k`` experts whatever
    the imbalance.
    """
    t = x.shape[0]
    e = router_w.shape[1]
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    sel = scores + router_b.astype(jnp.float32)
    per = sel.reshape(t, n_group, e // n_group)
    group_score = lax.top_k(per, 2)[0].sum(-1)              # [T, G]
    kept = jnp.zeros((t, n_group), bool).at[
        jnp.arange(t)[:, None],
        lax.top_k(group_score, topk_group)[1]].set(True)
    masked = jnp.where(kept[:, :, None], per, -jnp.inf).reshape(t, e)
    experts = lax.top_k(masked, k)[1].astype(jnp.int32)
    w = jnp.take_along_axis(scores, experts, axis=-1)
    return experts, w / w.sum(-1, keepdims=True) * scale


def softmax_topk_route(x, router_w, k):
    """Softmax router over all experts (Qwen3-MoE form,
    ``norm_topk_prob``).

    ``x`` [T, C]; ``router_w`` [C, E].  Probabilities ``p = softmax(x
    W)`` over all ``E`` experts in float32; the ``k`` largest are
    chosen and their weights are their ``p`` renormalised to sum 1 over
    the chosen.  No bias, no groups, no scale.

    Returns ``(experts int32 [T, k], weights float32 [T, k])``, as
    :func:`grouped_topk_route`.
    """
    probs = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST), axis=-1)
    w, experts = lax.top_k(probs, k)
    return experts.astype(jnp.int32), w / w.sum(-1, keepdims=True)


def _round_up(n, to):
    return -(-n // to) * to


def _worst_rows(m, num_held, tile_rows):
    """Rows of a layout that holds every assignment on one held expert."""
    return _round_up(m + num_held * (tile_rows - 1), tile_rows)


class _Runs(NamedTuple):
    """The stable sort by held expert that every size of the layout
    shares, and each held expert's run in it."""
    order: jax.Array       # [M] the assignments in sorted order
    counts: jax.Array      # [num_held] an expert's assignments
    first: jax.Array       # [num_held] its first place in sorted order
    row_first: jax.Array   # [num_held] its first row of the layout
    tile_end: jax.Array    # [num_held] the tiles up to and incl. its last


def _sorted_runs(local_expert, num_held, tile_rows):
    counts = jnp.zeros(num_held + 1, jnp.int32).at[local_expert].add(1)
    counts = counts[:num_held]
    padded = -(-counts // tile_rows) * tile_rows
    order = jnp.argsort(local_expert, stable=True).astype(jnp.int32)
    return _Runs(order, counts, jnp.cumsum(counts) - counts,
                 jnp.cumsum(padded) - padded,
                 jnp.cumsum(padded) // tile_rows)


def _tile_experts(tile_end, num_held, rows, tile_rows):
    """The expert of each of a layout's ``rows // tile_rows`` tiles; a
    tile past the last valid one names that one's expert, so the kernel
    fetches nothing for it."""
    n_valid = tile_end[-1:]
    tiles = jnp.arange(rows // tile_rows, dtype=jnp.int32)
    tile_expert = jnp.searchsorted(
        tile_end, jnp.minimum(tiles, jnp.maximum(n_valid - 1, 0)),
        side="right")
    return jnp.minimum(tile_expert, num_held - 1).astype(jnp.int32)


def _gathered_rows(x, runs, tile_expert, tile_rows, k, dtype):
    """The layout's rows, gathered: each row's assignment is its place
    in sorted order read through ``order`` (its token that over ``k``),
    and a row past its expert's count is a pad row, zero."""
    with device_scope("moe.route"):
        # by tile, then broadcast over its rows: the one gather of
        # ``rows`` scalars is the one through ``order``
        tiles = tile_expert.shape[0]
        row = jnp.arange(tiles * tile_rows, dtype=jnp.int32).reshape(
            tiles, tile_rows)
        offset = row - runs.row_first[tile_expert][:, None]
        live = (offset < runs.counts[tile_expert][:, None]).reshape(-1)
        place = (runs.first[tile_expert][:, None] + offset).reshape(-1)
        token = runs.order[jnp.clip(place, 0, runs.order.shape[0] - 1)] \
            // k
    # the layout's construction keeps the scope's name (it was a scatter)
    with device_scope("moe.scatter"):
        return jnp.where(live[:, None], x.astype(dtype)[token], 0)


def _assignment_rows(local_expert, runs, num_held, rows):
    """The layout's row of each assignment (``dest``), ``rows`` for an
    assignment that gets none."""
    sorted_e = local_expert[runs.order]
    at = jnp.minimum(sorted_e, num_held - 1)
    rank = jnp.arange(local_expert.shape[0], dtype=jnp.int32) \
        - runs.first[at]
    dest_sorted = jnp.where(sorted_e < num_held,
                            runs.row_first[at] + rank, rows)
    return jnp.zeros(local_expert.shape[0], jnp.int32).at[runs.order].set(
        dest_sorted.astype(jnp.int32))


def expert_tiles(local_expert, num_held, tile_rows):
    """Lay ``M`` assignments out for :func:`moe_gmm`: sorted by held
    expert, each expert's rows padded to whole tiles of ``tile_rows``.

    ``local_expert`` int32 [M]: the held expert's index, or ``num_held``
    for an assignment that goes to an expert held elsewhere (it gets no
    row).  Returns ``(dest [M] — the assignment's row, ``rows`` for
    none; tile_expert [rows // tile_rows]; n_valid [1]; counts
    [num_held]; rows)`` with ``rows`` the static size of the layout,
    enough for every assignment to land on one expert.  A smaller
    layout that holds ``n_valid`` tiles is this one's first rows.
    """
    rows = _worst_rows(local_expert.shape[0], num_held, tile_rows)
    runs = _sorted_runs(local_expert, num_held, tile_rows)
    return (_assignment_rows(local_expert, runs, num_held, rows),
            _tile_experts(runs.tile_end, num_held, rows, tile_rows),
            runs.tile_end[-1:].astype(jnp.int32), runs.counts, rows)


def expert_layout_rows(m, num_held, num_experts, tile_rows):
    """Rows of the layout :func:`held_experts_ffn` builds for ``m``
    assignments routed over ``num_experts`` experts of which
    ``num_held`` are held: twice the assignments the held experts can
    expect, plus every held expert's last partial tile, in whole tiles.
    Twice the mean, as :func:`expert_tile_rows` takes it.  A step whose
    tiles need more runs at the worst case's size (every assignment on
    one held expert: ``m + num_held * (tile_rows - 1)``), so no
    assignment is dropped; where this is not less than that, the worst
    case is the only size."""
    expect = -(-m * num_held // num_experts)
    return _round_up(2 * expect + num_held * (tile_rows - 1), tile_rows)


def expert_tile_rows(m, num_experts):
    """Height of a row tile for ``m`` assignments routed over
    ``num_experts`` experts: the power of two that holds twice the rows
    an expert can expect, between 16 (the smallest bf16 tile) and 256.

    :func:`moe_gmm` reads a tile's expert's weights once a TILE, so an
    expert's weights cross HBM once a run only while its rows fit one
    tile: a decode step's few rows an expert take 16, a prompt of 1,024
    rows over 512 experts 32, a chunk of 2,048 rows over 256 experts
    128, over 128 experts 256.  Twice the mean, because a full chunk
    gives half the experts more than the mean and an uneven router some
    of them far more.  Not taller than that, and never past 256: the
    MXU multiplies a tile's pad rows too, the layout (``m + held *
    (tile_rows - 1)`` rows) grows with the height, and at 256 rows a
    tile's matmul already takes as long as its weights' read (measured
    on a v5e at the served widths: CHANGES.md, PR 35).
    """
    twice = -(-2 * m // num_experts)
    return min(max(16, 1 << (twice - 1).bit_length()), 256)


def swiglu_activation(gu, limit=None):
    """``silu(gate) * up`` of ``gu`` = ``[gate | up]`` on its last axis;
    with a ``limit``, ``silu(min(gate, limit)) * clip(up, -limit,
    limit)`` (a clamped SwiGLU)."""
    half = gu.shape[-1] // 2
    if limit is None:
        return jax.nn.silu(gu[..., :half]) * gu[..., half:]
    return jax.nn.silu(jnp.minimum(gu[..., :half], limit)) \
        * jnp.clip(gu[..., half:], -limit, limit)


def held_experts_ffn(x, experts, weights, gu_w, down_w, first,
                     num_experts, tile_rows=None, layout_rows=None,
                     limit=None):
    """The held experts' part of ``sum_e w_e down_e(silu(gate_e x) *
    up_e x)`` for tokens ``x`` [T, C] routed by
    :func:`grouped_topk_route` (or :func:`softmax_topk_route`) over
    ``num_experts`` experts (a clamped
    SwiGLU where ``limit`` is given: :func:`swiglu_activation`).

    ``gu_w`` [E_held, C, 2F] (gate columns, then up), ``down_w``
    [E_held, F, C]: the matrices of experts ``first .. first + E_held``
    of the router's range.  Terms of experts held elsewhere are left
    out; the weights stay as normalised over all ``k``.  ``tile_rows``
    is :func:`expert_tile_rows`'s and ``layout_rows``
    :func:`expert_layout_rows`'s unless a test gives its own.

    The layout is GATHERED from ``x`` by its rows (a row's token from
    the sort, a pad row zero): its cost follows the rows it holds, not
    the assignments.  It holds ``layout_rows`` rows; a step whose tiles
    need more takes the worst case's size under a ``lax.cond``.
    Returns ``(y float32 [T, C], stats)`` with ``stats`` the step's
    counts as float32 scalars: held experts hit, local assignments, the
    most tokens one held expert got, the row tiles that hold a token
    (``weight_tiles``: how often an expert's weights crossed HBM) and
    ``layout_overflow`` (1 where the worst case's size ran).
    """
    from ..ops.pallas.grouped_matmul import moe_gmm

    t, c = x.shape
    k = experts.shape[1]
    num_held, _, two_f = gu_w.shape
    m = t * k
    if tile_rows is None:
        tile_rows = expert_tile_rows(m, num_experts)
    if layout_rows is None:
        layout_rows = expert_layout_rows(m, num_held, num_experts,
                                         tile_rows)
    worst = _worst_rows(m, num_held, tile_rows)
    with device_scope("moe.route"):
        local = experts.reshape(m) - first
        is_local = (local >= 0) & (local < num_held)
        local = jnp.where(is_local, local, num_held).astype(jnp.int32)
        runs = _sorted_runs(local, num_held, tile_rows)
        dest = _assignment_rows(local, runs, num_held, worst)
        counts, n_valid = runs.counts, runs.tile_end[-1:]

    def layer(rows):
        with device_scope("moe.route"):
            tile_expert = _tile_experts(runs.tile_end, num_held, rows,
                                        tile_rows)
        x_rows = _gathered_rows(x, runs, tile_expert, tile_rows, k,
                                gu_w.dtype)
        # float32 between the two matmuls and after them: the only
        # rounding to the stored type is of each matmul's input, as in a
        # dense MLP
        with device_scope("moe.experts"):
            gu = moe_gmm(x_rows, gu_w, tile_expert, n_valid, tile_rows,
                         out_dtype=jnp.float32)
            act = swiglu_activation(gu, limit).astype(gu_w.dtype)
            y_rows = moe_gmm(act, down_w, tile_expert, n_valid, tile_rows,
                             out_dtype=jnp.float32)
        with device_scope("moe.combine"):
            y = jnp.where(is_local[:, None],
                          y_rows[jnp.minimum(dest, rows - 1)], 0.0) \
                * weights.reshape(m, 1)
            return y.reshape(t, k, c).sum(1)

    if layout_rows >= worst:
        y, overflow = layer(worst), jnp.zeros((), bool)
    else:
        overflow = n_valid[0] * tile_rows > layout_rows
        y = lax.cond(overflow, lambda: layer(worst),
                     lambda: layer(layout_rows))
    with device_scope("moe.route"):
        stats = {"experts_hit": (counts > 0).sum().astype(jnp.float32),
                 "local_assignments": counts.sum().astype(jnp.float32),
                 "max_tokens_per_expert": counts.max().astype(jnp.float32),
                 "weight_tiles": n_valid[0].astype(jnp.float32),
                 "layout_overflow": overflow.astype(jnp.float32)}
    return y, stats
