"""The per-channel gated delta rule (Kimi delta attention) on a
per-slot recurrent state: one decode step as a Pallas TPU kernel that
updates the state IN PLACE, and the chunked form for a whole prompt in
XLA ops.

A head's state ``S`` is ``[Dk, Dv]`` float32.  One token::

    S <- Diag(a) S;   S <- S + k (beta (v - S^T k))^T;   o = S^T q

with ``a = exp(g)`` in (0, 1] a key channel.  Written on the state as
it was before the decay: ``u = beta v - S^T (beta a k)``, then
``S <- Diag(a) S + k u^T``.

``kda_step`` holds every slot's state as ``[slots, H, Dk, Dv]``; a grid
cell owns ``heads_per_cell`` heads of one slot, reads their state
once and writes it once onto the same buffer
(``input_output_aliases``), all on the VPU in float32: the kernel is
bound by the state's bytes.  The five vectors a head needs arrive as
one ``[S, 5, H, D]`` operand (``a, k, q, beta a k, beta v``); those that
scale the state's rows are transposed in the cell.  A slot that is not
live is passed ``a = 1, k = 0``: its state is written back unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .flash_attention import _pallas_call, _pl

_HIGH = lax.Precision.HIGHEST


def _kda_step_kernel(vec_ref, s_ref, o_ref, s_out_ref, *, hb):
    # rows of the state are key channels: the vectors that scale rows
    # are needed as columns
    a_t = vec_ref[0, 0].T                                   # (D, hb)
    k_t = vec_ref[0, 1].T
    q_t = vec_ref[0, 2].T
    akb_t = vec_ref[0, 3].T
    vb = vec_ref[0, 4]                                      # (hb, D)
    for i in range(hb):
        s = s_ref[0, i]                                     # (Dk, Dv)
        u = vb[i:i + 1] - jnp.sum(s * akb_t[:, i:i + 1], axis=0,
                                  keepdims=True)            # (1, Dv)
        s = s * a_t[:, i:i + 1] + k_t[:, i:i + 1] * u
        s_out_ref[0, i] = s
        o_ref[0, i:i + 1, :] = jnp.sum(s * q_t[:, i:i + 1], axis=0,
                                       keepdims=True)


def _step_vectors(q, k, v, g, beta, active):
    a = jnp.exp(g)
    if active is not None:
        live = active[:, None, None]
        a = jnp.where(live, a, 1.0)
        k = jnp.where(live, k, 0.0)
    b = beta[..., None]
    return jnp.stack([a, k, q, b * a * k, b * v], axis=1) \
        .astype(jnp.float32)                                # [S, 5, H, D]


def kda_step(state, q, k, v, g, beta, active=None, heads_per_cell=8):
    """One token for every slot.

    - ``state``: float32 [R, H, Dk, Dv], ``R >= S`` (rows past ``S``,
      the engine's scratch row, are left as they are) — donated by the
      caller's jit and updated in place;
    - ``q, k, v, g``: [S, H, D] (``q``, ``k`` already normalised, ``g``
      the log decay <= 0); ``beta``: [S, H]; ``active``: bool [S].

    Returns ``(o float32 [S, H, Dv], new_state)``.
    """
    pl = _pl()
    s_n, h, d = q.shape
    hb = heads_per_cell if h % heads_per_cell == 0 else h
    vecs = _step_vectors(q, k, v, g, beta, active)
    vec_spec = pl.BlockSpec((1, 5, hb, d), lambda s, j: (s, 0, j, 0))
    state_spec = pl.BlockSpec((1, hb, d, state.shape[3]),
                              lambda s, j: (s, j, 0, 0))
    o, new_state = _pallas_call(
        functools.partial(_kda_step_kernel, hb=hb),
        [vecs, state],
        name="kda_step",
        grid=(s_n, h // hb),
        in_specs=[vec_spec, state_spec],
        out_specs=[pl.BlockSpec((1, hb, state.shape[3]),
                                lambda s, j: (s, j, 0)), state_spec],
        out_shape=[jax.ShapeDtypeStruct((s_n, h, state.shape[3]),
                                        jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={1: 1})
    return o, new_state


def kda_step_reference(state, q, k, v, g, beta, active=None):
    """jnp oracle of :func:`kda_step`."""
    s_n = q.shape[0]
    a, k, q, akb, vb = jnp.moveaxis(
        _step_vectors(q, k, v, g, beta, active), 1, 0)
    s = state[:s_n]
    u = vb - jnp.einsum("shkv,shk->shv", s, akb, precision=_HIGH)
    s = s * a[..., None] + k[..., None] * u[:, :, None, :]
    o = jnp.einsum("shkv,shk->shv", s, q, precision=_HIGH)
    return o, state.at[:s_n].set(s)


def kda_chunked(q, k, v, g, beta, chunk=64):
    """The same recurrence over a whole sequence from a zero state, a
    chunk at a time: inside a chunk the corrections ``u`` solve one
    unit lower-triangular system, between chunks one state is passed.

    ``q, k, v, g``: float32 [T, H, D]; ``beta``: [T, H].  A position
    with ``g = 0`` and ``beta = 0`` (padding) leaves the state as it is.
    Every decay that is formed is a difference ``G_i - G_j`` with
    ``i >= j`` of the chunk's running sums, so no exponent is positive.

    Returns ``(o [T, H, Dv], final state [H, Dk, Dv])``.
    """
    t, h, d = q.shape
    chunk = min(chunk, t)
    pad = -t % chunk
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, pad), (0, 0)))
    n = (t + pad) // chunk
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    eye = jnp.eye(chunk, dtype=jnp.float32)

    def one_chunk(s0, xs):
        q_c, k_c, v_c, g_c, b_c = xs                        # [C, H, D]
        run = jnp.cumsum(g_c, axis=0)
        pair = jnp.exp(jnp.where(lower[:, :, None, None],
                                 run[:, None] - run[None, :], -jnp.inf))
        kk = jnp.einsum("ihd,jhd,ijhd->hij", k_c, k_c, pair)
        qk = jnp.einsum("ihd,jhd,ijhd->hij", q_c, k_c, pair)
        from_start = jnp.exp(run)
        rhs = b_c[..., None] * (v_c - jnp.einsum(
            "ihk,hkv->ihv", k_c * from_start, s0, precision=_HIGH))
        system = eye + jnp.where(strict, kk, 0.0) \
            * b_c.T[:, :, None]                             # [H, C, C]
        u = jax.scipy.linalg.solve_triangular(
            system, rhs.transpose(1, 0, 2), lower=True,
            unit_diagonal=True)                             # [H, C, Dv]
        o = jnp.einsum("ihk,hkv->ihv", q_c * from_start, s0,
                       precision=_HIGH) + jnp.einsum(
            "hij,hjv->ihv", jnp.where(lower, qk, 0.0), u,
            precision=_HIGH)
        to_end = jnp.exp(run[-1][None] - run)
        s1 = s0 * from_start[-1][:, :, None] + jnp.einsum(
            "jhk,hjv->hkv", k_c * to_end, u, precision=_HIGH)
        return s1, o

    split = lambda a: a.reshape((n, chunk) + a.shape[1:])
    s_fin, o = lax.scan(one_chunk, jnp.zeros((h, d, v.shape[2]),
                                             jnp.float32),
                        tuple(split(a.astype(jnp.float32))
                              for a in (q, k, v, g, beta)))
    return o.reshape(n * chunk, h, -1)[:t], s_fin
