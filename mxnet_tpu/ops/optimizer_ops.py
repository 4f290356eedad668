"""Optimizer update operators.

TPU-native equivalents of /root/reference/src/operator/optimizer_op-inl.h.
In the reference these run as graph ops so the KVStore server can execute
updates remotely (update_on_kvstore); here they are pure functions returning
the *new* (weight, states...) — the optimizer/KVStore layer writes results
back, and inside a pjit'd train step XLA turns the write-back into an
in-place donation.

Semantics match the reference exactly (rescale_grad, clip_gradient applied
before wd, update order) so convergence curves are comparable.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from .registry import register_op
from .. import telemetry as _telemetry


def _rescale(grad, rescale_grad, clip_gradient):
    grad = grad * rescale_grad
    if clip_gradient is not None and clip_gradient >= 0:
        grad = jnp.clip(grad, -clip_gradient, clip_gradient)
    return grad


def _live_rows(grad):
    """Rows the (masked-dense row_sparse) gradient actually touches —
    the lazy-update predicate the reference evaluated over the sparse
    gradient's idx array (src/operator/optimizer_op.cc SGDUpdateRsp).
    Shares the liveness definition with RowSparseNDArray.indices."""
    from ..ndarray.sparse import live_row_mask
    return live_row_mask(grad).reshape((-1,) + (1,) * (grad.ndim - 1))


#: per-step scalars (a scheduler's lr, Adam's bias-corrected lr) are traced
#: arguments, not compile-time constants — one executable per shape, not one
#: per value (registry.OpDef.dynamic_params)
_DYN = ("lr", "wd", "rescale_grad")


@register_op("sgd_update", arg_names=("weight", "grad"),
             param_defaults={"lr": 0.01, "wd": 0.0, "rescale_grad": 1.0,
                             "clip_gradient": -1.0, "lazy_update": False},
             dynamic_params=_DYN)
def _sgd_update(weight, grad, lr=0.01, wd=0.0, rescale_grad=1.0,
                clip_gradient=-1.0, lazy_update=False):
    g = _rescale(grad, rescale_grad, clip_gradient)
    new_w = weight - lr * (g + wd * weight)
    if lazy_update:
        # rows absent from the gradient stay untouched — including their
        # weight-decay term, matching the reference's sparse sgd_update
        return jnp.where(_live_rows(grad), new_w, weight)
    return new_w


@register_op("sgd_mom_update", arg_names=("weight", "grad", "mom"),
             num_outputs=2,
             param_defaults={"lr": 0.01, "momentum": 0.0, "wd": 0.0,
                             "rescale_grad": 1.0, "clip_gradient": -1.0,
                             "lazy_update": False},
             dynamic_params=_DYN)
def _sgd_mom_update(weight, grad, mom, lr=0.01, momentum=0.0, wd=0.0,
                    rescale_grad=1.0, clip_gradient=-1.0, lazy_update=False):
    g = _rescale(grad, rescale_grad, clip_gradient)
    new_mom = momentum * mom - lr * (g + wd * weight)
    if lazy_update:
        live = _live_rows(grad)
        new_mom = jnp.where(live, new_mom, mom)
        return jnp.where(live, weight + new_mom, weight), new_mom
    return weight + new_mom, new_mom


@register_op("mp_sgd_update", arg_names=("weight", "grad", "weight32"),
             num_outputs=2,
             param_defaults={"lr": 0.01, "wd": 0.0, "rescale_grad": 1.0,
                             "clip_gradient": -1.0},
             dynamic_params=_DYN)
def _mp_sgd_update(weight, grad, weight32, lr=0.01, wd=0.0, rescale_grad=1.0,
                   clip_gradient=-1.0):
    # fp16 weights with fp32 master copy (mp_sgd_update in the reference)
    grad = _rescale(grad.astype(jnp.float32), rescale_grad, clip_gradient)
    new_w32 = weight32 - lr * (grad + wd * weight32)
    return new_w32.astype(weight.dtype), new_w32


@register_op("mp_sgd_mom_update",
             arg_names=("weight", "grad", "mom", "weight32"), num_outputs=3,
             param_defaults={"lr": 0.01, "momentum": 0.0, "wd": 0.0,
                             "rescale_grad": 1.0, "clip_gradient": -1.0},
             dynamic_params=_DYN)
def _mp_sgd_mom_update(weight, grad, mom, weight32, lr=0.01, momentum=0.0,
                       wd=0.0, rescale_grad=1.0, clip_gradient=-1.0):
    grad = _rescale(grad.astype(jnp.float32), rescale_grad, clip_gradient)
    new_mom = momentum * mom - lr * (grad + wd * weight32)
    new_w32 = weight32 + new_mom
    return new_w32.astype(weight.dtype), new_mom, new_w32


@register_op("adam_update", arg_names=("weight", "grad", "mean", "var"),
             num_outputs=3,
             param_defaults={"lr": 0.001, "beta1": 0.9, "beta2": 0.999,
                             "epsilon": 1e-8, "wd": 0.0, "rescale_grad": 1.0,
                             "clip_gradient": -1.0, "lazy_update": False},
             dynamic_params=_DYN)
def _adam_update(weight, grad, mean, var, lr=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                 lazy_update=False):
    g = _rescale(grad, rescale_grad, clip_gradient) + wd * weight
    new_mean = beta1 * mean + (1 - beta1) * g
    new_var = beta2 * var + (1 - beta2) * jnp.square(g)
    new_weight = weight - lr * new_mean / (jnp.sqrt(new_var) + epsilon)
    if lazy_update:
        # reference AdamUpdateRsp: m/v/w advance only on rows the sparse
        # gradient carries
        live = _live_rows(grad)
        return (jnp.where(live, new_weight, weight),
                jnp.where(live, new_mean, mean),
                jnp.where(live, new_var, var))
    return new_weight, new_mean, new_var


@register_op("rmsprop_update", arg_names=("weight", "grad", "n"),
             num_outputs=2,
             param_defaults={"lr": 0.001, "gamma1": 0.95, "epsilon": 1e-8,
                             "wd": 0.0, "rescale_grad": 1.0,
                             "clip_gradient": -1.0, "clip_weights": -1.0},
             dynamic_params=_DYN)
def _rmsprop_update(weight, grad, n, lr=0.001, gamma1=0.95, epsilon=1e-8,
                    wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                    clip_weights=-1.0):
    grad = _rescale(grad, rescale_grad, clip_gradient) + wd * weight
    new_n = (1 - gamma1) * jnp.square(grad) + gamma1 * n
    new_weight = weight - lr * grad / jnp.sqrt(new_n + epsilon)
    if clip_weights is not None and clip_weights > 0:
        new_weight = jnp.clip(new_weight, -clip_weights, clip_weights)
    return new_weight, new_n


@register_op("rmspropalex_update",
             arg_names=("weight", "grad", "n", "g", "delta"), num_outputs=4,
             param_defaults={"lr": 0.001, "gamma1": 0.95, "gamma2": 0.9,
                             "epsilon": 1e-8, "wd": 0.0, "rescale_grad": 1.0,
                             "clip_gradient": -1.0, "clip_weights": -1.0},
             dynamic_params=_DYN)
def _rmspropalex_update(weight, grad, n, g, delta, lr=0.001, gamma1=0.95,
                        gamma2=0.9, epsilon=1e-8, wd=0.0, rescale_grad=1.0,
                        clip_gradient=-1.0, clip_weights=-1.0):
    grad = _rescale(grad, rescale_grad, clip_gradient) + wd * weight
    new_n = (1 - gamma1) * jnp.square(grad) + gamma1 * n
    new_g = (1 - gamma1) * grad + gamma1 * g
    new_delta = gamma2 * delta - lr * grad / \
        jnp.sqrt(new_n - jnp.square(new_g) + epsilon)
    new_weight = weight + new_delta
    if clip_weights is not None and clip_weights > 0:
        new_weight = jnp.clip(new_weight, -clip_weights, clip_weights)
    return new_weight, new_n, new_g, new_delta


# -- tree-wide fused apply ---------------------------------------------------
#
# The per-op updates above dispatch one XLA kernel per parameter when called
# imperatively (the reference's server-side/kvstore shape).  The fused train
# step instead maps ONE update rule over the whole parameter pytree inside a
# single jitted program: per-parameter lr_mult/wd_mult are baked in as a
# static aux tree (they come from symbol attrs / Parameter objects and only
# change on reconfiguration, which rebuilds the program), while lr / wd /
# rescale_grad / t stay dynamic scalars so schedulers and Trainer.step's
# 1/batch_size rescale never trigger a recompile.

FUSED_KINDS = ("sgd", "sgd_mom", "adam")


def zero_stage(default=0):
    """The cross-replica weight-update sharding stage (arXiv 2004.13336 /
    ZeRO-1): 0 = replicated optimizer state, 1 = optimizer state +
    update sharded 1/N over the ``dp`` mesh axis (grads reduce-scattered,
    updated params all-gathered — still ONE donated program per step).
    Env contract: ``MXTPU_ZERO=1`` (SCALING.md)."""
    try:
        return int(os.environ.get("MXTPU_ZERO", "") or default)
    except ValueError:
        return default


def make_fused_apply(kind, mults, momentum=0.0, beta1=0.9, beta2=0.999,
                     epsilon=1e-8, clip_gradient=None, zero_shardings=None):
    """Build (init_state, apply) for a tree-wide optimizer update.

    ``kind``  — one of FUSED_KINDS.
    ``mults`` — static dict name -> (lr_mult, wd_mult).
    ``zero_shardings`` — ZeRO-1 mode: {name: NamedSharding} placing each
        param's optimizer state 1/N over the data-parallel mesh axis;
        init_state then materializes state ALREADY sharded (a replicated
        zeros tree for a billion-param model would defeat the point of
        sharding it).  The matching gradient reduce-scatter / param
        all-gather live in :func:`make_guarded_apply` — the apply body
        itself stays placement-agnostic arithmetic.

    init_state(params) -> state dict (name -> per-param state pytree)
    apply(params, grads, state, lr, wd, rescale_grad, t)
        -> (new_params, new_state); pure, jit/donation-friendly.  ``t`` is
        the 1-based update count (Adam bias correction); unused by sgd.
    """
    if kind not in FUSED_KINDS:
        raise ValueError("unsupported fused optimizer kind %r (want one of "
                         "%s)" % (kind, list(FUSED_KINDS)))
    mults = {k: (float(lm), float(wm)) for k, (lm, wm) in mults.items()}
    clip = float(clip_gradient) if clip_gradient is not None and \
        clip_gradient > 0 else None

    def _placed(name, z):
        if zero_shardings is None or name not in zero_shardings:
            return z
        # fresh buffers, not device_put: this state tree is DONATED by
        # the fused step (sharding.fresh_device_put docs)
        from ..parallel.sharding import fresh_device_put
        return fresh_device_put(z, zero_shardings[name])

    def init_state(params):
        if kind == "sgd":
            return {name: () for name in params}
        if kind == "sgd_mom":
            return {name: _placed(name, jnp.zeros_like(w))
                    for name, w in params.items()}
        return {name: (_placed(name, jnp.zeros_like(w)),
                       _placed(name, jnp.zeros_like(w)))
                for name, w in params.items()}

    def apply(params, grads, state, lr, wd, rescale_grad, t):
        if kind == "adam":
            # reference Adam bias correction folded into lr
            # (optimizer.py Adam.update); t is dynamic so consecutive
            # steps reuse the same program
            lr = lr * jnp.sqrt(1.0 - beta2 ** t) / (1.0 - beta1 ** t)
        new_params, new_state = {}, {}
        for name in params:
            w, g = params[name], grads[name]
            lm, wm = mults.get(name, (1.0, 1.0))
            p_lr, p_wd = lr * lm, wd * wm
            if kind == "sgd":
                new_params[name] = _sgd_update(
                    w, g, lr=p_lr, wd=p_wd, rescale_grad=rescale_grad,
                    clip_gradient=clip)
                new_state[name] = ()
            elif kind == "sgd_mom":
                new_params[name], new_state[name] = _sgd_mom_update(
                    w, g, state[name], lr=p_lr, momentum=momentum, wd=p_wd,
                    rescale_grad=rescale_grad, clip_gradient=clip)
            else:
                mean, var = state[name]
                new_w, new_mean, new_var = _adam_update(
                    w, g, mean, var, lr=p_lr, beta1=beta1, beta2=beta2,
                    epsilon=epsilon, wd=p_wd, rescale_grad=rescale_grad,
                    clip_gradient=clip)
                new_params[name] = new_w
                new_state[name] = (new_mean, new_var)
        return new_params, new_state

    return init_state, apply


# -- divergence guard --------------------------------------------------------
#
# The fused train step applies the optimizer inside the same XLA program as
# forward+backward; one batch producing a non-finite gradient would silently
# drive the whole parameter tree to NaN and every subsequent step would
# compound it.  The guard below folds an all-finite check on the GLOBAL
# gradient tree into that same program (still one dispatch per step): when
# any gradient leaf is NaN/Inf the update is a tree-wide no-op — params and
# optimizer state pass through unchanged — and the scalar verdict is
# returned so the host can count skips and fail loudly after K consecutive
# ones (see max_consecutive_skips / MXNetError in module.py & trainer.py).


def all_finite(tree):
    """Scalar bool: every leaf of ``tree`` is entirely finite.  One
    fused reduction chain, no host sync."""
    ok = jnp.bool_(True)
    for leaf in jax.tree_util.tree_leaves(tree):
        ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(leaf)))
    return ok


def make_guarded_apply(apply_fn, zero_shardings=None, param_shardings=None):
    """Wrap a tree-wide ``apply`` (from make_fused_apply) with the
    divergence guard.

    Returns ``guarded(params, grads, state, lr, wd, rescale_grad, t,
    poison) -> (new_params, new_state, ok)``: when the (poisoned) gradient
    tree contains NaN/Inf, params/state pass through unchanged and ``ok``
    is False.  ``poison`` is a dynamic scalar added to every gradient —
    0.0 in production, NaN when the ``grad.nan`` fault-injection site
    fires — so tests drive the skip path through the very same compiled
    program, with no trace divergence between guarded and injected runs.

    **ZeRO-1** (``zero_shardings`` = {name: NamedSharding} over the dp
    axis, ``param_shardings`` = each param's resident sharding, normally
    replicated): the guard becomes the cross-replica weight-update
    sharding of arXiv 2004.13336, still inside the ONE donated program —

    - gradients are constrained onto ``zero_shardings`` straight out of
      the backward pass: XLA lowers the dp gradient sum as a
      reduce-scatter instead of an all-reduce (each replica keeps only
      its 1/N slice, at half the all-reduce's bytes);
    - the all-finite verdict reduces over the SHARDED grads (each device
      scans 1/N, one tiny cross-replica AND joins the verdicts);
    - the optimizer arithmetic — and the guard's no-op select — runs on
      the 1/N shards against the sharded optimizer state;
    - only the final updated params are constrained back to
      ``param_shardings``, the one all-gather of the step.

    The skip/rollback contract is untouched: the select happens before
    the all-gather, so a non-finite batch republishes the OLD param
    shards and the gathered result is bit-identical to never updating.
    """
    def _wsc(tree, shardings):
        return {name: jax.lax.with_sharding_constraint(v, shardings[name])
                for name, v in tree.items()} if shardings else tree

    def guarded(params, grads, state, lr, wd, rescale_grad, t, poison):
        # scope names are what a device trace is read by (PERF.md
        # section 3): the guard's pass over the gradients apart from
        # the optimizer's arithmetic
        with _telemetry.device_scope("divergence_guard"):
            grads = {name: g + poison for name, g in grads.items()}
            # dp grad sum → reduce-scatter
            grads = _wsc(grads, zero_shardings)
            ok = all_finite(grads)
        with _telemetry.device_scope("optimizer_apply"):
            new_params, new_state = apply_fn(params, grads, state, lr, wd,
                                             rescale_grad, t)
            # 1/N update compute
            new_params = _wsc(new_params, zero_shardings)
        with _telemetry.device_scope("divergence_guard"):
            new_params = jax.tree_util.tree_map(
                lambda n, o: jnp.where(ok, n, o), new_params, params)
            new_state = jax.tree_util.tree_map(
                lambda n, o: jnp.where(ok, n, o), new_state, state)
        if zero_shardings:
            new_params = _wsc(new_params, param_shardings)  # all-gather
        return new_params, new_state, ok

    return guarded


def max_consecutive_skips():
    """K in the graceful-degradation contract: after K consecutive
    guard-skipped steps the training loop raises MXNetError instead of
    silently looping on a permanently-divergent configuration.
    Overridable per-run via MXTPU_MAX_CONSECUTIVE_SKIPS."""
    return int(os.environ.get("MXTPU_MAX_CONSECUTIVE_SKIPS", "100"))


def raise_skip_limit_error(limit):
    from ..base import MXNetError
    raise MXNetError(
        "divergence guard: %d consecutive steps produced non-finite "
        "gradients — training cannot progress (lower the learning "
        "rate, check the data pipeline, or raise "
        "MXTPU_MAX_CONSECUTIVE_SKIPS)" % limit)


def handle_guard_verdict(ok, optimizer, indices, streak, pre_num_update,
                         raise_on_limit=True, backfill_verdict=False):
    """Host-side bookkeeping shared by Module.fit_step and
    gluon.Trainer._fused_step after the guarded program returns.

    On a skipped step the optimizer clock is rewound so the batch is
    indistinguishable from one that never arrived: ``_index_update_count``
    (Adam's t) for every updated index and ``num_update`` (the lr
    scheduler's clock, captured by the caller BEFORE its _update_count
    calls) both roll back.  Returns the new consecutive-skip streak;
    with ``raise_on_limit`` it raises MXNetError at
    max_consecutive_skips().  The Trainer resolves verdicts from its
    save/flush paths with ``raise_on_limit=False`` — a checkpoint write
    must never be aborted by a training-health error — and re-checks the
    limit at the top of the next step() instead.
    """
    ok_host = bool(ok)
    if backfill_verdict:
        # flight recorder: the Trainer records its step with a pending
        # (None) verdict before this resolves one step late; back-fill
        # both ways — ok steps become False-skipped, diverged True.
        # Module.fit_step records the verdict inline instead (marking
        # here would force a flight-ring drain on every step).
        _telemetry.mark_last_step_verdict(ok_host)
    if ok_host:
        return 0
    from .. import profiler as _profiler
    for i in indices:
        optimizer._index_update_count[i] -= 1
    optimizer.num_update = pre_num_update
    _profiler.note_skipped_step()
    streak += 1
    limit = max_consecutive_skips()
    if raise_on_limit and streak >= limit:
        raise_skip_limit_error(limit)
    return streak


@register_op("ftrl_update", arg_names=("weight", "grad", "z", "n"),
             num_outputs=3,
             param_defaults={"lr": 0.1, "lamda1": 0.01, "beta": 1.0,
                             "wd": 0.0, "rescale_grad": 1.0,
                             "clip_gradient": -1.0},
             dynamic_params=_DYN)
def _ftrl_update(weight, grad, z, n, lr=0.1, lamda1=0.01, beta=1.0, wd=0.0,
                 rescale_grad=1.0, clip_gradient=-1.0):
    grad = _rescale(grad, rescale_grad, clip_gradient)
    new_n = n + jnp.square(grad)
    sigma = (jnp.sqrt(new_n) - jnp.sqrt(n)) / lr
    new_z = z + grad - sigma * weight
    new_weight = jnp.where(
        jnp.abs(new_z) <= lamda1, jnp.zeros_like(weight),
        -(new_z - jnp.sign(new_z) * lamda1) /
        ((beta + jnp.sqrt(new_n)) / lr + wd))
    return new_weight, new_z, new_n


@register_op("adamax_update", arg_names=("weight", "grad", "m", "u"),
             num_outputs=3,
             param_defaults={"lr": 0.002, "beta1": 0.9, "beta2": 0.999,
                             "wd": 0.0, "rescale_grad": 1.0,
                             "clip_gradient": -1.0},
             dynamic_params=_DYN)
def _adamax_update(weight, grad, m, u, lr=0.002, beta1=0.9, beta2=0.999,
                   wd=0.0, rescale_grad=1.0, clip_gradient=-1.0):
    # ``lr`` arrives bias-corrected (lr / (1 - beta1^t)) from the host,
    # like adam_update's — reference optimizer.py:927 AdaMax
    g = _rescale(grad, rescale_grad, clip_gradient) + wd * weight
    new_m = beta1 * m + (1.0 - beta1) * g
    new_u = jnp.maximum(beta2 * u, jnp.abs(g))
    return weight - lr * new_m / new_u, new_m, new_u


@register_op("nadam_update", arg_names=("weight", "grad", "m", "v"),
             num_outputs=3,
             param_defaults={"lr": 0.001, "beta1": 0.9, "beta2": 0.999,
                             "epsilon": 1e-8, "wd": 0.0, "rescale_grad": 1.0,
                             "clip_gradient": -1.0, "momentum_t": 0.9,
                             "momentum_t_1": 0.9, "m_schedule": 0.9,
                             "m_schedule_next": 0.81, "coef2": 1.0},
             dynamic_params=_DYN + ("momentum_t", "momentum_t_1",
                                    "m_schedule", "m_schedule_next",
                                    "coef2"))
def _nadam_update(weight, grad, m, v, lr=0.001, beta1=0.9, beta2=0.999,
                  epsilon=1e-8, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                  momentum_t=0.9, momentum_t_1=0.9, m_schedule=0.9,
                  m_schedule_next=0.81, coef2=1.0):
    # Nesterov Adam (reference optimizer.py:975).  The momentum schedule
    # (mu_t, mu_{t+1}, their running products, and 1 - beta2^t) is t-bound
    # host state, so it rides in as dynamic scalars — one compiled program
    # serves the whole training run.
    g = _rescale(grad, rescale_grad, clip_gradient) + wd * weight
    new_m = beta1 * m + (1.0 - beta1) * g
    new_v = beta2 * v + (1.0 - beta2) * jnp.square(g)
    g_prime = g / (1.0 - m_schedule)
    m_prime = new_m / (1.0 - m_schedule_next)
    v_prime = new_v / coef2
    m_bar = (1.0 - momentum_t) * g_prime + momentum_t_1 * m_prime
    return (weight - lr * m_bar / (jnp.sqrt(v_prime) + epsilon),
            new_m, new_v)
