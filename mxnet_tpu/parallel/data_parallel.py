"""Jitted SPMD train-step builder: DP (+ optional TP/FSDP) in one program.

Replaces the reference's whole data-parallel sandwich —
`DataParallelExecutorGroup` batch slicing
(/root/reference/python/mxnet/module/executor_group.py:296-600), KVStore
push/pull (/root/reference/src/kvstore/comm.h), and server-side optimizer
(/root/reference/src/kvstore/kvstore_dist_server.h:109-180) — with one
`jit` whose in_shardings shard the batch over ``dp`` and whose parameter
shardings encode TP/FSDP.  XLA inserts the gradient psum (grad of a
dp-sharded loss w.r.t. replicated params IS the allreduce) and overlaps it
with the backward pass — the comm/compute overlap MXNet engineered by
pushing per-key engine ops.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import AXIS_DP
from .. import telemetry as _telemetry
from . import sharding as shd


def sgd_momentum_init(params):
    return {"mom": jax.tree_util.tree_map(jnp.zeros_like, params)}


def sgd_momentum_apply(params, grads, state, lr=0.01, momentum=0.9, wd=0.0):
    """Matches the reference's sgd_mom_update semantics
    (/root/reference/src/operator/optimizer_op-inl.h): mom = m*mom - lr*(g
    + wd*w); w += mom."""
    def upd(w, g, m):
        g = g + wd * w
        m_new = momentum * m - lr * g
        return w + m_new, m_new
    flat = jax.tree_util.tree_map(upd, params, grads, state["mom"])
    new_params = jax.tree_util.tree_map(lambda t: t[0], flat,
                                        is_leaf=lambda t: isinstance(t, tuple))
    new_mom = jax.tree_util.tree_map(lambda t: t[1], flat,
                                     is_leaf=lambda t: isinstance(t, tuple))
    return new_params, {"mom": new_mom}


def make_train_step(loss_fn, mesh, optimizer_apply=None, optimizer_init=None,
                    param_rules=None, dp_axis=AXIS_DP, donate=True,
                    batch_ndims=None):
    """Build (init_fn, step_fn).

    ``loss_fn(params, batch, rng) -> scalar`` — pure; ``batch`` a pytree of
    arrays with leading batch dim (sharded over ``dp_axis``).
    ``param_rules`` — sharding.PartitionRule list (TP/FSDP); default
    replicated.  ``optimizer_apply(params, grads, state) -> (params,
    state)`` — default SGD+momentum.

    Returns:
      init_fn(params) -> (sharded_params, opt_state)
      step_fn(params, opt_state, batch, rng) -> (params, opt_state, loss)
    """
    optimizer_apply = optimizer_apply or functools.partial(
        sgd_momentum_apply, lr=0.01, momentum=0.9)
    optimizer_init = optimizer_init or sgd_momentum_init
    rules = param_rules or []

    def param_sharding(params):
        return {
            name: NamedSharding(
                mesh, shd._validate_spec(shd.spec_for(name, v, rules),
                                         v.shape, mesh))
            for name, v in params.items()}

    def init_fn(params):
        shardings = param_sharding(params)
        params = {k: jax.device_put(v, shardings[k])
                  for k, v in params.items()}
        state = optimizer_init(params)

        def place_leaf(name, leaf):
            # per-param state (momentum etc.) follows its param's
            # sharding — a replicated momentum for a tp-sharded weight
            # would force an all-gather every update.  Only leaves that
            # mirror the param's shape qualify (Adafactor-style factored
            # or scalar state stays replicated).
            if name in params and \
                    getattr(leaf, "shape", None) == params[name].shape:
                return jax.device_put(leaf, shardings[name])
            return jax.device_put(leaf, NamedSharding(mesh, P()))

        def place(sub):
            if isinstance(sub, dict):
                return {k: place_leaf(k, v) if not isinstance(v, dict)
                        else place(v) for k, v in sub.items()}
            return jax.tree_util.tree_map(
                lambda s: jax.device_put(s, NamedSharding(mesh, P())),
                sub)
        state = place(state) if isinstance(state, dict) else \
            jax.tree_util.tree_map(
                lambda s: jax.device_put(s, NamedSharding(mesh, P())),
                state)
        return params, state

    def batch_sharding(batch):
        return jax.tree_util.tree_map(
            lambda b: NamedSharding(mesh, shd.batch_spec(b.ndim, dp_axis)),
            batch)

    def step(params, opt_state, batch, rng):
        # scope names are what a device trace is read by (PERF.md
        # section 3); the backward's ops carry "loss" inside their
        # transpose(jvp(...)) path
        with _telemetry.device_scope("loss"):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch, rng)
        with _telemetry.device_scope("optimizer_apply"):
            new_params, new_state = optimizer_apply(params, grads,
                                                    opt_state)
        return new_params, new_state, loss

    programs = {}

    def program(params, opt_state):
        """(jitted step, the callable that dispatches it), built at the
        first call: the new params and state are pinned to land exactly
        where the donated ones lived.  Left to propagation, a one-device
        mesh hands back SingleDeviceShardings, which miss the jit cache
        on the second call — one full recompile of the step."""
        if not programs:
            def where(tree):
                return jax.tree_util.tree_map(lambda x: x.sharding, tree)
            jitted = jax.jit(
                step, donate_argnums=(0, 1) if donate else (),
                out_shardings=(where(params), where(opt_state), None))
            call = jitted
            if donate:
                # donated program compiling lazily at first dispatch:
                # keep it out of jax's persistent cache on backends
                # where replaying a donated executable from that cache
                # corrupts the heap (aot_cache docs, ROBUSTNESS.md §8)
                # — launch.py exports that cache to every worker by
                # default
                from .. import aot_cache
                call = aot_cache.donation_cache_guard(jitted)
            programs.update(jitted=jitted, call=call)
        return programs["jitted"], programs["call"]

    def place(batch):
        return jax.tree_util.tree_map(
            lambda b, s: jax.device_put(b, s) if not _is_committed(b, s)
            else b, batch, batch_sharding(batch))

    # the ambient mesh is how mesh-aware ops inside loss_fn (the Pallas
    # attention kernels, which GSPMD cannot partition) find the axes to
    # shard_map over
    def step_fn(params, opt_state, batch, rng):
        with jax.set_mesh(mesh):
            return program(params, opt_state)[1](
                params, opt_state, place(batch), rng)

    def lower(params, opt_state, batch, rng):
        """``jax.jit(...).lower`` of the step on the same arguments:
        the handle for ahead-of-time compilation and for reading what
        the program holds (``.compile().as_text()``)."""
        with jax.set_mesh(mesh):
            return program(params, opt_state)[0].lower(
                params, opt_state, place(batch), rng)

    step_fn.lower = lower
    return init_fn, step_fn


def _is_committed(arr, target_sharding):
    s = getattr(arr, "sharding", None)
    return s is not None and s == target_sharding


class DataParallelTrainer:
    """Stateful convenience wrapper over `make_train_step`."""

    def __init__(self, loss_fn, mesh, params, optimizer_apply=None,
                 optimizer_init=None, param_rules=None):
        self._init, self._step = make_train_step(
            loss_fn, mesh, optimizer_apply=optimizer_apply,
            optimizer_init=optimizer_init, param_rules=param_rules)
        self.params, self.opt_state = self._init(params)
        self.mesh = mesh

    def step(self, batch, rng):
        self.params, self.opt_state, loss = self._step(
            self.params, self.opt_state, batch, rng)
        return loss
