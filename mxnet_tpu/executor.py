"""Executor: a bound, compiled symbolic graph.

TPU-native analogue of the reference GraphExecutor
(/root/reference/src/executor/graph_executor.cc + python/mxnet/executor.py).
Where the reference built a backward graph (nnvm Gradient pass), planned
memory, and pushed cached engine ops per node (RunOps :1421), this executor
traces the whole Symbol into ONE JAX function and jit-compiles it:

- forward      → jitted graph evaluation (XLA fusion ≈ PlanMemory+bulking);
                 a training forward runs under jax.vjp and keeps its
                 residuals (the reference's data_entry_ activations)
- backward     → applies the saved vjp residuals (backward-only work);
                 without a preceding training forward it falls back to a
                 fused forward+vjp program
- forward_backward → one fused jitted fwd+bwd program (the fit hot path)
- aux states   → threaded functionally and written back (BatchNorm stats)
- grad_req     → write / add / null per argument, as in the reference

Recompilation happens automatically per input shape (the reference's
BucketingModule rebinds per bucket; XLA's jit cache plays that role).
"""
from __future__ import annotations

import functools

import numpy as _np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as _P

from . import telemetry as _telemetry
from .base import MXNetError
from .ndarray.ndarray import NDArray

__all__ = ["Executor"]


class Executor:
    def __init__(self, symbol, ctx, args, args_grad, grad_req, aux_states,
                 group2ctx=None, shared_exec=None, mesh=None,
                 batch_names=None, dp_axis="dp", partition_rules=None):
        self._symbol = symbol
        self._ctx = ctx
        self._mesh = mesh
        self._dp_axis = dp_axis
        self._partition_rules = partition_rules
        self._batch_names = frozenset(batch_names or ())
        self.arg_dict = dict(args)
        self.grad_dict = dict(args_grad) if args_grad else {}
        self.aux_dict = dict(aux_states) if aux_states else {}
        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()
        if isinstance(grad_req, str):
            self._grad_req = {n: grad_req for n in self._arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self._grad_req = dict(zip(self._arg_names, grad_req))
        else:
            self._grad_req = dict(grad_req or {})
        for n in self._arg_names:
            self._grad_req.setdefault(n, "null")
            if self._grad_req[n] != "null" and n not in self.grad_dict:
                a = self.arg_dict.get(n)
                if a is not None:
                    self.grad_dict[n] = NDArray(jnp.zeros_like(a._data),
                                                self._ctx)
        self._group2ctx = group2ctx
        self._monitor_callback = None
        self._monitor_all = False
        self.outputs = []
        self._fwd_cache = {}
        self._grad_fn = None
        self._lin_fns = None
        self._saved_vjp = None
        self._shardings = self._build_shardings() if mesh is not None else {}
        # graph rewrite pipeline (mxnet_tpu.graph, ROADMAP item 3): the
        # compiler stage between bind and trace→jit.  Every jitted path
        # (forward/backward/fused fit step) lowers the REWRITTEN graph;
        # the original symbol keeps serving names/shapes/serialization
        # and the monitor's per-op interpret mode.  ctx_group binds skip
        # it (fused regions would erase per-node placement), and any
        # pass failure falls back to the unrewritten graph — the
        # pipeline may only ever make a bind faster, never break it.
        self._opt_symbol = symbol
        self._graph_report = None
        if not group2ctx:
            from . import graph as _graph
            if _graph.enabled():
                try:
                    self._opt_symbol, self._graph_report = \
                        _graph.optimize(symbol)
                except Exception as e:
                    import logging
                    logging.warning(
                        "mxnet_tpu.executor: graph rewrite pipeline "
                        "failed (%s: %s); lowering the unrewritten "
                        "graph", type(e).__name__, e)
                    self._opt_symbol = symbol
        self._interp_plan = None
        self._plan = self._build_plan(self._opt_symbol)

    # -- SPMD placement ----------------------------------------------------
    def _build_shardings(self):
        """Mesh layout, resolved ONCE at bind: batch args sharded over
        ``dp`` (sharding.batch_spec), every other array placed by the
        bind's partition rules (sharding.match_partition_rules — regex
        rules over the named param tree, replicated when none matches).
        This single placement decision replaces the reference's
        DataParallelExecutorGroup batch slicing
        (/root/reference/python/mxnet/module/executor_group.py:296-378) —
        XLA GSPMD partitions the one compiled program across the mesh and
        inserts the gradient all-reduce (vjp of a replicated parameter
        against dp-sharded activations IS a psum over ``dp``)."""
        from .parallel import sharding as _shd
        mesh, axis = self._mesh, self._dp_axis
        ndev = mesh.shape[axis]
        batch, ruled = {}, {}
        for name, arr in list(self.arg_dict.items()) + \
                list(self.aux_dict.items()):
            if name in self._batch_names and arr.ndim >= 1:
                if arr.shape[0] % ndev:
                    raise MXNetError(
                        "batch axis of %r (shape %s) not divisible by the "
                        "%d-device data-parallel mesh" %
                        (name, arr.shape, ndev))
                batch[name] = _shd.batch_spec(arr.ndim, axis)
            else:
                ruled[name] = arr
        specs = _shd.match_partition_rules(
            self._partition_rules or [], ruled, mesh=mesh)
        specs.update(batch)
        return {name: NamedSharding(mesh, spec)
                for name, spec in specs.items()}

    def param_spec(self, name):
        """The bound PartitionSpec of ``name`` (P() when unsharded /
        no mesh) — the base the ZeRO-1 state placement composes with."""
        s = self._shardings.get(name)
        return s.spec if s is not None else _P()

    def zero_shardings(self, update_names):
        """{name: NamedSharding} placing each updated param's optimizer
        state / reduce-scattered gradient 1/N over the data-parallel
        axis (parallel.sharding.zero1_partition), or None when this bind
        has no mesh / no dp axis to shard over.  Leaves that cannot
        shard (no dim divisible by the axis) come back replicated —
        counted on ``sharding.fallbacks``."""
        mesh = self._mesh
        if mesh is None or self._dp_axis not in mesh.shape or \
                mesh.shape[self._dp_axis] <= 1:
            return None
        from .parallel.sharding import zero1_partition
        shapes = {n: self.arg_dict[n]._data for n in update_names}
        base = {n: self.param_spec(n) for n in update_names}
        specs = zero1_partition(shapes, mesh, axis=self._dp_axis,
                                base_specs=base)
        return {n: NamedSharding(mesh, s) for n, s in specs.items()}

    def _placed(self, name, data):
        """Reshard ``data`` to its mesh placement (no-op when it already
        lives there, or when no mesh is attached).  Batch feeds move
        with a plain device_put (they are never donated); params/aux
        feed the fused step's DONATED trees, so their placement must
        materialize fresh XLA-owned buffers — an eager device_put can
        alias the source (e.g. checkpoint-loaded arrays still held by
        Module._arg_params) and donating an aliased buffer corrupts the
        heap (parallel.sharding.fresh_device_put, PR-7 root cause)."""
        target = self._shardings.get(name)
        if target is None:
            return data
        if getattr(data, "sharding", None) == target:
            return data
        if name in self._batch_names:
            return jax.device_put(data, target)
        from .parallel.sharding import fresh_device_put
        return fresh_device_put(data, target)

    # -- graph compilation -------------------------------------------------
    def _build_plan(self, symbol=None):
        """Assemble the pure graph function over (args, aux, rng, train)."""
        symbol = symbol if symbol is not None else self._opt_symbol
        nodes = symbol._topo_nodes()
        sym_outputs = symbol._outputs

        # ctx_group model parallelism (reference: nnvm PlaceDevice pass +
        # _CrossDeviceCopy, graph_executor.cc:309-395).  TPU-native: each
        # group's ctx resolves to a device and jax.device_put at the
        # group cut moves the activation; ops after the cut follow their
        # data (JAX computation-follows-data).  This requires EAGER
        # execution — inside jit, device_put is only a hint this JAX
        # version ignores — so multi-device group binds run the graph
        # op-by-op (self._staged); single-device binds keep the fused
        # one-program jit path.
        placement = {}
        if self._group2ctx:
            for node in nodes:
                grp = (node.attrs or {}).get("ctx_group")
                if grp and grp in self._group2ctx:
                    placement[id(node)] = \
                        self._group2ctx[grp].jax_device()
        in_play = set(placement.values())
        if in_play:
            in_play.add(self._ctx.jax_device())
        self._staged = len(in_play) > 1
        # static per-node device assignment for staged mode: a node runs
        # on its group's device, else follows its first placed input
        # (vars default to the bind ctx) — computed from graph structure
        # so the eager path never inspects runtime values (tracers under
        # jax.vjp have no .devices())
        node_dev = {}
        if self._staged:
            default_dev = self._ctx.jax_device()
            for node in nodes:
                dev = placement.get(id(node))
                if dev is None:
                    if node.is_var:
                        dev = default_dev
                    else:
                        for inp, _ in node.inputs:
                            if node_dev.get(id(inp)) is not None:
                                dev = node_dev[id(inp)]
                                break
                        dev = dev or default_dev
                node_dev[id(node)] = dev

        staged = self._staged

        # ONE per-node evaluation core shared with the gluon symbolic
        # CachedOp (graph.make_eval_fn): _train threading, RNG fold-in
        # by topo index, visible/aux-extra split, aux write-back pairing
        from .graph.graph import apply_node, aux_writebacks

        def graph_fn(arg_vals, aux_vals, rng, train, tap=None):
            """tap(node, vis_outputs) is called per node when set — used by
            the monitor's eager interpret mode only (never under jit)."""
            vals = {}
            new_aux = {}

            for i, node in enumerate(nodes):
                if node.is_var:
                    v = aux_vals[node.name] if node.is_aux_var \
                        else arg_vals[node.name]
                    dev = placement.get(id(node))
                    if dev is not None and tap is None:
                        v = jax.device_put(v, dev)
                    vals[id(node)] = [v]
                    continue
                inputs = [vals[id(inp)][idx] for inp, idx in node.inputs]
                if staged and inputs:
                    # eager cross-device cut: align every input onto the
                    # node's statically-assigned device — the
                    # _CrossDeviceCopy the reference inserted.  device_put
                    # to the same device is a no-op; on tracers (under
                    # jax.vjp) it records the transfer.
                    target = node_dev[id(node)]
                    inputs = [jax.device_put(x, target) for x in inputs]
                vis, extra = apply_node(node, inputs, rng, i, train)
                dev = placement.get(id(node))
                if dev is not None and tap is None:
                    # placement constraints only under jit — eager
                    # (monitor interpret) mode would make mixed-device
                    # op calls illegal in JAX
                    vis = [jax.device_put(v, dev) for v in vis]
                vals[id(node)] = vis
                if node.op.mutate_aux and extra and train:
                    new_aux.update(aux_writebacks(node, extra))
                if tap is not None:
                    tap(node, vis)

            outs = [vals[id(n)][i] for n, i in sym_outputs]
            return outs, new_aux

        return graph_fn

    @staticmethod
    def _instrument(fn, first_call_compiles=True):
        """Dispatch/compile accounting around a jitted program (shapes
        are fixed at bind time, so first call == the one XLA compile —
        except warm-loaded AOT executables, which never compile)."""
        from . import profiler as _profiler
        return _profiler.instrument(
            fn, first_call_compiles=first_call_compiles)

    def _fwd(self, train):
        fn = self._fwd_cache.get(train)
        if fn is None:
            plan = self._plan
            fn = functools.partial(plan, train=train)
            if not self._staged:
                # staged (multi-device ctx_group) binds run eagerly:
                # jit would collapse placement onto one device
                fn = self._instrument(self._guard_mesh_cache(jax.jit(fn)))
            self._fwd_cache[train] = fn
        return fn

    def _guard_mesh_cache(self, fn):
        """Keep MESH programs out of jax's persistent compilation cache
        on backends where a replayed (deserialized) SPMD executable is
        unsound even donation-free (aot_cache.deserialized_spmd_safe —
        the launcher exports JAX_COMPILATION_CACHE_DIR by default, so
        without this every restarted rank would re-execute its mesh
        forwards from bytes).  No-op for single-device binds and on
        donation/SPMD-safe backends."""
        if self._mesh is None:
            return fn
        from . import aot_cache as _aot
        return _aot.donation_cache_guard(fn)

    def _diff_names(self):
        return tuple(sorted(
            n for n, r in self._grad_req.items() if r != "null"
            and n in self.arg_dict))

    def _vjp_forward(self, arg_vals, aux_vals, rng):
        """Run the training forward under jax.vjp → (outs, new_aux, vjp).
        The single construction both the split path (_make_lin_fns) and
        the fused grad program (_make_grad_fn) build on."""
        plan = self._plan
        diff_names = self._diff_names()
        fixed = {k: v for k, v in arg_vals.items() if k not in diff_names}

        def f(diff_args):
            merged = dict(fixed)
            merged.update(diff_args)
            outs, new_aux = plan(merged, aux_vals, rng, True)
            return tuple(outs), new_aux

        diff_args = {k: arg_vals[k] for k in diff_names}
        outs, vjp, new_aux = jax.vjp(f, diff_args, has_aux=True)
        return outs, new_aux, vjp

    def _make_lin_fns(self):
        """Two-part train program for the split forward()/backward() path:
        forward runs once and carries its vjp residuals across the jit
        boundary (jax.vjp returns a tree_util.Partial — a pytree of
        residual arrays), backward just applies them.  The reference kept
        forward activations alive in the executor for exactly this
        (graph_executor.cc data_entry_); rounds 1-2 recomputed the whole
        forward inside backward instead."""
        if getattr(self, "_lin_fns", None) is not None:
            return self._lin_fns

        def fwd_lin(arg_vals, aux_vals, rng):
            return self._vjp_forward(arg_vals, aux_vals, rng)

        def bwd_apply(vjp, ograds):
            return vjp(tuple(ograds))[0]

        if not self._staged:
            fwd_lin = self._instrument(
                self._guard_mesh_cache(jax.jit(fwd_lin)))
            bwd_apply = self._instrument(
                self._guard_mesh_cache(jax.jit(bwd_apply)))
        self._lin_fns = (fwd_lin, bwd_apply)
        return self._lin_fns

    def _make_grad_fn(self):
        if self._grad_fn is not None:
            return self._grad_fn

        def grad_fn(arg_vals, aux_vals, rng, ograds):
            outs, new_aux, vjp = self._vjp_forward(arg_vals, aux_vals, rng)
            grads = vjp(tuple(ograds))[0]
            return outs, new_aux, grads

        if not self._staged:
            grad_fn = self._instrument(
                self._guard_mesh_cache(jax.jit(grad_fn)))
        self._grad_fn = grad_fn
        return grad_fn

    # -- execution ---------------------------------------------------------
    def _raw(self, d):
        if self._mesh is None:
            return {k: v._data for k, v in d.items()}
        out = {}
        for k, v in d.items():
            placed = self._placed(k, v._data)
            if placed is not v._data:
                v._set_data(placed)  # cache the mesh placement
            out[k] = placed
        return out

    def _raw_args(self):
        return self._raw(self.arg_dict)

    def _raw_aux(self):
        return self._raw(self.aux_dict)

    def _accum_grad(self, dst, g):
        """grad_req='add' accumulate; under a mesh the initial zeros may
        still be committed to one device while ``g`` comes out of the
        sharded program — move dst to g's placement first."""
        gshd = getattr(g, "sharding", None)
        if self._mesh is not None and \
                getattr(dst._data, "sharding", None) != gshd:
            dst._set_data(jax.device_put(dst._data, gshd))
        dst._set_data(dst._data + g)

    def _forward_interpret(self, train, rng):
        """Eager (uncompiled) forward calling the monitor callback with
        every node output — the XLA-era analogue of the reference's
        per-op executor monitor (graph_executor.cc:1399-1419).  Slow;
        used only when a Monitor installs with monitor_all.  Runs the
        ORIGINAL (unrewritten) graph so the monitor sees every per-op
        intermediate the user wrote, not the fused regions the rewrite
        pipeline lowered."""
        if self._interp_plan is None:
            self._interp_plan = self._plan \
                if self._opt_symbol is self._symbol \
                else self._build_plan(self._symbol)

        def tap(node, vis):
            for j, v in enumerate(vis):
                suffix = "_output" if len(vis) == 1 else "_output%d" % j
                self._monitor_callback(node.name + suffix,
                                       NDArray(v, self._ctx))
        return self._interp_plan(self._raw_args(), self._raw_aux(), rng,
                                 train, tap=tap)

    def forward(self, is_train=False, **kwargs):
        from . import random as _random
        from . import profiler as _profiler
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("unknown argument %s" % k)
            self.arg_dict[k]._set_data(
                v._data if isinstance(v, NDArray) else jnp.asarray(v))
        rng = _random.next_key()
        self._last_rng = rng
        self._saved_vjp = None
        if self._monitor_callback is not None and self._monitor_all:
            outs, new_aux = self._forward_interpret(bool(is_train), rng)
        elif is_train and any(r != "null" for r in self._grad_req.values()):
            # training forward keeps its vjp residuals so a following
            # backward() applies them instead of re-running the forward
            fwd_lin, _ = self._make_lin_fns()
            with _profiler._timed("executor_forward") as t:
                outs, new_aux, self._saved_vjp = fwd_lin(
                    self._raw_args(), self._raw_aux(), rng)
                t.sync_arrays = outs
        else:
            with _profiler._timed("executor_forward") as t:
                outs, new_aux = self._fwd(bool(is_train))(
                    self._raw_args(), self._raw_aux(), rng)
                t.sync_arrays = outs
        if is_train:
            for k, v in new_aux.items():
                self.aux_dict[k]._set_data(v)
        self.outputs = [NDArray(o, self._ctx) for o in outs]
        if self._monitor_callback is not None and not self._monitor_all:
            for name, arr in zip(self._output_names, self.outputs):
                self._monitor_callback(name, arr)
        return self.outputs

    def backward(self, out_grads=None, is_train=True):
        from . import profiler as _profiler
        if all(r == "null" for r in self._grad_req.values()):
            return
        if out_grads is None:
            ograds = [jnp.ones(o.shape, o._data.dtype) for o in self.outputs]
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            ograds = [g._data if isinstance(g, NDArray) else jnp.asarray(g)
                      for g in out_grads]
        if self._saved_vjp is not None:
            # residuals saved by the training forward — backward-only work
            _, bwd_apply = self._make_lin_fns()
            with _profiler._timed("executor_backward") as t:
                grads = bwd_apply(self._saved_vjp, tuple(ograds))
                t.sync_arrays = list(grads.values())
            self._saved_vjp = None
        else:
            grad_fn = self._make_grad_fn()
            rng = getattr(self, "_last_rng", None)
            if rng is None:
                from . import random as _random
                rng = _random.next_key()
            with _profiler._timed("executor_backward") as t:
                outs, new_aux, grads = grad_fn(self._raw_args(),
                                               self._raw_aux(),
                                               rng, tuple(ograds))
                t.sync_arrays = list(grads.values()) + list(outs)
            self.outputs = [NDArray(o, self._ctx) for o in outs]
        for name, g in grads.items():
            req = self._grad_req.get(name, "null")
            if req == "null":
                continue
            dst = self.grad_dict.get(name)
            if dst is None:
                continue
            if req == "add":
                self._accum_grad(dst, g)
            else:
                dst._set_data(g)

    def forward_backward(self, out_grads=None, **kwargs):
        """Fused train step: one compiled program for fwd+bwd+aux update."""
        from . import random as _random
        from . import profiler as _profiler
        self._saved_vjp = None  # residuals from any earlier split forward
        for k, v in kwargs.items():
            self.arg_dict[k]._set_data(
                v._data if isinstance(v, NDArray) else jnp.asarray(v))
        grad_fn = self._make_grad_fn()
        rng = _random.next_key()
        probe_outs, _ = jax.eval_shape(
            lambda a, x, r: self._plan(a, x, r, True),
            self._raw_args(), self._raw_aux(), jax.ShapeDtypeStruct(
                (2,), _np.uint32))
        if out_grads is None:
            ograds = tuple(jnp.ones(o.shape, o.dtype) for o in probe_outs)
        else:
            ograds = tuple(g._data if isinstance(g, NDArray)
                           else jnp.asarray(g) for g in out_grads)
        with _profiler._timed("executor_forward_backward") as t:
            outs, new_aux, grads = grad_fn(self._raw_args(),
                                           self._raw_aux(), rng, ograds)
            t.sync_arrays = list(grads.values()) + list(outs)
        for k, v in new_aux.items():
            self.aux_dict[k]._set_data(v)
        self.outputs = [NDArray(o, self._ctx) for o in outs]
        for name, g in grads.items():
            req = self._grad_req.get(name, "null")
            if req == "null" or name not in self.grad_dict:
                continue
            dst = self.grad_dict[name]
            if req == "add":
                self._accum_grad(dst, g)
            else:
                dst._set_data(g)
        return self.outputs

    def make_fit_step(self, update_names, apply_fn, opt_state=None,
                      cache_extra=None, zero_shardings=None):
        """Build the fused donated train-step program: forward + backward +
        tree-wide optimizer apply traced into ONE jitted XLA program.

        This is the single-dispatch-per-batch hot path the per-param
        update loop (module.update → one XLA kernel per parameter) cannot
        reach: XLA sees the whole step, fuses the optimizer arithmetic
        into the backward epilogue, and ``donate_argnums`` on params /
        optimizer state / aux turns every update into an in-place HBM
        write (the reference's PlanMemory inplace discipline).

        ``update_names``  — grad_req='write' parameters the step updates.
        ``apply_fn(params, grads, state, lr, wd, rescale, t)``
                          — pure tree-wide optimizer apply
                            (ops.optimizer_ops.make_fused_apply).
        ``opt_state``     — example optimizer-state tree (shapes/dtypes
                            only are used) enabling the AOT warm-start
                            path below.
        ``cache_extra``   — the caller's optimizer-config hash folded
                            into the AOT cache key (mults and
                            hyperparameters are baked into the traced
                            program, so they must invalidate it).

        **AOT warm-start** (``MXTPU_AOT_CACHE_DIR`` set,
        ``opt_state``/``cache_extra`` provided; single-device AND mesh
        binds — the key folds in mesh axes, device order and every
        input/ZeRO sharding, so reshaped meshes miss instead of
        colliding): the program is lowered + compiled ahead of time and
        the executable serialized into the content-addressed cache
        (mxnet_tpu.aot_cache); a restarted rank with the same key
        deserializes it and skips trace+compile entirely —
        time-to-first-step drops from an XLA compile to a file read,
        and the watchdog is told its startup grace can shrink.  Any
        cache failure falls back to the normal jit path.

        The apply is wrapped in the divergence guard
        (ops.optimizer_ops.make_guarded_apply): an all-finite check over
        the global gradient tree runs inside the SAME program — still one
        dispatch per step — and a non-finite batch turns the update into
        a tree-wide no-op.  ``poison`` (0.0 normally, NaN when the
        grad.nan fault-injection site fires) is a dynamic scalar, so
        injected and production steps share one compiled program.

        **Mesh binds** compile the same ONE donated program with explicit
        ``in_shardings``/``out_shardings`` resolved from the bind's
        partition rules (params/opt-state/aux per rule, batch over
        ``dp``): XLA GSPMD partitions it across the mesh and the gradient
        all-reduce rides inside.  With ``zero_shardings`` (the ZeRO-1
        mode, ops.optimizer_ops docs) the optimizer state lives sharded
        1/N over ``dp``, gradients are reduce-scattered, the update
        applies on the local 1/N shard, and only the updated params are
        all-gathered — the divergence guard's skip/rollback semantics
        run INSIDE the sharded program unchanged.

        Returns ``step(param_vals, opt_state, other_vals, aux_vals, rng,
        lr, wd, rescale, t, poison) -> (outs, new_params, new_state,
        new_aux, ok)`` where new_aux covers ALL aux states (unchanged
        ones pass through, so donated aux buffers stay owned by the
        caller's write-back) and ``ok`` is the guard verdict scalar.
        """
        from .ops.optimizer_ops import make_guarded_apply
        plan = self._plan
        update_names = tuple(update_names)
        if zero_shardings is not None and self._mesh is None:
            raise MXNetError("zero_shardings requires a mesh bind")
        param_shardings = {n: self._shardings[n] for n in update_names} \
            if zero_shardings is not None else None
        guarded = make_guarded_apply(apply_fn, zero_shardings=zero_shardings,
                                     param_shardings=param_shardings)

        def step(param_vals, opt_state, other_vals, aux_vals, rng,
                 lr, wd, rescale, t, poison):
            def f(p):
                merged = dict(other_vals)
                merged.update(p)
                outs, new_aux = plan(merged, aux_vals, rng, True)
                return tuple(outs), new_aux

            with _telemetry.device_scope("forward_backward"):
                outs, vjp, new_aux = jax.vjp(f, param_vals, has_aux=True)
                # loss heads seed with ones, exactly like
                # forward_backward's default out_grads — fused and
                # unfused paths share semantics
                ograds = tuple(jnp.ones(o.shape, o.dtype) for o in outs)
                grads = vjp(ograds)[0]
            new_params, new_state, ok = guarded(
                param_vals, grads, opt_state, lr, wd, rescale, t, poison)
            # the guard's skip covers aux too: a NaN batch must not commit
            # poisoned forward-pass statistics (BatchNorm moving mean/var)
            # any more than poisoned weights
            merged_aux = dict(aux_vals)
            with _telemetry.device_scope("divergence_guard"):
                for k, v in new_aux.items():
                    merged_aux[k] = jnp.where(ok, v, aux_vals[k])
            return outs, new_params, new_state, merged_aux, ok

        if self._staged:
            return step  # eager multi-device ctx_group binds can't donate
        from . import aot_cache as _aot
        # each fused program gets fresh attribution: a rebuild on this
        # bind (optimizer reconfigured) must not republish the previous
        # program's cost/memory numbers
        self._cost_doc = None
        mk_jit = self._fit_step_jit_factory(step, update_names, opt_state,
                                            zero_shardings)
        if opt_state is not None:
            # every fused bind with an example state tree goes through
            # the AOT compile path, cache or no cache: the same compile
            # the lazy jit would pay at first dispatch happens eagerly,
            # and the compiled handle is what cost/memory attribution
            # (compiled.cost_analysis / memory_analysis → xla.cost.* /
            # xla.memory.* gauges, OBSERVABILITY.md §8) and the
            # in-process memo need.  The disk tiers additionally need
            # the cache dir and the caller's config hash — and the mesh
            # layout is part of the executable's identity: same devices
            # under a different mesh shape / different input shardings
            # is a different program (the PR-6 topology-clobber class of
            # bug, aot_cache.fingerprint docs), folded into the key
            # alongside the optimizer-config hash.  Mesh programs on
            # backends that cannot execute ANY deserialized SPMD
            # executable (aot_cache.deserialized_spmd_safe: CPU heap
            # corruption / rendezvous deadlock, even donation-free) use
            # only the in-process memo tier — no disk.
            # cache_extra IS the program's identity (graph + optimizer
            # hash): without it the key would cover only backend +
            # shapes, and two same-shape different-graph binds would
            # collide in the memo/disk tiers — so a None cache_extra
            # keeps the eager compile (cost capture) but serves NO
            # cache tier, exactly the per-bind isolation the old lazy
            # path gave such callers
            identity_ok = cache_extra is not None
            disk_ok = identity_ok and _aot.enabled() and \
                (self._mesh is None or _aot.deserialized_spmd_safe())
            fn = self._aot_fit_step(
                step, update_names, opt_state,
                (cache_extra or "") +
                self._mesh_cache_extra(zero_shardings),
                mk_jit, disk_ok=disk_ok, memo_ok=identity_ok)
            if fn is not None:
                return fn
        # donated program compiling lazily at first dispatch (no example
        # opt-state tree, or the AOT path failed): keep it out of jax's
        # persistent cache on backends where replaying a donated
        # executable from that cache corrupts the heap (aot_cache docs)
        return self._instrument(_aot.donation_cache_guard(mk_jit()))

    def _fit_step_jit_factory(self, step, update_names, opt_state,
                              zero_shardings):
        """One place that turns the traced step into a jit: non-mesh
        binds keep the bare donated jit; mesh binds add the explicit
        in/out shardings so the SAME factory serves the lazy dispatch
        path, the AOT ``.lower(examples)`` path (ShapeDtypeStructs carry
        no committed placement — without explicit shardings the lowered
        program would be single-device), and the donation-free twin."""
        shardings = self._fit_step_shardings(update_names, opt_state,
                                             zero_shardings)

        def mk_jit(donated=True):
            kw = {}
            if shardings is not None:
                kw["in_shardings"], kw["out_shardings"] = shardings
            if donated:
                kw["donate_argnums"] = (0, 1, 3)
            return jax.jit(step, **kw)

        if self._mesh is not None:
            self._note_sharding_telemetry(update_names, opt_state,
                                          zero_shardings)
        return mk_jit

    def _fit_step_shardings(self, update_names, opt_state, zero_shardings):
        """(in_shardings, out_shardings) for the fused step on this
        bind's mesh, or None for single-device binds.  Opt-state
        shardings are pytree PREFIXES ({name: NamedSharding} broadcasting
        over e.g. Adam's (mean, var) tuple); scalar step inputs
        (lr/wd/rescale/t/poison) pass None = unconstrained."""
        if self._mesh is None:
            return None
        rep = NamedSharding(self._mesh, _P())
        params_sh = {n: self._shardings[n] for n in update_names}
        state_sh = dict(zero_shardings) if zero_shardings is not None \
            else {n: params_sh[n] for n in update_names}
        in_update = set(update_names)
        other_sh = {n: self._shardings[n] for n in self.arg_dict
                    if n not in in_update}
        aux_sh = {n: self._shardings[n] for n in self.aux_dict}
        in_sh = (params_sh, state_sh, other_sh, aux_sh, rep,
                 None, None, None, None, None)
        # outs stay unconstrained (loss heads come out dp-sharded with
        # the batch; pinning them replicated would buy an all-gather of
        # logits every step); params/state/aux must land exactly where
        # their donated inputs lived
        out_sh = (None, params_sh, state_sh, aux_sh, rep)
        return in_sh, out_sh

    def _mesh_cache_extra(self, zero_shardings):
        """Cache-key text for the mesh layout: axis names+sizes, the flat
        device order, every input's PartitionSpec, and the ZeRO specs.
        Folded into the AOT key so executables from different mesh
        shapes over the SAME device set can never collide."""
        if self._mesh is None:
            return ""
        mesh = self._mesh
        specs = sorted((n, str(s.spec)) for n, s in self._shardings.items())
        zspecs = sorted((n, str(s.spec)) for n, s in
                        (zero_shardings or {}).items())
        return "|mesh:%s|dev:%s|in:%s|zero:%s" % (
            tuple(mesh.shape.items()),
            ",".join(str(d.id) for d in mesh.devices.flat), specs, zspecs)

    def _note_sharding_telemetry(self, update_names, opt_state,
                                 zero_shardings):
        """Publish the step's sharding economics (OBSERVABILITY.md):

        - ``sharding.opt_state_bytes_per_device`` — bytes of optimizer
          state each device actually holds (1/N of the sharded leaves +
          all of the replicated fallbacks);
        - ``sharding.collective_bytes_per_step`` — per-device bytes the
          weight-update collectives move each step (ring-collective
          model): reduce-scatter B(N-1)/N + all-gather B(N-1)/N per
          ZeRO-sharded param vs all-reduce 2B(N-1)/N per replicated one
          — equal totals, but ZeRO holds 1/N of the state and runs 1/N
          of the update math."""
        mesh = self._mesh
        n = mesh.shape.get(self._dp_axis, 1)

        def shard_factor(spec):
            """How many ways a leaf with ``spec`` is split across the
            mesh: the product of EVERY named axis in the spec (a
            P('tp','dp') leaf on a dp=4,tp=2 mesh occupies 1/8 per
            device, not 1/4)."""
            f = 1
            for entry in tuple(spec or ()):
                if entry is None:
                    continue
                for a in (entry if isinstance(entry, tuple) else (entry,)):
                    f *= mesh.shape[a]
            return f

        state_bytes = 0
        if opt_state is not None:
            for name, sub in opt_state.items():
                if zero_shardings is not None and name in zero_shardings:
                    f = shard_factor(zero_shardings[name].spec)
                else:
                    f = shard_factor(self.param_spec(name))
                for leaf in jax.tree_util.tree_leaves(sub):
                    state_bytes += getattr(leaf, "nbytes", 0) // f
        coll_bytes = 0
        if n > 1:
            for name in update_names:
                b = self.arg_dict[name]._data.nbytes
                coll_bytes += 2 * b * (n - 1) // n
        _telemetry.gauge("sharding.opt_state_bytes_per_device") \
            .set(state_bytes)
        # the ring MODEL: what the weight-update collectives should move
        # if the program contains exactly the collectives the ZeRO/DP
        # design predicts.  sharding.collective_bytes_per_step starts as
        # this model and is OVERWRITTEN by the measurement from the
        # compiled program's actual collective ops once the fused step
        # compiles (_publish_cost_telemetry) — the modeled gauge stays
        # for comparison (a large gap means the compiler emitted
        # different collectives than the design assumes).
        _telemetry.gauge("sharding.collective_bytes_modeled") \
            .set(coll_bytes)
        _telemetry.gauge("sharding.collective_bytes_per_step") \
            .set(coll_bytes)
        _telemetry.gauge("sharding.zero_stage").set(
            1 if zero_shardings is not None else 0)

    # -- compile-time cost attribution (OBSERVABILITY.md §8) ---------------
    _DTYPE_BYTES = {"pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
                    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
                    "s32": 4, "u32": 4, "f32": 4,
                    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
                    # fp8 families (quantized-comm collectives must not
                    # count as zero-payload opaque types)
                    "f8e4m3": 1, "f8e4m3fn": 1, "f8e4m3fnuz": 1,
                    "f8e4m3b11fnuz": 1, "f8e5m2": 1, "f8e5m2fnuz": 1,
                    "f8e3m4": 1, "f8e8m0fnu": 1}

    @classmethod
    def _hlo_collective_bytes(cls, hlo_text, n):
        """Measured per-device collective traffic of one step, from the
        compiled (post-GSPMD, post-optimization) HLO: every collective
        op's OUTPUT shape — per-device in the partitioned module —
        converted to ring-equivalent bytes moved with ``n``
        participants:

        - all-reduce: ``2·B·(n-1)/n`` (ring RS+AG of the full buffer B =
          output size),
        - all-gather: ``B·(n-1)/n`` (B = gathered output),
        - reduce-scatter: ``B_full·(n-1)/n = B_out·(n-1)`` (output is the
          1/n shard),
        - all-to-all: ``B·(n-1)/n``; collective-permute: ``B``.

        ``n`` is approximated by the bind's data-parallel axis size
        (collectives over other mesh axes get the same factor — close
        enough for the gauge's job of replacing a formula that guessed
        at the program's very structure).  Async pairs count once (the
        ``-done`` op carries the result; ``-start`` outputs are
        bookkeeping tuples).  Returns ``(bytes, {op: count})``."""
        import re
        total = 0
        counts = {}
        op_re = re.compile(
            r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
            r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
            r"collective-permute)((?:-start|-done)?)\(")
        shape_re = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
        for m in op_re.finditer(hlo_text):
            shapes, op, suffix = m.group(1), m.group(2), m.group(3)
            if suffix == "-start":
                continue
            b = 0
            for dt, dims in shape_re.findall(shapes):
                size = cls._DTYPE_BYTES.get(dt)
                if size is None:
                    continue  # token/opaque types carry no payload
                numel = 1
                for d in dims.split(","):
                    if d:
                        numel *= int(d)
                b += numel * size
            if n > 1:
                factor = {"all-reduce": 2.0 * (n - 1) / n,
                          "all-gather": (n - 1) / n,
                          "reduce-scatter": float(n - 1),
                          "all-to-all": (n - 1) / n,
                          "collective-permute": 1.0}[op]
            else:
                factor = 0.0
            total += int(b * factor)
            counts[op] = counts.get(op, 0) + 1
        return total, counts

    def _analyze_compiled(self, compiled):
        """JSON-able compile-time attribution of the fused step, from
        the backend's own accounting of the AOT-compiled program:
        ``cost_analysis`` (flops / bytes-accessed per execution),
        ``memory_analysis`` (argument / output / temp / alias /
        generated-code bytes resident per device), and the measured
        collective bytes (mesh binds).  Every field is best-effort —
        a backend that reports nothing yields None, never an error."""
        doc = {}
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            if ca:
                cost = {"flops": ca.get("flops"),
                        "bytes_accessed": ca.get("bytes accessed"),
                        "transcendentals": ca.get("transcendentals")}
                doc["cost"] = {k: v for k, v in cost.items()
                               if v is not None}
        except Exception:
            pass
        try:
            ma = compiled.memory_analysis()
            if ma is not None:
                doc["memory"] = {
                    "argument_bytes": int(ma.argument_size_in_bytes),
                    "output_bytes": int(ma.output_size_in_bytes),
                    "temp_bytes": int(ma.temp_size_in_bytes),
                    "alias_bytes": int(ma.alias_size_in_bytes),
                    "generated_code_bytes":
                        int(ma.generated_code_size_in_bytes),
                }
        except Exception:
            pass
        if self._mesh is not None:
            try:
                n = self._mesh.shape.get(self._dp_axis, 1)
                bytes_, counts = self._hlo_collective_bytes(
                    compiled.as_text(), n)
                doc["collectives"] = {"bytes_per_step": bytes_,
                                      "ops": counts,
                                      "participants": n}
            except Exception:
                pass
        if self._graph_report is not None:
            # the rewrite pipeline's pass report rides the AOT entry
            # metadata next to the cost/memory attribution, so a warm
            # restart can still say what the stored program was built
            # from (nodes before/after, rewrites by pattern, pass time)
            doc["graph"] = self._graph_report
        return doc or None

    def _capture_cost_telemetry(self, compiled):
        """Derive (once per bind) and publish the attribution doc for
        the fused step.  Returns the doc — the AOT cache stores it as
        entry metadata so a warm restart republishes the original
        compile's numbers without a compiled object that can re-derive
        them."""
        doc = getattr(self, "_cost_doc", None)
        if doc is None:
            doc = self._analyze_compiled(compiled)
        return self._publish_cost_telemetry(doc)

    def _publish_cost_telemetry(self, doc):
        """Set the xla.cost.* / xla.memory.* gauges (and overwrite the
        modeled collective-bytes gauge with the measured value) from an
        attribution doc.  Idempotent; kept separate from capture so
        probes that reset the registry mid-run can republish
        (:meth:`publish_cost_telemetry`)."""
        if not doc:
            return None
        self._cost_doc = doc
        for k, v in (doc.get("cost") or {}).items():
            _telemetry.gauge("xla.cost.%s_per_step" % k).set(v)
        for k, v in (doc.get("memory") or {}).items():
            _telemetry.gauge("xla.memory.%s" % k).set(v)
        coll = doc.get("collectives")
        if coll and coll.get("bytes_per_step") is not None:
            _telemetry.gauge("sharding.collective_bytes_per_step") \
                .set(coll["bytes_per_step"])
        return doc

    def publish_cost_telemetry(self):
        """Re-publish the bind's attribution gauges (no-op before the
        fused step compiled).  For probes (steptrace) that reset the
        telemetry registry after warmup."""
        return self._publish_cost_telemetry(
            getattr(self, "_cost_doc", None))

    def _aot_fit_step(self, step, update_names, opt_state, cache_extra,
                      mk_jit, disk_ok=True, memo_ok=True):
        """AOT-compile the fused step against the bound shapes and run it
        through the persistent executable cache.  Returns the
        instrumented program, or None to fall back to plain jit (any
        cache/serialization trouble must never break training).

        Three tiers (aot_cache module docs):

        - **memo hit** — same-process rebuild: the original compiled
          object, any backend, free;
        - **disk hit, donated variant** (TPU-class): deserialize and run —
          no trace, no compile;
        - **disk hit, plain variant** (CPU): deserialize the donation-free
          twin for the first steps, compile the donated program in the
          background, hot-swap when ready (:meth:`_twin_hotswap`).

        A miss compiles the donated program (outside jax's persistent
        cache where donated replay is unsafe), then serializes this
        backend's consumable variant off the hot path."""
        from . import aot_cache as _aot
        from . import watchdog as _watchdog

        def sds(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)

        try:
            in_update = set(update_names)
            examples = (
                {n: sds(self.arg_dict[n]._data) for n in update_names},
                jax.tree_util.tree_map(sds, opt_state),
                {n: sds(a._data) for n, a in self.arg_dict.items()
                 if n not in in_update},
                {n: sds(a._data) for n, a in self.aux_dict.items()},
                jax.ShapeDtypeStruct((2,), _np.uint32),   # rng key
                # lr/wd/rescale/t/poison lower as weak-typed Python
                # floats, exactly what the hot path passes per step
                0.01, 0.0, 1.0, 1.0, 0.0)
            key = _aot.cache_key("fit_step", examples, extra=cache_extra)
            memo = _aot.memo_get(key) if memo_ok else None
            if memo is not None:
                # original compiled object: cost attribution re-derives
                # (or a prior capture on this executor already published)
                self._capture_cost_telemetry(memo)
                _telemetry.note_program("fit_step", memo)
                return self._instrument(memo, first_call_compiles=False)
            loaded = _aot.load(key) if disk_ok else None
            if loaded is not None:
                compiled, var, meta = loaded
                # no trace, no (foreground) compile: the startup-grace
                # window sized for XLA compilation can shrink
                _watchdog.note_warm_start()
                # a deserialized executable cannot always re-derive its
                # analyses — republish the original compile's numbers
                # from the entry sidecar
                self._publish_cost_telemetry(
                    meta or self._analyze_compiled(compiled))
                if var == _aot.VARIANT_DONATED:
                    _aot.memo_put(key, compiled)
                    _telemetry.note_program("fit_step", compiled)
                    return self._instrument(compiled,
                                            first_call_compiles=False)
                return self._twin_hotswap(mk_jit, examples, key, compiled)
            with _telemetry.span("aot.compile", cat="aot"):
                with _aot.bypass_persistent_cache():
                    compiled = mk_jit().lower(*examples).compile()
            meta = self._capture_cost_telemetry(compiled)
            if memo_ok:
                _aot.memo_put(key, compiled)
            if disk_ok:
                self._spawn_aot_store(mk_jit, examples, key, compiled,
                                      meta)
            _telemetry.note_program("fit_step", compiled)
            return self._instrument(compiled)
        except Exception as e:
            import logging
            logging.warning("mxnet_tpu.executor: AOT warm-start path "
                            "unavailable (%s: %s); using plain jit",
                            type(e).__name__, e)
            return None

    def _spawn_aot_store(self, mk_jit, examples, key, compiled,
                         meta=None):
        """Serialize this backend's consumable variant into the cache off
        the hot path — ONE shared implementation of the §8 variant
        policy (``aot_cache.spawn_variant_store``; the serving engine
        uses the same one).  ``meta`` (the donated compile's cost/memory
        attribution) rides along: the donated and twin programs share
        one computation, and a warm restart republishes these numbers
        without re-deriving them."""
        from . import aot_cache as _aot
        _aot.spawn_variant_store(mk_jit, examples, key, compiled, meta,
                                 where="mxnet_tpu.executor")

    def _twin_hotswap(self, mk_jit, examples, key, twin):
        """Warm CPU restart: run the deserialized donation-free twin NOW
        (instant first step), compile the donated program in the
        background, and swap it in between steps
        (``aot_cache.twin_hotswap_cell`` — shared with the serving
        engine).  Until the swap the twin costs an extra param-tree copy
        per step; after it, steady state is identical to a cold start.
        The swap is a single dict read per call — no dispatches added,
        so steptrace's 1.0/step contract holds through it."""
        from . import aot_cache as _aot
        call = _aot.twin_hotswap_cell(mk_jit, examples, key, twin,
                                      where="mxnet_tpu.executor")
        return self._instrument(call, first_call_compiles=False)

    # -- parameter management ----------------------------------------------
    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self._arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self._arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self._aux_names]

    @property
    def output_dict(self):
        return dict(zip(self._output_names, self.outputs))

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for name, array in arg_params.items():
            if name in self.arg_dict:
                self.arg_dict[name]._set_data(jnp.asarray(
                    array.asnumpy() if isinstance(array, NDArray)
                    else array, self.arg_dict[name]._data.dtype))
            elif not allow_extra_params:
                raise ValueError("Find name \"%s\" that is not in the "
                                 "arguments" % name)
        if aux_params:
            for name, array in aux_params.items():
                if name in self.aux_dict:
                    self.aux_dict[name]._set_data(jnp.asarray(
                        array.asnumpy() if isinstance(array, NDArray)
                        else array, self.aux_dict[name]._data.dtype))
                elif not allow_extra_params:
                    raise ValueError("Find name \"%s\" that is not in the "
                                     "auxiliary states" % name)

    def set_monitor_callback(self, callback, monitor_all=False):
        """monitor_all taps every node output via interpret mode (slow,
        debug-only); otherwise only final outputs are reported."""
        self._monitor_callback = callback
        self._monitor_all = monitor_all

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Rebind with new shapes (jit handles recompilation)."""
        from . import nd
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        new_args = {}
        for name, shape in zip(self._arg_names, arg_shapes):
            cur = self.arg_dict[name]
            new_args[name] = cur if cur.shape == shape else \
                nd.zeros(shape, ctx=self._ctx, dtype=cur.dtype)
        new_aux = {}
        for name, shape in zip(self._aux_names, aux_shapes):
            cur = self.aux_dict[name]
            new_aux[name] = cur if cur.shape == shape else \
                nd.zeros(shape, ctx=self._ctx, dtype=cur.dtype)
        grad_req = self._grad_req
        args_grad = {n: nd.zeros(a.shape, ctx=self._ctx, dtype=a.dtype)
                     for n, a in new_args.items()
                     if grad_req.get(n, "null") != "null"}
        return Executor(self._symbol, self._ctx, new_args, args_grad,
                        grad_req, new_aux, group2ctx=self._group2ctx,
                        mesh=self._mesh, batch_names=self._batch_names,
                        dp_axis=self._dp_axis,
                        partition_rules=self._partition_rules)
