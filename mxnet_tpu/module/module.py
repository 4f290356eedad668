"""Module: the symbolic training Model API.

Port of /root/reference/python/mxnet/module/module.py (246-631).  The
reference bound one executor per GPU and layered gradient reduction over
KVStore (DataParallelExecutorGroup, module/executor_group.py:99).  The
TPU-native design binds ONE executor — XLA SPMD over a device mesh replaces
the per-device executor group, and the fused forward_backward is a single
compiled program.  Multi-context calls (context=[tpu(0), tpu(1), ...]) keep
working: the batch stays whole and the step is sharded across the mesh by
the parallel layer rather than split by Python.
"""
from __future__ import annotations

import logging

from .. import context as ctx_mod
from .. import ndarray as nd
from .. import optimizer as opt
from ..base import MXNetError
from ..initializer import Uniform, InitDesc
from ..model import (_create_kvstore, _initialize_kvstore, _update_params,
                     _update_params_on_kvstore, load_checkpoint,
                     save_checkpoint)
from .base_module import BaseModule, _check_input_names

__all__ = ["Module"]


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None, partition_rules=None):
        """``partition_rules``: optional parallel.sharding rule list
        ((pattern, PartitionSpec[, ndim]) tuples or PartitionRule
        objects) resolved over the named param tree at bind — model code
        stays sharding-agnostic while a multi-context bind places every
        param per rule (replicated when no rule matches)."""
        super().__init__(logger=logger)
        self._partition_rules = partition_rules
        if context is None:
            context = ctx_mod.current_context()
        if isinstance(context, ctx_mod.Context):
            context = [context]
        self._context = context
        self._work_load_list = work_load_list

        self._symbol = symbol
        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []
        state_names = list(state_names) if state_names is not None else []
        fixed_param_names = list(fixed_param_names) \
            if fixed_param_names is not None else []

        _check_input_names(symbol, data_names, "data", True)
        _check_input_names(symbol, label_names, "label", False)
        _check_input_names(symbol, state_names, "state", True)
        _check_input_names(symbol, fixed_param_names, "fixed_param", True)

        arg_names = symbol.list_arguments()
        input_names = data_names + label_names + state_names
        self._param_names = [x for x in arg_names if x not in input_names]
        self._fixed_param_names = fixed_param_names
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = label_names
        self._state_names = state_names
        self._output_names = symbol.list_outputs()

        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False

        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None
        self._fused = None  # fused fit_step cache (program + opt state)
        self._consec_guard_skips = 0  # divergence-guard skip streak
        self._precision = None  # PrecisionPolicy (mxnet_tpu.precision)

        self._exec = None
        self._data_shapes = None
        self._label_shapes = None

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """Create a Module from a saved checkpoint (reference :146)."""
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False,
                        keep_last=None, mode=None):
        """Save symbol+params(+optimizer states) (reference :173).

        Crash-safe: every artifact is written atomically and the epoch's
        manifest commits last (checkpoint.CheckpointManager), so a crash
        mid-save can never produce a checkpoint that recovery would
        mistake for complete.  ``keep_last`` prunes to the N newest
        complete checkpoints.  ``mode`` ("sync"/"async"/None→env): under
        the async pipeline this call only snapshots to host memory and
        the write overlaps subsequent training; writer failures surface
        on the next fit_step/save/flush (checkpoint.py)."""
        from ..checkpoint import CheckpointManager
        states = None
        if save_optimizer_states:
            states = self._optimizer_states_bytes()
        arg_params, aux_params = self.get_params()
        CheckpointManager(prefix, keep_last=keep_last).save(
            epoch, arg_params, aux_params, symbol=self._symbol,
            optimizer_states=states, mode=mode,
            sharding=self._sharding_stamp(),
            # the streaming-fit sugar: BaseModule.fit stamps the
            # StreamLoader's exact-once cursor here at each epoch
            # boundary, so a plain module_checkpoint callback writes
            # manifests StreamLoader(resume=...) can replay
            stream_cursor=getattr(self, "_stream_cursor", None))

    def _sharding_stamp(self):
        """Manifest stamp for the run's in-memory layout (SCALING.md):
        {"zero_stage", "mesh", "opt_state", "specs"} when the fused step
        runs ZeRO-1 on a mesh, else None.  The state PAYLOAD on disk is
        always full-size — `_optimizer_states_bytes` flushes through the
        Updater, and converting a dp-sharded jax array to host bytes IS
        the all-gather-on-save — so the stamp documents provenance and
        lets an elastic resume at a different world size reshard
        deliberately instead of guessing."""
        fused = self._fused
        if not fused or not fused.get("zero"):
            return None
        mesh = self._exec._mesh
        return {
            "zero_stage": 1,
            "mesh": {k: int(v) for k, v in mesh.shape.items()},
            "opt_state": "gathered",
            "specs": {name: str(s.spec)
                      for name, s in fused["zero"].items()},
        }

    # -- properties --------------------------------------------------------
    @property
    def graph_report(self):
        """The bind's graph rewrite-pipeline pass report (nodes
        before/after, rewrites by pattern, per-pass wall time), or None
        before bind / with the pipeline disabled."""
        return self._exec._graph_report if self._exec is not None else None

    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        _, out_shapes, _ = self._symbol.infer_shape(
            **dict([(d[0], d[1]) for d in
                    (self._data_shapes + (self._label_shapes or []))]))
        return list(zip(self._output_names, out_shapes))

    # -- parameters --------------------------------------------------------
    def get_params(self):
        assert self.binded and self.params_initialized
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def init_params(self, initializer=None, arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        """Initialize parameters (reference module.py:246)."""
        if self.params_initialized and not force_init:
            return
        assert self.binded, "call bind before initializing the parameters"
        if initializer is None:
            initializer = Uniform(0.01)

        if self._arg_params is None:
            self._arg_params = {
                name: nd.zeros(self._exec.arg_dict[name].shape,
                               dtype=self._exec.arg_dict[name].dtype)
                for name in self._param_names}
        if self._aux_params is None:
            self._aux_params = {
                name: nd.zeros(self._exec.aux_dict[name].shape,
                               dtype=self._exec.aux_dict[name].dtype)
                for name in self._aux_names}

        attrs = self._symbol.attr_dict()

        def _impl(name, arr, cache):
            if cache is not None:
                if name in cache:
                    cache_arr = cache[name]
                    if cache_arr is not arr:
                        if cache_arr.shape != arr.shape:
                            raise MXNetError(
                                "Shape mismatch for %s: %s vs %s" %
                                (name, str(cache_arr.shape),
                                 str(arr.shape)))
                        cache_arr.copyto(arr)
                else:
                    if not allow_missing:
                        raise RuntimeError("%s is not presented" % name)
                    if initializer is not None:
                        initializer(name, arr)
            else:
                initializer(name, arr)

        for name, arr in sorted(self._arg_params.items()):
            desc = InitDesc(name, attrs.get(name))
            _impl(desc, arr, arg_params)
        for name, arr in sorted(self._aux_params.items()):
            desc = InitDesc(name, attrs.get(name))
            _impl(desc, arr, aux_params)

        self.params_initialized = True
        self._params_dirty = False
        self._push_params_to_exec()

    def _push_params_to_exec(self):
        for name, arr in self._arg_params.items():
            if name in self._exec.arg_dict:
                self._exec.arg_dict[name]._set_data(arr._data)
        for name, arr in self._aux_params.items():
            if name in self._exec.aux_dict:
                self._exec.aux_dict[name]._set_data(arr._data)

    def _sync_params_from_devices(self):
        for name in self._param_names:
            self._arg_params[name]._set_data(self._exec.arg_dict[name]._data)
        for name in self._aux_names:
            self._aux_params[name]._set_data(self._exec.aux_dict[name]._data)
        self._params_dirty = False

    # -- binding -----------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        """Compile the symbol for the given shapes (reference module.py:351).

        simple_bind → trace → XLA; PlanMemory/bulking are XLA's problem now.
        """
        if force_rebind:
            self._exec = None
            self.binded = False
        if self.binded:
            self.logger.warning("Already binded, ignoring bind()")
            return
        # the fused step program closes over the executor being replaced;
        # optimizer state (plain jnp arrays) survives via _fused_setup
        self._fused_flush_to_updater()
        self._fused = None

        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad

        def _norm(shapes):
            if shapes is None:
                return None
            out = []
            for s in shapes:
                if hasattr(s, "name"):
                    out.append((s.name, tuple(s.shape)))
                else:
                    out.append((s[0], tuple(s[1])))
            return out

        self._data_shapes = _norm(data_shapes)
        self._label_shapes = _norm(label_shapes)

        shape_kwargs = dict(self._data_shapes)
        if self._label_shapes:
            shape_kwargs.update(dict(self._label_shapes))

        req = {}
        for name in self._symbol.list_arguments():
            if name in self._data_names:
                req[name] = "write" if inputs_need_grad else "null"
            elif name in self._label_names or name in self._state_names:
                req[name] = "null"
            elif name in self._fixed_param_names:
                req[name] = "null"
            elif not for_training:
                req[name] = "null"
            else:
                req[name] = grad_req if isinstance(grad_req, str) \
                    else grad_req.get(name, "write")

        ctx = self._context[0]
        mesh = batch_names = None
        if len(self._context) > 1:
            # Module(context=[N devices]) → one SPMD program over a dp mesh.
            # The reference sliced every batch across per-device executors
            # (executor_group.py:296-378) and reduced grads through KVStore;
            # here the whole batch is dp-sharded into ONE compiled step and
            # XLA inserts the gradient all-reduce over ICI.
            from ..parallel.mesh import dp_mesh_from_ctx
            mesh = dp_mesh_from_ctx(self._context)
            batch_names = self._data_names + self._label_names
        self._exec = self._symbol.simple_bind(
            ctx, grad_req=req, mesh=mesh, batch_names=batch_names,
            partition_rules=self._partition_rules, **shape_kwargs)
        self.binded = True
        if shared_module is not None and shared_module.params_initialized:
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
            self.params_initialized = True
            self._push_params_to_exec()
        elif self.params_initialized:
            self._push_params_to_exec()

    # -- optimizer ---------------------------------------------------------
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        """Set up optimizer + kvstore (reference module.py:460)."""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return

        if self._params_dirty:
            self._sync_params_from_devices()

        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), self._arg_params)
        batch_size = self._data_shapes[0][1][0]
        if kvstore and "dist" in kvstore.type and \
                "_sync" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        if isinstance(optimizer, str):
            idx2name = {i: n for i, n in enumerate(self._param_names)}
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt.create(optimizer, sym=self.symbol,
                                   param_idx2name=idx2name,
                                   **optimizer_params)
        else:
            assert isinstance(optimizer, opt.Optimizer)

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None
        optimizer.set_lr_mult({})
        optimizer.set_wd_mult({})

        if kvstore:
            param_arrays = [[self._exec.arg_dict[n]]
                            for n in self._param_names]
            _initialize_kvstore(kvstore=kvstore, param_arrays=param_arrays,
                                arg_params=self._arg_params,
                                param_names=self._param_names,
                                update_on_kvstore=update_on_kvstore)
            if update_on_kvstore:
                kvstore.set_optimizer(self._optimizer)
        if not update_on_kvstore:
            self._updater = opt.get_updater(optimizer)

        self.optimizer_initialized = True
        self._fused = None  # rebuilt lazily against the new optimizer
        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    # -- computation -------------------------------------------------------
    def _feed_batch(self, data_batch):
        feeds = {}
        data = data_batch.data
        for name, arr in zip(self._data_names, data):
            feeds[name] = arr
        if self._label_names and data_batch.label:
            for name, arr in zip(self._label_names, data_batch.label):
                feeds[name] = arr
        return feeds

    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        if is_train is None:
            is_train = self.for_training
        feeds = self._feed_batch(data_batch)
        self._exec.forward(is_train=is_train, **feeds)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._exec.backward(out_grads=out_grads)

    def forward_backward(self, data_batch):
        """One fused jitted program for fwd+bwd (the per-batch hot path)."""
        assert self.binded and self.params_initialized
        feeds = self._feed_batch(data_batch)
        self._exec.forward_backward(**feeds)

    def set_precision(self, policy):
        """Install a :class:`mxnet_tpu.precision.PrecisionPolicy` (or
        None to clear).  The policy's fingerprint keys the fused-step
        program — changing it rebuilds instead of replaying a stale
        executable — and its loss scaler (if any) threads through the
        step's dynamic ``rescale_grad`` and consumes the divergence-
        guard verdict (skip accounting unchanged)."""
        self._precision = policy
        self._fused = None

    # -- fused fit step ----------------------------------------------------
    def _fused_eligible(self):
        """Can this configuration run fwd+bwd+update as ONE donated XLA
        program?  kvstore aggregation, grad_req='add' accumulation,
        inputs_need_grad, installed monitors, staged (multi-ctx-group)
        binds, and non-fusable optimizers all keep the split path."""
        if self._kvstore is not None or self._update_on_kvstore:
            return False
        if self._optimizer is None or self._optimizer.fused_kind() is None:
            return False
        if self._exec is None or self._exec._staged:
            return False
        if self._exec._monitor_callback is not None:
            return False
        if self.inputs_need_grad:
            return False
        for name in self._param_names:
            if self._exec._grad_req.get(name, "null") not in ("write",
                                                              "null"):
                return False
        return True

    def _fused_update_names(self):
        return [n for n in self._param_names
                if self._exec._grad_req.get(n) == "write"]

    def _fused_setup(self):
        """(Re)build the fused step program + optimizer state.  The cache
        key covers everything baked statically into the program
        (optimizer identity/kind and the per-param mult aux tree);
        lr / wd / rescale_grad / t stay dynamic so schedulers never force
        a rebuild."""
        from ..ops.optimizer_ops import zero_stage
        opt = self._optimizer
        kind = opt.fused_kind()
        update_names = self._fused_update_names()
        idx2name = {i: n for i, n in enumerate(self._param_names)
                    if n in set(update_names)}
        mults = opt.fused_mults(idx2name)
        # ZeRO-1 (MXTPU_ZERO=1, SCALING.md): optimizer state sharded 1/N
        # over the dp mesh axis.  The env value is part of the cache key
        # — toggling it across a re-setup must rebuild the program AND
        # re-place the state — but the sharding resolution itself runs
        # only on rebuild (this method is on the per-step path)
        # the SAME gate zero_shardings applies (mesh with a >1 dp axis),
        # so the key flag always equals the resolved (zero is not None)
        # and the state-carry fast path stays live on dp-less meshes
        mesh = self._exec._mesh
        want_zero = zero_stage() >= 1 and mesh is not None and \
            self._exec._dp_axis in mesh.shape and \
            mesh.shape[self._exec._dp_axis] > 1
        from ..precision import policy_fingerprint
        precision_fp = policy_fingerprint(self._precision)
        key = (id(opt), kind, tuple(update_names),
               tuple(sorted(mults.items())),
               tuple(sorted(opt.fused_hyper().items())),
               want_zero, precision_fp)
        if self._fused is not None and self._fused["key"] == key:
            return self._fused
        zero = self._exec.zero_shardings(update_names) \
            if want_zero else None
        init_state, apply_fn = opt.make_fused_apply(idx2name,
                                                    zero_shardings=zero)
        params = {n: self._exec.arg_dict[n] for n in update_names}
        if self._fused is not None and self._fused["kind"] == kind and \
                self._fused["key"][-2] == (zero is not None) and \
                set(self._fused["state"]) == set(update_names):
            state = self._fused["state"]  # mults changed; state carries
        else:
            # park accumulated momentum/Adam moments in the Updater
            # FIRST (same discipline as Trainer._fused_step): a rebuild
            # that can't carry state directly (kind change, MXTPU_ZERO
            # toggled between steps) re-seeds from the Updater, and
            # without this flush the re-seed would silently rewind to
            # whatever the Updater last saw
            self._fused_flush_to_updater()
            state = self._fused_state_from_updater(kind, init_state, params,
                                                   zero_shardings=zero)
        # everything baked statically into the traced program feeds the
        # AOT warm-start cache key (aot_cache.cache_key adds the backend
        # fingerprint and the full input tree shapes/dtypes itself).
        # The GRAPH must be in the key too: two networks with identical
        # param names/shapes but different ops (relu vs tanh, a changed
        # loss) would otherwise collide and a restart would silently
        # train the wrong program
        import hashlib as _hashlib
        graph = _hashlib.sha256(
            self._symbol.tojson().encode("utf-8")).hexdigest()
        cache_extra = repr((graph, type(opt).__name__, kind,
                            tuple(update_names),
                            tuple(sorted(mults.items())),
                            tuple(sorted(opt.fused_hyper().items())),
                            precision_fp))
        self._fused = {
            "key": key, "kind": kind, "update_names": update_names,
            "state": state, "zero": zero,
            "step": self._exec.make_fit_step(update_names, apply_fn,
                                             opt_state=state,
                                             cache_extra=cache_extra,
                                             zero_shardings=zero),
        }
        return self._fused

    def _fused_state_from_updater(self, kind, init_state, params,
                                  zero_shardings=None):
        """Seed fused optimizer state, adopting any state the Updater
        already holds (e.g. from load_optimizer_states).  Under ZeRO-1
        every leaf — freshly-initialized AND Updater-loaded (checkpoint
        states are saved gathered) — is placed onto its 1/N dp sharding:
        this is the reshard-on-load half of the elastic contract (a
        checkpoint written at world N loads at world M because the state
        payload is always full-size on disk)."""
        # _raw commits params to their mesh placement first, so
        # zeros_like state inherits it (mixed committed devices would
        # fail the jitted fused step)
        raw = self._exec._raw(params)
        state = init_state(raw)
        if self._updater is not None and self._updater.states:
            from ..optimizer import fused_state_from_updater
            for i, name in enumerate(self._param_names):
                if name in state and i in self._updater.states:
                    state[name] = fused_state_from_updater(
                        kind, self._updater.states[i], params[name])
        if self._exec._mesh is not None:
            # align every state leaf (incl. Updater-loaded ones) with its
            # param's sharding — or its ZeRO-1 shard placement.  Fresh
            # buffers (not device_put): this tree is DONATED on the next
            # fit_step while the Updater keeps referencing the loaded
            # arrays (sharding.fresh_device_put docs — the resume-crash
            # root cause)
            import jax
            from ..parallel.sharding import fresh_device_put
            placed = {}
            for name, st in state.items():
                target = (zero_shardings or {}).get(name,
                                                    raw[name].sharding)
                placed[name] = jax.tree_util.tree_map(
                    lambda s, _t=target: fresh_device_put(s, _t), st)
            state = placed
        return state

    def _fused_flush_to_updater(self):
        """Mirror fused optimizer state back into the Updater's per-index
        dict so save_optimizer_states round-trips across paths."""
        if self._fused is None or self._updater is None:
            return
        from ..optimizer import fused_state_to_updater
        kind = self._fused["kind"]
        for i, name in enumerate(self._param_names):
            if name in self._fused["state"]:
                self._updater.states[i] = fused_state_to_updater(
                    kind, self._fused["state"][name])

    def fit_step(self, data_batch):
        """One donated XLA program per batch: fwd + bwd + optimizer.

        The BaseModule.fit hot loop calls this instead of the
        forward_backward()/update() pair; ineligible configurations fall
        back to exactly that pair.  Steady state: ONE dispatch, zero
        compiles (profiler.step_stats proves it)."""
        assert self.binded and self.params_initialized and \
            self.optimizer_initialized
        from ..checkpoint import check_async_error
        # a background checkpoint write that failed must stop the run at
        # the NEXT step, not rot silently (one global None-check — no
        # dispatches, steptrace's 1.0/step contract holds)
        check_async_error()
        if not self._fused_eligible():
            return super().fit_step(data_batch)
        from .. import telemetry as _telemetry
        with _telemetry.span("fit_step", "step",
                             step=self._optimizer.num_update):
            self._fused_fit_step(data_batch)

    def _fused_fit_step(self, data_batch):
        from .. import fault as _fault
        from .. import profiler as _profiler
        from .. import random as _random
        from .. import telemetry as _telemetry
        from .. import watchdog as _watchdog
        from ..ndarray.ndarray import NDArray
        from ..ops.optimizer_ops import handle_guard_verdict

        # hang-defense probe: a wedged step stops renewing the lease
        # below; the watchdog (armed when MXTPU_STALL_TIMEOUT is set)
        # diagnoses and exits 75 — retryable by the launcher
        _fault.stall_if("worker.stall")
        with _telemetry.span("fit_step.feed", "step"):
            fused = self._fused_setup()
            exe = self._exec
            feeds = self._feed_batch(data_batch)
            for k, v in feeds.items():
                exe.arg_dict[k]._set_data(
                    v._data if isinstance(v, nd.NDArray) else
                    nd.array(v)._data)

            update_names = fused["update_names"]
            in_update = set(update_names)
            param_vals = exe._raw(
                {n: exe.arg_dict[n] for n in update_names})
            other_vals = exe._raw({n: a for n, a in exe.arg_dict.items()
                                   if n not in in_update})
            aux_vals = exe._raw_aux()

            opt = self._optimizer
            first_idx = None
            update_idxs = []
            pre_num_update = opt.num_update
            for i, name in enumerate(self._param_names):
                if name in in_update:
                    opt._update_count(i)
                    update_idxs.append(i)
                    if first_idx is None:
                        first_idx = i
            t = float(opt._index_update_count[first_idx]) \
                if first_idx is not None else 1.0
            lr = opt.fused_base_lr()
            wd = float(opt.wd)
            rescale = float(opt.rescale_grad)
            scaler = getattr(self._precision, "loss_scaler", None)
            if scaler is not None:
                # loss scaling (precision.py): the graph's loss head is
                # pre-scaled by scaler.scale; undo it on the grads
                # through the DYNAMIC rescale scalar — scale moves never
                # recompile
                rescale *= scaler.unscale
            poison = float("nan") if _fault.trigger("grad.nan") else 0.0
            rng = _random.next_key()

        with _telemetry.stamp_span("fit_step.dispatch") as disp:
            # straggler stand-in: a bounded delay INSIDE the timed
            # dispatch window, so the injected slowness shows exactly
            # where a slow host's would — in this rank's
            # fit_step.dispatch percentiles (job_report.py's straggler
            # blame keys off them)
            _fault.delay_if("step.slow")
            outs, new_params, new_state, new_aux, ok = fused["step"](
                param_vals, fused["state"], other_vals, aux_vals, rng,
                lr, wd, rescale, t, poison)
        # "fit_step.sync" is the host's time from the program call's
        # return to the guard's verdict: re-pointing the wrappers (its
        # child "fit_step.rebind", which overlaps the device's work),
        # then the wait for the readback
        with _telemetry.stamp_span("fit_step.sync") as sync:
            with _telemetry.span("fit_step.rebind", "step"):
                fused["state"] = new_state
                # donated inputs are dead now — re-point every wrapper
                # at the step's outputs before anything else can touch
                # them
                for name, v in new_params.items():
                    exe.arg_dict[name]._set_data(v)
                for name, v in new_aux.items():
                    exe.aux_dict[name]._set_data(v)
                exe.outputs = [NDArray(o, exe._ctx) for o in outs]
                self._params_dirty = True
                _profiler.note_step()
            # divergence guard verdict: reading the scalar costs one
            # small host readback that the fit loop's metric update would
            # force anyway (PERF.md "Divergence guard"); a skipped step
            # rewinds the optimizer clocks so it is as if the batch never
            # arrived.  The readback is also the step's device-sync
            # point.
            ok_host = bool(ok)
        # loss for the flight recorder, free of extra syncs: only a
        # scalar head (loss-output nets) is worth a host read, and only
        # while recording actually consumes it
        loss = float(outs[0]) if outs and not outs[0].shape \
            and _telemetry.enabled() else None
        _telemetry.note_train_step(disp.t0, disp.t1, sync.t1, not ok_host,
                                   loss)
        # progress lease: one monotonic store per completed step (no
        # dispatches — steptrace's 1.0 dispatch/step still holds)
        _watchdog.renew("fit_step", phase="train")
        self._consec_guard_skips = handle_guard_verdict(
            ok_host, opt, update_idxs, self._consec_guard_skips,
            pre_num_update)
        if scaler is not None:
            # the scaler consumes the SAME verdict the guard already
            # acted on: backoff on a skipped step, growth on a clean
            # streak — skipped_steps accounting is untouched
            scaler.update(ok_host)

    def update(self):
        """Apply optimizer using accumulated grads (reference module.py:615)."""
        assert self.binded and self.params_initialized and \
            self.optimizer_initialized
        if self._fused is not None:
            # momentum/mean/var accumulated by fused steps must seed the
            # per-param Updater, and vice versa on the next fit_step
            self._fused_flush_to_updater()
            self._fused = None
        self._params_dirty = True
        param_arrays = [[self._exec.arg_dict[n]] for n in self._param_names]
        grad_arrays = [[self._exec.grad_dict.get(n)]
                       for n in self._param_names]
        if self._update_on_kvstore:
            _update_params_on_kvstore(param_arrays, grad_arrays,
                                      self._kvstore, self._param_names)
        else:
            _update_params(param_arrays, grad_arrays,
                           updater=self._updater,
                           num_device=len(self._context),
                           kvstore=self._kvstore,
                           param_names=self._param_names)

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return self._exec.outputs

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and \
            self.inputs_need_grad
        return [self._exec.grad_dict[n] for n in self._data_names]

    def update_metric(self, eval_metric, labels):
        eval_metric.update(labels, self._exec.outputs)

    def install_monitor(self, mon):
        assert self.binded
        mon.install(self._exec)

    def get_states(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return [self._exec.arg_dict[n] for n in self._state_names]

    def set_states(self, states=None, value=None):
        assert self.binded and self.params_initialized
        if states is not None:
            for name, arr in zip(self._state_names, states):
                self._exec.arg_dict[name]._set_data(
                    arr._data if isinstance(arr, nd.NDArray) else arr)
        else:
            for name in self._state_names:
                self._exec.arg_dict[name][:] = value

    # -- optimizer state io -------------------------------------------------
    def _optimizer_states_bytes(self):
        """Current optimizer state as the payload save_optimizer_states
        persists (fused state flushed into the Updater first)."""
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            return self._kvstore._optimizer_states_bytes()
        self._fused_flush_to_updater()
        return self._updater.get_states()

    def save_optimizer_states(self, fname):
        """Atomic, checksummed write (checkpoint.write_state_file)."""
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            from ..checkpoint import write_state_file
            self._fused_flush_to_updater()
            write_state_file(fname, self._updater.get_states())

    def load_optimizer_states(self, fname):
        """Validated read: a torn/corrupt state file raises MXNetError
        naming the path instead of a cryptic unpickling error."""
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            from ..checkpoint import load_state_file
            load_state_file(fname, self._updater.set_states)
            self._fused = None  # re-seed fused state from the Updater
        self._consec_guard_skips = 0  # fresh state, fresh streak

    def reshape(self, data_shapes, label_shapes=None):
        """Re-bind for new shapes (XLA re-jits; params carry over)."""
        assert self.binded
        self._sync_params_from_devices() if self._params_dirty else None
        self.binded = False
        self._exec = None
        self.bind(data_shapes, label_shapes,
                  for_training=self.for_training,
                  inputs_need_grad=self.inputs_need_grad,
                  force_rebind=True)
