"""First-class precision policy (ISSUE 20): per-layer dtype resolution
laws, the loss-scaling hook's interplay with the PR-2 divergence guard
(skipped_steps accounting unchanged), and the policy hash folded into
the fused-step AOT fingerprints so a policy change can never replay a
stale executable."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import fault, nd, profiler
from mxnet_tpu.gluon import Trainer
from mxnet_tpu.precision import (LossScaler, PrecisionPolicy,
                                 policy_fingerprint)


# ---------------------------------------------------------------------------
# resolution laws (pure host)
# ---------------------------------------------------------------------------

def test_resolution_default_chain():
    """Law 1: compute defaults to param, output defaults to compute —
    at every level of qualification."""
    p = PrecisionPolicy()
    assert p.resolve("anything") == ("fp32", "fp32", "fp32")
    p = PrecisionPolicy(param_dtype="bf16")
    assert p.resolve("x") == ("bf16", "bf16", "bf16")
    p = PrecisionPolicy(param_dtype="bf16", compute_dtype="fp32")
    assert p.resolve("x") == ("bf16", "fp32", "fp32")
    p = PrecisionPolicy(compute_dtype="bf16", output_dtype="fp32")
    assert p.resolve("x") == ("fp32", "bf16", "fp32")


def test_resolution_overrides_last_match_fieldwise():
    """Law 2: fnmatch overrides in declaration order, LAST match wins
    FIELD-WISE; unset fields fall through to the defaults chain."""
    p = PrecisionPolicy(param_dtype="fp32", overrides={
        "blocks.*": {"param": "bf16"},
        "blocks.3": {"compute": "fp16"},
    })
    # only the glob matches: param override, compute/output follow it
    assert p.resolve("blocks.1") == ("bf16", "bf16", "bf16")
    # both match: blocks.3 keeps the earlier match's param (field-wise
    # merge) and its own compute; output follows compute
    assert p.resolve("blocks.3") == ("bf16", "fp16", "fp16")
    # no match: policy-wide defaults
    assert p.resolve("embed") == ("fp32", "fp32", "fp32")


def test_resolution_canonical_spellings_and_errors():
    """Law 3: fp32/float32/np.float32 are ONE name; junk raises."""
    import jax.numpy as jnp
    a = PrecisionPolicy(param_dtype="float32", compute_dtype=np.float32)
    b = PrecisionPolicy(param_dtype="fp32", compute_dtype=jnp.float32)
    assert a.resolve("x") == b.resolve("x") == ("fp32", "fp32", "fp32")
    with pytest.raises(ValueError, match="unsupported param dtype"):
        PrecisionPolicy(param_dtype="int7")
    with pytest.raises(ValueError, match="unknown override fields"):
        PrecisionPolicy(overrides={"x": {"storage": "bf16"}})
    with pytest.raises(ValueError, match="unknown kv_dtype"):
        PrecisionPolicy(kv_dtype="int3")


def test_fingerprint_laws():
    """Two spellings of one policy hash identically; any material
    change re-keys; the scaler's DYNAMIC scale never does."""
    a = PrecisionPolicy(param_dtype="float32", kv_dtype="int8")
    b = PrecisionPolicy(param_dtype="fp32", kv_dtype="int8")
    assert a.fingerprint() == b.fingerprint()
    assert policy_fingerprint(None) == ""
    assert a.fingerprint() != PrecisionPolicy(kv_dtype="bf16").fingerprint()
    assert a.fingerprint() != PrecisionPolicy(
        param_dtype="fp32", kv_dtype="int8",
        overrides={"blocks.*": {"compute": "bf16"}}).fingerprint()
    c = PrecisionPolicy(loss_scaler=LossScaler(init_scale=4.0))
    fp0 = c.fingerprint()
    c.loss_scaler.update(False)          # scale moves...
    assert c.loss_scaler.scale == 2.0
    assert c.fingerprint() == fp0        # ...fingerprint must not


def test_loss_scaler_dynamics():
    s = LossScaler(init_scale=16.0, growth_factor=2.0,
                   backoff_factor=0.5, growth_interval=3)
    assert s.unscale == 1.0 / 16.0
    s.update(False)
    assert s.scale == 8.0 and s.overflows == 1
    for _ in range(2):
        s.update(True)
    assert s.scale == 8.0                # streak not yet at interval
    s.update(True)
    assert s.scale == 16.0 and s.good_steps == 0
    # a skip resets the streak too
    s.update(True); s.update(False); s.update(True); s.update(True)
    assert s.scale == 8.0
    # floor at 1.0; static scaler never moves
    for _ in range(20):
        s.update(False)
    assert s.scale == 1.0
    st = LossScaler(init_scale=4.0, dynamic=False)
    st.update(False); st.update(True)
    assert st.scale == 4.0 and st.overflows == 0


# ---------------------------------------------------------------------------
# decode_params threading
# ---------------------------------------------------------------------------

def test_decode_params_policy_cast():
    """Per-layer cast: blocks.* to bf16, embeddings/final LN kept fp32
    — and the GQA-converted (split q/k/v) tree casts the same way."""
    import jax.numpy as jnp
    from mxnet_tpu.gluon.model_zoo import gpt
    mx.random.seed(0)
    net = gpt.GPTLM(31, 2, 8, 2, max_len=16)
    net.initialize()
    pol = PrecisionPolicy(overrides={"blocks.*": {"param": "bf16"}})
    for kvh in (None, 1):
        p = gpt.decode_params(net, kv_heads=kvh, policy=pol)
        assert p["wte"].dtype == jnp.float32
        assert p["lnf_g"].dtype == jnp.float32
        for lp in p["layers"]:
            for k, v in lp.items():
                assert v.dtype == jnp.bfloat16, (kvh, k, v.dtype)
    # no policy: unchanged fp32 tree
    p = gpt.decode_params(net)
    assert all(v.dtype == jnp.float32 for v in p["layers"][0].values())


def test_engine_accepts_policy_as_kv_dtype():
    """Serving kv_dtype is ONE instance of the general policy: the
    engine unwraps a PrecisionPolicy into its page storage mode."""
    from mxnet_tpu.gluon.model_zoo import gpt
    from mxnet_tpu.serving import ServingEngine
    mx.random.seed(0)
    net = gpt.GPTLM(31, 1, 8, 2, max_len=32)
    net.initialize()
    eng = ServingEngine(net, num_slots=2, page_size=8, num_pages=8,
                        max_prefill_len=8, max_seq_len=16,
                        kv_dtype=PrecisionPolicy(kv_dtype="int8"))
    assert eng.kv_dtype == "int8"
    assert eng.alloc.kv_itemsize == 1


# ---------------------------------------------------------------------------
# fused-step threading (Module + Trainer)
# ---------------------------------------------------------------------------

def _mlp_symbol(grad_scale=1.0):
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=3, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax",
                                grad_scale=grad_scale)


def _train_iter(seed=0):
    rs = np.random.RandomState(seed)
    X = rs.randn(64, 10).astype(np.float32)
    w = rs.randn(10, 3).astype(np.float32)
    y = (X @ w).argmax(axis=1).astype(np.float32)
    return mx.io.NDArrayIter(X, y, batch_size=32, shuffle=False,
                             label_name="softmax_label")


def _make_module(grad_scale=1.0, policy=None):
    train = _train_iter()
    mod = mx.mod.Module(_mlp_symbol(grad_scale), context=mx.cpu())
    mod.bind(data_shapes=train.provide_data,
             label_shapes=train.provide_label)
    mx.random.seed(7)
    mod.init_params(mx.initializer.Uniform(0.1))
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    if policy is not None:
        mod.set_precision(policy)
    return mod, train


def _run_epochs(mod, train, n=3):
    for _ in range(n):
        train.reset()
        for batch in train:
            mod.fit_step(batch)
    mod._sync_params_from_devices()
    return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}


def test_module_loss_scaling_identity():
    """A statically-scaled loss (grad_scale=S on the head) + a scaler
    with scale S trains to the SAME weights as the unscaled baseline:
    the unscale threads through the dynamic rescale scalar, and S is a
    power of two, so every scaled value is exact.  The law is about
    the arithmetic, not about two programs' instruction order: the
    scaled and the unscaled step are different XLA programs (one
    multiplies by grad_scale), the compiler may fuse and reassociate
    them differently, and then a sum differs in its last bit — so the
    comparison allows a few ulps of fp32 at the weights' scale and no
    more (an unscale that was off by any factor would miss by orders
    of magnitude)."""
    S = 8.0
    ref = _run_epochs(*_make_module())
    pol = PrecisionPolicy(loss_scaler=LossScaler(init_scale=S,
                                                 dynamic=False))
    scaled = _run_epochs(*_make_module(grad_scale=S, policy=pol))
    for k in ref:
        np.testing.assert_allclose(
            scaled[k], ref[k], rtol=4 * np.finfo(np.float32).eps,
            atol=4 * np.finfo(np.float32).eps * np.abs(ref[k]).max())


def test_module_scaler_rides_guard_verdict():
    """grad.nan poisons ONE step: the divergence guard skips it exactly
    as without a scaler (skipped_steps +1, optimizer clock rewound, 1.0
    dispatch/step) and the scaler backs off on that SAME verdict, then
    grows back on the clean streak."""
    pol = PrecisionPolicy(loss_scaler=LossScaler(
        init_scale=16.0, growth_interval=4))
    mod, train = _make_module(policy=pol)
    train.reset()
    batch = next(iter(train))
    mod.fit_step(batch)                      # warm (compile)
    base_updates = mod._optimizer.num_update
    profiler.reset_step_stats()
    fault.configure("grad.nan:1")
    try:
        mod.fit_step(batch)                  # poisoned -> skipped
    finally:
        fault.reset()
    st = profiler.step_stats()
    assert st["skipped_steps"] == 1 and st["dispatch_count"] == 1, st
    assert mod._optimizer.num_update == base_updates  # clock rewound
    assert pol.loss_scaler.scale == 8.0
    assert pol.loss_scaler.overflows == 1
    assert mod._consec_guard_skips == 1
    for _ in range(4):
        mod.fit_step(batch)                  # clean streak
    assert mod._consec_guard_skips == 0
    assert pol.loss_scaler.scale == 16.0     # grew back after interval
    st = profiler.step_stats()
    assert st["skipped_steps"] == 1, st      # accounting unchanged


def test_module_policy_hash_rekeys_fused_step():
    """The policy fingerprint lives in BOTH the in-process fused key
    and the AOT cache_extra: changing the policy rebuilds the program,
    re-setting an equivalent policy replays it."""
    mod, train = _make_module()
    train.reset()
    batch = next(iter(train))
    mod.fit_step(batch)
    assert mod._fused["key"][-1] == ""       # no policy
    step0 = mod._fused["step"]
    pol = PrecisionPolicy(param_dtype="fp32", kv_dtype="int8")
    mod.set_precision(pol)
    mod.fit_step(batch)
    assert mod._fused["key"][-1] == pol.fingerprint()
    assert mod._fused["step"] is not step0   # rebuilt, not replayed
    step1 = mod._fused["step"]
    # an EQUIVALENT policy (different spelling) must not rebuild
    mod.set_precision(PrecisionPolicy(param_dtype="float32",
                                      kv_dtype="int8"))
    mod.fit_step(batch)
    assert mod._fused["key"][-1] == pol.fingerprint()


def _gluon_problem(seed=3):
    from mxnet_tpu import autograd, gluon
    mx.random.seed(seed)
    rs = np.random.RandomState(seed)
    X = nd.array(rs.randn(64, 8).astype(np.float32))
    Y = nd.array(rs.randn(64, 1).astype(np.float32))
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"))
    net.add(gluon.nn.Dense(1))
    net.initialize(mx.initializer.Uniform(0.1))
    with autograd.record():
        loss = ((net(X) - Y) ** 2).mean()
    loss.backward()
    return net, X, Y


def test_trainer_loss_scaling_identity_and_rekey():
    """Trainer path: scale_loss(S) + the policy's unscale give the
    bit-identical updates of the unscaled run, and the policy hash
    re-keys the tree-wide fused program."""
    from mxnet_tpu import autograd
    S = 32.0

    def run(policy):
        net, X, Y = _gluon_problem()
        trainer = Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.05, "momentum": 0.9},
                          kvstore=None)
        if policy is not None:
            trainer.set_precision(policy)
        scaler = policy.loss_scaler if policy is not None else None
        for _ in range(4):
            with autograd.record():
                loss = ((net(X) - Y) ** 2).mean()
                if scaler is not None:
                    loss = scaler.scale_loss(loss)
            loss.backward()
            trainer.step(batch_size=64)
        key = trainer._fused["key"]
        return [v.data().asnumpy()
                for v in net.collect_params().values()], key

    ref, key0 = run(None)
    pol = PrecisionPolicy(loss_scaler=LossScaler(init_scale=S,
                                                 dynamic=False))
    scaled, key1 = run(pol)
    assert key0[-1] == "" and key1[-1] == pol.fingerprint()
    for r, s in zip(ref, scaled):
        np.testing.assert_array_equal(r, s)


def test_trainer_scaler_consumes_late_verdict():
    """Trainer resolves the guard verdict one step LATE: the scaler's
    backoff lands when the verdict does, and the skip streak counts
    exactly as without a scaler."""
    from mxnet_tpu import autograd
    pol = PrecisionPolicy(loss_scaler=LossScaler(init_scale=16.0))
    net, X, Y = _gluon_problem()
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.05}, kvstore=None)
    trainer.set_precision(pol)

    def one_step():
        with autograd.record():
            loss = ((net(X) - Y) ** 2).mean()
        loss.backward()
        trainer.step(batch_size=64)

    one_step()                               # warm
    fault.configure("grad.nan:1")
    try:
        one_step()                           # poisoned; verdict pending
    finally:
        fault.reset()
    assert pol.loss_scaler.overflows == 0    # not yet resolved
    one_step()                               # resolves the late verdict
    assert pol.loss_scaler.overflows == 1
    assert pol.loss_scaler.scale == 8.0
    trainer._resolve_pending_verdict()
    assert trainer._consec_guard_skips == 0  # clean step reset streak
