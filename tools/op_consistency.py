#!/usr/bin/env python
"""Accelerator-vs-CPU operator consistency sweep.

The TPU analogue of the reference rerunning its whole operator suite on
GPU with ``check_consistency`` (/root/reference/tests/python/gpu/
test_operator_gpu.py, python/mxnet/test_utils.py:check_consistency):
every forward case from the numeric-gradient sweep
(tests/test_operator_grad_sweep.py) executes on the accelerator backend
AND on the XLA CPU backend, and the outputs must agree within per-dtype
tolerances.  This is what systematically checks that the lowerings the
CPU test suite validated produce the same numbers on the actual TPU.

On a CPU-only host both sides use the same backend and the sweep
degenerates to a smoke check (noted in the output).

Usage: python tools/op_consistency.py  (OP_CONSISTENCY_DTYPES=... to
restrict dtypes).  Exit code: 0 = pass, 1 = any mismatch.
"""
from __future__ import annotations

import importlib.util
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

TOLS = {  # per-dtype (rtol, atol), mirroring check_consistency's scaling
    "float32": (2e-5, 2e-5),
    "bfloat16": (2e-2, 2e-2),
}


def _load_sweep():
    path = os.path.join(REPO, "tests", "test_operator_grad_sweep.py")
    spec = importlib.util.spec_from_file_location("_grad_sweep", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main():
    import numpy as np
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import registry

    # lowering-semantics comparison: keep MXU matmuls in fp32 so a
    # mismatch means a wrong lowering, not accumulation-precision noise
    jax.config.update("jax_default_matmul_precision", "float32")

    dtypes = os.environ.get("OP_CONSISTENCY_DTYPES",
                            "float32,bfloat16").split(",")
    sweep = _load_sweep()
    accel = jax.devices()[0]
    cpu = jax.devices("cpu")[0]
    degenerate = accel.platform == "cpu"

    ran = skipped = 0
    failures = []
    for case in sweep.CASES:
        op = registry.get_op(case.op)
        if op.aux_names(case.params) or op.needs_rng or op.takes_train \
                or case.aux:
            skipped += 1  # stateful/rng ops: covered by their own tests
            continue
        params = dict(case.params)
        r = sweep.rng(0)
        raw = [sweep._sample(domain, shape, r)
               for _, shape, domain in case.inputs]
        for dt in dtypes:
            if dt == "bfloat16" and case.op.startswith("linalg_"):
                continue  # XLA decompositions (cholesky/trsm) are
                # fp32/fp64-only; bf16 linalg is not a supported path
            params_dt = params
            args = []
            for (name, _, domain), x in zip(case.inputs, raw):
                if name in case.fixed or domain.startswith("int"):
                    args.append(jnp.asarray(x, jnp.float32))
                else:
                    args.append(jnp.asarray(x.astype(np.float32), dt))
            fn = op.jitted(**op.canon_params(params_dt))
            try:
                with jax.default_device(accel):
                    out_a = fn(*[jax.device_put(a, accel) for a in args])
                with jax.default_device(cpu):
                    out_c = fn(*[jax.device_put(a, cpu) for a in args])
            except Exception as e:  # a backend refusing the case IS a finding
                failures.append((case.cid, dt, "raised: %r" % (e,)))
                continue
            flat_a = out_a if isinstance(out_a, (list, tuple)) else [out_a]
            flat_c = out_c if isinstance(out_c, (list, tuple)) else [out_c]
            rtol, atol = TOLS.get(dt, (2e-2, 2e-2))
            for i, (a, c) in enumerate(zip(flat_a, flat_c)):
                a = np.asarray(a, np.float64)
                c = np.asarray(c, np.float64)
                bad = ~np.isclose(a, c, rtol=rtol, atol=atol,
                                  equal_nan=True)
                if bad.any():
                    err = np.abs(a - c)[bad].max()
                    failures.append((case.cid, dt,
                                     "out%d max|Δ|=%.3g (%d/%d elems)"
                                     % (i, err, bad.sum(), bad.size)))
            ran += 1

    print("op_consistency: accel=%s cpu=%s cases_ran=%d skipped=%d "
          "dtypes=%s%s" % (accel.platform, cpu.platform, ran, skipped,
                           dtypes,
                           " [DEGENERATE: accel==cpu]" if degenerate
                           else ""))
    for cid, dt, msg in failures:
        print("  MISMATCH %s [%s]: %s" % (cid, dt, msg))
    if not failures:
        print("op_consistency: PASS")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
