"""Test configuration.

Runs the whole suite on a virtual 8-device CPU mesh — the TPU-native
analogue of the reference's "fake cluster" strategy (multi-process local
launcher + repeated cpu() contexts, see SURVEY.md §4): multi-chip sharding
is validated without real chips via
``--xla_force_host_platform_device_count=8``.

Must set the env vars BEFORE jax is imported anywhere.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax

# exact matmuls for numeric checks (benchmarks use the fast bf16 default)
jax.config.update("jax_default_matmul_precision", "float32")
# allow real float64 in tests — check_numeric_gradient's finite differences
# need fp64 to resolve eps=1e-4 perturbations
jax.config.update("jax_enable_x64", True)
jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(0)
    import mxnet_tpu as mx
    mx.random.seed(0)
    yield
