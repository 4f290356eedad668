"""Pallas flash-attention kernel vs the O(T²) oracle (fwd + grads).

The driver runs in a subprocess, under the Pallas interpreter on CPU;
tests/test_chip_compile.py compiles the same kernels for the chip.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_driver(section):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    r = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tests", "flash_attention_driver.py"),
         section],
        env=env, capture_output=True, timeout=420)
    out = r.stdout.decode() + r.stderr.decode()
    assert r.returncode == 0, out[-2000:]
    return out


def test_flash_attention_kernels():
    """Core tier (fast sibling): every kernel entry point vs the O(T²)
    oracle — fwd, cross-attention, grads, odd lengths under jit, the
    op/layer wrappers, segment packing."""
    assert "FLASH_OK" in _run_driver("core")


@pytest.mark.slow
def test_flash_attention_extended():
    """Exhaustive tier: ring flash across the 8-device mesh and ring
    segment masks, interpret-mode sweeps too long for tier-1."""
    assert "FLASH_EXTENDED_OK" in _run_driver("extended")
