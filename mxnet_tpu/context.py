"""Device context.

TPU-native analogue of the reference ``python/mxnet/context.py`` — a
``Context`` names a logical device (``cpu(0)``, ``tpu(3)``; ``gpu`` is kept as
an alias family so reference scripts run unmodified and maps to the default
accelerator).  A Context resolves lazily to a concrete ``jax.Device``; data
placement uses ``jax.device_put``.

Unlike the reference there is no per-device worker thread or stream — XLA owns
scheduling — so Context is pure placement metadata plus the thread-local
"current context" stack used by ``with mx.tpu(0):``.

Reference: /root/reference/python/mxnet/context.py
"""
from __future__ import annotations

import logging
import threading

import jax

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus", "num_tpus"]


class Context:
    """A logical device context.

    Parameters
    ----------
    device_type : str
        'cpu', 'gpu', 'tpu', or 'cpu_pinned'.  'gpu' is accepted for
        compatibility with reference scripts and resolves to the platform's
        default accelerator (TPU when present).
    device_id : int
        Index into the device list of that platform.
    """

    # dev_type enumeration kept numerically compatible with the reference
    # (include/mxnet/base.h Context::DeviceType) plus kTPU.
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}
    _default_ctx = threading.local()
    _warned_no_accel = False

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __repr__(self):
        return self.__str__()

    def __enter__(self):
        self._old_ctx = getattr(Context._default_ctx, "value", None)
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    # -- JAX resolution ----------------------------------------------------
    def jax_device(self):
        """Resolve to a concrete jax.Device.

        'tpu'/'gpu' map onto the accelerator platform when present (falling
        back to CPU so tests run anywhere); 'cpu'/'cpu_pinned' map to host.
        """
        # local_devices only: under multi-process (launch.py / pods) the
        # global list contains peers' non-addressable devices
        devs = jax.local_devices()
        accel = [d for d in devs if d.platform != "cpu"]
        if self.device_type in ("tpu", "gpu"):
            if not accel and not Context._warned_no_accel:
                # said once, at INFO: CPU test runs live on this
                # resolution; code that must be on a chip checks the
                # platform of what it built (chip_smoke.py)
                Context._warned_no_accel = True
                logging.info("mxnet_tpu.context: no accelerator here — "
                             "'%s' contexts resolve to CPU devices",
                             self.device_type)
            pool = accel or [d for d in devs if d.platform == "cpu"]
        else:
            pool = [d for d in devs if d.platform == "cpu"]
        if not pool:
            pool = devs
        return pool[self.device_id % len(pool)]

    def empty_cache(self):
        """Compatibility no-op (XLA owns the memory pools)."""


def cpu(device_id=0):
    """Return a CPU context."""
    return Context("cpu", device_id)


def gpu(device_id=0):
    """Return an accelerator context (alias; resolves to TPU when present)."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    """Return a TPU context — the native device of this framework."""
    return Context("tpu", device_id)


def num_gpus():
    """Number of accelerator devices visible to this process."""
    return len([d for d in jax.devices() if d.platform != "cpu"])


num_tpus = num_gpus


def current_context():
    """Return the current context (default ``tpu(0)`` — TPU-first)."""
    ctx = getattr(Context._default_ctx, "value", None)
    if ctx is None:
        ctx = Context("tpu", 0)
        Context._default_ctx.value = ctx
    return ctx
