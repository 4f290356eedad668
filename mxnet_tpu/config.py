"""Environment-flag configuration.

The reference documented ~25 ``MXNET_*`` runtime knobs
(/root/reference/docs/how_to/env_var.md); most configured machinery XLA
now owns (engine threads, memory pools, bulking, cudnn autotune).  This
module is the single registry of every knob this framework reads: each
flag has a typed default and a docstring, reference-era ``MXNET_*`` names
stay readable where a counterpart exists, and absorbed knobs are listed
explicitly so users migrating scripts can see where tuning moved.

Usage::

    from mxnet_tpu import config
    config.flag("MXTPU_ATTENTION_IMPL")      # resolved value
    config.describe()                        # table of all flags

Flags are read from the environment at call time (not import time), so
tests and launchers can set them per process.
"""
from __future__ import annotations

import os
from collections import OrderedDict, namedtuple

__all__ = ["flag", "describe", "FLAGS"]

_Flag = namedtuple("_Flag", ["name", "default", "type", "doc", "aliases"])

#: every environment knob the framework reads, in one place
FLAGS = OrderedDict()


def _register(name, default, type_, doc, aliases=()):
    FLAGS[name] = _Flag(name, default, type_, doc, tuple(aliases))


_register("MXTPU_COORDINATOR", "", str,
          "host:port of the jax.distributed coordinator; set by "
          "tools/launch.py (replaces ps-lite's DMLC_PS_ROOT_URI).")
_register("MXTPU_NUM_WORKERS", 1, int,
          "number of worker processes in the distributed job "
          "(replaces DMLC_NUM_WORKER).")
_register("MXTPU_WORKER_RANK", 0, int,
          "this process's rank (replaces DMLC_WORKER_ID).")
_register("MXTPU_ATTENTION_IMPL", "", str,
          "'flash' forces the Pallas attention kernel, 'xla' the jnp "
          "online-softmax path; empty auto-selects (flash on TPU).")
_register("MXNET_CPU_WORKER_NTHREADS", 1, int,
          "host-side worker threads for the Python image pipeline "
          "(image/image.py); the native pipeline uses "
          "preprocess_threads from ImageRecordIter instead.",
          aliases=("MXTPU_CPU_WORKER_NTHREADS",))
_register("MXNET_PROFILER_AUTOSTART", 0, int,
          "start the chrome-trace profiler at import (profiler.py).",
          aliases=("MXTPU_PROFILER_AUTOSTART",))
_register("MXTPU_NATIVE_IO", 1, int,
          "use the C++ decode pipeline (src/mxtpu) for ImageRecordIter "
          "when the shared library builds; 0 forces the Python fallback.")
_register("MXTPU_BUILD_NATIVE", 1, int,
          "build libmxtpu.so on demand at first use (native.py); 0 "
          "disables compilation (Python fallbacks only).")
_register("MXTPU_CHECKPOINT_FORMAT", "binary", str,
          "'binary' writes reference-compatible V2 .params files "
          "(ndarray/serialization.py); 'npz' writes the rounds-1/2 "
          "container. Loading auto-detects either.")

#: reference knobs with no counterpart here, and where the concern went.
#: (docs/how_to/env_var.md names; listed so migrating users can grep.)
ABSORBED = {
    "MXNET_GPU_WORKER_NTHREADS": "XLA owns device scheduling",
    "MXNET_GPU_COPY_NTHREADS": "XLA owns transfers",
    "MXNET_CPU_PRIORITY_NTHREADS": "no priority queue; XLA async dispatch",
    "MXNET_CPU_NNPACK_NTHREADS": "no NNPACK; XLA:CPU",
    "MXNET_EXEC_ENABLE_INPLACE": "XLA buffer assignment + jit donation",
    "NNVM_EXEC_MATCH_RANGE": "XLA memory planning",
    "MXNET_EXEC_NUM_TEMP": "XLA memory planning",
    "MXNET_GPU_MEM_POOL_RESERVE": "XLA/TPU allocator",
    "MXNET_ENGINE_TYPE": "no dependency engine; XLA async dispatch",
    "MXNET_EXEC_BULK_EXEC_INFERENCE": "whole graph is one XLA program",
    "MXNET_EXEC_BULK_EXEC_TRAIN": "whole graph is one XLA program",
    "MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN": "whole graph is one program",
    "MXNET_KVSTORE_REDUCTION_NTHREADS": "jitted psum collectives",
    "MXNET_KVSTORE_BIGARRAY_BOUND": "jitted psum collectives",
    "MXNET_ENABLE_GPU_P2P": "ICI topology is XLA's concern",
    "MXNET_BACKWARD_DO_MIRROR": "use jax.checkpoint/remat policies",
    "MXNET_CUDNN_AUTOTUNE_DEFAULT": "XLA autotuning",
    "MXNET_PROFILER_MODE": "profiler.py records all scopes",
}


def flag(name):
    """Resolve a registered flag: environment (primary name, then
    aliases), else default.  Raises KeyError for unregistered names so
    stray env reads don't creep back in."""
    spec = FLAGS[name]
    for key in (spec.name,) + spec.aliases:
        raw = os.environ.get(key)
        if raw is not None:
            return spec.type(raw)
    return spec.default


def describe():
    """Human-readable table of all flags (value <- source)."""
    lines = []
    for spec in FLAGS.values():
        val = flag(spec.name)
        src = "env" if any(k in os.environ
                           for k in (spec.name,) + spec.aliases) \
            else "default"
        lines.append("%-32s %-10r (%s)  %s"
                     % (spec.name, val, src, spec.doc))
    lines.append("")
    lines.append("Reference knobs absorbed by the TPU design:")
    for k, why in ABSORBED.items():
        lines.append("  %-40s -> %s" % (k, why))
    return "\n".join(lines)
