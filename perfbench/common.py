"""What every runner shares: the device check, the compile-cache watch,
seeded weights made on the device, host spans and the profiler slice.

Copies of ``chip_smoke.py``'s proven helpers (``Watch``, ``device_doc``,
``has_kernel``), not imports: later PRs may change the smoke and may not
change the yardstick.
"""
import gc
import glob
import json
import os
import time
from statistics import median

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def with_tiny(doc, tiny):
    """The file's sizes, or with ``--tiny`` the same file with its
    ``tiny`` block laid over it (one level deep)."""
    out = {k: v for k, v in doc.items() if k != "tiny"}
    if tiny:
        for k, v in doc.get("tiny", {}).items():
            out[k] = dict(out[k], **v) if isinstance(v, dict) and \
                isinstance(out.get(k), dict) else v
    return out


T0 = time.perf_counter()


def load_cell(workload, tiny):
    """``BENCHMARK.json``'s entry of the cell and the three files it
    names: ``(entry, cell, config, traffic)``."""
    bench = load_json(ROOT, "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    cell = load_json(HERE, "workloads", entry["name"] + ".json")
    config = load_json(ROOT, cfg_entry["file"])
    traffic = load_json(HERE, "traffic", entry["traffic"] + ".json")
    return (bench, entry) + tuple(with_tiny(d, tiny)
                                  for d in (cell, config, traffic))


def require_platform(entry, tiny):
    """The device as JAX reports it; no TPU (or too few chips) ends the
    run with a non-zero exit and no result.  ``--tiny`` wants the CPU."""
    if tiny:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    dev = device_doc()
    want = "cpu" if tiny else "tpu"
    if dev["platform"] != want:
        raise SystemExit("perfbench: found platform %r, this run needs %r "
                         "(--tiny is the CPU rehearsal)"
                         % (dev["platform"], want))
    if dev["count"] < entry["chips"]:
        raise SystemExit("perfbench: the cell asks for %d chip(s), JAX "
                         "reports %d" % (entry["chips"], dev["count"]))
    return dev, want


def say(what, **fields):
    """A free line before the result line (``t``: seconds since this
    module was imported, which is the start of the run)."""
    print(json.dumps(dict(note=what, t=round(time.perf_counter() - T0, 3),
                          **fields)), flush=True)


def device_doc():
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def peaks_for(kind):
    table = load_json(HERE, "peaks.json")
    if kind not in table:
        raise KeyError("no peaks for device kind %r in perfbench/peaks.json"
                       % kind)
    return table[kind]


class Watch:
    """Compile-cache hits and misses, backend compiles, device placement
    and memory."""

    def __init__(self, want_platform):
        import jax
        self.want = want_platform
        self.cache = {"hits": 0, "misses": 0}
        self.compiles = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_):
        if event.endswith("/compilation_cache/cache_hits"):
            self.cache["hits"] += 1
        elif event.endswith("/compilation_cache/cache_misses"):
            self.cache["misses"] += 1

    def _on_duration(self, event, _secs, **_kw):
        if event.endswith("backend_compile_duration"):
            self.compiles += 1

    def on_device(self, tree, what):
        import jax
        for leaf in jax.tree_util.tree_leaves(tree):
            got = {d.platform for d in leaf.devices()}
            assert got == {self.want}, \
                "%s lives on %s, want %s" % (what, got, self.want)

    @staticmethod
    def memory(device=None):
        import jax
        stats = (device or jax.devices()[0]).memory_stats() or {}
        return {k: stats.get(k) for k in
                ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


def place_compile_cache():
    """The persistent compile cache, before the first compile: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else the program's fixed
    ``<checkout>/.jax_cache``.  Every program is kept, however quick to
    compile, and no size cap evicts one cell's programs for another's:
    the second run of a cell in a checkout compiles nothing."""
    import jax
    from mxnet_tpu import aot_cache
    path = aot_cache.enable_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def dir_megabytes(path):
    total = 0
    for root, _, files in os.walk(path):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(root, f))
            except OSError:
                pass
    return total / 1e6


def program_memory(compiled):
    """``memory_analysis()`` of a compiled program as plain ints."""
    ma = compiled.memory_analysis()
    return {k: int(getattr(ma, k + "_size_in_bytes", 0) or 0)
            for k in ("argument", "output", "temp", "alias",
                      "generated_code")}


def memory_peak_bytes(programs, chips=1):
    """Peak bytes on the fullest chip: the allocator's own peak, or the
    largest program's arguments + temporaries + fresh outputs by the
    compiler's count, whichever is more.  The TPU allocator's
    ``peak_bytes_in_use`` leaves most program temporaries out (4.7 GB
    reported against 11.5 GB compiled for the GPT train step, PERF.md),
    so without the compiler's count the number would not mean what its
    name says."""
    import jax
    compiled = max([p["argument"] + p["temp"] + max(0, p["output"] - p["alias"])
                    for p in programs] or [0])
    return max([compiled] + [Watch.memory(d)["peak_bytes_in_use"] or 0
                             for d in jax.devices()[:chips]])


def has_kernel(compiled):
    return "tpu_custom_call" in compiled.as_text()


def seeded_gpt_weights(net, seed, keep_grads):
    """Give every parameter of a GPTLM its value in ONE jitted call on
    the device, from the seed, in the type it is stored in: matrices and
    embeddings normal(0, 0.02), LayerNorm gains 1, biases and LayerNorm
    offsets 0.  ``Parameter.set_data`` takes the arrays as a checkpoint
    load would; ``net.initialize()`` (host-side, leaf by leaf) is not
    run."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ndarray import NDArray

    params = list(net.collect_params().values())
    if not keep_grads:
        # a serving process never reads a gradient: no buffer for one
        for p in params:
            p.grad_req = "null"

    def make(key):
        out = []
        for i, p in enumerate(params):
            if p.name.endswith("gamma"):
                out.append(jnp.ones(p.shape, jnp.float32))
            elif p.name.endswith(("beta", "bias")):
                out.append(jnp.zeros(p.shape, jnp.float32))
            else:
                out.append(0.02 * jax.random.normal(
                    jax.random.fold_in(key, i), p.shape, jnp.float32))
        return out

    for p, value in zip(params, jax.jit(make)(seed_key(seed))):
        p.set_data(NDArray(value))


def seed_key(seed):
    """A PRNG key from any whole number, however large."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


class Spans:
    """Host spans on the profiler's clock.  Outside a traced slice a span
    costs one ``if``."""

    def __init__(self):
        self.on = False

    def __call__(self, name):
        if not self.on:
            return _NULL
        import jax
        return jax.profiler.TraceAnnotation(name)


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


_NULL = _Null()


class TraceSlice:
    """Starts the profiler at ``start_s`` into the window and stops it
    ``length_s`` later (the runner polls ``tick`` between steps)."""

    def __init__(self, spans, out_dir, start_s, length_s, enabled):
        self.spans, self.dir = spans, out_dir
        self.start_s, self.length_s = start_s, length_s
        self.state = "waiting" if enabled else "off"
        self.t_on = None

    def tick(self, since_open_s):
        import jax
        if self.state == "waiting" and since_open_s >= self.start_s:
            # the Python tracer would slow exactly the host code whose
            # gaps the slice is there to show: runtime events and the
            # benchmark's own spans only
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.spans.on = True
            self.t_on = time.perf_counter()
            self.state = "tracing"
        elif self.state == "tracing" and \
                time.perf_counter() - self.t_on >= self.length_s:
            self.stop()

    def stop(self):
        import jax
        if self.state == "tracing":
            self.spans.on = False
            jax.profiler.stop_trace()
            self.state = "done"

    def file(self):
        if self.state != "done":
            return None
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


def step_window(ctx, step):
    """The window of a training runner: ``step()`` under a ``train_step``
    span until ``--seconds`` have passed.  Returns ``(steps, window_s,
    span_records)``; the window closes when the step that crosses
    ``--seconds`` ends."""
    gc.collect()
    gc.freeze()
    steps, records = 0, []
    t_open = time.perf_counter()
    ctx.opened(t_open)
    while True:
        now = time.perf_counter()
        if now - t_open >= ctx.seconds:
            break
        ctx.slice.tick(now - t_open)
        with ctx.spans("train_step"):
            step()
        steps += 1
        if ctx.spans.on:
            records.append({"steps": 1})
    window_s = time.perf_counter() - t_open
    ctx.slice.stop()
    return steps, window_s, records


def loss_checks(losses):
    """``(finite, falling)``: every loss is a finite number, and the
    median of the last five lies below the first."""
    finite = all(l == l and abs(l) < 1e30 for l in losses)
    return finite, median(losses[-5:]) < losses[0]


def percentile(values, q):
    """The q-th percentile (0-100) by linear interpolation, all digits."""
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))
