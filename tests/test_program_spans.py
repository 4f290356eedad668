"""The program's own spans on the profiler's clock (ISSUE 24).

``telemetry.span`` enters a ``jax.profiler.TraceAnnotation`` whenever a
``jax.profiler`` trace is being taken, so the phases of
``ServingEngine.step`` and ``Module.fit_step`` lie in the device trace's
own file.  Here, on the CPU:

- under a real ``jax.profiler.start_trace`` a tiny engine run and a tiny
  ``fit_step`` run leave every span of OBSERVABILITY.md section 2's step
  tables in the host plane, nested as documented and with their args;
- with no trace session a span is no profiler event, and every phase
  histogram counts exactly one observation per occurrence;
- every Pallas kernel is a ``pallas_call`` under its stable name, and the
  lowered decode / prefill / fused-fit / gpt_spmd programs hold every
  documented scope name;
- the span inventory lint, twin of ``test_metrics_inventory.py``: a span
  name in code has a row in OBSERVABILITY.md's span tables, and a row
  there has its span in code.
"""
import glob
import importlib
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import profiler, telemetry
from mxnet_tpu.gluon.model_zoo import gpt

pytestmark = pytest.mark.telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: span -> (parent span or None, args it carries)
SERVE_SPANS = {
    "serve.step": (None, ("step", "live", "queued")),
    "serve.sweep": ("serve.step", ()),
    "serve.admit": ("serve.step", ("admitted",)),
    "serve_prefill": ("serve.step", ("rid", "trace", "prompt",
                                     "prefix_len", "queue_wait_us")),
    "serve_prefill.dispatch": ("serve_prefill", ()),
    "serve_prefill.sync": ("serve_prefill", ()),
    "serve.decode.pack": ("serve.step", ("live",)),
    "serve_step.dispatch": ("serve.step", ()),
    "serve_step.sync": ("serve.step", ()),
    "serve.decode.emit": ("serve.step", ("tokens",)),
}
FIT_SPANS = {
    "fit_step": (None, ("step",)),
    "fit_step.feed": ("fit_step", ()),
    "fit_step.dispatch": ("fit_step", ()),
    "fit_step.sync": ("fit_step", ()),
    "fit_step.rebind": ("fit_step.sync", ()),
}


def _engine(**over):
    from mxnet_tpu.serving import ServingEngine
    np.random.seed(0)
    mx.random.seed(0)
    net = gpt.GPTLM(64, 2, 32, 2, max_len=48)
    net.initialize()
    kw = dict(num_slots=3, page_size=8, max_prefill_len=16,
              max_seq_len=32, record_logits=False)
    kw.update(over)
    return ServingEngine(net, **kw)


def _serve(eng, n_req=4, max_new=5):
    rng = np.random.RandomState(1)
    reqs = [eng.submit(rng.randint(0, 64, 6 + i).astype(np.int32), max_new)
            for i in range(n_req)]
    steps = 0
    while not all(r.done for r in reqs):
        eng.step()
        steps += 1
        assert steps < 200
    return reqs, steps


def _module():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
    sym = mx.sym.SoftmaxOutput(fc2, name="softmax")
    rs = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rs.randn(32, 10).astype(np.float32),
                           rs.randint(0, 4, 32).astype(np.float32),
                           batch_size=16, label_name="softmax_label")
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Uniform(0.1))
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.05),))
    return mod, list(it)


def _traced(tmp, work):
    """Run ``work()`` under a jax.profiler trace (Python tracer off, as
    the benchmark's slice) and return the events of the thread that ran
    it: ``[(name, start_ns, end_ns, {arg: value})]``."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test_marker"):
            work()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = [(e.name.split("#", 1)[0], e.start_ns,
                       e.start_ns + e.duration_ns, dict(e.stats))
                      for e in line.events]
            if any(n == "test_marker" for n, _, _, _ in events):
                return events
    raise AssertionError("the traced thread is not in the host plane")


@pytest.fixture(scope="module")
def serve_trace(tmp_path_factory):
    eng = _engine()
    _serve(eng, n_req=2)                       # compile outside the trace
    return _traced(tmp_path_factory.mktemp("serve_trace"),
                   lambda: _serve(eng))


@pytest.fixture(scope="module")
def fit_trace(tmp_path_factory):
    mod, batches = _module()
    for b in batches:
        mod.fit_step(b)
    return _traced(tmp_path_factory.mktemp("fit_trace"),
                   lambda: [mod.fit_step(b) for b in batches * 2])


def _check_span(events, name, parent, args, table):
    mine = [e for e in events if e[0] == name]
    assert mine, "no %r event in the traced thread" % name
    for _, s, e, stats in mine:
        for a in args:
            assert a in stats, "%r lacks its %r arg: %r" % (name, a, stats)
        if parent is not None:
            assert any(p[0] == parent and p[1] <= s and e <= p[2]
                       for p in events), \
                "%r at %d lies in no %r" % (name, s, parent)
    # siblings (the program spans that share this one's parent) never
    # overlap it
    siblings = [e for e in events if e[0] != name and e[0] in table
                and table[e[0]][0] == parent]
    for _, s, e, _ in mine:
        for n2, s2, e2, _ in siblings:
            assert e <= s2 or e2 <= s, \
                "%r [%d, %d] overlaps its sibling %r [%d, %d]" \
                % (name, s, e, n2, s2, e2)


@pytest.mark.parametrize("name", sorted(SERVE_SPANS))
def test_serving_span_in_the_trace(serve_trace, name):
    parent, args = SERVE_SPANS[name]
    _check_span(serve_trace, name, parent, args, SERVE_SPANS)


@pytest.mark.parametrize("name", sorted(FIT_SPANS))
def test_fit_step_span_in_the_trace(fit_trace, name):
    parent, args = FIT_SPANS[name]
    _check_span(fit_trace, name, parent, args, FIT_SPANS)


def test_span_args_say_what_happened(serve_trace):
    """``serve.admit`` counts what it admitted, one ``serve_prefill``
    per admitted request carries that request's sizes, and
    ``serve.decode.emit`` counts the step's tokens."""
    admitted = sum(int(st["admitted"]) for n, _, _, st in serve_trace
                   if n == "serve.admit")
    prefills = [st for n, _, _, st in serve_trace if n == "serve_prefill"]
    assert admitted == len(prefills) == 4
    assert sorted(int(p["prompt"]) for p in prefills) == [6, 7, 8, 9]
    assert len({p["trace"] for p in prefills}) == 4
    emitted = sum(int(st["tokens"]) for n, _, _, st in serve_trace
                  if n == "serve.decode.emit")
    assert emitted == 4 * 5 - 4        # the first tokens are the prefills'
    # no program span takes a name the benchmark's thread is found by
    assert not {"step", "train_step", "submit"} & \
        {n for n, _, _, _ in serve_trace}


def test_speculative_path_has_the_same_split(tmp_path):
    eng = _engine(spec_k=2)
    _serve(eng, n_req=2)
    events = _traced(tmp_path, lambda: _serve(eng))
    names = {n for n, _, _, _ in events}
    for name in ("serve.decode.pack", "serve_step.dispatch",
                 "serve_step.sync", "serve.decode.emit"):
        assert name in names, name
        _check_span(events, name, "serve.step", SERVE_SPANS[name][1],
                    SERVE_SPANS)


def _phase_counts():
    rep = telemetry.report()["phases"]
    return {k: v["count"] for k, v in rep.items()}


def test_no_trace_no_event_and_one_observation_per_serving_phase():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    eng = _engine()
    _serve(eng, n_req=2)
    telemetry.reset()
    prefills, decodes = eng.prefills, eng.decode_steps
    made = []
    real = telemetry._trace_annotation()

    class Spy(real):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)
    telemetry._TraceAnnotation = Spy
    try:
        _, steps = _serve(eng)
    finally:
        telemetry._TraceAnnotation = real
    assert not made, "a span made a profiler event with no trace on"
    prefills = eng.prefills - prefills
    decodes = eng.decode_steps - decodes
    counts = _phase_counts()
    assert prefills == 4 and decodes > 0
    assert counts["serve.step"] == counts["serve.sweep"] == \
        counts["serve.admit"] == steps
    for phase in ("serve_prefill", "serve_prefill.dispatch",
                  "serve_prefill.sync"):
        assert counts[phase] == prefills, phase
    for phase in ("serve.decode.pack", "serve_step.dispatch",
                  "serve_step.sync", "serve.decode.emit"):
        assert counts[phase] == decodes, phase
    recs = telemetry.flight_records()
    assert sum(r["where"] == "serve_step" for r in recs) == \
        min(decodes, telemetry.flight_capacity() - prefills)


def test_no_trace_one_observation_per_fit_step_phase():
    mod, batches = _module()
    for b in batches:
        mod.fit_step(b)
    telemetry.reset()
    profiler.reset_step_stats()
    for b in batches * 3:
        mod.fit_step(b)
    n = 3 * len(batches)
    counts = _phase_counts()
    for phase in FIT_SPANS:
        assert counts[phase] == n, (phase, counts[phase])
    assert profiler.step_stats()["dispatch_count"] == n
    recs = telemetry.flight_records()
    assert len(recs) == n and all(r["where"] == "fit_step" for r in recs)
    # the record's two phases are the stamped spans' own intervals, end
    # to end inside the whole-step span
    rep = telemetry.report()["phases"]
    assert rep["fit_step.dispatch"]["sum"] + rep["fit_step.sync"]["sum"] \
        <= rep["fit_step"]["sum"]


def test_mx_profiler_stream_gets_the_spans_once_with_args(tmp_path):
    """While ``mx.profiler`` collects, every program span is one event of
    its stream (the stamped dispatch / sync pair through the step
    record), args included."""
    eng = _engine()
    _serve(eng, n_req=2)
    profiler.profiler_set_config(filename=str(tmp_path / "p.json"))
    decodes = eng.decode_steps
    profiler.profiler_set_state("run")
    try:
        _, steps = _serve(eng)
        telemetry.flight_records()             # settle the step records
        with profiler._lock:
            events = list(profiler._events)
    finally:
        profiler.profiler_set_state("stop")
    decodes = eng.decode_steps - decodes
    by = {}
    for ev in events:
        by.setdefault(ev["name"], []).append(ev)
    assert len(by["serve.step"]) == steps
    assert {"step", "live", "queued", "depth"} <= set(
        by["serve.step"][0]["args"])
    for name in ("serve_step.dispatch", "serve_step.sync",
                 "serve.decode.pack", "serve.decode.emit"):
        assert len(by[name]) == decodes, name
    assert len(by["serve_prefill"]) == \
        len(by["serve_prefill.dispatch"]) == 4


# -- stable device-side names ----------------------------------------------

flash = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")
paged = importlib.import_module("mxnet_tpu.ops.pallas.paged_attention")
layer_norm = importlib.import_module("mxnet_tpu.ops.pallas.layer_norm")


def _pallas_names(jaxpr):
    """Names of every ``pallas_call`` in a jaxpr, sub-jaxprs included
    (the kernels' own bodies are not walked)."""
    names, todo = set(), [getattr(jaxpr, "jaxpr", jaxpr)]
    while todo:
        for eqn in todo.pop().eqns:
            if eqn.primitive.name == "pallas_call":
                names.add(eqn.params["name"])
                continue
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        todo.append(sub)
    return names


def _qkv(t=64, d=16):
    rs = np.random.RandomState(0)
    return [jnp.asarray(rs.randn(1, 2, t, d), jnp.float32)
            for _ in range(3)]


def _flash_grad(q, k, v):
    return jax.grad(lambda q, k, v: flash.flash_attention(
        q, k, v, causal=True).sum(), argnums=(0, 1, 2))(q, k, v)


def _kernel_jaxpr(kernel):
    if kernel == "flash_fwd":
        return jax.make_jaxpr(lambda q, k, v: flash.flash_attention(
            q, k, v, causal=True))(*_qkv())
    if kernel in ("flash_bwd_dq", "flash_bwd_dkv"):
        return jax.make_jaxpr(_flash_grad)(*_qkv())
    if kernel == "paged_decode":
        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.randn(2, 2, 16), jnp.float32)
        pages = jnp.asarray(rs.randn(6, 8, 2 * 16), jnp.float32)
        tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
        return jax.make_jaxpr(lambda q, kp, vp: paged.paged_attention(
            q, kp, vp, tables, jnp.asarray([9, 5], jnp.int32)))(
                q, pages, pages)
    assert kernel == "layer_norm"
    x = jnp.ones((16, 128), jnp.float32)
    g = jnp.ones((128,), jnp.float32)
    return jax.make_jaxpr(lambda x, r, g, b: layer_norm._kernel_call(
        x, r, g, b, 1e-5, True)[0])(x, x, g, g)


@pytest.mark.parametrize("kernel", [
    "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "paged_decode",
    "layer_norm"])
def test_kernel_is_a_pallas_call_under_its_name(kernel):
    names = _pallas_names(_kernel_jaxpr(kernel))
    assert kernel in names, "%r not among pallas_call names %r" \
        % (kernel, sorted(names))


@pytest.fixture(scope="module")
def program_text():
    """The text of each program a scope name is documented for: the
    engine's two programs and the fused fit step as they were compiled
    (HLO keeps a scope in every instruction's ``op_name``), the
    ``gpt_spmd`` step as lowered."""
    eng = _engine()
    out = {"decode": eng._decode.__wrapped__.as_text(),
           "prefill": eng._prefill.__wrapped__.as_text()}
    mod, batches = _module()
    mod.fit_step(batches[0])
    out["fit"] = mod._fused["step"].__wrapped__.as_text()

    from mxnet_tpu import parallel as par
    from mxnet_tpu.gluon.block import functionalize
    from mxnet_tpu.parallel import gpt_spmd
    np.random.seed(0)
    mx.random.seed(0)
    net = gpt.GPTLM(64, 1, 32, 2, max_len=16)
    net.initialize()
    x = jnp.zeros((2, 16), jnp.int32)
    fn, params = functionalize(net, x, train=True)
    mesh = par.make_mesh(devices=jax.devices()[:1], dp=1, tp=1)
    init, step = gpt_spmd.make_train_step(fn, mesh, lr=0.01)
    params, state = init(params)
    out["gpt_spmd"] = step.lower(
        params, state, {"x": x, "y": x},
        jax.random.PRNGKey(0)).compile().as_text()
    return out


SCOPES = {
    "decode": ("embed", "norm", "attn.proj", "attn", "attn.out",
               "kv_write", "mlp", "lm_head", "sample"),
    "prefill": ("embed", "norm", "attn.proj", "attn", "attn.gather",
                "attn.out", "kv_write", "mlp", "lm_head", "sample"),
    "fit": ("forward_backward", "divergence_guard", "optimizer_apply"),
    "gpt_spmd": ("loss", "optimizer_apply"),
}


@pytest.mark.parametrize("program,scope", [
    (prog, scope) for prog in sorted(SCOPES) for scope in SCOPES[prog]])
def test_program_holds_its_scope_name(program_text, program, scope):
    text = program_text[program]
    assert re.search(r'op_name="[^"]*[/(]%s[/)]' % re.escape(scope), text), \
        "scope %r is not in the %s program's text" % (scope, program)


# -- span inventory lint ----------------------------------------------------

#: a span through any of the module's import aliases, or bare inside
#: telemetry.py; ``\s*`` spans line breaks
_SPAN_RE = re.compile(
    r"(?<![\w])(?:span|stamp_span|observe_phase)\(\s*['\"]([a-z0-9_.]+)['\"]"
    r"(?:\s+if\s+\w+\s+else\s+['\"]([a-z0-9_.]+)['\"])?")
#: the step-record path: note_train_step(..., where="x") writes the
#: phases x.dispatch and x.sync
_WHERE_RE = re.compile(
    r"note_train_step\([^)]*?['\"]([a-z_]+)['\"]\s*\)", re.S)
_ROW_RE = re.compile(r"^\|(?P<names>[^|]+)\|(?P<cat>[^|]+)\|")
_NAME_RE = re.compile(r"`([a-z0-9_.]+)`")
#: second cells that make a row a span row: section 2's categories, and
#: the type cells the metric tables give a span's phase histogram
_CATS = ("data", "step", "checkpoint", "aot", "kvstore", "serving",
         "span", "phase", "phase hist")


def spans_in_code():
    out = {}
    for dirpath, dirnames, filenames in os.walk(
            os.path.join(REPO, "mxnet_tpu")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fname in filenames:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            with open(path, encoding="utf-8") as f:
                src = f.read()
            rel = os.path.relpath(path, REPO)
            names = {n for pair in _SPAN_RE.findall(src) for n in pair
                     if n}
            if fname != "telemetry.py":
                for w in _WHERE_RE.findall(src):
                    names.add(w + ".dispatch")
            for n in names:
                out.setdefault(n, set()).add(rel)
    return out


def spans_in_doc():
    """Names from every OBSERVABILITY.md table row whose second cell is a
    span category (the section 2 tables)."""
    with open(os.path.join(REPO, "OBSERVABILITY.md"),
              encoding="utf-8") as f:
        lines = f.read().splitlines()
    rows = set()
    for line in lines:
        m = _ROW_RE.match(line.strip())
        if not m or m.group("cat").strip() not in _CATS:
            continue
        last = None
        for name in _NAME_RE.findall(m.group("names")):
            # `a.dispatch` / `.sync` abbreviates a.sync
            if name.startswith(".") and last:
                name = last.rsplit(".", 1)[0] + name
            rows.add(name)
            last = name
    return rows


def test_span_scan_is_alive():
    code, doc = spans_in_code(), spans_in_doc()
    assert len(code) >= 30, sorted(code)
    assert len(doc) >= 30, sorted(doc)
    assert "serve.decode.emit" in code and "fit_step.rebind" in code


def test_every_code_span_documented():
    code, doc = spans_in_code(), spans_in_doc()
    missing = {n: sorted(code[n]) for n in code if n not in doc}
    assert not missing, (
        "spans in code but MISSING from OBSERVABILITY.md's span tables: "
        "%s" % missing)


def test_every_documented_span_live():
    code, doc = spans_in_code(), spans_in_doc()
    live = set(code) | {n.rsplit(".", 1)[0] + ".sync" for n in code
                        if n.endswith(".dispatch")}
    stale = sorted(doc - live)
    assert not stale, (
        "OBSERVABILITY.md documents spans no code writes anymore: %s"
        % stale)


# -- scope inventory lint ---------------------------------------------------

#: a name through the primitive, with or without the module's alias
_SCOPE_RE = re.compile(r"(?<![\w.])(?:_telemetry\.|telemetry\.)?"
                       r"device_scope\(\s*['\"]([a-z0-9_.]+)['\"]")
_SCOPE_ROW_RE = re.compile(r"^\|(?P<names>[^|]+)\|\s*scope\s*\|")


def _package_sources():
    for dirpath, dirnames, filenames in os.walk(
            os.path.join(REPO, "mxnet_tpu")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fname in filenames:
            if fname.endswith(".py"):
                path = os.path.join(dirpath, fname)
                with open(path, encoding="utf-8") as f:
                    yield os.path.relpath(path, REPO), f.read()


def scopes_in_code():
    out = {}
    for rel, src in _package_sources():
        for name in _SCOPE_RE.findall(src):
            out.setdefault(name, set()).add(rel)
    return out


def scopes_in_doc():
    """Names from the rows of OBSERVABILITY.md's "Device-side names"
    tables whose kind is ``scope``."""
    with open(os.path.join(REPO, "OBSERVABILITY.md"),
              encoding="utf-8") as f:
        rows = [_SCOPE_ROW_RE.match(line.strip()) for line in f]
    return {n for m in rows if m for n in _NAME_RE.findall(m.group("names"))}


def test_every_scope_in_code_is_declared_and_documented():
    code, doc = scopes_in_code(), scopes_in_doc()
    assert len(code) >= 20, sorted(code)
    undeclared = {n: sorted(code[n]) for n in code
                  if n not in telemetry.DEVICE_SCOPES}
    assert not undeclared, undeclared
    missing = {n: sorted(code[n]) for n in code if n not in doc}
    assert not missing, (
        "scopes in code but MISSING from OBSERVABILITY.md's device-side "
        "names: %s" % missing)


def test_every_declared_and_documented_scope_is_live():
    code, doc = scopes_in_code(), scopes_in_doc()
    assert doc == set(telemetry.DEVICE_SCOPES), (
        sorted(doc ^ set(telemetry.DEVICE_SCOPES)))
    stale = sorted(doc - set(code))
    assert not stale, (
        "OBSERVABILITY.md documents scopes no code enters anymore: %s"
        % stale)


def test_no_named_scope_outside_telemetry():
    """Every device-side scope goes through ``telemetry.device_scope``,
    which holds it to the declared names."""
    users = sorted(rel for rel, src in _package_sources()
                   if "named_scope" in src)
    assert users == [os.path.join("mxnet_tpu", "telemetry.py")], users
