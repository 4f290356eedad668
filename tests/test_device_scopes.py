"""Device time by scope (ISSUE 36).

``telemetry.device_scope`` names the stretches of a traced program,
``telemetry.note_program`` / ``program_scopes`` give every compiled
instruction its scope path, and ``perfbench/readers/device_scopes.py``
sums a traced slice by program and scope.  Here, on the CPU:

- the tiny engines of all four model families and the fused fit step:
  every scope documented for a program is on at least one of its
  instructions, the table's paths use declared names only, and the share
  of the instructions the program wrote (non-trivial, with an
  ``op_name`` of its own) that lie in no scope is under a stated limit:
  the CPU's stand-in for ``scope.unattributed_pct.*``;
- the reader on synthetic documents: a ``while`` over its body's events
  is not counted twice, a run the window's edge clips is no whole run,
  two programs that share a module name are told apart by a run's
  instruction names, a name in no table counts as unattributed;
- the reader on one slice recorded on the chip with the table it was
  read by: the parts sum to the runs' busy time;
- the registry: a dead engine leaves nothing, noting reads no text.
"""
import gc
import json
import os
import re
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import aot_cache, telemetry

from test_program_spans import _engine, _module

pytestmark = pytest.mark.telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(REPO, "perfbench")
for _p in (REPO, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import trace_reduce                               # noqa: E402
from readers import device_scopes                 # noqa: E402


# -- (a) the programs' tables -----------------------------------------------

MOE = ("moe", "moe.route", "moe.scatter", "moe.experts", "moe.combine",
       "moe.shared")
#: (family, program) -> the scopes OBSERVABILITY.md documents for it
DOCUMENTED = {
    ("gpt", "serve_decode"): (
        "embed", "norm", "attn.proj", "attn", "attn.out", "kv_write",
        "mlp", "lm_head", "sample"),
    ("gpt", "serve_prefill"): (
        "embed", "norm", "attn.proj", "attn", "attn.gather", "attn.out",
        "kv_write", "mlp", "lm_head", "sample"),
    ("ling3", "serve_decode"): (
        "embed", "norm", "attn.proj", "attn", "attn.out", "kv_write",
        "state_write", "kda_step", "mlp", "lm_head", "sample") + MOE,
    ("ling3", "serve_prefill"): (
        "embed", "norm", "attn.proj", "attn", "attn.out", "kv_write",
        "state_write", "kda_scan", "mlp", "lm_head", "sample") + MOE,
    ("dsv32", "serve_decode"): (
        "embed", "norm", "attn.proj", "attn", "attn.gather", "attn.out",
        "index", "index.select", "kv_write", "mlp", "lm_head",
        "sample") + MOE,
    ("dsv32", "serve_prefill"): (
        "embed", "norm", "attn.proj", "attn", "attn.gather", "attn.out",
        "index", "index.select", "kv_write", "mlp", "lm_head",
        "sample") + MOE,
    ("kexaone", "serve_decode"): (
        "embed", "norm", "attn.proj", "attn.full", "attn.window",
        "attn.out", "kv_write", "mlp", "lm_head", "sample") + MOE,
    ("kexaone", "serve_prefill"): (
        "embed", "norm", "attn.proj", "attn.full", "attn.window",
        "attn.out", "kv_write", "mlp", "lm_head", "sample") + MOE,
    ("gigachat", "serve_decode"): (
        "embed", "norm", "attn.proj", "attn.mla", "attn.out", "kv_write",
        "gdn.proj", "gdn_step", "gdn.out", "state_write", "mlp", "lm_head",
        "sample") + MOE,
    ("gigachat", "serve_prefill"): (
        "embed", "norm", "attn.proj", "attn.mla", "attn.out", "kv_write",
        "gdn.proj", "gdn_scan", "gdn.out", "state_write", "mlp", "lm_head",
        "sample") + MOE,
    ("fit", "fit_step"): (
        "forward_backward", "divergence_guard", "optimizer_apply"),
}
#: most of a program's own instructions that may lie in no scope, %: the
#: tiny programs read 0-6 (iotas, compares and the reports' small sums
#: written between two scopes)
UNSCOPED_LIMIT_PCT = 8.0

_TRIVIAL = ("parameter", "constant", "bitcast", "tuple",
            "get-tuple-element")
_OPCODE = re.compile(r"^\s*(?:ROOT )?%(\S+) = .*?[})\]] ([\w\-]+)\(")


def _family(name):
    from mxnet_tpu.serving import ServingEngine
    if name == "gpt":
        return _engine()
    if name == "fit":
        mod, batches = _module()
        mod.fit_step(batches[0])
        return mod
    from mxnet_tpu.gluon.model_zoo import (deepseek_v32, exaone_moe,
                                           gigachat35, ling3)
    np.random.seed(0)
    mx.random.seed(0)
    if name == "ling3":
        return ServingEngine(
            ling3.ling3_tiny().init_seeded(3), num_slots=2, page_size=8,
            num_pages=64, max_prefill_len=136, max_seq_len=160)
    if name == "dsv32":
        return ServingEngine(
            deepseek_v32.deepseek_v32_tiny().init_seeded(3), num_slots=2,
            page_size=8, num_pages=40, max_prefill_len=16, max_seq_len=72)
    if name == "gigachat":
        return ServingEngine(
            gigachat35.gigachat35_tiny().init_seeded(3), num_slots=2,
            page_size=4, num_pages=48, max_prefill_len=12, max_seq_len=80)
    assert name == "kexaone"
    return ServingEngine(
        exaone_moe.exaone_moe_tiny().init_seeded(3), num_slots=2,
        page_size=4, num_pages=48, max_prefill_len=12, max_seq_len=80)


@pytest.fixture(scope="module")
def programs():
    """``{(family, program): (table, text)}``: every family's programs as
    ``program_scopes()`` has them, found by the compiled object its
    owner holds."""
    out, owners = {}, []
    for family in ("gpt", "ling3", "dsv32", "kexaone", "gigachat", "fit"):
        before = {id(c) for c in telemetry._programs}
        owner = _family(family)
        owners.append(owner)
        held = [owner._fused["step"]] if family == "fit" \
            else [owner._decode, owner._prefill]
        for prog in held:
            compiled = prog.__wrapped__
            assert id(compiled) not in before
            name = telemetry._programs[compiled]
            telemetry.program_scopes()
            module, scopes = telemetry._scope_tables[compiled]
            entry = {"program": name, "module": module, "scopes": scopes}
            assert entry in telemetry.program_scopes()
            out[family, name] = (entry, compiled.as_text())
    yield out
    del owners


@pytest.mark.parametrize("family,program,scope", [
    (f, p, s) for (f, p), scopes in sorted(DOCUMENTED.items())
    for s in scopes])
def test_documented_scope_is_on_an_instruction(programs, family, program,
                                               scope):
    table = programs[family, program][0]["scopes"]
    assert any(scope in path.split("/") for path in table.values()), \
        "no instruction of %s's %s lies in %r" % (family, program, scope)


@pytest.mark.parametrize("family,program", sorted(DOCUMENTED))
def test_table_uses_declared_names_and_covers_the_program(
        programs, family, program):
    entry, text = programs[family, program]
    assert entry["module"] == {"serve_decode": "jit_decode",
                               "serve_prefill": "jit_prefill",
                               "fit_step": "jit_step"}[program]
    table = entry["scopes"]
    used = {part for path in table.values() if path
            for part in path.split("/")}
    assert used <= telemetry.DEVICE_SCOPES, used - telemetry.DEVICE_SCOPES
    assert used >= set(DOCUMENTED[family, program])
    own = unscoped = 0
    for line in text.splitlines():
        m = _OPCODE.match(line)
        if not m or m.group(2) in _TRIVIAL \
                or 'op_name="jit(' not in line:
            continue
        assert m.group(1) in table, m.group(1)
        own += 1
        unscoped += not table[m.group(1)]
    assert own > 100
    assert 100.0 * unscoped / own < UNSCOPED_LIMIT_PCT, \
        "%d of %d instructions of %s's %s lie in no scope" \
        % (unscoped, own, family, program)


def test_scope_path_of_an_op_name():
    path = telemetry._scope_path
    assert path("jit(prefill)/jit(main)/while/body/moe/moe.scatter/"
                "scatter") == "moe/moe.scatter"
    assert path("jit(step)/transpose(jvp(forward_backward))/mul") \
        == "forward_backward"
    assert path("jit(decode)/attn.window/vmap()/reshape;"
                "jit(decode)/embed/reshape") == "attn.window"
    assert path("jit(decode)/moe/moe.route/jit(searchsorted)/jit(decode)/"
                "moe/moe.route/jit(searchsorted)/while/body/add") \
        == "moe/moe.route"
    # a kernel named as the scope that holds it counts once
    assert path("jit(decode)/kda_step/kda_step") == "kda_step"
    # a jitted function is never a scope, whatever its name
    assert path("jit(sample)/cond/branch_1_fun/add") == ""
    assert path("") == ""


def test_a_fusion_is_its_roots_and_a_copy_lies_in_no_scope():
    """No ``op_name``: a fusion takes the name its fused computation ends
    on; whatever else the compiler made (a layout copy, the start / done
    pair of a prefetch) stays empty whoever uses it, and so does an
    instruction the program itself wrote outside every scope."""
    module, table = telemetry._parse_scopes("""\
HloModule jit_decode, is_scheduled=true

%fused_computation.1 (p: f32[8]) -> (f32[8], f32[8]) {
  %p = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(decode)/attn.proj/norm/mul"}
  %neg.1 = f32[8]{0} negate(%mul.1), metadata={op_name="jit(decode)/attn.proj/neg"}
  ROOT %tuple.1 = (f32[8]{0}, f32[8]{0}) tuple(%mul.1, %neg.1)
}

ENTRY %main.5 (x: f32[8], w: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %w = f32[8]{0} parameter(1)
  %fusion.7 = (f32[8]{0}, f32[8]{0}) fusion(%x), kind=kLoop, calls=%fused_computation.1
  %copy-start.2 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%w)
  %copy.3 = f32[8]{0} copy(%x)
  %copy-done.2 = f32[8]{0} copy-done(%copy-start.2)
  %iota.4 = f32[8]{0} iota(), iota_dimension=0, metadata={op_name="jit(decode)/iota"}
  %copy.9 = f32[8]{0} copy(%iota.4)
  %dot.6 = f32[8]{0} multiply(%copy.3, %copy-done.2), metadata={op_name="jit(decode)/mlp/dot_general"}
  ROOT %add.2 = f32[8]{0} add(%dot.6, %iota.4), metadata={op_name="jit(decode)/lm_head/add"}
}
""")
    assert module == "jit_decode"
    assert table["fusion.7"] == "attn.proj"
    assert table["mul.1"] == "attn.proj/norm"
    assert table["copy.3"] == table["copy-done.2"] == ""
    assert table["copy-start.2"] == ""
    assert table["iota.4"] == "" and table["copy.9"] == ""
    assert table["dot.6"] == "mlp" and table["add.2"] == "lm_head"


def test_device_scope_refuses_an_undeclared_name():
    with pytest.raises(ValueError, match="DEVICE_SCOPES"):
        telemetry.device_scope("attention")
    # 27 before the gated delta rule's four scopes and `attn.mla`
    assert len(telemetry.DEVICE_SCOPES) <= 32


# -- (d) the registry ----------------------------------------------------------

def test_a_dead_engine_leaves_nothing_in_the_registry():
    """The registry holds a program weakly: with its engine gone and the
    in-process memo cleared nobody can run it, and no table is left."""
    def mine():
        return [p for p in telemetry.program_scopes()
                if p["program"].startswith("serve_")
                and len(p["scopes"]) == sizes]
    eng = _engine(num_slots=5)             # a shape no other test builds
    compiled = eng._decode.__wrapped__
    assert telemetry._programs[compiled] == "serve_decode"
    telemetry.program_scopes()
    sizes = len(telemetry._scope_tables[compiled][1])
    assert mine()
    n = len(telemetry._programs)
    del eng, compiled
    aot_cache.clear_memo()
    gc.collect()
    assert len(telemetry._programs) <= n - 2
    assert not mine()


def test_noting_a_program_reads_no_text():
    class Compiled:
        reads = 0

        def as_text(self):
            Compiled.reads += 1
            return "HloModule jit_x\n\nENTRY %main () -> f32[] {\n" \
                '  ROOT %c.1 = f32[] constant(0), metadata={op_name=' \
                '"jit(x)/embed/c"}\n}\n'

    prog = Compiled()
    telemetry.note_program("x", prog)
    telemetry.note_program("x", prog)
    assert Compiled.reads == 0
    entry, = [p for p in telemetry.program_scopes() if p["program"] == "x"]
    assert entry == {"program": "x", "module": "jit_x",
                     "scopes": {"c.1": "embed"}}
    telemetry.program_scopes()
    assert Compiled.reads == 1             # parsed once, however often read
    del prog, entry
    gc.collect()
    assert not [p for p in telemetry.program_scopes()
                if p["program"] == "x"]


def test_an_engine_notes_its_programs_once_and_steps_note_nothing(
        monkeypatch):
    noted = []
    monkeypatch.setattr(telemetry, "note_program",
                        lambda name, compiled: noted.append(name))
    eng = _engine(num_slots=4)
    assert sorted(noted) == ["serve_decode", "serve_prefill"]
    rng = np.random.RandomState(1)
    req = eng.submit(rng.randint(0, 64, 6).astype(np.int32), 4)
    while not req.done:
        eng.step()
    assert len(noted) == 2


# -- (b) the reader on synthetic documents -------------------------------------

def _doc(modules, ops, steps=((0.0, 1000.0),)):
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["step", s, d] for s, d in steps]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]}]}


def _op(name, start, dur, opcode="fusion"):
    return ["%%%s = f32[8]{0} %s(f32[8]{0} %%p.1)" % (name, opcode),
            start, dur]


def _table(module, scopes, program="serve_x"):
    return {"program": program, "module": module, "scopes": scopes}


def test_a_while_is_not_counted_twice():
    doc = _doc([["jit_prefill(1)", 100.0, 500.0]],
               [_op("while.1", 100.0, 400.0, "while"),
                _op("fusion.1", 110.0, 100.0), _op("fusion.2", 250.0, 200.0),
                _op("fusion.3", 520.0, 50.0)])
    red = device_scopes.breakdown(doc, [_table("jit_prefill", {
        "while.1": "attn.full", "fusion.1": "attn.full",
        "fusion.2": "moe/moe.scatter", "fusion.3": "norm"})])
    prog = red["programs"]["jit_prefill"]
    assert prog["runs"] == prog["whole_runs"] == 1
    # the loop keeps what its body does not cover: 400 - 100 - 200
    assert prog["scopes"] == pytest.approx({
        "attn.full": 200e-9, "moe/moe.scatter": 200e-9, "norm": 50e-9})
    assert red["busy_s"] == pytest.approx(450e-9)
    assert red["unattributed_s"] == 0.0

    def q(what, scope):
        return device_scopes.quantity(red, {
            "what": what, "program": "^jit_prefill", "scope": scope})
    assert q("ms_per_run", r"(^|/)attn\.full(/|$)") == pytest.approx(200e-6)
    assert q("ms_per_run", r"(^|/)moe(/|$)") == pytest.approx(200e-6)
    assert q("ms_per_run", r"(^|/)norm$") == pytest.approx(50e-6)
    assert device_scopes.quantity(red, {"what": "unattributed_pct"}) == 0.0
    assert device_scopes.quantity(red, {
        "what": "ms_per_run", "program": "^jit_decode", "scope": "."}) is None


def test_a_run_the_windows_edge_clips_is_no_whole_run():
    doc = _doc([["jit_decode(1)", -50.0, 100.0],
                ["jit_decode(1)", 200.0, 100.0],
                ["jit_decode(1)", 950.0, 100.0]],
               [_op("fusion.1", -40.0, 80.0), _op("fusion.1", 210.0, 80.0),
                _op("fusion.1", 960.0, 80.0)])
    red = device_scopes.breakdown(
        doc, [_table("jit_decode", {"fusion.1": "kv_write"})])
    prog = red["programs"]["jit_decode"]
    assert (prog["runs"], prog["whole_runs"]) == (3, 1)
    assert prog["whole_run_s"] == pytest.approx(100e-9)
    # clipped: 40 ns of the first run and 40 of the last lie in the window
    assert prog["scopes"]["kv_write"] == pytest.approx(160e-9)
    assert prog["whole_scopes"]["kv_write"] == pytest.approx(80e-9)
    args = {"program": "^jit_decode", "scope": "kv_write"}
    assert device_scopes.quantity(red, dict(args, what="ms_per_run")) \
        == pytest.approx(80e-6)
    with pytest.raises(ValueError, match="unknown quantity"):
        device_scopes.quantity(red, dict(args, what="seconds"))


def test_two_programs_of_one_module_name_are_told_apart_by_their_runs():
    """Both tables hold ``fusion.1``; only the long prefill's holds
    ``fusion.9``, only the short one's ``fusion.4``."""
    short = _table("jit_prefill", {"fusion.1": "kv_write",
                                   "fusion.4": "attn"})
    long_ = _table("jit_prefill", {"fusion.1": "attn.proj",
                                   "fusion.9": "mlp"})
    doc = _doc([["jit_prefill(1)", 0.0, 100.0],
                ["jit_prefill(2)", 200.0, 100.0],
                ["jit_prefill(2)", 400.0, 100.0]],
               [_op("fusion.1", 0.0, 50.0), _op("fusion.4", 50.0, 50.0),
                _op("fusion.1", 200.0, 30.0), _op("fusion.9", 230.0, 70.0),
                _op("fusion.1", 400.0, 100.0)])   # fits both, which differ
    red = device_scopes.breakdown(doc, [short, long_, dict(short)])
    assert red["programs"]["jit_prefill"]["scopes"] == pytest.approx({
        "kv_write": 50e-9, "attn": 50e-9, "attn.proj": 30e-9,
        "mlp": 70e-9, "": 100e-9})
    assert red["unattributed_s"] == pytest.approx(100e-9)
    assert red["unattributed"] == [["fusion fusion f32[8]",
                                    pytest.approx(100e-9)]]


def test_an_instruction_in_no_table_counts_as_unattributed():
    doc = _doc([["jit_decode(1)", 0.0, 100.0], ["jit_other(1)", 200.0, 50.0]],
               [_op("fusion.1", 0.0, 60.0), _op("copy.7", 60.0, 40.0, "copy"),
                _op("fusion.2", 200.0, 50.0), _op("fusion.3", 300.0, 10.0)])
    red = device_scopes.breakdown(
        doc, [_table("jit_decode", {"fusion.1": "moe/moe.experts"})])
    assert red["programs"]["jit_decode"]["scopes"] == pytest.approx(
        {"moe/moe.experts": 60e-9, "": 40e-9})
    assert red["programs"]["jit_other"]["scopes"] == pytest.approx(
        {"": 50e-9})
    assert red["programs"][device_scopes.NO_PROGRAM]["scopes"] \
        == pytest.approx({"": 10e-9})
    assert device_scopes.quantity(red, {"what": "unattributed_pct"}) \
        == pytest.approx(100.0 * 100 / 160)
    assert [n for n, _ in red["unattributed"]] == [
        "fusion fusion f32[8]", "copy copy f32[8]"]


def test_the_reader_reads_nothing_where_there_is_nothing(monkeypatch):
    assert device_scopes.breakdown({"planes": []}, []) is None
    host_only = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["step", 0.0, 10.0]]}]}]}
    assert device_scopes.breakdown(host_only, []) is None
    args = {"what": "unattributed_pct"}
    assert device_scopes.quantity(None, args) is None
    assert device_scopes.value({}, args) is None
    monkeypatch.setattr(device_scopes.program_spans, "newest_trace",
                        lambda: None)
    assert device_scopes.value({"trace": {"spans": [1]}}, args) is None
    # the parent of the PR that added the table reads as nothing
    monkeypatch.setattr(device_scopes.program_spans, "newest_trace",
                        lambda: "/nonexistent.xplane.pb")
    monkeypatch.delattr(telemetry, "program_scopes")
    assert device_scopes.value({"trace": {"spans": [1]}}, args) is None


# -- the metric files --------------------------------------------------------------

def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _scope_metrics():
    out = []
    for m in _bench()["per_layer"]:
        with open(os.path.join(BENCH_DIR, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        if spec["reader"] == "device_scopes":
            out.append((m, spec))
    return out


@pytest.mark.parametrize("name", [m["name"] for m, _ in _scope_metrics()])
def test_scope_metric_reads_declared_scopes_in_one_cell(name):
    m, spec = next((m, s) for m, s in _scope_metrics()
                   if m["name"] == name)
    assert m["source"] == "device_trace" and m["layer"] == "programs"
    assert m["better"] == "lower" and len(m["workloads"]) == 1
    cell, = [w for w in _bench()["workloads"]
             if w["name"] == m["workloads"][0]]
    moved, = [e for e in _bench()["end_to_end"] if e["name"] == m["moves"]]
    assert cell["name"] in moved["workloads"]
    args = spec["args"]
    if args["what"] == "unattributed_pct":
        assert m["unit"] == "%" and set(args) == {"what"}
        return
    assert args["what"] == "ms_per_run" and m["unit"] == "ms"
    assert any(re.search(args["program"], mod)
               for mod in ("jit_decode", "jit_prefill", "jit_step"))
    # the pattern finds a declared scope at any depth of a path and
    # nothing that merely starts like one
    hit = {s for s in telemetry.DEVICE_SCOPES
           if re.search(args["scope"], "embed/" + s)}
    assert hit and all(re.search(args["scope"], s) for s in hit), hit
    assert not re.search(args["scope"], "x" + sorted(hit)[0])
    assert not re.search(args["scope"], "")


# -- (c) the reader on a slice recorded on the chip ------------------------------

DATA = os.path.join(BENCH_DIR, "tests", "data")
SLICE = os.path.join(DATA, "kexaone-serve-mixedlen.scopes.slice.json.gz")
TABLE = os.path.join(DATA, "kexaone-serve-mixedlen.scopes.table.json")


@pytest.fixture(scope="module")
def recorded():
    doc = trace_reduce.read_doc(SLICE)
    with open(TABLE) as f:
        tables = json.load(f)
    return doc, tables, device_scopes.breakdown(doc, tables)


def test_recorded_slice_parts_sum_to_the_runs_busy_time(recorded):
    """A few steps of ``kexaone-serve-mixedlen`` holding a whole chunk
    run: by program, the scopes' seconds are the union of the run's
    operation intervals (nothing twice, nothing lost), a whole run's
    parts fill its "XLA Modules" event to 1%, and nearly all of it lies
    in a declared scope."""
    doc, tables, red = recorded
    assert os.path.getsize(SLICE) + os.path.getsize(TABLE) < 500_000
    plane, = [p for p in doc["planes"]
              if trace_reduce.DEVICE_PLANE.match(p["name"])]
    lines = {l["name"]: l["events"] for l in plane["lines"]}
    _, bench = trace_reduce.host_spans(doc)
    lo = min(s for _, s, _ in bench)
    hi = max(s + d for _, s, d in bench)
    union = sum(e - s for s, e in trace_reduce._union(
        [[a, b] for _, a, b in trace_reduce._clip(lines["XLA Ops"], lo, hi)]))
    assert red["busy_s"] == pytest.approx(union * 1e-9, rel=1e-9)
    assert red["busy_s"] == pytest.approx(
        trace_reduce.reduce(doc)["busy_s"], rel=1e-9)
    prefill = red["programs"]["jit_prefill"]
    decode = red["programs"]["jit_decode"]
    assert prefill["whole_runs"] >= 1 and decode["whole_runs"] >= 2
    for prog in (prefill, decode):
        parts = sum(prog["whole_scopes"].values())
        assert parts == pytest.approx(prog["whole_run_s"], rel=0.01)
    assert 100.0 * red["unattributed_s"] / red["busy_s"] < 5.0
    used = {part for p in red["programs"].values() for path in p["scopes"]
            if path for part in path.split("/")}
    assert used <= telemetry.DEVICE_SCOPES


@pytest.mark.parametrize("metric", [
    "attn_full.prefill_ms.mixedlen", "moe_layout.prefill_ms.mixedlen",
    "moe_experts.prefill_ms.mixedlen", "moe.decode_ms.mixedlen",
    "scope.unattributed_pct.mixedlen"])
def test_recorded_slice_gives_every_metric_of_its_cell(recorded, metric):
    with open(os.path.join(BENCH_DIR, "layer_metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    value = device_scopes.quantity(recorded[2], spec["args"])
    assert value is not None and value > 0.0
    if spec["args"]["what"] == "ms_per_run":
        prog = recorded[2]["programs"][
            "jit_" + metric.split(".")[1].split("_")[0]]
        assert value < 1e3 * prog["whole_run_s"] / prog["whole_runs"]


def test_by_hand_entry_prints_the_same_breakdown(recorded, capsys):
    device_scopes.main([SLICE, TABLE])
    out = capsys.readouterr().out
    assert "jit_prefill:" in out and "moe/moe.scatter" in out
    assert "longest unattributed:" in out
