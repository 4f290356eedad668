"""Metrics inventory lint (ISSUE 18 satellite): the no-silent-caps
contract applied to the metric namespace itself — the fault-site lint's
(test_fault_inventory.py) twin for the telemetry registry.

The telemetry plane is only trustworthy if every metric is DOCUMENTED:
an operator reading an ``alert`` event, a ``fleet_top`` column, or a
pulled stream line must be able to look the name up in OBSERVABILITY.md
and learn its type and meaning.  This lint enumerates every
counter/gauge/histogram NAME LITERAL registered across the runtime
(``mxnet_tpu/``, ``tools/``) and asserts:

- every metric name in code has a table row in OBSERVABILITY.md whose
  type cell says counter/gauge/histogram;
- every such documented row corresponds to a name in code (no stale
  docs describing metrics that no longer exist).

Parameterized names line up by placeholder: ``rpc.breaker.%s`` in code
matches the documented ``rpc.breaker.<replica>`` (both normalize their
placeholder to ``<>``).  Indirections count too: checkpoint.py's
``retry_counter="ckpt.io_retries"`` default registers a counter even
though the literal never touches ``telemetry.counter(...)`` directly.

Adding a metric therefore REQUIRES an OBSERVABILITY.md row in the same
change, mechanically — exactly how a fault site requires its
ROBUSTNESS.md §4 row.
"""
import os
import re

import pytest

pytestmark = pytest.mark.telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: a registration through any of the module's import aliases
#: (telemetry / _telemetry / _tel) — ``\s*`` spans line breaks, so
#: black-wrapped calls still count
_CALL_RE = re.compile(
    r"(?:_?telemetry|_tel)\.(counter|gauge|histogram)"
    r"\(\s*['\"]([^'\"]+)['\"]")
#: telemetry.py registers against its own module-level helpers bare
_BARE_RE = re.compile(
    r"(?<![\w.])(counter|gauge|histogram)\(\s*['\"]([^'\"]+)['\"]")
#: name literals that reach the registry through a parameter default
_INDIRECT_RES = (
    ("counter", re.compile(r"retry_counter=['\"]([a-z0-9_.]+)['\"]")),
)
#: an OBSERVABILITY.md table row: | `name` [/ `name`...] | type | ...
_ROW_RE = re.compile(r"^\|(?P<names>[^|]+)\|(?P<type>[^|]+)\|")
_NAME_RE = re.compile(r"`([a-zA-Z0-9_.%<>*{}]+)`")
_TYPES = ("counter", "gauge", "histogram")


def _norm(name):
    """Collapse every placeholder spelling — ``%s`` / ``%d`` /
    ``{field}`` in code, ``<replica>`` / ``<reason>`` in docs — to
    ``<>`` so parameterized families line up."""
    name = re.sub(r"%\([a-zA-Z_]+\)[sdr]|%[sdr]|\{[^}]*\}", "<>", name)
    return re.sub(r"<[^>]*>", "<>", name)


def _matches(a, b):
    """True when two normalized names denote the same metric family.
    A template matches its instances both ways: code's
    ``xla.cost.<>_per_step`` is documented by the enumerated
    ``xla.cost.flops_per_step`` row, and a documented
    ``rpc.breaker.<>`` template covers any literal instance."""
    if a == b:
        return True
    for tpl, other in ((a, b), (b, a)):
        if "<>" in tpl:
            pat = re.escape(tpl).replace(re.escape("<>"),
                                         r"[a-zA-Z0-9_]+")
            if re.fullmatch(pat, other):
                return True
    return False


def _py_files(*roots):
    for root in roots:
        root = os.path.join(REPO, root)
        if os.path.isfile(root):
            yield root
            continue
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for name in filenames:
                if name.endswith(".py"):
                    yield os.path.join(dirpath, name)


def metrics_in_code():
    """{normalized name: {(relpath, type), ...}} for every registered
    counter/gauge/histogram literal under the runtime roots."""
    out = {}
    for path in _py_files("mxnet_tpu", "tools"):
        with open(path, encoding="utf-8") as f:
            src = f.read()
        rel = os.path.relpath(path, REPO)
        rex = _BARE_RE if path.endswith(os.path.join(
            "mxnet_tpu", "telemetry.py")) else _CALL_RE
        for m in rex.finditer(src):
            out.setdefault(_norm(m.group(2)), set()).add(
                (rel, m.group(1)))
        for kind, irex in _INDIRECT_RES:
            for m in irex.finditer(src):
                out.setdefault(_norm(m.group(1)), set()).add(
                    (rel, kind))
    return out


def metrics_in_doc():
    """{normalized name: type cell} from every OBSERVABILITY.md table
    row whose type column names a registry kind.  A first cell may
    hold several names (``\\`kv.push_keys\\` / \\`kv.pull_keys\\```);
    wildcard cross-references (``\\`router.*\\```) are not rows."""
    with open(os.path.join(REPO, "OBSERVABILITY.md"),
              encoding="utf-8") as f:
        lines = f.read().splitlines()
    rows = {}
    for line in lines:
        m = _ROW_RE.match(line.strip())
        if not m:
            continue
        typ = m.group("type").strip().lower()
        if not any(t in typ for t in _TYPES):
            continue
        for name in _NAME_RE.findall(m.group("names")):
            if "*" in name or "." not in name:
                continue
            rows[_norm(name)] = typ
    return rows


def test_scan_is_alive():
    code = metrics_in_code()
    assert len(code) > 50, (
        "the metric scan found only %d names — the regex rotted"
        % len(code))
    doc = metrics_in_doc()
    assert len(doc) > 50, (
        "the OBSERVABILITY.md row scan found only %d names — the "
        "table parser rotted" % len(doc))


def test_every_code_metric_documented():
    code = metrics_in_code()
    doc = metrics_in_doc()
    undocumented = sorted(
        n for n in code if not any(_matches(n, d) for d in doc))
    assert not undocumented, (
        "metrics registered in code but MISSING from the "
        "OBSERVABILITY.md tables: %s (registered at %s)"
        % (undocumented,
           {n: sorted(code[n]) for n in undocumented}))


def test_every_doc_row_live():
    code = metrics_in_code()
    doc = metrics_in_doc()
    stale = sorted(
        d for d in doc if not any(_matches(d, n) for n in code))
    assert not stale, (
        "OBSERVABILITY.md documents metrics no code registers "
        "anymore: %s — drop the rows or restore the metrics" % stale)


def test_documented_type_matches_registration():
    """A row that calls a histogram a counter sends an operator to the
    wrong query; where both sides carry a type, they must agree."""
    code = metrics_in_code()
    doc = metrics_in_doc()
    wrong = []
    for name, typ in doc.items():
        kinds = {k for n in code if _matches(name, n)
                 for _, k in code[n]}
        if kinds and not any(k in typ for k in kinds):
            wrong.append((name, typ.strip(), sorted(kinds)))
    assert not wrong, (
        "OBSERVABILITY.md type cells disagree with the registration "
        "kind: %s" % wrong)
