"""Fault-site inventory lint (ISSUE 14 satellite): the no-silent-caps
contract applied to the fault grammar itself.

The fault-injection layer is only trustworthy if every site is
(a) DOCUMENTED — an operator reading ROBUSTNESS.md §4 must see the
complete drill surface, and (b) DRILLED — a site nothing exercises is
a recovery path nothing proves.  This lint enumerates every site
string passed to ``fault.trigger`` / ``check`` / ``stall_if`` /
``delay_if`` / ``exit_if`` / ``is_active`` across the runtime
(``mxnet_tpu/``, ``tools/``) and asserts:

- every site in code has a row in the ROBUSTNESS.md §4 table;
- every row in the table corresponds to a site in code (no stale
  docs describing drills that no longer exist);
- every site is referenced by at least one file under ``tests/``
  (the drill exists — a fault path with no test is undrilled);
- every ``rpc.*`` site's row names WHICH PLANE it cuts — control
  (liveness/drain) vs data (submit/status) — because the whole point
  of the ISSUE-17 liveness design is that the two planes fail
  independently and the failover verdict must not confuse them.

Adding a fault site therefore REQUIRES a §4 row and a test in the
same change, mechanically.
"""
import os
import re

import pytest

pytestmark = pytest.mark.fault

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: a fault-site check: fault.trigger("site") / _fault.stall_if('site')…
_CALL_RE = re.compile(
    r"(?:\b|_)fault\.(?:trigger|check|stall_if|delay_if|exit_if|"
    r"is_active)\(\s*['\"]([a-z0-9_.]+)['\"]")
#: a §4 table row: | `site` | effect |
_ROW_RE = re.compile(r"^\|\s*`([a-z0-9_.]+)`\s*\|")


def _py_files(*roots):
    for root in roots:
        root = os.path.join(REPO, root)
        if os.path.isfile(root):
            yield root
            continue
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in dirnames
                           if d != "__pycache__"]
            for name in filenames:
                if name.endswith(".py"):
                    yield os.path.join(dirpath, name)


def sites_in_code():
    sites = {}
    for path in _py_files("mxnet_tpu", "tools"):
        with open(path, encoding="utf-8") as f:
            src = f.read()
        for m in _CALL_RE.finditer(src):
            sites.setdefault(m.group(1), []).append(
                os.path.relpath(path, REPO))
    return sites


def doc_rows():
    """ROBUSTNESS.md §4 site table rows (between the §4 and §5
    headings), as {site: full row text}."""
    with open(os.path.join(REPO, "ROBUSTNESS.md"),
              encoding="utf-8") as f:
        text = f.read()
    start = text.index("## 4. Fault injection")
    end = text.index("## 5.", start)
    rows = {}
    for line in text[start:end].splitlines():
        m = _ROW_RE.match(line.strip())
        if m and m.group(1) != "site":
            rows[m.group(1)] = line.strip()
    return rows


def sites_in_doc():
    return set(doc_rows())


def test_every_code_site_documented_and_every_doc_row_live():
    code = sites_in_code()
    assert code, "the site scan found nothing — the regex rotted"
    doc = sites_in_doc()
    undocumented = sorted(set(code) - doc)
    assert not undocumented, (
        "fault sites checked in code but MISSING from the "
        "ROBUSTNESS.md §4 table: %s (sites live at %s)"
        % (undocumented,
           {s: code[s] for s in undocumented}))
    stale = sorted(doc - set(code))
    assert not stale, (
        "ROBUSTNESS.md §4 documents fault sites no code checks "
        "anymore: %s — drop the rows or restore the drills" % stale)


def test_every_rpc_site_row_names_its_plane():
    """ISSUE 17: the liveness protocol's central claim is that the
    control plane (heartbeat/drain) and the data plane (submit/status)
    fail INDEPENDENTLY — a cut control plane with a healthy data plane
    must never fail a replica over.  An operator triaging a drill row
    therefore needs to know which plane each ``rpc.*`` site cuts; a
    row that doesn't say is a row that can't be acted on."""
    rows = doc_rows()
    rpc_sites = sorted(s for s in sites_in_code()
                       if s.startswith("rpc."))
    assert rpc_sites, "no rpc.* sites found — the site scan rotted"
    planes = ("control plane", "data plane", "both planes")
    unnamed = [s for s in rpc_sites
               if s in rows
               and not any(p in rows[s].lower() for p in planes)]
    assert not unnamed, (
        "ROBUSTNESS.md §4 rows for rpc.* fault sites that never say "
        "which plane (control vs data) the drill cuts: %s" % unnamed)


def test_every_site_exercised_by_a_test():
    code = sites_in_code()
    tests_dir = os.path.join(REPO, "tests")
    corpus = {}
    for path in _py_files("tests"):
        with open(path, encoding="utf-8") as f:
            corpus[os.path.relpath(path, tests_dir)] = f.read()
    # this lint enumerates sites from source, so its own strings never
    # count as "a drill exists"
    corpus.pop(os.path.basename(__file__), None)
    undrilled = sorted(s for s in code
                       if not any(s in text
                                  for text in corpus.values()))
    assert not undrilled, (
        "fault sites no test exercises: %s — every recovery path "
        "must be drilled, not just written (checked at %s)"
        % (undrilled, {s: code[s] for s in undrilled}))
