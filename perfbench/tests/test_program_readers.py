"""Checks of the readers PR 24 added (``program_spans``,
``request_events``) and of the metric files that read kernels by their
own names, on slices recorded on the chip with the program's spans in
them (``data/*.spans.slice.json.gz``, cut with ``trace_reduce.py
--export``).

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH_DIR)

import trace_reduce                               # noqa: E402
from readers import program_spans, request_events, trace_ops  # noqa: E402


def slice_doc(cell):
    return trace_reduce.read_doc(
        os.path.join(HERE, "data", cell + ".spans.slice.json.gz"))


def metric_args(name):
    with open(os.path.join(BENCH_DIR, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    return spec["reader"], spec["args"]


def test_program_spans_on_a_synthetic_trace():
    """Idle inside a span is its wall time minus the device-busy time
    under it; a span cut by the window's edge is left out."""
    doc = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["step", 0.0, 100.0], ["serve.step#step=3#", 5.0, 90.0],
            ["serve_step.sync", 60.0, 30.0],
            ["step", 100.0, 100.0], ["serve.step", 101.0, 98.0],
            ["serve.step", 199.5, 10.0]]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["a", 10.0, 30.0], ["b", 30.0, 20.0], ["a", 110.0, 80.0]]}]}]}
    sl = program_spans.slice_of_doc(doc)
    spans = program_spans.spans_matching(sl, r"^serve\.step$")
    assert [(s[1], s[2]) for s in spans] == [(5.0, 95.0), (101.0, 199.0)]
    assert [s[3] for s in spans] == pytest.approx([50.0, 18.0])
    sync = program_spans.spans_matching(sl, r"^serve_step\.sync$")
    assert [s[3] for s in sync] == pytest.approx([30.0])


def test_program_spans_without_spans_or_trace_reads_nothing(monkeypatch):
    doc = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["step", 0.0, 10.0]]}]}]}
    sl = program_spans.slice_of_doc(doc)
    assert program_spans.spans_matching(sl, r"^serve\.step$") == []
    monkeypatch.setattr(program_spans, "newest_trace", lambda: None)
    assert program_spans.value(
        {"trace": {"spans": [1]}},
        {"what": "idle_ms_per_span", "match": "^fit_step$"}) is None
    assert program_spans.value({}, {"what": "idle_ms_per_span",
                                    "match": "^fit_step$"}) is None


@pytest.mark.parametrize("cell,metric,inside", [
    ("resnet50-fit", "fit_step.host_ms_per_step", "train_step"),
    ("gpt2m-serve-chat", "engine.host_ms_per_step.chat", "step"),
    ("gpt2m-serve-backlog", "engine.host_ms_per_step.backlog", "step"),
])
def test_host_ms_on_a_recorded_slice(cell, metric, inside, monkeypatch):
    """The metric as its file defines it: every whole program span of the
    slice is found, each lies inside one benchmark span, and the idle
    time inside them is no more than the reduction's own idle total."""
    reader, args = metric_args(metric)
    assert reader == "program_spans"
    doc = slice_doc(cell)
    sl = program_spans.slice_of_doc(doc)
    spans = program_spans.spans_matching(sl, args["match"])
    red = trace_reduce.reduce(doc, 1)
    bench = [s for s in red["spans"] if s["name"] == inside]
    assert 0 < len(spans) <= len(bench)
    thread, _ = trace_reduce.host_spans(doc)
    outer = [(s, s + d) for n, s, d in thread
             if trace_reduce._span_name(n) == inside]
    for _, s, e, idle in spans:
        assert any(a <= s and e <= b for a, b in outer)
        assert 0 <= idle <= e - s
    assert sum(s[3] for s in spans) * 1e-9 <= red["idle_s"] * (1 + 1e-9)
    monkeypatch.setattr(program_spans, "newest_trace", lambda: cell)
    monkeypatch.setattr(program_spans, "slice_of", lambda path: sl)
    got = program_spans.value({"trace": red}, args)
    assert got == pytest.approx(
        1e-6 * sum(s[3] for s in spans) / len(spans))
    # the program's share is no more than what the benchmark's own span
    # reads from outside
    host_ms = 1e3 * sum(s["wall_s"] - s["busy_s"] for s in bench) \
        / len(bench)
    assert got <= host_ms * len(bench) / len(spans) * (1 + 1e-9)


def test_idle_gaps_of_a_recorded_slice_name_program_spans():
    """What this PR is for: the idle pieces inside ``fit_step`` carry the
    program's names, and bare ``train_step`` owns only what lies outside
    ``fit_step``."""
    red = trace_reduce.reduce(slice_doc("resnet50-fit"), 1)
    gaps = dict(red["idle_gaps"])
    assert any(name.startswith("fit_step") for name in gaps)
    inside = sum(v for k, v in gaps.items() if k.startswith("fit_step"))
    assert gaps.get("train_step", 0.0) < inside


def test_kernels_are_read_by_their_own_names():
    """``paged_decode.device_ms.backlog`` matches the Mosaic calls of the
    recorded decode steps and nothing else."""
    reader, args = metric_args("paged_decode.device_ms.backlog")
    assert reader == "trace_ops"
    red = trace_reduce.reduce(slice_doc("gpt2m-serve-backlog"), 1)
    rec = {"trace": red, "peaks": None, "config": {}, "counters": {}}
    got = trace_ops.value(rec, args)
    mosaic = sum(s for name, s in red["device_ops"]
                 if name.endswith(" tpu_custom_call"))
    assert got == pytest.approx(1e3 * mosaic / len(red["spans"]))
    assert got > 0


def test_request_events_reader():
    from mxnet_tpu import telemetry
    telemetry.reset()
    rec = {"counters": {"window_s": 10.0}}
    admit = {"event": "admit", "field": "queue_wait_s"}
    prefill = {"event": "prefill", "field": ["dispatch_s", "sync_s"]}
    assert request_events.value(rec, admit) is None
    base = telemetry._perf_base
    # an admission before the window, then two inside it
    for t_s, wait in ((1.0, 9.0), (25.0, 0.002), (29.0, 0.004)):
        telemetry.note_request_event(
            "t", "admit", t_ns=base + int(t_s * 1e9),
            args={"queue_wait_s": wait})
    telemetry.note_request_event(
        "t", "prefill", t_ns=base + int(29.5 * 1e9),
        args={"dispatch_s": 0.003, "sync_s": 0.2})
    telemetry.note_request_event("", "tokens", t_ns=base + int(30e9),
                                 args={"traces": []})
    assert request_events.value(rec, admit) == pytest.approx(3.0)
    assert request_events.value(rec, prefill) == pytest.approx(203.0)
    assert request_events.value(
        rec, {"event": "swap", "field": "dur_s"}) is None
    telemetry.reset()
