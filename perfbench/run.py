#!/usr/bin/env python3
"""perfbench/run.py -- run ONE cell of BENCHMARK.json once, in this process.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found BY NAME from ``BENCHMARK.json``: its
configuration file, ``workloads/<cell>.json`` (runner and its parameters),
``traffic/<traffic>.json`` (the mix the one generator reads),
``runners/<runner>.py`` and, for a traced run, ``layer_metrics/<metric>.json``
with ``readers/<reader>.py``.  This file holds no cell, model, mix or
metric name; adding any of them is adding files (perfbench/README.md).

The last line of standard output is the one JSON object of the contract:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``.  Without a TPU (or with fewer chips than the cell asks) the
run fails with a non-zero exit and prints no result.  ``--tiny`` is the CPU
rehearsal of the same control flow at the files' ``tiny`` sizes: it says
``"platform": "cpu"`` and every metric is ``null``.
"""
import time
T_START = time.perf_counter()

import argparse
import importlib
import json
import os
import shutil
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)


def applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the traced run's .xplane.pb under "
                    "perfbench_out/<cell>/trace for reading by hand")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at the files' tiny sizes")
    args = ap.parse_args()

    import common
    bench, entry, cell, config, traffic = common.load_cell(
        args.workload, args.tiny)
    seconds = args.seconds if args.seconds is not None \
        else float(bench["run_seconds"])
    dev, want = common.require_platform(entry, args.tiny)
    import jax

    cache_dir = common.place_compile_cache()
    watch = common.Watch(want)
    out_dir = os.path.join(ROOT, "perfbench_out", entry["name"])
    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    spans = common.Spans()
    tr = cell.get("trace_slice", {})
    ctx = types.SimpleNamespace(
        chips=entry["chips"], cell=cell, config=config,
        traffic=traffic, seed=args.seed, seconds=seconds, tiny=args.tiny,
        watch=watch, spans=spans, setup_s=None,
        slice=common.TraceSlice(
            spans, trace_dir, min(tr.get("start_s", 2.0), seconds / 4),
            min(tr.get("length_s", 5.0), seconds / 2), bool(args.trace)))

    def opened(t_open):
        ctx.setup_s = t_open - T_START
        ctx.misses_at_open = watch.cache["misses"]
        ctx.compiles_at_open = watch.compiles
    ctx.opened = opened
    common.say("start", cell=entry["name"], device=dev, seed=args.seed,
               seconds=seconds, trace=args.trace, jax=jax.__version__,
               compile_cache_dir=cache_dir)

    runner = importlib.import_module("runners." + cell["runner"])
    rec = runner.run(ctx)
    rec["end_to_end"]["setup_s"] = ctx.setup_s
    rec["counters"]["cache_misses"] = ctx.misses_at_open
    common.say("setup", setup_s=ctx.setup_s, compile_cache=dict(watch.cache),
               compile_cache_mb=common.dir_megabytes(cache_dir),
               programs=rec["programs"], memory=watch.memory())

    dev["memory_peak_bytes"] = common.memory_peak_bytes(
        rec["programs"], entry["chips"])
    result = {"correct": rec["correct"], "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": {}, "device": dev}
    if rec.get("why_not_correct"):
        common.say("not_correct", **rec["why_not_correct"])

    if args.trace:
        import trace_reduce
        path = ctx.slice.file()
        if path is None:
            raise SystemExit("perfbench: the traced run left no trace file")
        rec["trace"] = trace_reduce.reduce_file(
            path, entry["chips"], rec.get("span_records"))
        dev["busy_s"] = rec["trace"]["busy_s"]
        dev["window_s"] = rec["trace"]["window_s"]
        result["breakdown"] = {
            "device_ops": rec["trace"]["device_ops"][:10],
            "idle_gaps": rec["trace"]["idle_gaps"][:10]}
        rec["peaks"] = None if args.tiny else common.peaks_for(dev["kind"])
        rec["config"] = config
        for m in bench["per_layer"]:
            if not applies(m, entry["name"]):
                continue
            spec = common.load_json(HERE, "layer_metrics",
                                    m["name"] + ".json")
            reader = importlib.import_module("readers." + spec["reader"])
            value = reader.value(rec, spec.get("args", {}))
            if value is not None:
                result["metrics"][m["name"]] = {
                    "value": None if args.tiny else value,
                    "unit": m["unit"]}
        if not args.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        for m in bench["end_to_end"]:
            if applies(m, entry["name"]) and m["name"] in rec["end_to_end"]:
                result["metrics"][m["name"]] = {
                    "value": None if args.tiny
                    else rec["end_to_end"][m["name"]],
                    "unit": m["unit"]}
    sys.stdout.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
