#!/usr/bin/env python3
"""perfbench/sets.py -- several runs of cells in one call, and their spread.

    python3 perfbench/sets.py <tag> <cell>:<runs>:<trace>[:<seconds>] ...

Runs each cell ``runs`` times, one new process per run (this parent never
touches JAX, so each child has the chip to itself), seeds
3000000001, 3000000002, ... in every set so that two sets share seeds.
Every run's last line goes to ``chiprun_out/<tag>.jsonl`` and its whole
output to ``chiprun_out/<tag>.<cell>.<i>.log``; the summary gives, per
metric, the median and the spread the contract bounds by: the distance
between the first and third quartile (``statistics.quantiles(n=4)``) as
a share of the median, leaving out each cell's first run for ``setup_s``
(it compiles).
"""
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv):
    tag, specs = argv[0], argv[1:]
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    summary = {}
    with open(os.path.join(out_dir, tag + ".jsonl"), "a") as sink:
        for spec in specs:
            cell, runs, trace, *rest = spec.split(":")
            seconds = rest[0] if rest else str(bench["run_seconds"])
            rows = []
            for i in range(int(runs)):
                cmd = bench["command"] + [
                    "--workload", cell, "--seed", str(3000000001 + i),
                    "--seconds", seconds, "--trace", trace]
                t0 = time.time()
                p = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                                   text=True)
                log = os.path.join(out_dir, "%s.%s.%d.log" % (tag, cell, i))
                with open(log, "w") as f:
                    f.write(p.stdout + "\n--- stderr ---\n" + p.stderr[-20000:])
                lines = p.stdout.strip().splitlines()
                last = lines[-1] if lines else ""
                row = {"cell": cell, "run": i, "rc": p.returncode,
                       "wall_s": time.time() - t0, "trace": int(trace)}
                try:
                    row["result"] = json.loads(last)
                except ValueError:
                    row["result"] = None
                    row["tail"] = (p.stdout[-1500:] + p.stderr[-3000:])
                sink.write(json.dumps(row) + "\n")
                sink.flush()
                print(json.dumps(row)[:3000], flush=True)
                rows.append(row)
            summary[cell + ":" + trace] = spread(rows)
    print(json.dumps({"summary": summary}, indent=1))


def spread(rows):
    by = {}
    for r in rows:
        for name, m in ((r["result"] or {}).get("metrics") or {}).items():
            by.setdefault(name, []).append(m["value"])
    out = {}
    for name, vals in by.items():
        if name == "setup_s":
            out["setup_s.first"] = vals[0]
            vals = vals[1:]
        vals = [v for v in vals if v is not None]
        if not vals:
            continue
        doc = {"n": len(vals), "median": statistics.median(vals),
               "values": vals}
        if len(vals) >= 2 and doc["median"]:
            q = statistics.quantiles(vals, n=4)
            doc["iqr_share"] = (q[2] - q[0]) / abs(doc["median"])
        out[name] = doc
    return out


if __name__ == "__main__":
    main(sys.argv[1:])
