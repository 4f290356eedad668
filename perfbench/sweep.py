#!/usr/bin/env python3
"""perfbench/sweep.py -- the one-off rate sweep behind a fixed-rate cell.

    python3 perfbench/sweep.py --workload <cell> --base-rate <requests/s> [--seconds 30] [--multipliers 0.5,0.7,0.85,1.0,1.15]

One process, one engine (the cell's own, built as the ``serve`` runner
builds it), several rates in turn: the cell's traffic mix with its
arrival rate replaced by ``multiplier x base-rate``, ``--seconds`` each,
the engine drained between rates.  For each rate: completed tokens/s,
the tails, and the queue length in the middle and at the end of the
slice.  The knee is the highest rate at which the queue is no longer at
the end of its slice than in the middle; the cell's file then fixes
0.8 x that.  The benchmark itself never searches for a rate.
"""
import argparse
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)


def steady_population(rate, decode_s, prefill_s, slots, mean_out):
    """Requests in flight at ``rate`` by Little's law: each lives
    ``mean_out`` cycles of one decode step plus the prefills admitted
    ahead of it (rate x cycle of them), capped at the slots."""
    if rate * prefill_s >= 1:
        return slots
    cycle = decode_s / (1 - rate * prefill_s)
    return int(min(slots, max(1, round(rate * mean_out * cycle))))


def mean_output(mix, eng):
    import trafficgen
    return trafficgen.requests(mix, 0, 256, eng.page_size).mean_output()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--base-rate", type=float, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--multipliers", default="0.5,0.7,0.85,1.0,1.15")
    ap.add_argument("--decode-s", type=float, default=None,
                    help="seconds of one decode step, and")
    ap.add_argument("--prefill-s", type=float, default=None,
                    help="of one prefill: each rate then starts from the "
                    "population Little's law expects of it, not from the "
                    "mix's own")
    ap.add_argument("--seed", type=int, default=3000000001)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()

    import common
    from runners import serve
    _, entry, cell, config, mix = common.load_cell(args.workload, args.tiny)
    dev, want = common.require_platform(entry, args.tiny)
    common.place_compile_cache()
    config["vocab_real"] = config["vocab_size"] if args.tiny else 50257
    spans = common.Spans()
    ctx = types.SimpleNamespace(
        cell=cell, config=config, seed=args.seed, tiny=args.tiny,
        watch=common.Watch(want))
    eng, _net, _programs = serve.build_engine(ctx)
    off = common.TraceSlice(spans, None, 0, 0, False)
    rows = []
    for i, mult in enumerate(float(m) for m in args.multipliers.split(",")):
        rate = mult * args.base_rate
        m = dict(mix, arrivals={"process": "poisson", "rate_per_s": rate})
        if args.decode_s and args.prefill_s:
            m["initial_population"] = steady_population(
                rate, args.decode_s, args.prefill_s,
                eng.num_slots, mean_output(m, eng))
        w = serve.drive(eng, m, cell["runner_params"], args.seed + i,
                        args.seconds, config["vocab_real"], spans, off,
                        lambda t: None)
        row = {"multiplier": mult, "rate_per_s": rate,
               "tok_s": w["tokens"] / w["window_s"],
               "queued_mid": w["queued_mid"],
               "queued_close": w["queued_close"],
               "sustained": w["queued_close"] <= w["queued_mid"],
               "ttft_p95_ms": 1e3 * common.percentile(w["ttfts"], 95)
               if w["ttfts"] else None,
               "itl_p95_ms": 1e3 * common.percentile(w["gaps"], 95)
               if len(w["gaps"]) else None,
               "ttft_samples": len(w["ttfts"]),
               "initial_population": m["initial_population"],
               "failed": w["failed"], "attempted": w["attempted"]}
        rows.append(row)
        common.say("rate", **row)
        eng.run_until_idle()
    ok = [r["rate_per_s"] for r in rows if r["sustained"]]
    print(json.dumps({"sweep": rows, "knee_rate_per_s": max(ok, default=None),
                      "device": dev}), flush=True)


if __name__ == "__main__":
    main()
