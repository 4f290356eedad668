"""Serving-plane driver for the continual train-to-serve e2e
(tests/test_stream_e2e.py), run in a process of its own (the
serving_driver.py pattern) alongside the ``tools/launch.py --elastic``
training job:

- keeps one ServingReplica alive on the trainer's CheckpointManager
  prefix for the WHOLE run, hot-swapping every publication between
  decode steps and serving real greedy requests throughout;
- plays the stream WRITER: once the first publication lands (the job is
  demonstrably training), appends two more shards and seals the stream
  — the workers are consuming a live, growing shard set;
- after the job's final publication, re-publishes the same weights
  unchanged and asserts the swap is bit-invisible to greedy decode.

Usage: python stream_e2e_driver.py OUT_DIR

Writes ``OUT_DIR/serving-report.json`` and prints STREAM_SERVING_OK on
success; any assertion failure exits nonzero with the traceback.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import stream  # noqa: E402
from mxnet_tpu.checkpoint import CheckpointManager  # noqa: E402
from mxnet_tpu.gluon.model_zoo import gpt  # noqa: E402
from mxnet_tpu.serving import (CheckpointSubscriber, ServingEngine,  # noqa: E402
                               ServingReplica)

VOCAB, SEQ, SHARD_RECORDS = 16, 8, 24


def _records(ids, rng):
    out = []
    for i in ids:
        toks = rng.randint(0, VOCAB, (SEQ,)).astype(np.int32)
        out.append(np.concatenate([[np.int32(i)], toks])
                   .astype(np.int32).tobytes())
    return out


def main(out):
    rng = np.random.RandomState(1)
    prefix = os.path.join(out, "ck", "model")
    srv = gpt.GPTLM(VOCAB, 1, 16, 2, max_len=SEQ + 8, prefix="cts_")
    srv.initialize(mx.init.Xavier())
    eng = ServingEngine(srv, num_slots=2, page_size=8,
                        max_prefill_len=8, max_seq_len=16)
    sub = CheckpointSubscriber(prefix, srv)
    rep = ServingReplica(eng, replica_id="cts", subscriber=sub,
                         swap_poll_steps=1)
    probe = rng.randint(0, VOCAB, (5,)).astype(np.int32)

    applied = []
    served = 0
    appended = False
    next_id = 3 * SHARD_RECORDS  # the test wrote shards 0..2
    deadline = time.time() + 400
    done_path = os.path.join(out, "done-r0.json")
    while time.time() < deadline:
        e = rep.maybe_swap()
        if e is not None:
            applied.append(e)
        if sub.applied_epoch is not None and served < 8:
            # the replica actually SERVES while the trainer runs
            r = rep.submit(probe, 2)
            while not r.done:
                rep.step()
            assert r.verdict == "completed", (r.state, r.verdict)
            served += 1
        if not appended and CheckpointManager(prefix).latest():
            # first publication landed: the stream GROWS mid-job, then
            # seals — the workers consume a live, growing shard set
            w = stream.ShardSetWriter(os.path.join(out, "ss"))
            for _ in range(2):
                w.write_recordio_shard(_records(
                    range(next_id, next_id + SHARD_RECORDS), rng))
                next_id += SHARD_RECORDS
            w.seal()
            appended = True
            with open(os.path.join(out, "appended.json"), "w") as f:
                json.dump({"total_records": next_id}, f)
        if os.path.exists(done_path):
            break
        time.sleep(0.1)
    assert appended, "the stream never grew — no publication appeared"
    assert os.path.exists(done_path), "training job never finished"
    done = json.load(open(done_path))

    # serving stayed up across the whole membership arc
    assert rep.alive
    assert served >= 1, "the replica never completed a request in-run"
    assert applied, "no publication was hot-swapped during the run"

    # catch up to the final publication...
    for _ in range(20):
        e = rep.maybe_swap()
        if e is not None:
            applied.append(e)
        if sub.applied_epoch == done["final_gen"]:
            break
        time.sleep(0.1)
    mgr = CheckpointManager(prefix)
    assert sub.applied_epoch == done["final_gen"] == mgr.latest(), (
        "applied=%s seen=%s final_gen=%s latest=%s applied_list=%s"
        % (sub.applied_epoch, sub.seen_epoch, done["final_gen"],
           mgr.latest(), applied))
    tokens_before = eng.generate([probe], 4)

    # ...then the unchanged-weights law: a bit-identical re-publication
    # must be invisible to greedy decode (canary-verified swap)
    _, args_, _ = mgr.load(done["final_gen"])
    mgr.save(done["final_gen"] + 1,
             {k: mx.nd.array(v.asnumpy()) for k, v in args_.items()},
             {}, mode="sync")
    e = rep.maybe_swap()
    assert e == done["final_gen"] + 1, e
    applied.append(e)
    tokens_after = eng.generate([probe], 4)
    assert tokens_after == tokens_before, (
        "unchanged-weights hot-swap perturbed greedy tokens")
    assert len(applied) >= 2 and eng.swaps >= 2, (applied, eng.swaps)

    # the trainer's manifests carry the stream-cursor stamp
    info = mgr.manifest_info(done["final_gen"])
    assert info and info.get("stream_cursor", {}).get("mode") == "follow"

    with open(os.path.join(out, "serving-report.json"), "w") as f:
        json.dump({"applied": applied, "served": served,
                   "swaps": eng.swaps,
                   "final_gen": done["final_gen"]}, f)
    print("STREAM_SERVING_OK applied=%d served=%d swaps=%d"
          % (len(applied), served, eng.swaps))


if __name__ == "__main__":
    main(sys.argv[1])
