"""The continual train-to-serve loop, end to end (ISSUE 12 tentpole):

a 2-worker ``tools/launch.py --elastic`` job fine-tunes a small GPT from
an APPENDING shard stream (follow-mode StreamLoader), async-checkpoints
on a generation cadence (cursor snapshots + publications through one
CheckpointManager prefix), while THIS test process keeps a
ServingReplica alive on the same prefix, hot-swapping each publication.
Mid-stream, one rank hard-dies (worker.lost, exit 77): the launcher
evicts it, the survivor resumes from the newest COMPLETE cursor
generation + its paired checkpoint, and the stream is re-partitioned at
the new world size.  Assertions:

- **exact-once effective coverage** by id-set union: the records each
  attempt trained *up to the generation its successor resumed from*,
  plus everything the final attempt trained, is every record exactly
  once — replayed work after a rollback is discarded by construction;
- **serving stays up** across the whole membership arc and hot-swaps
  >= 2 publications (canary-verified), with bit-identical greedy
  tokens across an unchanged-weights publication.

Processes run under ``timeout -k`` (the hang suite's rule).
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAUNCH = os.path.join(REPO, "tools", "launch.py")

VOCAB, SEQ, BATCH, GEN_BATCHES = 16, 8, 4, 3
SHARD_RECORDS = 24
GPT_KW = "dict(vocab_size=%d, num_layers=1, units=16, num_heads=2, " \
         "max_len=%d, prefix='cts_')" % (VOCAB, SEQ + 8)


WORKER = """
import json, os, sys
sys.path.insert(0, %(repo)r)
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import autograd, elastic, fault, gluon, stream
from mxnet_tpu.checkpoint import CheckpointManager, flush_async
from mxnet_tpu.gluon.model_zoo import gpt

OUT = sys.argv[1]
VOCAB, SEQ, BATCH, GEN_BATCHES = %(vocab)d, %(seq)d, %(batch)d, %(genb)d
mem = elastic.membership()
rank, world = mem["rank"], mem["world_size"]
slot, attempt = mem["slot"], mem["attempt"]

np.random.seed(0)
mx.random.seed(0)
net = gpt.GPTLM(**%(gpt_kw)s)
net.initialize(mx.init.Xavier())

prefix = os.path.join(OUT, "ck", "model")
os.makedirs(os.path.dirname(prefix), exist_ok=True)
mgr = CheckpointManager(prefix)
cs = stream.CursorStore(os.path.join(OUT, "ck"))

# resume: the newest COMPLETE cursor generation that also has its
# paired checkpoint committed (rank 0 publishes ckpt epoch g with
# cursor generation g under one barrier cadence)
g, _ = cs.load_latest()
ck = mgr.latest()
start_gen = min(g or 0, ck or 0)
resume_cursors = cs.load(start_gen) if start_gen > 0 else None
if start_gen > 0:
    _, args_, _ = mgr.load(start_gen)
    params = net.collect_params()
    for name, val in args_.items():
        params[name].set_data(val)
with open(os.path.join(OUT, "resume-a%%d-r%%d.json" %% (attempt, rank)),
          "w") as f:
    json.dump({"gen": start_gen, "world": world, "slot": slot}, f)

ss = stream.load_shard_set(os.path.join(OUT, "ss"))


def decode(raw):
    arr = np.frombuffer(raw, np.int32)
    return arr[1:], arr[0]   # (tokens, record id)


ld = stream.StreamLoader(ss, BATCH, decode_fn=decode, mode="follow",
                         prefetch=0, poll_secs=0.1,
                         resume=resume_cursors)
trainer = gluon.Trainer(net.collect_params(), "sgd",
                        {"learning_rate": 0.02})
ce = gluon.loss.SoftmaxCrossEntropyLoss(axis=-1)


def barrier(name):
    try:
        from jax._src.distributed import global_state
        client = global_state.client
    except Exception:
        client = None
    if client is not None and world > 1:
        client.wait_at_barrier("%%s-a%%d" %% (name, attempt), 60000)


def publish(gen):
    # everyone's cursor first (the consistent snapshot), then rank 0's
    # checkpoint — the manager stamps this rank's cursor into the
    # manifest too (the single-rank view; CursorStore is the job one)
    cs.save(gen, ld.cursor())
    with open(os.path.join(OUT, "ids-a%%d-r%%d-g%%03d.json"
                           %% (attempt, rank, gen)), "w") as f:
        json.dump({"gen": gen, "ids": bucket}, f)
    del bucket[:]
    if rank == 0:
        mgr.save(gen, {p.name: p.data().copy()
                       for p in net.collect_params().values()}, {},
                 stream_cursor=ld.cursor())
        flush_async()


gen = start_gen
batch_n = 0
bucket = []
for b in iter(ld):
    toks, ids = b
    with autograd.record():
        # a real (bounded) next-token fine-tune objective — an
        # unbounded toy loss diverges in a few dozen steps and the
        # serving canary would (rightly) reject the weights
        logits = net(toks.slice_axis(axis=1, begin=0, end=SEQ - 1))
        labels = toks.slice_axis(axis=1, begin=1, end=SEQ)
        loss = ce(logits, labels).mean()
    loss.backward()
    trainer.step(toks.shape[0])
    bucket.extend(int(i) for i in ids.asnumpy().ravel())
    batch_n += 1
    # deterministic mid-stream death: slot 1, attempt 0, one batch
    # into generation 2 (generation 1 is complete, so resume has a
    # consistent snapshot and serving already saw one publication)
    if slot == 1 and attempt == 0 and batch_n == GEN_BATCHES + 1:
        fault.configure("worker.lost:1")
        fault.exit_if("worker.lost")
    if batch_n %% GEN_BATCHES == 0:
        gen += 1
        barrier("gen-%%d-pre" %% gen)
        publish(gen)
        barrier("gen-%%d-post" %% gen)

# stream sealed and exhausted: flush the tail bucket + one final
# publication (the serving side's last swap target)
with open(os.path.join(OUT, "ids-a%%d-r%%d-gend.json"
                       %% (attempt, rank)), "w") as f:
    json.dump({"gen": "end", "ids": bucket}, f)
del bucket[:]
barrier("final")
if rank == 0:
    mgr.save(gen + 1, {p.name: p.data().copy()
                       for p in net.collect_params().values()}, {},
             stream_cursor=ld.cursor())
    flush_async()
    with open(os.path.join(OUT, "done-r0.json"), "w") as f:
        json.dump({"attempt": attempt, "world": world,
                   "final_gen": gen + 1}, f)
ld.close()
"""


def _records(ids, rng):
    out = []
    for i in ids:
        toks = rng.randint(0, VOCAB, (SEQ,)).astype(np.int32)
        out.append(np.concatenate([[np.int32(i)], toks])
                   .astype(np.int32).tobytes())
    return out


@pytest.mark.slow
@pytest.mark.stream
@pytest.mark.elastic
@pytest.mark.serving
def test_continual_train_to_serve_loop(tmp_path):
    from mxnet_tpu import stream

    rng = np.random.RandomState(0)
    out = str(tmp_path)
    w = stream.ShardSetWriter(os.path.join(out, "ss"))
    next_id = 0
    for _ in range(3):  # the initial stream: 3 shards x 24 records
        w.write_recordio_shard(
            _records(range(next_id, next_id + SHARD_RECORDS), rng))
        next_id += SHARD_RECORDS

    script = tmp_path / "worker.py"
    script.write_text(WORKER % {
        "repo": REPO, "vocab": VOCAB, "seq": SEQ, "batch": BATCH,
        "genb": GEN_BATCHES, "gpt_kw": GPT_KW})
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXTPU_ASYNC_CKPT"] = "1"   # the async-cadence publication path
    run_dir = tmp_path / "run"
    train = subprocess.Popen(
        ["timeout", "-k", "10", "420",
         sys.executable, LAUNCH, "-n", "2", "--elastic",
         "--cpu-fake-devices", "--evict-after", "1",
         "--readmit-after", "99", "--max-restarts", "4",
         "--restart-backoff", "0.01", "--run-dir", str(run_dir),
         # this drill asserts the continual data/serving loop, not AOT
         # warm-start — and the shared cross-attempt executable cache
         # rides the known CPU-jaxlib donated-deserialize hazard
         # (ROBUSTNESS.md §8), whose probabilistic heap corruption
         # would flake THIS test about a different subsystem
         "--aot-cache-dir", "off",
         "--", sys.executable, str(script), out],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    # THE SERVING PLANE, in its own clean process (the serving_driver
    # pallas pattern): a replica on the same publication prefix for the
    # whole run — hot-swapping every checkpoint the live trainer
    # publishes, serving greedy requests throughout, growing + sealing
    # the stream once training is demonstrably under way
    serve = subprocess.Popen(
        ["timeout", "-k", "10", "440", sys.executable,
         os.path.join(REPO, "tests", "stream_e2e_driver.py"), out],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    try:
        t_out, t_err = train.communicate(timeout=440)
        s_out, s_err = serve.communicate(timeout=460)
    except Exception:
        train.kill()
        serve.kill()
        raise
    assert train.returncode == 0, (t_out[-2000:], t_err[-4000:])
    assert serve.returncode == 0, (s_out[-2000:], s_err[-4000:])
    assert "STREAM_SERVING_OK" in s_out, s_out[-2000:]

    total = json.loads(
        (tmp_path / "appended.json").read_text())["total_records"]
    assert total == 5 * SHARD_RECORDS

    # -- the elastic arc: slot 1 died mid-stream and was evicted ------------
    mem = json.loads((run_dir / "membership.json").read_text())
    events = [(t["event"], t.get("slot")) for t in mem["transitions"]]
    assert ("failure", 1) in events and ("evict", 1) in events
    last = mem["transitions"][-1]
    assert last["event"] == "complete" and last["world_size"] == 1
    done = json.loads((tmp_path / "done-r0.json").read_text())
    assert done["world"] == 1

    # -- exact-once effective coverage by id-set union ----------------------
    # effective history: each attempt counts only the generations its
    # successor resumed AT OR BEFORE (later work was rolled back with
    # the checkpoint and replayed); the last attempt counts everything
    # it trained, tail bucket included.
    resumes = {}
    for p in tmp_path.glob("resume-a*-r*.json"):
        a = int(p.stem.split("-")[1][1:])
        resumes[a] = json.loads(p.read_text())["gen"]
    attempts = sorted(resumes)
    assert len(attempts) >= 2, "no restart happened"
    assert resumes[attempts[0]] == 0          # attempt 0 started fresh
    assert resumes[attempts[-1]] >= 1, \
        "the final attempt did not resume from a cursor generation"
    effective = []
    for a in attempts:
        nxt = [b for b in attempts if b > a]
        cutoff = resumes[nxt[0]] if nxt else None
        for p in tmp_path.glob("ids-a%d-r*-g*.json" % a):
            doc = json.loads(p.read_text())
            if cutoff is None or (doc["gen"] != "end"
                                  and doc["gen"] <= cutoff):
                effective.extend(doc["ids"])
    assert sorted(effective) == list(range(total)), (
        "effective coverage is not exactly-once: %d trained ids, %d "
        "unique, %d expected"
        % (len(effective), len(set(effective)), total))

    # -- serving-plane report: >=2 hot-swaps, in-run service ----------------
    rep = json.loads((tmp_path / "serving-report.json").read_text())
    assert len(rep["applied"]) >= 2 and rep["swaps"] >= 2
    assert rep["served"] >= 1
    assert rep["final_gen"] == done["final_gen"]
