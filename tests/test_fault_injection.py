"""Fault injection: kill a worker mid-training, restart from checkpoint.

SURVEY §5's failure-detection/recovery requirement, TPU-era semantics:
a died peer strands the survivors inside a collective, so recovery is
(1) the LAUNCHER detects the death and tears the job down
(tools/launch.py _run_local_once), then (2) restarts the whole job and
every worker resumes from the last complete checkpoint — the
checkpoint-restart model TPU pods use, vs the reference's parameter-
server heartbeat hooks (/root/reference/src/kvstore/kvstore_dist.h:59-62).

The worker below trains a deterministic MLP with dist_sync gradients,
checkpoints every epoch, and rank 1 SIGKILLs itself mid-epoch-3 on the
first attempt only.  Asserts: the relaunched job resumed from epoch 2
(not from scratch), re-ran epoch 3 to the same loss the doomed attempt
saw (continuity), and finished all 5 epochs with a decreasing loss.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = """
import json, os, sys
sys.path.insert(0, %(repo)r)
import numpy as np
import mxnet_tpu as mx

attempt = int(os.environ.get("MXTPU_RESTART_ATTEMPT", "0"))
rank = int(os.environ["MXTPU_WORKER_RANK"])
tmp = %(tmp)r
prefix = os.path.join(tmp, "ckpt")

kv = mx.kv.create("dist_sync")
assert kv.num_workers == 2

rng = np.random.RandomState(0)
X = rng.randn(64, 10).astype(np.float32)
W = rng.randn(10, 2).astype(np.float32)
Y = (X @ W).argmax(1).astype(np.float32)
# each worker sees half the data (deterministic split by rank)
Xw, Yw = X[rank::2], Y[rank::2]

data = mx.sym.Variable("data")
net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
net = mx.sym.Activation(net, act_type="relu")
net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
net = mx.sym.SoftmaxOutput(net, name="softmax", normalization="batch")

it = mx.io.NDArrayIter(Xw, Yw, batch_size=16)
mod = mx.mod.Module(net, context=mx.cpu())
mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)

# resume from the newest COMPLETE checkpoint, else fresh init — the
# manager's manifest-validated discovery skips torn/partial checkpoints
# a crash may have left behind
mgr = mx.CheckpointManager(prefix)
start_epoch = mgr.latest() or 0
if start_epoch:
    _, args, auxs = mgr.load(start_epoch)
    mod.init_params(arg_params=args, aux_params=auxs, allow_missing=False)
    if rank == 0:
        print("RESUMED from epoch %%d" %% start_epoch, flush=True)
else:
    mx.random.seed(7)
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", magnitude=2))
# normalization="batch" already divides by the local batch; the dist
# push sums the 2 workers' normalized grads, so 0.5 restores the mean
mod.init_optimizer(kvstore=kv, optimizer="sgd",
                   optimizer_params={"learning_rate": 0.5,
                                     "rescale_grad": 0.5})

log_path = os.path.join(tmp, "loss_rank%%d.jsonl" %% rank)
for epoch in range(start_epoch + 1, 9):
    it.reset()
    losses = []
    for i, batch in enumerate(it):
        mod.forward_backward(batch)
        out = mod.get_outputs()[0].asnumpy()
        lbl = batch.label[0].asnumpy().astype(int)
        losses.append(float(-np.log(np.maximum(
            out[np.arange(len(lbl)), lbl], 1e-8)).mean()))
        mod.update()
        if attempt == 0 and rank == 1 and epoch == 3 and i == 1:
            os.kill(os.getpid(), 9)        # die mid-epoch, after updates
    kv.barrier()
    if rank == 0:
        mod.save_checkpoint(prefix, epoch)
        with open(log_path, "a") as f:
            f.write(json.dumps({"attempt": attempt, "epoch": epoch,
                                "loss": float(np.mean(losses))}) + "\\n")
kv.barrier()
open(os.path.join(tmp, "done_%%d" %% rank), "w").write("1")
"""


@pytest.mark.slow
def test_kill_worker_restart_resumes(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER % {"repo": REPO, "tmp": str(tmp_path)})
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--cpu-fake-devices", "--max-restarts", "1",
         sys.executable, str(script)],
        env=env, capture_output=True, timeout=600)
    out = r.stdout.decode() + r.stderr.decode()
    assert r.returncode == 0, out[-3000:]
    # the launcher saw the kill and restarted
    assert "terminating remaining workers" in out
    assert "restarting job from checkpoints" in out
    # the resumed attempt started from the epoch-2 checkpoint
    assert "RESUMED from epoch 2" in out
    # both workers finished
    assert (tmp_path / "done_0").exists() and (tmp_path / "done_1").exists()

    records = [json.loads(l) for l in
               (tmp_path / "loss_rank0.jsonl").read_text().splitlines()]
    by_attempt = {}
    for rec in records:
        by_attempt.setdefault(rec["attempt"], {})[rec["epoch"]] = rec["loss"]
    # attempt 0 completed epochs 1 and 2 before the kill
    assert set(by_attempt[0]) == {1, 2}
    # attempt 1 resumed at epoch 3 and ran to 8
    assert set(by_attempt[1]) == {3, 4, 5, 6, 7, 8}
    # continuity: resumed epoch-3 loss continues the curve (below epoch 2)
    assert by_attempt[1][3] < by_attempt[0][2]
    # training converged across the restart
    assert by_attempt[1][8] < by_attempt[0][1]
    assert by_attempt[1][8] < 0.5, by_attempt


# -- guarded fused step + torn checkpoint, end to end -----------------------
#
# The PR-2 acceptance scenario: with fault.py injecting a torn final-epoch
# checkpoint (rank 0's epoch-4 save "crashes" mid-write, leaving a
# truncated .params at the final path) and a 10%-rate NaN gradient, a
# 2-worker launch_local --max-restarts run still completes: recovery picks
# the last COMPLETE checkpoint (epoch 3, not the torn 4), the divergence
# guard absorbs the NaN batches (skipped_steps > 0, params untouched on
# those steps), loss keeps decreasing across the restart, and the guarded
# fused path still dispatches exactly ONE XLA program per step.

GUARDED_WORKER = """
import json, os, sys, time
sys.path.insert(0, %(repo)r)
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import fault, profiler

attempt = int(os.environ.get("MXTPU_RESTART_ATTEMPT", "0"))
rank = int(os.environ["MXTPU_WORKER_RANK"])
assert os.environ["MXTPU_NUM_WORKERS"] == "2"
tmp = %(tmp)r
prefix = os.path.join(tmp, "ckpt")

# file-based 2-rank barrier: each replica trains the fused NO-kvstore
# path (the guarded single-dispatch program under test), so the only
# cross-rank coordination needed is save/resume ordering.  A rank dying
# mid-epoch leaves its peer waiting here — the launcher detects the death
# and tears the job down, exactly like a stranded collective.
def barrier(tag):
    open(os.path.join(tmp, "sync_%%s_%%d_%%d" %% (tag, attempt, rank)),
         "w").write("1")
    other = os.path.join(tmp, "sync_%%s_%%d_%%d" %% (tag, attempt, 1 - rank))
    while not os.path.exists(other):
        time.sleep(0.01)

rng = np.random.RandomState(0)
X = rng.randn(64, 10).astype(np.float32)
W = rng.randn(10, 2).astype(np.float32)
Y = (X @ W).argmax(1).astype(np.float32)

data = mx.sym.Variable("data")
net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
net = mx.sym.Activation(net, act_type="relu")
net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
net = mx.sym.SoftmaxOutput(net, name="softmax")

it = mx.io.NDArrayIter(X, Y, batch_size=16)
mod = mx.mod.Module(net, context=mx.cpu())
mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)

mgr = mx.CheckpointManager(prefix)
start_epoch = mgr.latest() or 0
if start_epoch:
    _, args, auxs = mgr.load(start_epoch)
    mod.init_params(arg_params=args, aux_params=auxs, allow_missing=False)
    if rank == 0:
        print("RESUMED from epoch %%d" %% start_epoch, flush=True)
else:
    mx.random.seed(7)
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", magnitude=2))
mod.init_optimizer(kvstore=None, optimizer="sgd",
                   optimizer_params={"learning_rate": 0.5})

profiler.reset_step_stats()
n_steps = 0
log_path = os.path.join(tmp, "loss_rank%%d.jsonl" %% rank)
for epoch in range(start_epoch + 1, 7):
    it.reset()
    losses = []
    for batch in it:
        mod.fit_step(batch)          # guarded fused: ONE dispatch/step
        n_steps += 1
        out = mod.get_outputs()[0].asnumpy()
        lbl = batch.label[0].asnumpy().astype(int)
        losses.append(float(-np.log(np.maximum(
            out[np.arange(len(lbl)), lbl], 1e-8)).mean()))
    barrier("pre_save_%%d" %% epoch)
    if rank == 0:
        if attempt == 0 and epoch == 4:
            # tear THIS save: truncated .params lands at the final path,
            # then FaultInjected stands in for the crash (grad.nan stays
            # live for the run via the env spec on the restarted attempt)
            fault.configure("ckpt.write.torn:1")
        mod.save_checkpoint(prefix, epoch)
        with open(log_path, "a") as f:
            f.write(json.dumps({"attempt": attempt, "epoch": epoch,
                                "loss": float(np.mean(losses))}) + "\\n")
    barrier("post_save_%%d" %% epoch)

st = profiler.step_stats()
assert st["dispatch_count"] == n_steps, (st, n_steps)
if rank == 0:
    with open(os.path.join(tmp, "stats_%%d.json" %% attempt), "w") as f:
        json.dump({"steps": n_steps,
                   "dispatch_count": st["dispatch_count"],
                   "skipped_steps": st["skipped_steps"]}, f)
barrier("finish")
open(os.path.join(tmp, "done_%%d" %% rank), "w").write("1")
"""


@pytest.mark.slow
@pytest.mark.fault
def test_torn_ckpt_and_nan_grads_guarded_run_completes(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(GUARDED_WORKER % {"repo": REPO, "tmp": str(tmp_path)})
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXTPU_FAULT"] = "grad.nan:0.1"   # every rank, every attempt
    env["MXTPU_FAULT_SEED"] = "0"         # same skip pattern on all ranks
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--cpu-fake-devices", "--max-restarts", "1",
         "--restart-backoff", "0.1",
         sys.executable, str(script)],
        env=env, capture_output=True, timeout=600)
    out = r.stdout.decode() + r.stderr.decode()
    assert r.returncode == 0, out[-3000:]
    # the torn save crashed rank 0; the launcher classified it retryable,
    # backed off, and restarted the job
    assert "terminating remaining workers" in out
    assert "classified retryable" in out
    assert "restarting job from checkpoints" in out
    # recovery skipped the torn epoch-4 checkpoint (it IS on disk at the
    # final path) and resumed from the last complete one
    assert (tmp_path / "ckpt-0004.params").exists()
    assert "RESUMED from epoch 3" in out
    assert (tmp_path / "done_0").exists() and (tmp_path / "done_1").exists()

    # the guard absorbed NaN batches without costing extra dispatches
    stats = json.loads((tmp_path / "stats_1.json").read_text())
    assert stats["skipped_steps"] > 0, stats
    assert stats["dispatch_count"] == stats["steps"], stats

    records = [json.loads(l) for l in
               (tmp_path / "loss_rank0.jsonl").read_text().splitlines()]
    by_attempt = {}
    for rec in records:
        by_attempt.setdefault(rec["attempt"], {})[rec["epoch"]] = rec["loss"]
    assert set(by_attempt[0]) == {1, 2, 3}          # epoch 4 save died
    assert set(by_attempt[1]) == {4, 5, 6}          # resumed after 3
    # training still converges through skips + restart
    assert by_attempt[1][6] < by_attempt[0][1], by_attempt