"""Hang defense: worker watchdog, launcher heartbeats, guarded bring-up.

Every detection path is driven end to end through fault-injected HANGS
(``mxnet_tpu.fault`` ``*.stall``/``kv.hang`` sites sleep without
renewing any lease) and asserted on the full contract: exit code 75
(EX_TEMPFAIL), all-thread stack dump, flight-recorder postmortem naming
the wedged lease, and launcher classification ``retryable: stall``.

Guard rail (the ``hang`` marker's contract, pytest.ini): every process
spawned here runs under a ``timeout -k`` wrapper *inside the test*, so a
detection regression fails an assertion instead of wedging the tier-1
suite.  The multi-process stall-restart integration lives at the bottom
under the ``slow`` marker.
"""
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import fault, watchdog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAUNCH = os.path.join(REPO, "tools", "launch.py")

# inline module-training preamble shared by the stall worker scripts
_PREAMBLE = """
import os, sys, time
sys.path.insert(0, %(repo)r)
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import fault

def make_module():
    rs = np.random.RandomState(0)
    X = rs.randn(64, 10).astype(np.float32)
    Y = rs.randint(0, 2, 64).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=16)
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=8,
                              name="fc1"), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    return mod, list(it)
""" % {"repo": REPO}


@pytest.fixture(autouse=True)
def _clean_faults():
    fault.reset()
    watchdog.disarm()  # clears leases other tests' renewals left behind
    yield
    fault.reset()
    watchdog.disarm()


def _run_guarded(script, env_extra, budget=120):
    """Run a python script under ``timeout -k`` (the hang-marker guard:
    a detection regression exits 124/137 here, never wedges pytest)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra)
    return subprocess.run(
        ["timeout", "-k", "10", str(budget), sys.executable, "-c",
         script], env=env, capture_output=True, timeout=budget + 30)


def _stall_artifacts(pm_dir):
    """(postmortem_doc, stacks_text) dumped by the stalled worker."""
    pms = [f for f in os.listdir(pm_dir) if f.startswith("postmortem-")]
    stacks = [f for f in os.listdir(pm_dir)
              if f.startswith("stall-stacks-")]
    assert pms, "no postmortem dumped in %s" % pm_dir
    assert stacks, "no stack dump in %s" % pm_dir
    with open(os.path.join(pm_dir, pms[0])) as f:
        doc = json.load(f)
    with open(os.path.join(pm_dir, stacks[0])) as f:
        text = f.read()
    return doc, text


# -- in-process watchdog unit behaviour (test hook, no hard exits) ----------

def _wait_for(pred, budget=15.0):
    t0 = time.time()
    while not pred() and time.time() - t0 < budget:
        time.sleep(0.02)
    return pred()


def test_watchdog_lease_expiry_and_renewal():
    events = []
    assert watchdog.arm(timeout=0.3, grace=5.0,
                        on_stall=lambda *a: events.append(a))
    assert not watchdog.arm(timeout=0.3)  # idempotent while armed
    watchdog.renew("x")
    assert _wait_for(lambda: events)
    name, age, limit = events[0]
    assert name == "x" and age > limit
    watchdog.disarm()
    assert not watchdog.armed()

    # renewal keeps a lease alive (generous margins: CI boxes stall
    # innocent sleeps under load)
    events2 = []
    watchdog.arm(timeout=30.0, grace=60.0,
                 on_stall=lambda *a: events2.append(a))
    for _ in range(5):
        watchdog.renew("y")
        time.sleep(0.02)
    assert not events2
    watchdog.release("y")
    # scoped guard: expiry inside the block is a stall naming the guard
    with watchdog.guard("blocked.op", timeout=0.3):
        assert _wait_for(lambda: events2)
    assert events2[0][0] == "blocked.op"
    watchdog.disarm()


def test_watchdog_startup_grace_covers_first_step():
    """No lease ever renewed + grace expired = 'first step never
    completed' — its own stall class (wedged bring-up / compile)."""
    events = []
    watchdog.arm(timeout=300.0, grace=0.2,
                 on_stall=lambda *a: events.append(a))
    assert _wait_for(lambda: events)
    assert events[0][0] == "startup"
    watchdog.disarm()


def test_watchdog_grace_extends_leases_until_first_renewal():
    """A lease alive before the first renewal (prefetched data while the
    first step compiles) runs on the GRACE budget, not the steady-state
    timeout; and after any progress an empty lease table means idle,
    never a stall."""
    events = []
    watchdog.arm(timeout=0.2, grace=30.0,
                 on_stall=lambda *a: events.append(a))
    with watchdog.guard("warmup.op"):      # held well past the timeout
        # an auxiliary (data) renewal — batch 1 delivered pre-compile —
        # must NOT end the grace window
        watchdog.renew("data", primary=False)
        time.sleep(0.8)
        assert not events, events          # grace governs pre-progress
        watchdog.renew("fit_step")         # first STEP = first progress
    watchdog.release("fit_step")
    watchdog.release("data")
    time.sleep(0.8)                        # idle, zero leases
    assert not events, events              # idle-after-progress ≠ stall
    watchdog.disarm()


def test_watchdog_not_armed_without_env(monkeypatch):
    monkeypatch.delenv("MXTPU_STALL_TIMEOUT", raising=False)
    assert not watchdog.maybe_arm()
    assert not watchdog.armed()
    # renew/guard stay no-ops re: arming — zero risk to non-opted runs
    watchdog.renew("z")
    with watchdog.guard("w"):
        pass
    assert not watchdog.armed()
    watchdog.release("z")


def test_heartbeat_file_step_and_phase(tmp_path):
    p = watchdog.start_heartbeat(str(tmp_path), rank=7, interval=0.05)
    try:
        assert _wait_for(lambda: os.path.exists(p))
        watchdog.renew("fit_step", step=41, phase="train")
        assert _wait_for(
            lambda: json.load(open(p)).get("step") == 41)
        doc = json.load(open(p))
        assert doc["rank"] == "7" and doc["pid"] == os.getpid()
        assert doc["phase"] == "train"
        m1 = os.stat(p).st_mtime
        assert _wait_for(lambda: os.stat(p).st_mtime > m1)
    finally:
        watchdog.stop_heartbeat()
    watchdog.release("fit_step")


def test_classify_exit_stall_and_port_classes():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import launch
    kind, reason = launch.classify_exit(75)
    assert kind == "retryable" and "stall" in reason
    kind, reason = launch.classify_exit(76)
    assert kind == "retryable" and "port" in reason
    assert launch.classify_exit(2)[0] == "permanent"  # unchanged


# -- stalled worker → exit 75 + artifacts (every fault site) ----------------

@pytest.mark.fault
@pytest.mark.hang
def test_worker_stall_exits_75_with_stacks_and_postmortem(tmp_path):
    """The acceptance path in one process: a wedged train step stops
    renewing the fit_step lease; the watchdog dumps all-thread stacks +
    the flight-recorder postmortem and exits 75."""
    script = _PREAMBLE + """
mod, batches = make_module()
for b in batches:
    mod.fit_step(b)                    # warm + create the lease
fault.configure("worker.stall:1")
for _ in range(1000):
    for b in batches:
        mod.fit_step(b)                # wedges here
print("UNREACHABLE", flush=True)
"""
    r = _run_guarded(script, {
        "MXTPU_STALL_TIMEOUT": "1.0",
        "MXTPU_STARTUP_GRACE": "300",
        "MXTPU_POSTMORTEM_DIR": str(tmp_path),
    })
    err = r.stderr.decode()
    assert r.returncode == 75, (r.returncode, err[-2000:])
    assert b"UNREACHABLE" not in r.stdout
    assert "stall: lease 'fit_step' expired" in err
    assert "Thread" in err  # all-thread stack dump on stderr
    doc, stacks = _stall_artifacts(str(tmp_path))
    assert doc["reason"].startswith("stall: lease 'fit_step'")
    assert doc["watchdog"]["leases"]["fit_step"]["age_s"] > 1.0
    assert doc["counters"]["watchdog.stalls"] == 1
    assert doc["fault_fires"] == {"worker.stall": 1}
    # the stack dump reaches into the wedged frame (fault.stall_if)
    assert "stall_if" in stacks
    # flight recorder carried real step records up to the stall
    assert doc["last_steps"], "flight ring empty at stall"


@pytest.mark.fault
@pytest.mark.hang
def test_kv_hang_guard_detected(tmp_path):
    """A peer-loss deadlock stand-in inside a collective/barrier: the
    scoped kv lease expires even though no renewal will ever come.
    This hang precedes any training progress, so detection runs on the
    STARTUP GRACE budget (pre-progress leases are grace-extended — a
    bring-up barrier legitimately waits for peers still compiling)."""
    script = """
import sys; sys.path.insert(0, %(repo)r)
import mxnet_tpu as mx
from mxnet_tpu import fault
kv = mx.kv.create("local")
fault.configure("kv.hang:1")
kv.barrier()
print("UNREACHABLE", flush=True)
""" % {"repo": REPO}
    r = _run_guarded(script, {
        "MXTPU_STALL_TIMEOUT": "0.5",
        "MXTPU_STARTUP_GRACE": "1",
        "MXTPU_POSTMORTEM_DIR": str(tmp_path),
    })
    assert r.returncode == 75, r.stderr.decode()[-2000:]
    doc, stacks = _stall_artifacts(str(tmp_path))
    assert "kv.barrier" in doc["reason"]
    assert "stall_if" in stacks


@pytest.mark.fault
@pytest.mark.hang
def test_data_stall_detected_via_consumer_lease(tmp_path):
    """A wedged prefetch producer starves the consumer; the consumer-side
    'data' lease expires.  A step-lease renewal simulates the completed
    train step that ends the grace window (the data lease is auxiliary —
    its own renewals deliberately do not)."""
    script = """
import sys; sys.path.insert(0, %(repo)r)
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import fault, watchdog
from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
ds = ArrayDataset(
    mx.nd.array(np.arange(80).reshape(20, 4).astype(np.float32)),
    mx.nd.array(np.arange(20).astype(np.float32)))
it = iter(DataLoader(ds, batch_size=2))
next(it)                         # first batch creates the data lease
watchdog.renew("trainer_step")   # a train step completed on it
watchdog.release("trainer_step")
fault.configure("data.stall:1")
for _ in it:                     # producer wedges; consumer starves
    pass
print("UNREACHABLE", flush=True)
""" % {"repo": REPO}
    r = _run_guarded(script, {
        "MXTPU_STALL_TIMEOUT": "0.5",
        "MXTPU_STARTUP_GRACE": "300",
        "MXTPU_POSTMORTEM_DIR": str(tmp_path),
    })
    assert r.returncode == 75, r.stderr.decode()[-2000:]
    doc, _ = _stall_artifacts(str(tmp_path))
    assert "lease 'data'" in doc["reason"]


@pytest.mark.fault
@pytest.mark.hang
def test_ckpt_write_stall_detected(tmp_path):
    """A stuck filesystem write (hung NFS stand-in) inside atomic_write
    expires the scoped ckpt.write lease.  Training progress first, so
    the steady-state timeout (not the startup grace) governs — the
    production shape: checkpoints happen after steps."""
    pm = tmp_path / "pm"
    pm.mkdir()
    script = """
import sys; sys.path.insert(0, %(repo)r)
from mxnet_tpu import checkpoint, fault, watchdog
watchdog.renew("fit_step")   # a step completed before this checkpoint
watchdog.release("fit_step")  # isolate the ckpt.write guard's verdict
fault.configure("ckpt.write.stall:1")
checkpoint.atomic_write(%(path)r, b"payload")
print("UNREACHABLE", flush=True)
""" % {"repo": REPO, "path": str(tmp_path / "x.bin")}
    r = _run_guarded(script, {
        "MXTPU_STALL_TIMEOUT": "0.5",
        "MXTPU_STARTUP_GRACE": "300",
        "MXTPU_POSTMORTEM_DIR": str(pm),
    })
    assert r.returncode == 75, r.stderr.decode()[-2000:]
    doc, _ = _stall_artifacts(str(pm))
    assert "ckpt.write" in doc["reason"]


# -- timeout-guarded distributed bring-up -----------------------------------

@pytest.mark.fault
@pytest.mark.hang
def test_bringup_dead_coordinator_raises_naming_it():
    """A worker pointed at a dead coordinator exits with MXNetError
    naming the address within the connect deadline — instead of blocking
    in jax.distributed.initialize forever."""
    script = """
import sys; sys.path.insert(0, %(repo)r)
try:
    import mxnet_tpu
except Exception as e:
    ok = (type(e).__name__ == "MXNetError"
          and "127.0.0.1:1" in str(e) and "coordinator" in str(e))
    print(str(e)[:300])
    sys.exit(42 if ok else 43)
sys.exit(44)
""" % {"repo": REPO}
    t0 = time.time()
    r = _run_guarded(script, {
        "MXTPU_COORDINATOR": "127.0.0.1:1",   # nothing listens on port 1
        "MXTPU_NUM_WORKERS": "2",
        "MXTPU_WORKER_RANK": "1",
        "MXTPU_CONNECT_TIMEOUT": "2",
        "MXTPU_CONNECT_RETRIES": "0",
    })
    assert r.returncode == 42, (r.returncode, r.stdout, r.stderr[-800:])
    assert time.time() - t0 < 60  # bounded, not the jax default 5 min


@pytest.mark.fault
@pytest.mark.hang
def test_bringup_port_in_use_exits_76():
    """Rank 0 losing the coordinator-port race exits the dedicated
    retryable class (76) so a --port 0 restart re-picks the port."""
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    s.listen(1)
    port = s.getsockname()[1]
    try:
        r = _run_guarded(
            "import sys; sys.path.insert(0, %r); import mxnet_tpu"
            % REPO,
            {"MXTPU_COORDINATOR": "127.0.0.1:%d" % port,
             "MXTPU_NUM_WORKERS": "2", "MXTPU_WORKER_RANK": "0"})
    finally:
        s.close()
    assert r.returncode == 76, (r.returncode, r.stderr.decode()[-800:])
    assert "already bound" in r.stderr.decode()


# -- launcher: heartbeat monitor + bounded teardown -------------------------

@pytest.mark.fault
@pytest.mark.hang
def test_launcher_heartbeat_timeout_kills_and_restarts(tmp_path):
    """The out-of-process detection channel: a worker whose interpreter
    goes quiet (heartbeat thread stopped — the wedged-in-native-code
    stand-in) is killed by the launcher on stale heartbeat mtime,
    classified retryable stall, and the job restarts to completion."""
    script = tmp_path / "worker.py"
    script.write_text("""
import os, sys, time
sys.path.insert(0, %(repo)r)
import mxnet_tpu as mx                 # starts the heartbeat thread
from mxnet_tpu import watchdog
attempt = int(os.environ.get("MXTPU_RESTART_ATTEMPT", "0"))
if attempt == 0:
    time.sleep(1.0)                    # let a few heartbeats land
    watchdog.stop_heartbeat()          # interpreter "wedges"
    time.sleep(3600)
open(os.path.join(%(tmp)r, "done"), "w").write("1")
""" % {"repo": REPO, "tmp": str(tmp_path)})
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXTPU_HEARTBEAT_INTERVAL"] = "0.1"
    r = subprocess.run(
        ["timeout", "-k", "10", "120",
         sys.executable, LAUNCH, "-n", "1", "--cpu-fake-devices",
         "--max-restarts", "1", "--heartbeat-timeout", "2",
         "--kill-grace", "1", "--restart-backoff", "0.01",
         sys.executable, str(script)],
        env=env, capture_output=True, timeout=150)
    err = r.stderr.decode()
    assert r.returncode == 0, err[-2000:]
    assert "heartbeat silent" in err
    assert "classified retryable" in err and "stall" in err
    assert "restarting job from checkpoints" in err
    assert (tmp_path / "done").exists()


@pytest.mark.fault
@pytest.mark.hang
def test_launcher_sigint_escalates_bounded(tmp_path):
    """Ctrl-C on a job whose worker swallows SIGINT/SIGTERM must still
    tear down within the bounded grace ladder (SIGINT→SIGTERM→SIGKILL),
    not wait() forever like the old KeyboardInterrupt path."""
    marker = tmp_path / "ready"
    worker = ("import signal, time, sys\n"
              "signal.signal(signal.SIGINT, signal.SIG_IGN)\n"
              "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
              "open(%r, 'w').write('1')\n"
              "time.sleep(3600)\n" % str(marker))
    env = dict(os.environ)
    p = subprocess.Popen(
        ["timeout", "-k", "10", "90",
         sys.executable, LAUNCH, "-n", "1", "--kill-grace", "0.5",
         sys.executable, "-c", worker],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    try:
        assert _wait_for(marker.exists, budget=60), "worker never started"
        p.send_signal(signal.SIGINT)
        t0 = time.time()
        rc = p.wait(timeout=30)   # bounded: 2 x grace + slack
        assert rc != 0
        assert time.time() - t0 < 20
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()


# -- the acceptance scenario: 2-worker job trains through a stall -----------

STALL_WORKER = """
import json, os, sys, time
sys.path.insert(0, %(repo)r)
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import fault, profiler, watchdog

attempt = int(os.environ.get("MXTPU_RESTART_ATTEMPT", "0"))
rank = int(os.environ["MXTPU_WORKER_RANK"])
assert os.environ["MXTPU_NUM_WORKERS"] == "2"
tmp = %(tmp)r
prefix = os.path.join(tmp, "ckpt")

# file-based 2-rank barrier (each replica trains the fused no-kvstore
# path); a stalled peer leaves the other rank waiting here until the
# launcher tears the job down
def barrier(tag):
    open(os.path.join(tmp, "sync_%%s_%%d_%%d" %% (tag, attempt, rank)),
         "w").write("1")
    other = os.path.join(tmp,
                         "sync_%%s_%%d_%%d" %% (tag, attempt, 1 - rank))
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
    mod.init_params(arg_params=args, aux_params=auxs,
                    allow_missing=False)
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
    if attempt == 0 and rank == 1 and epoch == 3:
        # wedge THIS rank's next train step: the in-process watchdog
        # must detect the expired fit_step lease, dump diagnostics, and
        # exit 75 — the launcher then restarts the whole job
        fault.configure("worker.stall:1")
    for batch in it:
        mod.fit_step(batch)          # lease renewed per step, 1 dispatch
        n_steps += 1
        out = mod.get_outputs()[0].asnumpy()
        lbl = batch.label[0].asnumpy().astype(int)
        losses.append(float(-np.log(np.maximum(
            out[np.arange(len(lbl)), lbl], 1e-8)).mean()))
    barrier("pre_save_%%d" %% epoch)
    if rank == 0:
        mod.save_checkpoint(prefix, epoch)
        with open(log_path, "a") as f:
            f.write(json.dumps({"attempt": attempt, "epoch": epoch,
                                "loss": float(np.mean(losses))}) + "\\n")
    barrier("post_save_%%d" %% epoch)

# steptrace's contract: lease renewals added ZERO dispatches
st = profiler.step_stats()
assert st["dispatch_count"] == n_steps, (st, n_steps)
if rank == 0:
    with open(os.path.join(tmp, "stats_%%d.json" %% attempt), "w") as f:
        json.dump({"steps": n_steps,
                   "dispatch_count": st["dispatch_count"]}, f)
barrier("finish")
watchdog.disarm()
open(os.path.join(tmp, "done_%%d" %% rank), "w").write("1")
"""


@pytest.mark.slow
@pytest.mark.fault
@pytest.mark.hang
def test_two_worker_job_survives_injected_stall(tmp_path):
    """ISSUE 4 acceptance: an injected worker.stall on a 2-worker local
    --max-restarts 1 job is detected, diagnosed (stack dump + postmortem
    naming the lease), classified retryable, and the restarted job
    trains to completion from its checkpoints with 1.0 dispatch/step."""
    script = tmp_path / "worker.py"
    script.write_text(STALL_WORKER % {"repo": REPO,
                                      "tmp": str(tmp_path)})
    pm = tmp_path / "pm"
    pm.mkdir()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXTPU_STALL_TIMEOUT"] = "3"
    env["MXTPU_STARTUP_GRACE"] = "300"
    env["MXTPU_POSTMORTEM_DIR"] = str(pm)
    r = subprocess.run(
        ["timeout", "-k", "15", "560",
         sys.executable, LAUNCH, "-n", "2", "--cpu-fake-devices",
         "--max-restarts", "1", "--restart-backoff", "0.1",
         "--kill-grace", "2",
         sys.executable, str(script)],
        env=env, capture_output=True, timeout=600)
    out = r.stdout.decode() + r.stderr.decode()
    assert r.returncode == 0, out[-3000:]
    # the stalled rank self-terminated with the stall exit code and the
    # launcher classified it retryable
    assert "exited with 75" in out
    assert "classified retryable" in out and "stall" in out
    assert "restarting job from checkpoints" in out
    # diagnosis artifacts: stack dump + postmortem naming the lease
    docs = [json.load(open(os.path.join(pm, f)))
            for f in os.listdir(pm) if f.startswith("postmortem-")]
    assert any(d["reason"].startswith("stall: lease 'fit_step'")
               for d in docs), [d["reason"] for d in docs]
    assert any(f.startswith("stall-stacks-") for f in os.listdir(pm))
    # the restarted job resumed from checkpoints and finished
    assert "RESUMED from epoch 2" in out
    assert (tmp_path / "done_0").exists()
    assert (tmp_path / "done_1").exists()
    # 1.0 dispatch/step held on the completed attempt (lease renewal
    # adds no dispatches)
    stats = json.loads((tmp_path / "stats_1.json").read_text())
    assert stats["dispatch_count"] == stats["steps"], stats
    # training converged across the stall + restart
    records = [json.loads(l) for l in
               (tmp_path / "loss_rank0.jsonl").read_text().splitlines()]
    by_attempt = {}
    for rec in records:
        by_attempt.setdefault(rec["attempt"],
                              {})[rec["epoch"]] = rec["loss"]
    assert set(by_attempt[0]) == {1, 2}       # stall hit epoch 3
    assert set(by_attempt[1]) == {3, 4, 5, 6}  # resumed after 2
    assert by_attempt[1][6] < by_attempt[0][1], by_attempt
