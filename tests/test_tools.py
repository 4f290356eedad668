"""Tests for tools/: im2rec, parse_log, launch (local), bandwidth."""
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def _write_images(root, n_per_class=3):
    from PIL import Image
    for cls in ["cats", "dogs"]:
        d = os.path.join(root, cls)
        os.makedirs(d, exist_ok=True)
        for i in range(n_per_class):
            arr = np.full((40, 40, 3),
                          60 if cls == "cats" else 180, np.uint8)
            Image.fromarray(arr).save(os.path.join(d, "im%d.jpg" % i))


def test_im2rec_list_and_pack(tmp_path):
    import im2rec
    root = str(tmp_path / "imgs")
    _write_images(root)
    prefix = str(tmp_path / "data")
    im2rec.main([prefix, root, "--list", "--recursive"])
    assert os.path.exists(prefix + ".lst")
    lines = open(prefix + ".lst").read().strip().splitlines()
    assert len(lines) == 6
    im2rec.main([prefix, root, "--resize", "32"])
    assert os.path.exists(prefix + ".rec")
    assert os.path.exists(prefix + ".idx")
    # the produced rec feeds ImageIter
    from mxnet_tpu import image
    it = image.ImageIter(batch_size=2, data_shape=(3, 28, 28),
                         path_imgrec=prefix + ".rec",
                         path_imgidx=prefix + ".idx")
    b = next(it)
    assert b.data[0].shape == (2, 3, 28, 28)
    labels = set()
    it.reset()
    for b in it:
        labels.update(b.label[0].asnumpy().tolist())
    assert labels == {0.0, 1.0}


def test_parse_log(tmp_path):
    import parse_log
    log = tmp_path / "train.log"
    log.write_text(
        "INFO Epoch[0] Train-accuracy=0.50\n"
        "INFO Epoch[0] Validation-accuracy=0.55\n"
        "INFO Epoch[0] Time cost=10.5\n"
        "INFO Epoch[1] Train-accuracy=0.80\n"
        "INFO Epoch[1] Validation-accuracy=0.75\n"
        "INFO Epoch[1] Time cost=9.5\n")
    data = parse_log.parse_log(open(str(log)))
    assert data[0][0] == 0.50 and data[1][2] == 0.75
    table = parse_log.format_table(data)
    assert "| 1 | 0.800000 | 0.750000 | 9.500000 |" in table


def test_bandwidth_measure():
    import importlib
    sys.path.insert(0, os.path.join(REPO, "tools", "bandwidth"))
    measure = importlib.import_module("measure")
    res = measure.measure(num_devices=0, size_mb=4.0, num_arrays=4,
                          iters=2, warmup=1)
    assert res["algbw_GBps"] > 0
    assert res["devices"] >= 1


def test_launch_local_spawns_workers(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(
        "import os\n"
        "rank = os.environ['MXTPU_WORKER_RANK']\n"
        "n = os.environ['DMLC_NUM_WORKER']\n"
        "open(os.path.join(%r, 'out_%%s.txt' %% rank), 'w').write(n)\n"
        % str(tmp_path))
    env = dict(os.environ)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "3", "--cpu-fake-devices", sys.executable, str(script)],
        env=env, capture_output=True, timeout=120)
    assert r.returncode == 0, r.stderr.decode()
    for rank in range(3):
        p = tmp_path / ("out_%d.txt" % rank)
        assert p.exists() and p.read_text() == "3"


def test_ipynb2md(tmp_path):
    import json
    import subprocess
    import sys
    nb = {"cells": [
        {"cell_type": "markdown", "source": ["# Title\n", "text"]},
        {"cell_type": "code", "source": ["print(1+1)"],
         "outputs": [{"text": ["2\n"]}]},
    ], "nbformat": 4}
    src = tmp_path / "nb.ipynb"
    src.write_text(json.dumps(nb))
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "ipynb2md.py"),
                        str(src)], capture_output=True)
    assert r.returncode == 0, r.stderr.decode()
    md = (tmp_path / "nb.md").read_text()
    assert "# Title" in md and "```python" in md and "2" in md


def test_bandwidth_compressed_kvstore_mode():
    sys.path.insert(0, os.path.join(REPO, "tools", "bandwidth"))
    import measure
    res = measure.measure_kvstore("device", size_mb=4.0, num_arrays=4,
                                  iters=2, warmup=1, gc_type="2bit")
    assert res["gc_type"] == "2bit"
    # 4 MB of fp32 over 4 keys = 250k elements/key -> ceil/4 bytes each
    per_key = int(res["total_mb"] * 1e6 / 4 / 4)
    assert res["wire_bytes_per_push"] == 4 * (-(-per_key // 4))
    assert res["GBps"] > 0


def test_launch_dry_run_ssh_and_mpi(tmp_path):
    """--dry-run prints the exact remote commands (reference launch.py's
    ssh/mpi tracker modes) without spawning anything."""
    import subprocess
    hostfile = tmp_path / "hosts"
    hostfile.write_text("nodeA\nnodeB\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--launcher", "ssh", "-H", str(hostfile),
         "--dry-run", "--port", "39999", "python", "train.py"],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-500:]
    lines = [l for l in r.stdout.splitlines() if l.startswith("ssh")]
    assert len(lines) == 2
    assert "nodeA" in lines[0] and "nodeB" in lines[1]
    assert "MXTPU_WORKER_RANK=0" in lines[0]
    assert "MXTPU_WORKER_RANK=1" in lines[1]
    assert "MXTPU_NUM_WORKERS=2" in lines[0]

    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "4", "--launcher", "mpi", "-H", str(hostfile),
         "--dry-run", "--port", "39999", "python", "train.py"],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-500:]
    out = r.stdout.strip()
    assert out.startswith("mpirun -np 4")
    assert "MXTPU_RANK_FROM_MPI=1" in out and "train.py" in out


def test_launch_dry_run_local_and_mpi_coordinator(tmp_path):
    import subprocess
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "3", "--dry-run", "--port", "39998", "python", "t.py"],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-400:]
    lines = r.stdout.strip().splitlines()
    assert len(lines) == 3 and all("127.0.0.1:39998" in l for l in lines)
    # mpi coordinator lives on the FIRST hostfile host (where rank 0 runs)
    hostfile = tmp_path / "hosts"
    hostfile.write_text("nodeX slots=4\nnodeY slots=4\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "8", "--launcher", "mpi", "-H", str(hostfile),
         "--dry-run", "--port", "39998", "python", "t.py"],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-400:]
    assert "MXTPU_COORDINATOR=nodeX:39998" in r.stdout


def test_op_consistency_runner():
    """The accelerator-vs-CPU sweep runner executes every pure forward
    case and passes (degenerate accel==cpu here)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env["OP_CONSISTENCY_DTYPES"] = "float32"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "op_consistency.py")],
        capture_output=True, text=True, env=env, timeout=540)
    assert r.returncode == 0, r.stdout[-800:] + r.stderr[-400:]
    assert "op_consistency: PASS" in r.stdout
    assert "cases_ran=0" not in r.stdout
