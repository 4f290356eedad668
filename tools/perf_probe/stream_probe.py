"""Streaming ingest beside the in-memory DataLoader, on the CPU.

Builds ONE synthetic shard set (float32 feature vectors + labels packed
as RecordIO records across several shards), then runs the same fused
MLP fit loop (steptrace.build_module's network) twice:

- **in-memory**: batches materialized up front (the PR-1 baseline —
  decode cost excluded by construction);
- **streaming**: batches decoded from the on-disk shards through
  ``mxnet_tpu.stream.StreamLoader``'s worker pool, re-iterated per
  epoch through the SAME device prefetcher.

What it counts (``tests/test_stream.py`` asserts these on a small
run): exactly 1.0 dispatch/step and 0 steady-state recompiles —
streaming feeds the same donated program, changing nothing above the
batch — every record read, none torn.  What it times is for reading by
hand, a CPU time and no contract: the steady-state step from disk over
the in-memory step (median over alternating paired segments; a
single-shot comparison of ~0.3 ms steps is all scheduler noise) and
the p99 of ``io.queue_wait`` beside one in-memory step.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def build_shard_set(root, n_batches=8, batch=64, dim=32, classes=4,
                    n_shards=4):
    """The synthetic stream: same data distribution as
    steptrace.build_module, packed as fixed-size records (x float32[dim]
    | y float32) across ``n_shards`` RecordIO shards."""
    import numpy as np
    from mxnet_tpu import stream

    rs = np.random.RandomState(0)
    n = n_batches * batch
    X = rs.randn(n, dim).astype(np.float32)
    y = rs.randint(0, classes, size=n).astype(np.float32)
    w = stream.ShardSetWriter(root)
    per = (n + n_shards - 1) // n_shards
    for k in range(n_shards):
        lo, hi = k * per, min((k + 1) * per, n)
        w.write_recordio_shard(
            X[i].tobytes() + y[i].tobytes() for i in range(lo, hi))
    w.seal()
    return stream.load_shard_set(root), X, y


def _decode(dim):
    import numpy as np

    def decode(raw):
        x = np.frombuffer(raw[:dim * 4], dtype=np.float32)
        y = np.frombuffer(raw[dim * 4:], dtype=np.float32)[0]
        return x, y
    return decode


def _decode_batch(dim):
    """Vectorized per-task decode (StreamLoader's ``decode_batch_fn``):
    one frombuffer+reshape over the whole chunk instead of a Python
    call per record — fixed-size records should always decode this
    way (DATA.md "Decode functions")."""
    import numpy as np

    def decode_batch(raws):
        arr = np.frombuffer(b"".join(raws), dtype=np.float32)
        arr = arr.reshape(len(raws), dim + 1)
        return list(zip(arr[:, :dim], arr[:, dim]))
    return decode_batch


def run(n_batches=64, pairs=9):
    import numpy as np  # noqa: F401 (decode closure)
    import steptrace as _steptrace
    import mxnet_tpu as mx
    from mxnet_tpu import profiler, stream, telemetry

    import shutil
    import tempfile

    batch, dim, classes = 64, 32, 4

    root = tempfile.mkdtemp(prefix="stream-probe-")
    try:
        shard_set, X, y = build_shard_set(root, n_batches, batch, dim,
                                          classes)
        mod, train = _steptrace.build_module(
            batch=batch, dim=dim, classes=classes, n_batches=n_batches)

        # THE comparison the contract states: the same fused fit loop
        # fed by (a) the PR-1 in-memory DataLoader — ArrayDataset +
        # batchify + device prefetcher — and (b) the StreamLoader
        # decoding the same records from disk shards through its worker
        # pool into the SAME device prefetcher.  Both sides pay
        # batchify + h2d per batch; streaming adds shard reads + decode,
        # which the pool must hide.
        from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
        mem_loader = DataLoader(ArrayDataset(X, y), batch_size=batch,
                                last_batch="keep")
        # chunk 256 = 4 batches per decode task: task management and
        # queue hops amortize 4x (per-record work is already one
        # vectorized numpy pass), which is what holds the 1.10x contract
        # at CPU-microbench step sizes; DATA.md "Sizing" carries the
        # guidance
        loader = stream.StreamLoader(
            shard_set, batch, decode_batch_fn=_decode_batch(dim),
            epoch=0, rank=0, world_size=1, seed=0,
            chunk_records=256, queue_depth=6)

        def to_databatch(b):
            return mx.io.DataBatch(data=[b[0]], label=[b[1]])

        def run_epoch(it):
            n = 0
            t0 = time.perf_counter()
            for b in it:
                mod.fit_step(to_databatch(b))
                n += 1
            return n, time.perf_counter() - t0

        def stream_epoch(epoch):
            loader.set_epoch(epoch)
            return run_epoch(loader)

        def mem_epoch():
            return run_epoch(mem_loader)

        # warm: trace+compile+allocator, one full pass per side so the
        # pool/readers/prefetcher are in steady state
        mem_epoch()
        stream_epoch(0)

        # the measured segments (one full epoch each side per pair):
        # alternate which side goes first so drift can't systematically
        # land on one side; the MEDIAN ratio kills the outliers a
        # shared box produces
        ratios, mem_s, stream_s = [], [], []
        for i in range(pairs):
            if i % 2:
                n, t = mem_epoch()
                m = t / n
                n, t = stream_epoch(i + 1)
                s = t / n
            else:
                n, t = stream_epoch(i + 1)
                s = t / n
                n, t = mem_epoch()
                m = t / n
            mem_s.append(m)
            stream_s.append(s)
            ratios.append(s / m)

        ratios.sort()
        mem_s.sort()
        stream_s.sort()
        ratio = ratios[len(ratios) // 2]

        # contract segment under reset counters: dispatch/recompile laws
        # + the io.queue_wait bound, measured over fresh telemetry
        telemetry.reset()
        profiler.reset_step_stats()
        n, _ = stream_epoch(100)
        stats = profiler.step_stats()
        rep = telemetry.report()
        ioq = (rep["phases"].get("io.queue_wait") or {})
        mem_step = mem_s[len(mem_s) // 2]
        return {
            "ratio_stream_vs_mem": round(ratio, 4),
            "ratio_pairs": [round(r, 4) for r in ratios],
            "mem_step_ms": round(mem_step * 1e3, 4),
            "stream_step_ms": round(stream_s[len(stream_s) // 2] * 1e3,
                                    4),
            "contract_steps": n,
            "dispatches_per_step": stats["dispatch_count"] / max(1, n),
            "compile_count": stats["compile_count"],
            "io_queue_wait_p99_ms": round(
                (ioq.get("p99") or 0.0) * 1e3, 4),
            "io_queue_wait_bound_ms": round(mem_step * 1e3, 4),
            "io_records": rep["counters"].get("io.records", 0),
            "io_bytes": rep["counters"].get("io.bytes", 0),
            "io_torn_records": rep["counters"].get("io.torn_records", 0),
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    print(json.dumps(run()))
