"""Streaming data plane (ISSUE 12): shard-set manifests, exact-once
(shard, offset) assignment laws, cursor resume at any world size, the
decode worker pool's robustness (torn tails, worker tracebacks, fault
sites), io.* telemetry + input-stall blame, and the fast in-process
sibling of the slow continual train-to-serve e2e
(tests/test_stream_e2e.py).
"""
import io as _io
import json
import os
import pickle
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import MXNetError, fault, recordio, stream, telemetry
from mxnet_tpu.stream import assignment as assign

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _int_records(ids):
    return [np.array([i], np.int32).tobytes() for i in ids]


def _decode(raw):
    return np.frombuffer(raw, np.int32)


def _ids_of(batches):
    return [int(b[i, 0].asnumpy()) for b in batches
            for i in range(b.shape[0])]


def _drain(loader):
    return _ids_of(list(loader))


@pytest.fixture
def shard_set(tmp_path):
    w = stream.ShardSetWriter(str(tmp_path / "ss"))
    n = 0
    for k in range(3):
        w.write_recordio_shard(_int_records(range(n, n + 10 + k)))
        n += 10 + k
    return stream.load_shard_set(str(tmp_path / "ss")), n


# -- shard-set manifests -----------------------------------------------------

@pytest.mark.stream
def test_manifest_roundtrip_append_refresh_seal(tmp_path):
    root = str(tmp_path / "ss")
    w = stream.ShardSetWriter(root)
    w.write_recordio_shard(_int_records(range(5)))
    ss = stream.load_shard_set(root)
    assert ss.sizes == [5] and not ss.closed
    assert ss.validate()
    assert ss.refresh() is False  # unchanged
    w.write_jsonl_shard([{"id": i} for i in range(4)])
    assert ss.refresh() is True   # append visible
    assert ss.sizes == [5, 4]
    assert ss.shards[1]["format"] == "jsonl"
    w.seal()
    ss.refresh()
    assert ss.closed
    # committed entries carry count/bytes/sha256
    for ent in ss.shards:
        assert ent["num_records"] and ent["bytes"] and ent["sha256"]
    with pytest.raises(MXNetError):
        stream.ShardSetWriter(root)  # sealed stream refuses appends


@pytest.mark.stream
def test_manifest_append_only_contract(tmp_path):
    root = str(tmp_path / "ss")
    w = stream.ShardSetWriter(root)
    w.write_recordio_shard(_int_records(range(5)))
    ss = stream.load_shard_set(root)
    # rewrite history: same length but different entry
    doc = json.loads((tmp_path / "ss" / "shardset.json").read_text())
    doc["shards"][0]["num_records"] = 99
    doc["version"] += 1
    (tmp_path / "ss" / "shardset.json").write_text(json.dumps(doc))
    with pytest.raises(MXNetError, match="append-only"):
        ss.refresh()


@pytest.mark.stream
def test_discover_glob_counts_complete_records(tmp_path):
    p = str(tmp_path / "a.rec")
    w = recordio.MXRecordIO(p, "w")
    for rec in _int_records(range(6)):
        w.write(rec)
    w.close()
    # torn tail: discovery counts up to the last whole record
    data = open(p, "rb").read()
    open(p, "wb").write(data[:-3])
    ss = stream.discover(str(tmp_path / "*.rec"))
    assert ss.sizes == [5] and ss.closed


# -- assignment laws ---------------------------------------------------------

@pytest.mark.stream
def test_ranges_exact_once_any_world(shard_set):
    ss, total = shard_set
    for world in (1, 2, 3, 8):
        seen = []
        for r in range(world):
            for s, a, b in assign.ranges_for_epoch(ss.sizes, 4, r, world):
                seen.extend((s, i) for i in range(a, b))
        assert len(seen) == total and len(set(seen)) == total, world


@pytest.mark.stream
def test_ranges_degrade_to_shard_for_epoch_for_unit_shards():
    """One record per shard == the PR-6 in-memory sample law, order
    included: position space IS the sample permutation."""
    from mxnet_tpu import elastic
    unit = [1] * 23
    for world in (1, 2, 3, 8):
        for r in range(world):
            got = [s for s, a, b in
                   assign.ranges_for_epoch(unit, 5, r, world, seed=3)]
            ref = elastic.shard_for_epoch(23, 5, r, world, seed=3)
            assert got == ref.tolist(), (world, r)


@pytest.mark.stream
def test_epoch_order_independent_of_world(shard_set):
    """The epoch's (shard, offset) order is ONE sequence; world size
    only cuts it — a reshard replays the same global order."""
    ss, total = shard_set

    def flat(world):
        out = []
        for r in range(world):
            out.extend(assign.ranges_for_epoch(ss.sizes, 2, r, world))
        return [(s, i) for s, a, b in out for i in range(a, b)]
    ref = flat(1)
    for world in (2, 3, 4):
        assert flat(world) == ref


@pytest.mark.stream
def test_resume_spans_partition_remainder_exactly(shard_set):
    ss, total = shard_set
    # old world 3, each rank consumed a different prefix
    cursors = []
    for r in range(3):
        lo, hi = assign.span_for_rank(total, r, 3)
        cursors.append({"rank": r, "world_size": 3,
                        "spans": [[lo, hi]], "consumed": r + 1})
    consumed = sum(c["consumed"] for c in cursors)
    for new_world in (1, 2, 4):
        rem = []
        for r in range(new_world):
            rem.extend(assign.resume_spans(cursors, r, new_world))
        covered = [p for a, b in rem for p in range(a, b)]
        assert len(covered) == len(set(covered)) == total - consumed
    # incomplete cursor sets are rejected — half a snapshot is none
    with pytest.raises(MXNetError, match="incomplete"):
        assign.resume_spans(cursors[:2], 0, 2)


@pytest.mark.stream
def test_cursor_store_complete_generation_law(tmp_path):
    cs = stream.CursorStore(str(tmp_path))
    cur = {"rank": 0, "world_size": 2, "mode": "follow", "shard": 0,
           "spans": [[0, 5]], "consumed": 2, "assigned": {}}
    cs.save(1, cur)
    assert cs.load_latest() == (None, None)  # rank 1 missing
    cs.save(1, dict(cur, rank=1, spans=[[5, 9]], consumed=1))
    g, cursors = cs.load_latest()
    assert g == 1 and [c["rank"] for c in cursors] == [0, 1]
    cs.save(2, dict(cur, consumed=4))
    g, _ = cs.load_latest()
    assert g == 1, "incomplete generation 2 must not be returned"


# -- recordio hardening (satellites) -----------------------------------------

@pytest.mark.stream
def test_recordio_torn_tail_raises_naming_path_offset(tmp_path):
    p = str(tmp_path / "t.rec")
    w = recordio.MXRecordIO(p, "w")
    for rec in _int_records(range(3)):
        w.write(rec)
    w.close()
    data = open(p, "rb").read()
    open(p, "wb").write(data[:-2])  # torn final record
    r = recordio.MXRecordIO(p, "r")
    assert r.read() is not None and r.read() is not None
    with pytest.raises(MXNetError) as e:
        r.read()
    assert p in str(e.value) and "offset" in str(e.value)
    r.close()
    # bad magic names path+offset too
    blob = b"\x00" * 16
    open(p, "wb").write(blob)
    r = recordio.MXRecordIO(p, "r")
    with pytest.raises(MXNetError, match="magic"):
        r.read()
    r.close()


@pytest.mark.stream
def test_indexed_recordio_torn_tail_via_read_idx(tmp_path):
    p, ip = str(tmp_path / "t.rec"), str(tmp_path / "t.idx")
    w = recordio.MXIndexedRecordIO(ip, p, "w")
    for i, rec in enumerate(_int_records(range(3))):
        w.write_idx(i, rec)
    w.close()
    data = open(p, "rb").read()
    open(p, "wb").write(data[:-2])
    r = recordio.MXIndexedRecordIO(ip, p, "r")
    assert r.read_idx(0) is not None
    with pytest.raises(MXNetError, match="offset"):
        r.read_idx(2)
    r.close()


@pytest.mark.stream
def test_recordio_teardown_idempotent_and_half_constructed(tmp_path):
    p = str(tmp_path / "t.rec")
    recordio.MXRecordIO(p, "w").close()
    r = recordio.MXRecordIO(p, "r")
    r.close()
    r.close()            # double close: no-op
    r.__del__()          # del after close: no-op
    # half-constructed (open() raised): __del__/close must not blow up
    with pytest.raises(FileNotFoundError):
        recordio.MXRecordIO(str(tmp_path / "missing" / "x.rec"), "r")
    ri = recordio.MXIndexedRecordIO.__new__(recordio.MXIndexedRecordIO)
    ri.close()           # nothing was ever opened
    ri.__del__()


@pytest.mark.stream
def test_recordio_reader_pickles_writer_refuses(tmp_path):
    p, ip = str(tmp_path / "t.rec"), str(tmp_path / "t.idx")
    w = recordio.MXIndexedRecordIO(ip, p, "w")
    for i, rec in enumerate(_int_records(range(4))):
        w.write_idx(i, rec)
    with pytest.raises(MXNetError, match="pickle"):
        pickle.dumps(w)  # open writer: reopen would truncate
    w.close()
    with pytest.raises(MXNetError, match="pickle"):
        pickle.dumps(w)  # CLOSED writer too: __setstate__ would reopen
        # with mode "w" and zero the completed shard
    r = recordio.MXIndexedRecordIO(ip, p, "r")
    r.read_idx(0)
    pos = r.tell()
    r2 = pickle.loads(pickle.dumps(r))  # decode-worker transport
    assert r2.tell() == pos             # position survives
    assert r2.keys == r.keys
    assert r2.read_idx(3) == r.read_idx(3)
    r.close()
    r2.close()
    r2.close()
    # plain reader round-trip too
    s = recordio.MXRecordIO(p, "r")
    s.read()
    s2 = pickle.loads(pickle.dumps(s))
    assert s2.read() == s.read()
    s.close()
    s2.close()


# -- StreamLoader ------------------------------------------------------------

@pytest.mark.stream
def test_loader_deterministic_and_reshuffles(shard_set):
    ss, total = shard_set
    with stream.StreamLoader(ss, 4, decode_fn=_decode, epoch=2, rank=0,
                             world_size=1, prefetch=0, num_workers=3,
                             chunk_records=3) as ld:
        a = _drain(ld)
        ld.set_epoch(2)
        assert _drain(ld) == a          # bit-deterministic replay
        ld.set_epoch(3)
        c = _drain(ld)
        assert sorted(c) == sorted(a) == list(range(total))
        assert c != a                   # epochs reshuffle shard order
        assert len(ld) == (total + 3) // 4


@pytest.mark.stream
def test_loader_epoch_resume_exact_once(shard_set):
    ss, total = shard_set
    seen = set()
    cursors = []
    for r in range(2):
        ld = stream.StreamLoader(ss, 4, decode_fn=_decode, epoch=7,
                                 rank=r, world_size=2, prefetch=0)
        it = iter(ld)
        for _ in range(2):
            b = next(it)
            seen.update(int(b[i, 0].asnumpy())
                        for i in range(b.shape[0]))
        cursors.append(ld.cursor())
        ld.close()
    assert all(c["epoch"] == 7 for c in cursors)
    for r in range(3):  # resume the SAME epoch at a NEW world size
        ld = stream.StreamLoader(ss, 4, decode_fn=_decode, epoch=7,
                                 rank=r, world_size=3, prefetch=0,
                                 resume=cursors)
        ids = _drain(ld)
        assert not (set(ids) & seen), "reshard replayed a record"
        seen.update(ids)
        ld.close()
    assert seen == set(range(total))


@pytest.mark.stream
def test_loader_epoch_resume_pins_cursor_snapshot(tmp_path):
    """Epoch cursors stamp the shard-set snapshot they were cut under:
    a manifest that GREW mid-epoch must not remap positions (the new
    shard enters at the next epoch), and a rewritten history must be
    rejected, not silently misread."""
    root = str(tmp_path / "ss")
    w = stream.ShardSetWriter(root)
    w.write_recordio_shard(_int_records(range(12)))
    w.write_recordio_shard(_int_records(range(12, 24)))
    ss = stream.load_shard_set(root)
    ld = stream.StreamLoader(ss, 4, decode_fn=_decode, epoch=3, rank=0,
                             world_size=1, prefetch=0)
    it = iter(ld)
    first = _ids_of([next(it)])
    cur = ld.cursor()
    assert cur["sizes"] == [12, 12]
    ld.close()
    w.write_recordio_shard(_int_records(range(24, 36)))  # grows mid-epoch
    ld2 = stream.StreamLoader(stream.load_shard_set(root), 4,
                              decode_fn=_decode, epoch=3, rank=0,
                              world_size=1, prefetch=0, resume=[cur])
    rest = _drain(ld2)
    # the resumed epoch covers exactly the SNAPSHOT's records once —
    # the appended shard waits for the next epoch
    assert sorted(first + rest) == list(range(24))
    ld2.close()
    # a rewritten snapshot (cursor sizes not a prefix of the current
    # set) is rejected loudly
    bad = dict(cur, sizes=[9, 9])
    with pytest.raises(MXNetError, match="incompatibly"):
        stream.StreamLoader(stream.load_shard_set(root), 4,
                            decode_fn=_decode, epoch=3, rank=0,
                            world_size=1, prefetch=0, resume=[bad])


@pytest.mark.stream
def test_jsonl_writer_rejects_line_breaking_records(tmp_path):
    w = stream.ShardSetWriter(str(tmp_path / "ss"))
    with pytest.raises(MXNetError, match="multi-line"):
        w.write_jsonl_shard(["a\nb"])
    with pytest.raises(MXNetError, match="empty"):
        w.write_jsonl_shard(["  "])


@pytest.mark.stream
def test_loader_half_constructed_del_is_silent():
    with pytest.raises(MXNetError):
        stream.StreamLoader(42, 4)  # bad shard_set: __init__ raises
    # nothing to assert beyond "no 'Exception ignored in __del__'" —
    # close() must tolerate the missing pool slot
    ld = stream.StreamLoader.__new__(stream.StreamLoader)
    ld.close()


@pytest.mark.stream
def test_loader_follow_append_seal_and_reshard(tmp_path):
    root = str(tmp_path / "ss")
    w = stream.ShardSetWriter(root)
    w.write_recordio_shard(_int_records(range(11)))
    w.write_recordio_shard(_int_records(range(11, 22)))
    w.write_recordio_shard(_int_records(range(22, 33)))
    w.seal()
    seen = set()
    cursors = []
    for r in range(2):
        ld = stream.StreamLoader(stream.load_shard_set(root), 4,
                                 decode_fn=_decode, mode="follow",
                                 rank=r, world_size=2, prefetch=0)
        it = iter(ld)
        for _ in range(2):
            b = next(it)
            seen.update(int(b[i, 0].asnumpy())
                        for i in range(b.shape[0]))
        cursors.append(ld.cursor())
        ld.close()
    ld = stream.StreamLoader(stream.load_shard_set(root), 4,
                             decode_fn=_decode, mode="follow", rank=0,
                             world_size=1, prefetch=0, resume=cursors)
    ids = _drain(ld)
    assert not (set(ids) & seen)
    seen.update(ids)
    assert seen == set(range(33))
    ld.close()


@pytest.mark.stream
def test_loader_follow_resume_empty_override_not_reconsumed(tmp_path):
    """Regression (caught by the continual e2e): when every old rank
    FULLY consumed the current shard, the resumed assignment's override
    for it is EMPTY — which must mean "nothing left", never "fall back
    to the fresh law and re-train the whole shard"."""
    root = str(tmp_path / "ss")
    w = stream.ShardSetWriter(root)
    w.write_recordio_shard(_int_records(range(24)))
    w.write_recordio_shard(_int_records(range(24, 48)))
    w.seal()
    cursors = []
    for r in range(2):
        ld = stream.StreamLoader(stream.load_shard_set(root), 4,
                                 decode_fn=_decode, mode="follow",
                                 rank=r, world_size=2, prefetch=0)
        it = iter(ld)
        for _ in range(3):   # exactly this rank's slice of shard 0
            next(it)
        c = ld.cursor()
        assert c["shard"] == 0 and c["consumed"] == 12
        cursors.append(c)
        ld.close()
    ld = stream.StreamLoader(stream.load_shard_set(root), 4,
                             decode_fn=_decode, mode="follow", rank=0,
                             world_size=1, prefetch=0, resume=cursors)
    ids = _drain(ld)
    assert ids == list(range(24, 48)), (
        "resume re-consumed the fully-covered shard: %s" % ids[:10])
    ld.close()


@pytest.mark.stream
def test_loader_torn_tail_skips_and_counts(tmp_path):
    root = str(tmp_path / "ss")
    w = stream.ShardSetWriter(root)
    w.write_recordio_shard(_int_records(range(8)))
    w.seal()
    ss = stream.load_shard_set(root)
    p = ss.shards[0]["path"]
    data = open(p, "rb").read()
    open(p, "wb").write(data[:-5])  # crashed-writer truncation
    torn0 = telemetry.counter("io.torn_records").value
    ld = stream.StreamLoader(ss, 4, decode_fn=_decode, epoch=0, rank=0,
                             world_size=1, prefetch=2, num_workers=1)
    got = _drain(ld)
    assert got == list(range(7))  # last record skipped, no garbage
    assert telemetry.counter("io.torn_records").value - torn0 == 1
    assert ld.cursor()["consumed"] == 8  # torn record still covered
    ld.close()


@pytest.mark.stream
@pytest.mark.fault
def test_loader_fault_sites(shard_set):
    ss, total = shard_set
    # io.shard.torn: one task reads as a torn tail; counted, no raise
    torn0 = telemetry.counter("io.torn_records").value
    fault.configure("io.shard.torn:1")
    try:
        ld = stream.StreamLoader(ss, 4, decode_fn=_decode, epoch=0,
                                 rank=0, world_size=1, prefetch=0,
                                 num_workers=1, chunk_records=4)
        got = _drain(ld)
        ld.close()
        fired = fault.fire_count("io.shard.torn")
    finally:
        fault.reset()
    torn = telemetry.counter("io.torn_records").value - torn0
    assert torn == 4 and len(got) == total - 4
    assert fired == 1

    # io.decode.error: raises at the consumption point with the worker
    # traceback attached (thread mode re-raises the original object)
    fault.configure("io.decode.error:1")
    try:
        ld = stream.StreamLoader(ss, 4, decode_fn=_decode, epoch=0,
                                 rank=0, world_size=1, prefetch=2,
                                 num_workers=1)
        with pytest.raises(fault.FaultInjected) as e:
            _drain(ld)
        ld.close()
    finally:
        fault.reset()
    import traceback as _tb
    frames = "".join(_tb.format_tb(e.value.__traceback__))
    assert "_worker_loop" in frames or "_run_task" in frames

    # io.decode.slow: fires and the run still completes
    fault.configure("io.decode.slow:2")
    try:
        ld = stream.StreamLoader(ss, 4, decode_fn=_decode, epoch=0,
                                 rank=0, world_size=1, prefetch=0)
        assert sorted(_drain(ld)) == list(range(total))
        ld.close()
        fired = fault.fire_count("io.decode.slow")
    finally:
        fault.reset()
    assert fired == 2


@pytest.mark.stream
@pytest.mark.fault
def test_loader_rebuilds_degraded_pool(shard_set):
    """A worker exits permanently after its first error; the next
    iteration must rebuild the pool to full strength instead of
    silently running at reduced decode throughput forever."""
    ss, total = shard_set
    fault.configure("io.decode.error:1")
    got = []
    try:
        ld = stream.StreamLoader(ss, 4, decode_fn=_decode, epoch=0,
                                 rank=0, world_size=1, prefetch=0,
                                 num_workers=2)
        with pytest.raises(fault.FaultInjected):
            for b in ld:
                got.extend(int(b[i, 0].asnumpy())
                           for i in range(b.shape[0]))
    finally:
        fault.reset()
    pool = ld._pool
    assert not pool.full_strength()     # one worker died on the error
    # re-iterating continues from the delivered cursor AND rebuilds the
    # pool: the union is still exactly-once, at full decode strength
    rest = _drain(ld)
    assert sorted(got + rest) == list(range(total))
    assert ld._pool is not pool and ld._pool.full_strength()
    ld.close()


@pytest.mark.stream
def test_loader_process_workers(shard_set):
    ss, total = shard_set
    ld = stream.StreamLoader(ss, 5, decode_fn=_decode, epoch=1, rank=0,
                             world_size=1, prefetch=0,
                             worker_mode="process", num_workers=2,
                             chunk_records=4)
    assert sorted(_drain(ld)) == list(range(total))
    ld.close()


@pytest.mark.stream
def test_loader_process_worker_unpicklable_error(shard_set):
    """A process-mode worker failure must surface even when the
    exception itself cannot cross the mp queue (unpicklable attribute):
    only the pre-formatted traceback strings are shipped, so the error
    item can never be lost to its own transport."""
    ss, total = shard_set

    class Boom(Exception):
        def __init__(self):
            super().__init__("boom")
            self.lock = __import__("threading").Lock()  # unpicklable

    def decode(raw):
        raise Boom()
    ld = stream.StreamLoader(ss, 4, decode_fn=decode, epoch=0, rank=0,
                             world_size=1, prefetch=0,
                             worker_mode="process", num_workers=2)
    with pytest.raises(MXNetError) as e:
        _drain(ld)
    assert "Boom" in str(e.value) and "worker traceback" in str(e.value)
    ld.close()


@pytest.mark.stream
def test_loader_decode_batch_fn_vectorized(shard_set):
    ss, total = shard_set

    def decode_batch(raws):
        arr = np.frombuffer(b"".join(raws), np.int32)
        return list(arr.reshape(-1, 1))
    ld = stream.StreamLoader(ss, 4, decode_batch_fn=decode_batch,
                             epoch=2, rank=0, world_size=1, prefetch=0)
    a = _drain(ld)
    ld.close()
    ld = stream.StreamLoader(ss, 4, decode_fn=_decode, epoch=2, rank=0,
                             world_size=1, prefetch=0)
    assert a == _drain(ld)  # identical stream, either decode shape
    ld.close()


@pytest.mark.stream
def test_loader_io_telemetry_populated(shard_set):
    ss, total = shard_set
    telemetry.reset()
    ld = stream.StreamLoader(ss, 4, decode_fn=_decode, epoch=0, rank=0,
                             world_size=1, prefetch=0)
    _drain(ld)
    ld.close()
    rep = telemetry.report()
    assert rep["counters"]["io.records"] == total
    assert rep["counters"]["io.bytes"] == total * 4
    assert rep["counters"]["data.batches"] == (total + 3) // 4
    assert rep["gauges"]["io.shards_open"] >= 1
    for phase in ("io.decode", "io.shard_open", "io.queue_wait"):
        assert rep["phases"].get(phase, {}).get("count"), phase


@pytest.mark.stream
def test_checkpoint_manifest_carries_stream_cursor(tmp_path):
    from mxnet_tpu.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path / "ck"))
    cur = {"mode": "follow", "shard": 2, "spans": [[0, 5]],
           "consumed": 3, "rank": 0, "world_size": 2, "assigned": {}}
    mgr.save(1, {"w": mx.nd.array([1.0])}, {}, mode="sync",
             stream_cursor=cur)
    info = mgr.manifest_info(1)
    assert info["stream_cursor"] == cur
    assert mgr.latest() == 1  # stamp never breaks validation


# -- probe structural contracts ---------------------------------------------

@pytest.mark.stream
def test_stream_probe_structural_contracts():
    """The 1-dispatch/0-recompile/no-torn laws of the stream probe on a
    small run.  The probe's ratio of step times is a CPU time and pins
    nothing here."""
    sys.path.insert(0, os.path.join(REPO, "tools", "perf_probe"))
    import stream_probe
    r = stream_probe.run(n_batches=8, pairs=3)
    assert r["dispatches_per_step"] == 1.0
    assert r["compile_count"] == 0
    assert r["io_torn_records"] == 0
    assert r["io_records"] == 8 * 64


# -- io.* reporting: input-stall blame distinct from compute blame -----------

def _hist(p50, count=50):
    return {"count": count, "sum": p50 * count, "min": p50 / 2,
            "max": p50 * 2, "p50": p50, "p90": p50, "p99": p50 * 1.5,
            "buckets": {}, "zeros": 0}


def _stream_line(rank, world, data_wait, dispatch=0.001, io=True):
    doc = {
        "schema": "mxtpu-telemetry-2", "time_unix": 1000.0 + rank,
        "identity": {"world_size": world, "rank": rank, "slot": rank,
                     "attempt": 0, "pid": 100 + rank},
        "counters": {"io.records": 5000 if io else 0,
                     "io.bytes": 640000, "io.torn_records": 1},
        "gauges": {"io.shards_open": 2},
        "phases": {"fit_step.dispatch": _hist(dispatch),
                   "fit_step.sync": _hist(dispatch / 2),
                   "data.prefetch_wait": _hist(data_wait),
                   "io.queue_wait": _hist(data_wait / 2),
                   "io.decode": _hist(1e-4)},
        "step_stats": {"steps": 50, "dispatch_count": 50,
                       "compile_count": 0, "skipped_steps": 0,
                       "step_time_ema_s": dispatch * 2},
    }
    return doc


@pytest.mark.stream
@pytest.mark.jobview
def test_job_report_blames_input_stall_distinctly(tmp_path):
    """A rank starved on its input pipeline (data.prefetch_wait +
    io.queue_wait skew) is called out as INPUT-STALL — not as a compute
    STRAGGLER — and streamed ranks get the io.* table."""
    sys.path.insert(0, os.path.join(REPO, "tools", "perf_probe"))
    import importlib
    import job_report
    importlib.reload(job_report)
    tdir = tmp_path / "telemetry"
    tdir.mkdir()
    for rank, wait in ((0, 1e-5), (1, 1e-5), (2, 0.08)):
        (tdir / ("stream-slot%d.jsonl" % rank)).write_text(
            json.dumps(_stream_line(rank, 3, wait)) + "\n")
    job = job_report.load_job(str(tmp_path))
    rows = job_report.rank_rows(
        job_report.group_attempts(job)[0])
    stalls = job_report.find_input_stalls(rows, 2.0)
    assert [r["rank"] for r, _ in stalls] == [2]
    assert not job_report.find_stragglers(rows, 2.0)  # compute is even
    out = _io.StringIO()
    job_report.render(job, out, factor=2.0)
    text = out.getvalue()
    assert "INPUT-STALL: rank 2" in text
    assert "input pipeline, not compute" in text
    assert "STRAGGLER" not in text
    assert "stream input plane (io.*)" in text
    assert "torn" in text


@pytest.mark.stream
@pytest.mark.jobview
def test_telemetry_report_renders_io_digest():
    sys.path.insert(0, os.path.join(REPO, "tools", "perf_probe"))
    import importlib
    import telemetry_report
    importlib.reload(telemetry_report)
    out = _io.StringIO()
    telemetry_report.render_report(_stream_line(0, 1, 1e-5), out)
    text = out.getvalue()
    assert "stream input plane: records=5000" in text
    assert "torn=1" in text
    assert "io.queue_wait" in text and "io.decode" in text


# -- fast continual train-to-serve sibling -----------------------------------

@pytest.mark.stream
@pytest.mark.serving
def test_continual_stream_publish_hotload_fast(tmp_path):
    """The tier-1 sibling of the slow continual e2e: a trainer consumes
    an APPENDING shard stream (follow mode), publishes checkpoints to a
    CheckpointManager prefix, and a CheckpointSubscriber hot-loads each
    publication — with the bit-identical guarantee for an
    unchanged-weights publication (the e2e adds elastic kill/reshard
    and the full ServingEngine on top)."""
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.checkpoint import CheckpointManager
    from mxnet_tpu.gluon.model_zoo import gpt
    from mxnet_tpu.serving import CheckpointSubscriber

    VOCAB, SEQ = 16, 8
    rng = np.random.RandomState(0)

    # the stream: token-sequence records, appended mid-run
    root = str(tmp_path / "ss")
    w = stream.ShardSetWriter(root)

    def recs(n):
        return [rng.randint(0, VOCAB, (SEQ,)).astype(np.int32).tobytes()
                for _ in range(n)]
    w.write_recordio_shard(recs(8))

    net = gpt.GPTLM(VOCAB, 1, 16, 2, max_len=SEQ + 8, prefix="cts_")
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05})
    prefix = str(tmp_path / "pub" / "model")
    os.makedirs(os.path.dirname(prefix), exist_ok=True)
    mgr = CheckpointManager(prefix)

    def publish(epoch):
        mgr.save(epoch, {p.name: p.data().copy()
                         for p in net.collect_params().values()},
                 {}, mode="sync")

    ld = stream.StreamLoader(
        root + "/shardset.json", 4,
        decode_fn=lambda raw: np.frombuffer(raw, np.int32),
        mode="follow", rank=0, world_size=1, prefetch=0,
        poll_secs=0.01)
    steps = 0
    epoch = 0
    for toks in iter(ld):
        with autograd.record():
            logits = net(toks)
            lp = mx.nd.log_softmax(logits, axis=-1)
            loss = 0.0 - lp.slice_axis(axis=-1, begin=0, end=1).mean()
        loss.backward()
        trainer.step(toks.shape[0])
        steps += 1
        if steps == 1:
            epoch += 1
            publish(epoch)          # first publication mid-stream
            w.write_recordio_shard(recs(4))   # the stream GROWS
            w.seal()
    assert steps == 3  # 8 + 4 records / batch 4
    assert ld.cursor()["shard"] == 2 or ld.cursor()["consumed"] >= 4
    ld.close()
    epoch += 1
    publish(epoch)

    # a fresh serving-side net hot-loads each publication
    srv = gpt.GPTLM(VOCAB, 1, 16, 2, max_len=SEQ + 8, prefix="cts_")
    srv.initialize(mx.init.Xavier())
    probe = rng.randint(0, VOCAB, (1, 5)).astype(np.int32)
    sub = CheckpointSubscriber(prefix, srv)
    e = sub.poll()
    assert e == epoch
    sub.load_params(e)
    sub.applied_epoch = sub.seen_epoch = e
    t1 = gpt.generate(srv, probe, 4)[0].tolist()
    # trained and serving nets agree bit-for-bit after the load
    assert t1 == gpt.generate(net, probe, 4)[0].tolist()
    # an unchanged-weights publication must be bit-invisible
    publish(epoch + 1)
    e2 = sub.poll()
    assert e2 == epoch + 1
    sub.load_params(e2)
    assert gpt.generate(srv, probe, 4)[0].tolist() == t1


# -- epoch-boundary prefetch-ahead (ISSUE 14 satellite) ----------------------

@pytest.mark.stream
def test_epoch_prefetch_bit_identical_and_counted(shard_set,
                                                  monkeypatch):
    """Speculative next-epoch decode must change NOTHING about what is
    delivered — same ids, same order — and the counters prove the
    speculation actually ran and was adopted."""
    ss, total = shard_set
    telemetry.reset()
    with stream.StreamLoader(ss, 4, decode_fn=_decode, epoch=2, rank=0,
                             world_size=1, num_workers=2,
                             chunk_records=5) as ld:
        a2 = _drain(ld)                    # arms epoch-3 speculation
        spec = ld._spec
        assert spec is not None and spec["epoch"] == 3
        assert telemetry.counter("io.epoch_prefetch").value == \
            len(spec["keys"]) > 0
        ld.set_epoch(3)
        a3 = _drain(ld)                    # consumes the speculation
        assert telemetry.counter("io.epoch_prefetch_hits").value > 0
    monkeypatch.setenv("MXTPU_STREAM_EPOCH_PREFETCH", "0")
    with stream.StreamLoader(ss, 4, decode_fn=_decode, epoch=2, rank=0,
                             world_size=1, num_workers=2,
                             chunk_records=5) as ld0:
        b2 = _drain(ld0)
        assert ld0._spec is None           # knob off: no speculation
        ld0.set_epoch(3)
        b3 = _drain(ld0)
    assert (a2, a3) == (b2, b3)            # bit-identical either way
    assert sorted(a3) == list(range(total))


@pytest.mark.stream
def test_epoch_prefetch_invalidated_by_growth_and_skip(shard_set,
                                                       tmp_path):
    """A wrong guess must be DISCARDED, never served: growing the
    manifest (sizes change) and jumping to a different epoch both
    invalidate the speculation, and coverage stays exact."""
    root = str(tmp_path / "ss2")
    w = stream.ShardSetWriter(root)
    w.write_recordio_shard(_int_records(range(8)))
    ld = stream.StreamLoader(stream.load_shard_set(root), 4,
                             decode_fn=_decode, epoch=0, rank=0,
                             world_size=1, num_workers=2)
    a0 = _drain(ld)
    assert ld._spec is not None and ld._spec["epoch"] == 1
    w.write_recordio_shard(_int_records(range(8, 14)))  # stream grows
    hits0 = telemetry.counter("io.epoch_prefetch_hits").value
    ld.set_epoch(1)                        # refresh picks the growth up
    a1 = _drain(ld)
    assert telemetry.counter("io.epoch_prefetch_hits").value == hits0
    assert sorted(a0) == list(range(8))
    assert sorted(a1) == list(range(14))   # new shard covered
    # epoch skip: speculation was for epoch 2, we pin epoch 5
    assert ld._spec is not None and ld._spec["epoch"] == 2
    ld.set_epoch(5)
    a5 = _drain(ld)
    assert sorted(a5) == list(range(14))
    ld.close()


@pytest.mark.stream
@pytest.mark.fault
def test_epoch_prefetch_hides_decode_latency(shard_set, monkeypatch):
    """The pin the satellite asks for: with a slow decoder
    (io.decode.slow), the set_epoch boundary costs the consumer ~zero
    pool spin-up when speculation ran — and a full chunk-decode delay
    when it is disabled."""
    import time as _time
    ss, _total = shard_set
    monkeypatch.setenv("MXTPU_FAULT_DELAY_SECS", "0.3")
    fault.configure("io.decode.slow:1000")
    try:
        with stream.StreamLoader(ss, 4, decode_fn=_decode, epoch=0,
                                 rank=0, world_size=1, num_workers=1,
                                 chunk_records=16, prefetch=0) as ld:
            _drain(ld)                     # arms + starts epoch-1 work
            _time.sleep(1.3)               # the pool decodes ahead
            telemetry.reset()
            ld.set_epoch(1)
            it = iter(ld)
            t0 = _time.perf_counter()
            next(it)
            warm_dt = _time.perf_counter() - t0
            list(it)                       # drain cleanly
        assert warm_dt < 0.2, warm_dt      # never paid the 0.3s decode
        spin_p99 = telemetry.histogram("io.pool_spinup").percentile(
            0.99)
        assert spin_p99 < 0.2, spin_p99
        monkeypatch.setenv("MXTPU_STREAM_EPOCH_PREFETCH", "0")
        with stream.StreamLoader(ss, 4, decode_fn=_decode, epoch=0,
                                 rank=0, world_size=1, num_workers=1,
                                 chunk_records=16, prefetch=0) as ld0:
            _drain(ld0)
            _time.sleep(1.3)
            ld0.set_epoch(1)
            it = iter(ld0)
            t0 = _time.perf_counter()
            next(it)
            cold_dt = _time.perf_counter() - t0
            list(it)
        assert cold_dt >= 0.2, cold_dt     # the boundary pays decode
    finally:
        fault.reset()
