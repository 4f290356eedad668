"""StreamLoader: shard sets → decoded batches, through a worker pool.

The PR-1 ``gluon.data.DataLoader`` owns the *device* half of the input
pipeline: a double-buffered prefetcher overlapping batchify + host→
device transfer with device compute.  This module adds the *disk* half
in front of it — and feeds the **same** prefetcher, unchanged:

    shards on disk → decode worker pool → ordered record stream →
    batchify → ``_PrefetchIter`` (h2d overlap, ``data`` watchdog lease,
    ``data.*`` fault sites) → training loop

- **Workers** decode RecordIO/JSONL records into samples off the
  consumer thread (``MXTPU_STREAM_WORKERS``, default 2) — threads by
  default, forked processes with ``MXTPU_STREAM_WORKER_MODE=process``
  (decode is numpy/bytes work; it must never touch jax).  Queues are
  bounded; results re-order by sequence number so the delivered record
  order is bit-deterministic regardless of worker scheduling.
- **Assignment** comes from ``stream.assignment``: epoch mode applies
  the exact-once (shard, offset)-range laws; follow mode consumes an
  appending stream shard-by-shard, each shard partitioned across the
  current world.  ``cursor()`` exposes the consumed position in the
  world-agnostic resume form; folding happens when a batch is
  *delivered to the consumer*, so a cursor never claims records whose
  batches died in the prefetch queue.
- **Robustness**: a torn shard tail (crashed writer) is skipped and
  counted (``io.torn_records`` — no silent caps), worker exceptions
  re-raise at the consumption point with the worker's traceback, and
  the ``io.shard.torn`` / ``io.decode.error`` / ``io.decode.slow``
  fault sites drill each path deterministically.
- **Telemetry** (OBSERVABILITY.md): ``io.shard_open`` / ``io.decode`` /
  ``io.queue_wait`` phases, ``io.records`` / ``io.bytes`` /
  ``io.torn_records`` counters, ``io.shards_open`` gauge — the input-
  stall half of ``job_report.py``'s straggler blame.

DATA.md is the user-facing contract (env knobs, sizing, semantics).
"""
from __future__ import annotations

import json as _json
import logging
import os
import queue as _queue
import struct as _struct
import threading
import time
import traceback

from .. import fault as _fault
from .. import telemetry as _telemetry
from .. import watchdog as _watchdog
from ..base import MXNetError
from ..recordio import _LEN_MASK as _REC_LEN_MASK
from ..recordio import _MAGIC as _REC_MAGIC
from . import assignment as _assign
from .manifest import ShardSet, load_shard_set

__all__ = ["StreamLoader"]


def _env_int(name, default):
    try:
        return int(os.environ.get(name, ""))
    except ValueError:
        return default


def _env_float(name, default):
    try:
        return float(os.environ.get(name, ""))
    except ValueError:
        return default


# -- shard readers (worker side) --------------------------------------------

class _RecordIOShardReader:
    """Range reads over one RecordIO shard.  Prefers the ``.idx``
    sidecar: a contiguous record range becomes ONE seek + ONE read of
    the covering byte span, parsed in memory (frame validation per
    record, zero syscalls per record — the difference between ~4 µs and
    ~0.5 µs a record, which matters because worker threads share the
    consumer's GIL).  Falls back to a position-tracking sequential walk
    when the sidecar is missing or short.  A torn record ends the
    shard: the remainder of the requested range comes back as a torn
    count, never as garbage."""

    def __init__(self, shard):
        from .. import recordio as _recordio
        path = shard["path"]
        idx_path = os.path.splitext(path)[0] + ".idx"
        self._reader = None
        self._indexed = None
        self._offsets = None
        if os.path.isfile(idx_path):
            r = _recordio.MXIndexedRecordIO(idx_path, path, "r")
            if len(r.keys) >= shard.get("num_records", 0):
                offs = [r.idx[k] for k in r.keys]
                if offs == sorted(offs):
                    self._indexed = r
                    self._offsets = offs
                else:
                    r.close()  # unsorted offsets: no contiguous spans
            else:
                r.close()  # short sidecar (torn idx): walk sequentially
        if self._indexed is None:
            self._reader = _recordio.MXRecordIO(path, "r")
            self._pos = 0

    def _parse_blob(self, blob, path, base, count):
        """Frame-validated record parse of one in-memory byte span."""
        out = []
        pos = 0
        n = len(blob)
        for _ in range(count):
            if pos + 8 > n:
                return out, "truncated record header in %s at offset " \
                    "%d — torn tail from a crashed writer?" \
                    % (path, base + pos)
            magic, lrec = _struct.unpack_from("<II", blob, pos)
            if magic != _REC_MAGIC:
                return out, "invalid record magic 0x%08x in %s at " \
                    "offset %d" % (magic, path, base + pos)
            length = lrec & _REC_LEN_MASK
            if pos + 8 + length > n:
                return out, "truncated record payload in %s at offset " \
                    "%d — torn tail from a crashed writer?" \
                    % (path, base + pos)
            out.append(blob[pos + 8:pos + 8 + length])
            pos += 8 + length + ((-length) % 4)
        return out, None

    def read_range(self, start, stop):
        if self._indexed is not None:
            base = self._offsets[start]
            f = self._indexed.handle
            f.seek(base)
            if stop < len(self._offsets):
                blob = f.read(self._offsets[stop] - base)
            else:
                blob = f.read()
            out, err = self._parse_blob(
                blob, self._indexed.uri, base, stop - start)
            return out, (stop - start - len(out)) if err else 0, err
        r = self._reader
        if start < self._pos:
            r.reset()
            self._pos = 0
        out = []
        try:
            while self._pos < start:
                if r.read() is None:
                    return out, stop - start, \
                        "shard ended at record %d (< range start %d)" \
                        % (self._pos, start)
                self._pos += 1
            while self._pos < stop:
                rec = r.read()
                if rec is None:
                    return out, stop - self._pos, \
                        "shard ended at record %d of claimed range" \
                        % self._pos
                out.append(rec)
                self._pos += 1
            return out, 0, None
        except MXNetError as e:
            torn = stop - max(self._pos, start)
            # the torn record leaves the file position mid-frame: reset
            # so a later range re-walks from 0 and hits the same torn
            # point deterministically instead of reading garbage
            r.reset()
            self._pos = 0
            return out, torn, str(e)

    def close(self):
        for r in (self._indexed, self._reader):
            if r is not None:
                r.close()


class _JsonlShardReader:
    """Range reads over one JSONL shard (lines cached on open — stream
    shards are sized to fit host memory per DATA.md).  An unterminated
    final line is a torn tail and is never returned as a record."""

    def __init__(self, shard):
        with open(shard["path"], "rb") as f:
            data = f.read()
        lines = [ln for ln in data.split(b"\n") if ln.strip()]
        self._torn_tail = bool(data) and not data.endswith(b"\n")
        if self._torn_tail and lines:
            lines = lines[:-1]
        self._lines = lines
        self._path = shard["path"]

    def read_range(self, start, stop):
        n = len(self._lines)
        out = [self._lines[i].decode("utf-8")
               for i in range(start, min(stop, n))]
        torn = max(0, stop - max(start, n))
        err = None
        if torn:
            err = "jsonl shard %s holds %d whole line(s), range asked " \
                  "up to %d%s" % (self._path, n, stop,
                                  " (unterminated torn tail)"
                                  if self._torn_tail else "")
        return out, torn, err

    def close(self):
        self._lines = None


def _open_reader(shard):
    if shard.get("format") == "jsonl":
        return _JsonlShardReader(shard)
    return _RecordIOShardReader(shard)


def _default_decode(shard_format):
    if shard_format == "jsonl":
        return _json.loads
    return lambda raw: raw


# -- the decode worker pool --------------------------------------------------

_READER_CACHE_CAP = 8  # open readers per worker; LRU beyond this


def _run_task(task, decode_fn, decode_batch_fn, readers, worker_id):
    """One decode task on a worker: open (cached) → range read → decode.
    Returns ``(gen, seq, samples, meta)``; every failure mode that is
    not a torn tail raises (the pool converts it into the consumer
    re-raise)."""
    gen, seq, shard, shard_idx, start, stop = task
    meta = {"shard": shard_idx, "worker": worker_id, "torn": 0,
            "bytes": 0, "open_s": None, "decode_s": 0.0,
            "torn_err": None, "readers_open": len(readers)}
    if start >= stop:
        return gen, seq, [], meta
    if _fault.trigger("io.shard.torn"):
        # the drill: the whole range reads as a torn tail — skipped and
        # counted by the consumer, exactly like a real crashed-writer
        # truncation
        meta["torn"] = stop - start
        meta["torn_err"] = "[fault injection] site io.shard.torn fired " \
                           "for %s[%d:%d]" % (shard["path"], start, stop)
        return gen, seq, [], meta
    key = shard["path"]
    reader = readers.get(key)
    if reader is None:
        t0 = time.perf_counter()
        reader = _open_reader(shard)
        meta["open_s"] = time.perf_counter() - t0
        if len(readers) >= _READER_CACHE_CAP:
            old_key, old = next(iter(readers.items()))
            old.close()
            del readers[old_key]
        readers[key] = reader
    else:
        # LRU touch: re-insert at the back so active shards survive
        del readers[key]
        readers[key] = reader
    meta["readers_open"] = len(readers)
    raws, torn, torn_err = reader.read_range(start, stop)
    meta["torn"], meta["torn_err"] = torn, torn_err
    _fault.delay_if("io.decode.slow")
    _fault.check("io.decode.error",
                 "decode worker failure at %s[%d:%d]"
                 % (shard["path"], start, stop))
    t0 = time.perf_counter()
    if decode_batch_fn is not None:
        # vectorized task decode (one numpy pass over the whole chunk
        # instead of a Python call per record — the GIL these workers
        # share with the consumer is the scarce resource)
        samples = list(decode_batch_fn(raws))
        if len(samples) != len(raws):
            raise MXNetError(
                "decode_batch_fn returned %d samples for %d records"
                % (len(samples), len(raws)))
    else:
        decode = decode_fn or _default_decode(shard.get("format"))
        samples = [decode(raw) for raw in raws]
    meta["decode_s"] = time.perf_counter() - t0
    meta["bytes"] = sum(len(raw) for raw in raws)
    return gen, seq, samples, meta


def _worker_loop(worker_id, tasks, results, decode_fn, decode_batch_fn,
                 ship_exc):
    """Shared worker body (thread or forked process).  The first
    failure ships out as an error item — the exception object itself in
    thread mode (its ``__traceback__`` carries the worker frames for
    the consumer re-raise), ONLY the pre-formatted traceback strings in
    process mode (``ship_exc=False``): tracebacks don't pickle, and an
    exception object with an unpicklable attribute would be dropped by
    the mp queue's feeder thread — the error item must never be lost to
    its own transport."""
    readers = {}
    try:
        while True:
            task = tasks.get()
            if task is None:
                return
            try:
                results.put(_run_task(task, decode_fn, decode_batch_fn,
                                      readers, worker_id))
            except BaseException as e:  # noqa: BLE001 — re-raised there
                results.put(("__err__", task[0],
                             e if ship_exc else None,
                             traceback.format_exc(),
                             "%s: %s" % (type(e).__name__, e)))
                return
    finally:
        for r in readers.values():
            try:
                r.close()
            except Exception:
                pass


class _DecodePool:
    """N decode workers around bounded queues, shared across a loader's
    iterations (readers stay open, threads stay warm — a per-epoch
    respawn would re-pay thread spin-up and shard opens every epoch).
    Items are tagged with an iteration *generation*: ``begin()`` bumps
    it and drops whatever an abandoned iteration left queued, so stale
    in-flight results can never leak into the next epoch's order.

    ``mode`` is ``thread`` (default) or ``process`` (``fork`` — workers
    inherit the parent's decode closure and fault rules; they must
    never touch jax, and on platforms without fork the pool falls back
    to threads)."""

    def __init__(self, decode_fn, decode_batch_fn, num_workers, mode,
                 depth):
        self.num_workers = max(1, int(num_workers))
        self.depth = max(1, int(depth))
        self.window = self.depth + self.num_workers
        self.mode = mode
        self.gen = 0
        self._workers = []
        # a worker exits permanently after its first error; that exit
        # is recorded HERE (set when its __err__ item is consumed, any
        # generation) rather than inferred from is_alive() — the error
        # item lands on the queue BEFORE the thread terminates, so an
        # aliveness probe right after the re-raise races the scheduler
        self._degraded = False
        # items a SUPERSEDED consumer dequeued that belong to a newer
        # iteration: pushed back here (never dropped — the live
        # consumer would wait forever on the stolen sequence number)
        self._returns = []
        self._returns_lock = threading.Lock()
        if mode == "process":
            import multiprocessing as mp
            try:
                ctx = mp.get_context("fork")
            except ValueError:
                logging.warning(
                    "mxnet_tpu.stream: no fork start method on this "
                    "platform — decode workers fall back to threads")
                self.mode = mode = "thread"
        if mode == "process":
            self._tasks = ctx.Queue()
            self._results = ctx.Queue(maxsize=self.depth)
            spawn = lambda i: ctx.Process(  # noqa: E731
                target=_worker_loop,
                args=(i, self._tasks, self._results, decode_fn,
                      decode_batch_fn, False), daemon=True)
        else:
            self._tasks = _queue.Queue()
            self._results = _queue.Queue(maxsize=self.depth)
            spawn = lambda i: threading.Thread(  # noqa: E731
                target=_worker_loop,
                args=(i, self._tasks, self._results, decode_fn,
                      decode_batch_fn, True),
                daemon=True, name="mxtpu-stream-decode-%d" % i)
        for i in range(self.num_workers):
            w = spawn(i)
            w.start()
            self._workers.append(w)

    def begin(self):
        """Start a new iteration: bump the generation and drop tasks an
        abandoned iteration left queued (results already in flight are
        discarded by the generation filter in :meth:`get`).  Tasks
        already tagged with the NEW generation survive the drain — the
        epoch prefetch-ahead path submits the next epoch's first
        chunks under ``gen + 1`` before the iteration that will
        consume them begins, and dropping them would strand their
        sequence numbers forever."""
        self.gen += 1
        keep = []
        while True:
            try:
                item = self._tasks.get_nowait()
            except _queue.Empty:
                break
            if item[0] >= self.gen:
                keep.append(item)
        for item in keep:
            self._tasks.put(item)
        return self.gen

    def submit(self, gen, task_tail):
        self._tasks.put((gen,) + task_tail)

    def alive(self):
        return any(w.is_alive() for w in self._workers)

    def full_strength(self):
        """No worker has errored out and every worker is alive — a pool
        that survived an error is degraded and the loader rebuilds it
        at the next iteration rather than silently running at reduced
        decode throughput forever."""
        return bool(self._workers) and not self._degraded and \
            all(w.is_alive() for w in self._workers)

    @staticmethod
    def _item_gen(item):
        return item[1] if item[0] == "__err__" else item[0]

    def _take_return(self, gen):
        """Pop a pushed-back item of generation ``gen`` (pruning older
        leftovers an abandoned iteration will never collect)."""
        with self._returns_lock:
            self._returns = [i for i in self._returns
                             if self._item_gen(i) >= gen]
            for k, item in enumerate(self._returns):
                if self._item_gen(item) == gen:
                    return self._returns.pop(k)
        return None

    def _push_return(self, item):
        with self._returns_lock:
            self._returns.append(item)

    def get(self, gen):
        """Next result of generation ``gen`` (any order).  Stale-
        generation items are dropped; a NEWER-generation item here
        means another iteration superseded this consumer (one live
        iteration per loader — documented contract): the item is
        pushed back for the live consumer — never dropped — and THIS
        caller raises.  Raises the worker's failure at the consumption
        point — thread mode re-raises the original exception object
        (worker frames intact), process mode wraps the shipped
        traceback text.  A silently-dead worker pool (killed child)
        surfaces as MXNetError instead of a hang."""
        while True:
            item = self._take_return(gen)
            if item is None:
                try:
                    item = self._results.get(timeout=0.5)
                except _queue.Empty:
                    if not self.alive() and self._results.empty():
                        raise MXNetError(
                            "stream decode worker pool died without "
                            "reporting an error (killed process?)")
                    continue
            item_gen = self._item_gen(item)
            if item_gen > gen:
                # a newer-generation item in this consumer's hands:
                # hand it back either way — but it only means THIS
                # consumer is superseded when a newer iteration
                # actually began (pool.gen moved past ours).  The
                # other source of ahead-of-generation items is the
                # epoch prefetch-ahead (next epoch's chunks decoded
                # under gen+1 while this iteration drains its tail):
                # those belong to the NEXT consumer, not to anyone
                # superseding us.
                self._push_return(item)
                if self.gen > gen:
                    raise MXNetError(
                        "stream iteration superseded: a newer "
                        "iteration of this StreamLoader was started "
                        "(one live iteration per loader)")
                continue
            if isinstance(item, tuple) and item and item[0] == "__err__":
                _, err_gen, exc, tb_text, summary = item
                self._degraded = True  # its worker exits after this item
                if err_gen < gen:
                    # an abandoned iteration's worker died on a stale
                    # task: the pool shrank, but this iteration's data
                    # was never touched by it
                    logging.warning(
                        "mxnet_tpu.stream: decode worker died on a "
                        "stale-generation task: %s", summary)
                    continue
                if isinstance(exc, BaseException):
                    raise exc  # thread mode: original object + traceback
                raise MXNetError(
                    "stream decode worker failed: %s\n--- worker "
                    "traceback ---\n%s" % (summary, tb_text))
            if item_gen < gen:
                continue  # stale result from an abandoned iteration
            return item[1], item[2], item[3]

    def close(self):
        """Retire the workers: sentinel per worker, drain the bounded
        result queue so nobody stays wedged on a full put, bounded
        joins (a process that ignores them is terminated)."""
        for _ in self._workers:
            try:
                self._tasks.put(None)
            except Exception:
                pass
        deadline = time.monotonic() + 5.0
        for w in self._workers:
            while w.is_alive() and time.monotonic() < deadline:
                # keep the result queue draining so a worker blocked on
                # put() can reach its sentinel
                try:
                    self._results.get_nowait()
                    continue
                except _queue.Empty:
                    pass
                w.join(timeout=0.05)
            if w.is_alive() and hasattr(w, "terminate"):
                w.terminate()
        while True:
            try:
                self._results.get_nowait()
            except _queue.Empty:
                break
        with self._returns_lock:
            self._returns = []
        self._workers = []


# -- the loader --------------------------------------------------------------

class StreamLoader:
    """Batches from a shard set, exact-once across the elastic world.

    Two modes:

    - ``mode="epoch"`` (default): one finite pass per epoch over the
      shard set as pinned at ``set_epoch`` time, shards ordered by the
      epoch permutation, this rank's contiguous position span read as
      (shard, offset) ranges.  ``set_epoch(e)`` re-pins (an appending
      manifest is picked up at the next epoch); ``resume=`` takes a
      full cursor set and continues the interrupted epoch at ANY world
      size.
    - ``mode="follow"``: a continual stream — shards consumed once in
      publication order, each partitioned across the world; blocks
      (polling ``refresh()``) while the writer is ahead, ends when the
      manifest is sealed.  ``resume=`` re-partitions every old rank's
      un-consumed remainder.

    ``decode_fn(raw)`` maps one raw record (RecordIO payload bytes /
    JSONL line string) to a sample (anything the batchify accepts);
    defaults: raw bytes for RecordIO, ``json.loads`` for JSONL.

    Iteration yields device-prefetched batches through the PR-1
    ``_PrefetchIter`` (prefetch depth per ``MXTPU_DATA_PREFETCH``);
    ``cursor()`` is the world-agnostic resume stamp, advanced only when
    a batch is *delivered* to the caller.
    """

    def __init__(self, shard_set, batch_size, decode_fn=None,
                 decode_batch_fn=None, mode="epoch", epoch=0, rank=None,
                 world_size=None, seed=None, num_workers=None,
                 worker_mode=None, queue_depth=None, chunk_records=None,
                 prefetch=None, last_batch="keep", poll_secs=None,
                 batchify_fn=None, resume=None):
        from ..gluon.data import dataloader as _dl
        if isinstance(shard_set, str):
            shard_set = load_shard_set(shard_set)
        if not isinstance(shard_set, ShardSet):
            raise MXNetError("shard_set must be a ShardSet or a "
                             "manifest path, got %r" % (shard_set,))
        if mode not in ("epoch", "follow"):
            raise MXNetError("mode must be 'epoch' or 'follow'")
        if last_batch not in ("keep", "discard"):
            raise MXNetError("last_batch must be 'keep' or 'discard'")
        self._set = shard_set
        self._batch_size = int(batch_size)
        self._decode_fn = decode_fn
        self._decode_batch_fn = decode_batch_fn
        self._pool = None
        self._mode = mode
        if rank is None or world_size is None:
            from .. import elastic as _elastic
            mem = _elastic.membership()
            rank = mem["rank"] if rank is None else rank
            world_size = mem["world_size"] if world_size is None \
                else world_size
        self._rank, self._world = int(rank), int(world_size)
        self._seed = seed
        self._workers = num_workers if num_workers is not None \
            else _env_int("MXTPU_STREAM_WORKERS", 2)
        self._worker_mode = worker_mode or os.environ.get(
            "MXTPU_STREAM_WORKER_MODE", "thread")
        self._depth = queue_depth if queue_depth is not None \
            else _env_int("MXTPU_STREAM_QUEUE_DEPTH", 4)
        self._chunk = max(1, chunk_records if chunk_records is not None
                          else _env_int("MXTPU_STREAM_CHUNK_RECORDS", 64))
        self._prefetch = max(0, int(
            prefetch if prefetch is not None else _dl._default_prefetch()))
        self._last_batch = last_batch
        self._poll_secs = poll_secs if poll_secs is not None \
            else _env_float("MXTPU_STREAM_POLL_SECS", 0.2)
        self._batchify = batchify_fn or _dl.default_batchify_fn
        self._dl = _dl
        self._torn_warned = set()
        self._open_by_worker = {}
        # epoch-boundary prefetch-ahead (ISSUE 14 satellite): once this
        # rank's epoch-N spans are exhausted, the otherwise-idle decode
        # pool starts on epoch N+1's first chunks under the NEXT
        # iteration generation; set_epoch's re-pin is validated against
        # the speculation before the results are consumed (generation
        # tagging makes a wrong guess safe — it is simply discarded)
        self._epoch_prefetch = _env_int("MXTPU_STREAM_EPOCH_PREFETCH",
                                        1) > 0
        self._spec = None
        if mode == "epoch":
            self.set_epoch(epoch, resume=resume)
        else:
            self._shard_idx = 0
            self._consumed = 0
            self._assigned = {}
            if resume is not None:
                self._shard_idx, self._assigned = _assign.follow_resume(
                    resume, self._set.sizes, self._rank, self._world)

    # -- assignment state ----------------------------------------------------
    def set_epoch(self, epoch, resume=None):
        """Pin epoch ``epoch``'s assignment against the CURRENT shard
        list (refreshing the manifest first — this is where an appended
        shard enters coverage).  ``resume`` is a complete cursor set
        from a prior attempt of the SAME epoch: the remainder is
        re-partitioned for this rank at this world size — against the
        SHARD-SET SNAPSHOT the cursors were cut under (stamped into
        every epoch cursor), never the refreshed one: positions are
        meaningless under a different shard count/permutation, so a
        manifest that grew mid-epoch enters coverage at the NEXT epoch,
        and one that rewrote committed history is rejected."""
        if self._mode != "epoch":
            raise MXNetError("set_epoch on a follow-mode StreamLoader")
        self._set.refresh()
        self._epoch = int(epoch)
        self._sizes = self._set.sizes
        if resume is not None:
            for c in resume:
                if c.get("epoch") != self._epoch:
                    raise MXNetError(
                        "resume cursor is for epoch %s, not %d"
                        % (c.get("epoch"), self._epoch))
            snaps = {tuple(c.get("sizes") or ()) for c in resume}
            if len(snaps) != 1:
                raise MXNetError(
                    "resume cursors disagree on the shard-set snapshot "
                    "— not one consistent generation")
            snap = list(snaps.pop())
            if snap:
                if snap != self._sizes[:len(snap)]:
                    raise MXNetError(
                        "shard set changed incompatibly under the "
                        "cursors (snapshot sizes %s vs current %s): "
                        "committed history was rewritten, positions "
                        "cannot be mapped" % (snap, self._sizes))
                self._sizes = snap
            self._spans = _assign.resume_spans(resume, self._rank,
                                               self._world)
        else:
            lo, hi = _assign.span_for_rank(
                sum(self._sizes), self._rank, self._world)
            self._spans = [(lo, hi)] if hi > lo else []
        self._consumed = 0

    def cursor(self):
        """The world-agnostic resume stamp of what this loader has
        DELIVERED (batches handed to the caller — never prefetch-queue
        residents).  Pair it with the checkpoint the same cadence
        writes: ``CursorStore.save(generation, loader.cursor())``."""
        base = {"rank": self._rank, "world_size": self._world,
                "mode": self._mode}
        if self._mode == "epoch":
            base.update({"epoch": self._epoch,
                         "spans": [list(p) for p in self._spans],
                         "consumed": self._consumed,
                         # the snapshot positions are relative to — a
                         # resume must re-pin to exactly this view
                         "sizes": list(self._sizes)})
            return base
        sizes = self._set.sizes
        s = self._shard_idx
        if s < len(sizes):
            # membership check, NOT `or`: an empty override means "this
            # rank owns nothing of this shard" — falling through to the
            # fresh law would re-consume records another rank owns
            if str(s) in self._assigned:
                spans = self._assigned[str(s)]
            else:
                spans = [list(p) for p in _assign.follow_spans(
                    sizes[s], self._rank, self._world)]
        else:
            spans = []
        base.update({
            "shard": s, "spans": [list(p) for p in spans],
            "consumed": self._consumed,
            "assigned": {k: v for k, v in self._assigned.items()
                         if int(k) >= s},
        })
        return base

    def _fold(self, attrib):
        """Advance the durable cursor over delivered/ skipped records —
        called exactly when a batch crosses into the caller's hands."""
        if self._mode == "epoch":
            self._consumed += sum(n for _s, n in attrib)
            return
        for shard, n in attrib:
            if shard != self._shard_idx:
                for k in [k for k in self._assigned if int(k) < shard]:
                    del self._assigned[k]
                self._shard_idx = shard
                self._consumed = 0
            self._consumed += n

    # -- task generation -----------------------------------------------------
    def _chunks(self, ranges):
        for shard_idx, start, stop in ranges:
            shard = self._set.shards[shard_idx]
            for a in range(start, stop, self._chunk):
                yield (shard, shard_idx, a, min(a + self._chunk, stop))

    def _task_iter(self):
        if self._mode == "epoch":
            spans = _assign.slice_spans(
                self._spans, self._consumed,
                sum(b - a for a, b in self._spans))
            ranges = _assign.spans_to_ranges(self._sizes, self._epoch,
                                             spans, self._seed)
            for task in self._chunks(ranges):
                yield task
            return
        # follow mode: local pointers start at the durable cursor and
        # run ahead; the durable state advances at delivery (self._fold)
        s, skip = self._shard_idx, self._consumed
        while True:
            sizes = self._set.sizes
            if s >= len(sizes):
                if self._set.refresh():
                    continue
                if self._set.closed:
                    return
                yield None  # lull: writer hasn't published more yet
                continue
            # membership check, NOT `or`: an empty override means this
            # rank owns nothing of shard s (see cursor())
            if str(s) in self._assigned:
                spans = [tuple(p) for p in self._assigned[str(s)]]
            else:
                spans = _assign.follow_spans(sizes[s], self._rank,
                                             self._world)
            total = sum(b - a for a, b in spans)
            rem = _assign.slice_spans(spans, min(skip, total), total)
            if rem:
                for task in self._chunks([(s, a, b) for a, b in rem]):
                    yield task
            else:
                # a shard this rank owns nothing of must still advance
                # the cursor — as an IN-ORDER marker through the result
                # stream, never by mutating the durable state from this
                # read-ahead generator (deliveries for earlier shards
                # may still be in flight behind it)
                yield ("__skip__", s)
            s, skip = s + 1, 0

    # -- the ordered record/batch stream -------------------------------------
    def _ensure_pool(self):
        if self._pool is not None and self._pool.full_strength():
            return self._pool
        if self._pool is not None:
            self._pool.close()
        self._pool = _DecodePool(self._decode_fn, self._decode_batch_fn,
                                 self._workers, self._worker_mode,
                                 self._depth)
        return self._pool

    def close(self):
        """Retire the worker pool.  Idempotent; GC calls it too (also
        on a half-constructed instance whose __init__ raised before
        the pool slot existed), but a long-lived process cycling
        loaders should call it (or use the loader as a context
        manager) rather than waiting for GC."""
        pool = getattr(self, "_pool", None)
        self._pool = None
        if pool is not None:
            pool.close()

    __del__ = close

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- epoch-boundary prefetch-ahead ---------------------------------------
    def _spec_matches(self, spec, pool):
        """Does a recorded speculation describe EXACTLY the iteration
        about to run?  Inputs fully determine the task stream
        (``spans_to_ranges`` is pure), so matching inputs means the
        pre-submitted chunks are the iteration's true prefix."""
        return (spec is not None and self._mode == "epoch"
                and spec["pool"] == id(pool)
                and spec["epoch"] == self._epoch
                and spec["sizes"] == self._sizes
                and spec["spans"] == self._spans
                and spec["rank"] == self._rank
                and spec["world"] == self._world
                and self._consumed == 0)

    def _speculate(self, pool, gen):
        """This rank's epoch-N spans are exhausted and the pool is
        about to idle through ``set_epoch``: submit epoch N+1's first
        assigned chunks (the fresh law — a resume or a grown manifest
        invalidates the guess at the next iteration) under ``gen+1``,
        the generation the NEXT iteration's ``begin()`` will mint."""
        if not (self._epoch_prefetch and self._mode == "epoch"
                and pool.full_strength()):
            return
        next_epoch = self._epoch + 1
        sizes = self._sizes
        lo, hi = _assign.span_for_rank(sum(sizes), self._rank,
                                       self._world)
        spans = [(lo, hi)] if hi > lo else []
        if not spans:
            return
        ranges = _assign.spans_to_ranges(sizes, next_epoch, spans,
                                         self._seed)
        tasks, keys = [], []
        for task in self._chunks(ranges):
            if len(tasks) >= pool.window:
                break
            tasks.append(task)
            keys.append((task[1], task[2], task[3]))
        if not tasks:
            return
        for seq, task in enumerate(tasks):
            pool.submit(gen + 1, (seq,) + task)
        _telemetry.counter("io.epoch_prefetch").inc(len(tasks))
        self._spec = {"pool": id(pool), "gen": gen + 1,
                      "epoch": next_epoch, "sizes": list(sizes),
                      "spans": [(lo, hi)], "rank": self._rank,
                      "world": self._world, "keys": keys}

    def _adopt_speculation(self, pool, gen):
        """Called at iteration start (after ``begin()``): if the
        recorded speculation IS this iteration's prefix, return its
        chunk keys (the first ``len(keys)`` tasks are already in the
        pool under this generation); otherwise discard it — one more
        ``begin()`` makes the stale results unconsumable."""
        spec, self._spec = self._spec, None
        if spec is None:
            return gen, []
        if spec["gen"] == gen and self._spec_matches(spec, pool):
            _telemetry.counter("io.epoch_prefetch_hits").inc(
                len(spec["keys"]))
            return gen, spec["keys"]
        return pool.begin(), []

    def _results(self, pool, gen, preloaded=()):
        """Submit tasks into the pool (bounded window) and yield result
        items strictly in sequence order — byte-deterministic delivery
        no matter how workers interleave.  ``preloaded`` chunk keys
        were already submitted under this generation by the previous
        iteration's epoch prefetch-ahead: the iterator's first tasks
        are verified against them and NOT re-submitted."""
        tasks = self._task_iter()
        reorder = {}
        next_seq = 0
        submitted = len(preloaded)
        exhausted = False
        first_wait = True
        speculated = False
        for key in preloaded:
            t = next(tasks, None)
            actual = None if t is None or t[0] == "__skip__" \
                else (t[1], t[2], t[3])
            if actual != key:
                # inputs matched, so the pure task derivation cannot
                # diverge — reaching here is an internal bug, and
                # serving a mis-attributed chunk would silently break
                # exact-once; fail loudly instead
                raise MXNetError(
                    "epoch prefetch-ahead speculation diverged from "
                    "the live task stream (%r vs %r) — internal "
                    "invariant broken" % (key, actual))
        while True:
            while not exhausted and submitted - next_seq < pool.window:
                try:
                    t = next(tasks)
                except StopIteration:
                    exhausted = True
                    break
                if t is None:
                    break  # stream lull — no task to hand out yet
                if t[0] == "__skip__":
                    # zero-record shard for this rank: a local in-order
                    # marker, no pool round-trip
                    reorder[submitted] = ([], {
                        "shard": t[1], "worker": -1, "torn": 0,
                        "bytes": 0, "open_s": None, "decode_s": 0.0,
                        "torn_err": None, "readers_open": 0})
                    submitted += 1
                    continue
                pool.submit(gen, (submitted,) + t)
                submitted += 1
            if exhausted and not speculated:
                # the pool would idle through set_epoch: start on the
                # next epoch's first chunks while this iteration's
                # tail drains (their results are tagged gen+1 — the
                # next iteration consumes or discards them)
                speculated = True
                self._speculate(pool, gen)
            if next_seq == submitted:
                if exhausted:
                    return
                if pool.gen != gen:
                    # superseded mid-lull: an abandoned producer must
                    # not poll (and keep the "data" lease alive) forever
                    raise MXNetError(
                        "stream iteration superseded: a newer "
                        "iteration of this StreamLoader was started "
                        "(one live iteration per loader)")
                # follow-mode lull: the writer is ahead of us.  This
                # loop just POLLED the manifest — demonstrable liveness
                # — so renew the consumer's "data" lease (primary=False,
                # like the prefetcher's per-batch renewal): an armed
                # watchdog must not declare a healthy continual job
                # hung because its upstream paused between publishes
                _watchdog.renew("data", phase="stream-lull",
                                primary=False)
                time.sleep(self._poll_secs)
                continue
            while next_seq not in reorder:
                t0 = time.perf_counter()
                seq, samples, meta = pool.get(gen)
                dt = time.perf_counter() - t0
                # the FIRST wait of an iteration covers ramp-up —
                # startup, not steady state (the steptrace warmup
                # convention); it gets its own phase so the p99 of
                # io.queue_wait states steady-state starvation
                _telemetry.observe_phase(
                    "io.pool_spinup" if first_wait else "io.queue_wait",
                    dt)
                first_wait = False
                reorder[seq] = (samples, meta)
            samples, meta = reorder.pop(next_seq)
            next_seq += 1
            self._note(meta, samples)
            yield samples, meta

    def _note(self, meta, samples):
        """Consumer-side telemetry fold: counters plus the worker-
        measured phase durations (workers may be separate PROCESSES
        whose registries die with them, so durations ride the result
        and land in this process's histograms)."""
        if samples:
            _telemetry.counter("io.records").inc(len(samples))
        if meta["bytes"]:
            _telemetry.counter("io.bytes").inc(meta["bytes"])
        if meta["open_s"] is not None:
            _telemetry.observe_phase("io.shard_open", meta["open_s"])
        if samples or meta["decode_s"]:
            _telemetry.observe_phase("io.decode", meta["decode_s"])
        self._open_by_worker[meta["worker"]] = meta["readers_open"]
        _telemetry.gauge("io.shards_open").set(
            sum(self._open_by_worker.values()))
        if meta["torn"]:
            _telemetry.counter("io.torn_records").inc(meta["torn"])
            shard = meta["shard"]
            if shard not in self._torn_warned:
                self._torn_warned.add(shard)
                logging.warning(
                    "mxnet_tpu.stream: skipping %d torn record(s) in "
                    "shard %d (%s) — counted in io.torn_records",
                    meta["torn"], shard, meta["torn_err"])

    def _make_batches(self):
        """The producer generator ``_PrefetchIter`` wraps: yields
        ``(batch, attrib)`` pairs — the attribution rides OUTSIDE the
        batch so the delivery-side wrapper can fold the cursor exactly
        when the caller receives the batch."""
        pool = self._ensure_pool()
        gen = pool.begin()
        gen, preloaded = self._adopt_speculation(pool, gen)
        batches = _telemetry.counter("data.batches")
        B = self._batch_size
        try:
            # attribution entries are [shard, records, samples]:
            # decoded chunks carry records == samples, torn tails carry
            # records > 0 with 0 samples, skip markers 0/0 — so a batch
            # boundary can be cut at B SAMPLES while the cursor folds
            # RECORDS (torn records advance it without data)
            buf, attrib = [], []
            for samples, meta in self._results(pool, gen, preloaded):
                shard = meta["shard"]
                if samples:
                    buf.extend(samples)
                    attrib.append([shard, len(samples), len(samples)])
                if meta["torn"]:
                    attrib.append([shard, meta["torn"], 0])
                elif not samples:
                    # skip marker (a shard this rank owns nothing of):
                    # zero-record attribution advances the shard pointer
                    # in delivery order
                    attrib.append([shard, 0, 0])
                while len(buf) >= B:
                    with _telemetry.span("data.batchify", cat="data"):
                        out = self._batchify(buf[:B])
                    del buf[:B]
                    # cut the attribution at the batch's last sample;
                    # markers positioned after it ride the next batch
                    take, left, need = [], [], B
                    for shard_i, n_rec, n_smp in attrib:
                        if need == 0:
                            left.append([shard_i, n_rec, n_smp])
                        elif n_smp <= need:
                            take.append((shard_i, n_rec))
                            need -= n_smp
                        else:
                            take.append((shard_i, need))
                            left.append([shard_i, n_rec - need,
                                         n_smp - need])
                            need = 0
                    attrib = left
                    batches.inc()
                    yield out, take
            tail = [(s, n) for s, n, _smp in attrib]
            if buf and self._last_batch == "keep":
                with _telemetry.span("data.batchify", cat="data"):
                    out = self._batchify(buf)
                batches.inc()
                yield out, tail
            elif tail:
                # trailing torn records (or a discarded partial batch)
                # still count as covered — deliver the attribution on
                # an empty marker so the cursor reaches the end
                yield None, tail
        finally:
            # the pool persists across iterations (warm threads, open
            # readers); begin() on the next pass discards anything this
            # one left in flight
            pass

    def __iter__(self):
        bare = self._prefetch == 0
        if bare:
            inner = self._make_batches()
        else:
            inner = self._dl._PrefetchIter(self._make_batches,
                                           self._prefetch)

        def deliver():
            # prefetch=0 has no _PrefetchIter to own the "data" lease
            # lifecycle, so this wrapper does: renew per delivered
            # batch, release at iteration end — otherwise the lull
            # branch's renewal would CREATE a lease nothing ever
            # renews or retires, and an armed watchdog would kill a
            # healthy streaming job for it
            try:
                for batch, attrib in inner:
                    self._fold(attrib)
                    if batch is not None:
                        if bare:
                            _watchdog.renew("data", phase="data",
                                            primary=False)
                        yield batch
            finally:
                if bare:
                    _watchdog.release("data")
        return deliver()

    def __len__(self):
        if self._mode != "epoch":
            raise TypeError("a follow-mode stream has no length")
        n = sum(b - a for a, b in self._spans)
        if self._last_batch == "discard":
            return n // self._batch_size
        return (n + self._batch_size - 1) // self._batch_size
