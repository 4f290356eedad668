"""Multi-replica router: spread, retry-on-failover, at-most-once decode.

The fleet front-door (ISSUE 11, ROADMAP item 1): requests enter HERE,
are journaled under a router-scoped request id, and are placed on the
least-loaded live replica.  The survivability contract:

- **zero dropped accepted requests** — a replica dying mid-decode
  (:class:`~mxnet_tpu.serving.replica.ReplicaLost`, e.g. the
  ``serve.replica.lost`` drill) fails its incomplete requests over to a
  live replica; decode is per-request deterministic (greedy argmax, or
  the seeded per-request sampling law), so the re-run reproduces the
  victim's tokens and the caller never observes the failover beyond
  latency.  Honest caveat (SERVING.md §2b): a survivor whose
  prefix-cache state differs from the victim's computes first-token
  logits through a different float program (suffix vs dense prefill,
  ~1-ulp apart); token equality across cache states is an empirical
  robustness property pinned by the seeded drills, not an algebraic
  identity;
- **at-most-once decode** — the journal is the authority: a request
  recorded ``completed`` is NEVER re-executed, even when the replica it
  ran on dies later; a mid-flight victim's partial tokens are discarded
  and the request decodes exactly once more (bounded by
  ``max_retries``, then verdict ``retries_exhausted`` — bounded-retry,
  never a hang);
- **typed refusals spread** — a replica that sheds (SLO) or is draining
  refuses with a typed verdict; placement tries every live replica in
  load order before giving up, so one overloaded replica doesn't turn
  into a fleet-wide refusal;
- **replacement spin-up** — an optional ``spawn`` callback builds a
  fresh replica on failover (the PR-6 elastic replace move).  With a
  shared AOT cache / in-process memo the replacement comes up warm: 0
  foreground compiles before its first token (asserted by
  tests/serving_surv_driver.py ``section_router``);
- **fencing** (ISSUE 17) — every placement is stamped with the
  target's incarnation and the slot's fencing epoch; a failover bumps
  the victim slot's epoch and enrolls the abandoned handles in a
  bounded zombie watch.  A "dead" replica that was actually alive
  behind a partition and finishes its work late gets that completion
  REJECTED at the router (typed ``fenced`` verdict event +
  ``rpc.fenced_results`` counter; journal replay treats ``fenced``
  lines as non-terminal) — the split-brain case can be OBSERVED
  violating nothing, instead of trusted not to happen.

The journal can additionally be mirrored to a JSON-lines file
(``journal_path``; defaults to ``$MXTPU_SERVE_JOURNAL`` — the
tools/launch.py run-dir layout puts it next to the replica telemetry
streams) — one line per transition (accept / complete / failover /
retry / terminal verdict), the auditable "every accepted request
completed exactly once" record the e2e drill greps.  Each line is ONE
``os.write`` on an O_APPEND fd (the PR-8 emitter discipline): a crash
mid-write can truncate the FILE at a line boundary, never tear a line
into two readers' worth of garbage — ``serve_report`` still
skips-and-counts anything unparseable (no silent caps).

Request-scope tracing (ISSUE 13): ``submit`` mints the trace id and
passes it through every placement, so a failover re-decode on a
survivor replica continues the SAME trace (linked ``retry`` event);
journal lines carry the trace id, and the Router stamps the one FINAL
verdict event per trace (engine-level refusals on a spread are hops,
not terminals).

Replicas are duck-typed (``replica_id`` / ``alive`` / ``draining`` /
``load`` / ``idle`` / ``submit`` / ``step``): the in-process
:class:`~mxnet_tpu.serving.replica.ServingReplica` today, an RPC proxy
tomorrow.  Telemetry: ``router.requests`` / ``router.failovers`` /
``router.retries`` / ``router.replacements`` / ``router.refused``
counters, ``router.live_replicas`` gauge.
"""
from __future__ import annotations

import json
import os
import time

from .. import telemetry as _telemetry
from ..base import MXNetError
from .replica import ReplicaLost
from .scheduler import (CANCELLED, EXPIRED, FAILED, FINISHED, REJECTED,
                        SHED, SamplingParams, VERDICT_REJECTED)

__all__ = ["Router", "RouterRequest"]

#: router-request terminal verdict when every retry is burned
VERDICT_RETRIES_EXHAUSTED = "retries_exhausted"
VERDICT_NO_REPLICAS = "no_live_replicas"

#: engine states that are terminal-but-not-success (propagated verdicts)
_TERMINAL_FAILURES = (REJECTED, EXPIRED, FAILED, SHED, CANCELLED)


def _np_size(prompt):
    """Prompt length without importing numpy here (prompts are arrays
    or plain sequences — the router never touches their contents)."""
    size = getattr(prompt, "size", None)
    return len(prompt) if size is None else size


class RouterRequest:
    """The caller's handle: journaled id, terminal state + typed
    verdict, and the completed token list.  ``tokens`` is only
    populated at COMPLETION (a failover discards a victim's partial
    tokens — the re-run regenerates them deterministically)."""

    __slots__ = ("rid", "prompt", "max_new", "deadline_s", "deadline_t",
                 "state", "verdict", "error", "tokens", "replica_id",
                 "retries", "trace", "sampling", "spec_k", "_live",
                 "_home", "_placed_inc")

    def __init__(self, rid, prompt, max_new, deadline_s):
        self.rid = rid
        self.prompt = prompt
        self.max_new = max_new
        self.deadline_s = deadline_s
        # the deadline is ABSOLUTE from original submission: a failover
        # re-placement passes the REMAINING budget, never a fresh one —
        # retries must not multiply the caller's end-to-end bound
        self.deadline_t = (None if deadline_s is None
                           else time.perf_counter() + float(deadline_s))
        self.state = "submitted"
        self.verdict = None
        self.error = None
        self.tokens = None
        self.replica_id = None  # journal/display only — never identity
        self.retries = 0
        self.trace = None       # request-scope trace id (router-minted)
        self.sampling = None    # per-request SamplingParams (or None);
                                # a failover re-placement carries the
                                # SAME params + seed, so the re-decode
                                # is bit-identical (determinism law)
        self.spec_k = None      # per-request spec-decode cap (ISSUE
                                # 16); a scheduling knob only — carried
                                # through failover like sampling, but
                                # the token stream is identical at ANY
                                # spec_k (acceptance is exact)
        self._live = None      # the engine Request currently decoding
        self._home = None      # the replica OBJECT it decodes on (ids
                               # are caller-supplied and may collide)
        self._placed_inc = None  # fencing token: the target's
                                 # incarnation stamp at placement

    @property
    def done(self):
        return self.state not in ("submitted", "accepted")


class Router:
    def __init__(self, replicas, spawn=None, max_retries=1,
                 journal_path=None, journal_retention=4096,
                 fence_watch_s=30.0, telemetry_dir=None,
                 telemetry_interval_s=2.0):
        self._replicas = list(replicas)
        self._spawn = spawn
        self.max_retries = int(max_retries)
        self._journal = {}           # rid -> RouterRequest
        self._inflight = set()       # rids currently accepted somewhere
        # -- fencing (ISSUE 17): per-slot epochs + the zombie watch --
        # every failover bumps the victim slot's epoch; the victims'
        # abandoned handles are WATCHED (bounded by fence_watch_s) so a
        # zombie that finishes them behind a partition gets its late
        # completion observed and REJECTED with the typed ``fenced``
        # verdict event, instead of silently never being read — the
        # at-most-once law stays auditable, not merely structural
        self._fence_epoch = {}       # slot key -> fencing epoch
        self._fenced = []            # [{rr, mirror, proxy, ...}]
        self.fence_watch_s = float(fence_watch_s)
        # run-dir layout default (tools/launch.py exports it next to
        # the replica telemetry streams — serve_report's input contract)
        self._journal_path = (journal_path if journal_path is not None
                              else os.environ.get("MXTPU_SERVE_JOURNAL")
                              or None)
        #: terminal entries kept in memory (None = unbounded).  The
        #: in-memory journal only needs to cover in-flight work plus a
        #: recent-history window; the JSONL file (journal_path) is the
        #: durable all-time audit record — without a bound a long-lived
        #: router pins every prompt + token list it ever served.
        self.journal_retention = (None if journal_retention is None
                                  else max(1, int(journal_retention)))
        # -- fleet telemetry collector (ISSUE 18): when given a dir,
        # the router host periodically pulls every RPC replica's
        # telemetry over the wire and appends the returned lines to
        # <dir>/stream-<replica_id>.jsonl — the same layout
        # serve_report/telemetry_report already read, assembled with
        # ZERO shared-filesystem telemetry reads
        self.telemetry_dir = telemetry_dir
        self.telemetry_interval_s = float(telemetry_interval_s)
        self._tel_cursors = {}       # replica_id -> client-held cursor
        self._next_tel_pull = 0.0
        self._next_rid = 0
        self.failovers = 0
        self._gauge_live()

    # -- journal -----------------------------------------------------------
    def _log(self, event, rr, **extra):
        """One audit line, written as a SINGLE ``os.write`` on an
        O_APPEND fd (the PR-8 emitter discipline): a buffered writer
        flushes in stdio-chunk units, and a crash between chunks used to
        leave a torn line that poisoned the whole file for naive
        readers — a single append either lands whole or not at all, so
        a crash can truncate the journal, never tear it mid-line
        (serve_report still skips-and-counts the unparseable, because
        other writers make no such promise).  Open-per-line like the
        emitter: journal lines are per request TRANSITION, not per
        token, and a cached fd would leak one descriptor per journaled
        Router for the life of the process."""
        if not self._journal_path:
            return
        line = {"t": time.time(), "event": event, "rid": rr.rid,
                "trace": rr.trace, "replica": rr.replica_id,
                "state": rr.state, "verdict": rr.verdict,
                "retries": rr.retries}
        line.update(extra)
        try:
            fd = os.open(self._journal_path,
                         os.O_WRONLY | os.O_APPEND | os.O_CREAT, 0o644)
            try:
                os.write(fd,
                         (json.dumps(line) + "\n").encode("utf-8"))
            finally:
                os.close(fd)
        except OSError:
            pass  # the journal must never take the router down

    def replay_journal(self, path=None):
        """Rebuild the at-most-once authority from the journal file a
        previous router incarnation left behind (router restart).

        A crash can TRUNCATE the file mid-line — the single-``os.write``
        O_APPEND discipline means it never tears an EARLIER line — so a
        partial tail is skipped and counted, never allowed to poison
        the replay (the torn-tail contract ``serve_report`` applies to
        every artifact, applied to the authority itself).  Every
        complete entry replays: terminal requests land in the in-memory
        journal in their terminal state — a rid recorded ``complete``
        is never re-executed — and ``_next_rid`` advances past every
        replayed rid so new submissions cannot collide with history.
        Entries last seen ``accept``-ed (their replica may still be
        decoding them, or died with them) replay as journal records
        only: a restarted router has no engine handle to harvest, and
        re-submitting is the CALLER's decision, not a silent replay.

        ``fenced`` entries — a zombie incarnation's late completion,
        rejected at the router — replay as NON-TERMINAL: they are
        counted and advance ``_next_rid``, but never fold into the
        request's state or verdict (the fenced line describes the
        fenced-out incarnation's rejected work; the request's own
        story is told by its accept/retry/complete lines).

        Returns ``{"entries", "requests", "torn", "fenced"}``."""
        path = path or self._journal_path
        torn = applied = fenced = 0
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError:
            return {"entries": 0, "requests": 0, "torn": 0,
                    "fenced": 0}
        for line in raw.split(b"\n"):
            if not line.strip():
                continue
            try:
                doc = json.loads(line.decode("utf-8"))
                rid = int(doc["rid"])
            except (ValueError, TypeError, KeyError,
                    UnicodeDecodeError):
                torn += 1
                continue
            applied += 1
            rr = self._journal.get(rid)
            if rr is None:
                rr = RouterRequest(rid, None, 0, None)
                self._journal[rid] = rr
            rr.trace = doc.get("trace") or rr.trace
            if rid >= self._next_rid:
                self._next_rid = rid + 1
            if doc.get("event") == "fenced":
                fenced += 1
                continue  # non-terminal: never folds state/verdict
            # later lines win: the journal is append-ordered, so the
            # last complete line per rid IS its newest known state
            if doc.get("replica") is not None:
                rr.replica_id = doc["replica"]
            if doc.get("state"):
                rr.state = doc["state"]
            if doc.get("verdict"):
                rr.verdict = doc["verdict"]
            if doc.get("retries"):
                rr.retries = int(doc["retries"])
        return {"entries": applied, "requests": len(self._journal),
                "torn": torn, "fenced": fenced}

    def request(self, rid):
        return self._journal.get(rid)

    @property
    def requests(self):
        return list(self._journal.values())

    # -- streamed delivery (ISSUE 19) --------------------------------------
    def poll(self, rid, cursor=0, max_tokens=None):
        """Fleet-level token pull: tokens emitted after ``cursor`` plus
        a ``more`` flag — the delivery-plane twin of the telemetry
        cursor.  The cursor is an ABSOLUTE token index, and the
        determinism law is what makes it survive failover: a survivor's
        re-decode is bit-identical, so index ``cursor`` names the same
        token on the victim and on the survivor — the client sees no
        gap and no duplicate across a failover it never has to know
        happened.

        The poll is FORWARDED to the live replica whenever it speaks
        ``poll`` (RPC proxies, in-process replicas): the worker-side
        engine is what tracks ``last_poll_t``, so forwarding is what
        keeps an actively-polled stream out of the abandon sweep.  A
        dropped reply (``serve.stream.drop``, an unreachable worker)
        falls back to the local mirror's token slice — still
        exactly-once by index — with ``more=True`` so the client keeps
        polling.  A completed request serves straight from the
        journal's token list; polling a terminal request is always
        answerable (idempotent re-poll law)."""
        rr = self._journal.get(rid)
        if rr is None:
            return None
        cursor = max(0, int(cursor))
        doc = {"rid": rr.rid, "trace": rr.trace, "cursor": cursor,
               "tokens": [], "more": not rr.done, "state": rr.state,
               "verdict": rr.verdict, "done": rr.done}
        toks = rr.tokens
        if toks is None and rr._live is not None:
            # mid-decode: ask the replica that is decoding it — the
            # authoritative buffer, and the poll that feeds the
            # worker's abandon clock
            fwd = getattr(rr._home, "poll", None)
            if fwd is not None:
                try:
                    reply = fwd(rr.trace, cursor, max_tokens)
                except ReplicaLost:
                    reply = None
                if reply is not None and reply.get("known", True):
                    doc["cursor"] = int(reply.get("cursor", cursor))
                    doc["tokens"] = [int(t) for t in
                                     reply.get("tokens") or []]
                    # `more` and terminality come from the ROUTER's
                    # view: an engine-terminal verdict that has not
                    # been harvested yet is still in flight fleet-wise
                    # (it may fail over); only journal state is final
                    return doc
            # reply dropped / worker unreachable / fresh incarnation:
            # serve the mirror's slice — same absolute indexing, and
            # `more=True` keeps the client polling through recovery
            toks = getattr(rr._live, "tokens", None)
            if toks is not None:
                sliced = [int(t) for t in (
                    toks[cursor:] if max_tokens is None
                    else toks[cursor:cursor + max(1, int(max_tokens))])]
                doc["tokens"] = sliced
                doc["cursor"] = cursor + len(sliced)
            return doc
        if toks is not None:
            sliced = [int(t) for t in (
                toks[cursor:] if max_tokens is None
                else toks[cursor:cursor + max(1, int(max_tokens))])]
            doc["tokens"] = sliced
            doc["cursor"] = cursor + len(sliced)
            doc["more"] = (not rr.done) or doc["cursor"] < len(toks)
        return doc

    def cancel(self, rid):
        """Client-initiated teardown: forward to the replica decoding
        the request; the engine lands the typed ``cancelled`` verdict
        between decode steps (slot + pages released), the next
        ``_harvest`` journals it terminal.  Idempotent — cancelling a
        terminal request reports its existing verdict."""
        rr = self._journal.get(rid)
        if rr is None:
            return None
        if not rr.done and rr._home is not None:
            fwd = getattr(rr._home, "cancel", None)
            if fwd is not None:
                try:
                    fwd(rr.trace)
                except ReplicaLost:
                    pass  # the failover path owns this request now
            self._harvest()
        return {"rid": rr.rid, "trace": rr.trace, "state": rr.state,
                "verdict": rr.verdict, "done": rr.done}

    # -- placement ---------------------------------------------------------
    def _live(self):
        return [r for r in self._replicas if r.alive]

    def _gauge_live(self):
        _telemetry.gauge("router.live_replicas").set(len(self._live()))

    def submit(self, prompt, max_new, deadline_s=None, sampling=None,
               spec_k=None):
        """Journal a request and place it.  The handle is terminal
        immediately when every live replica refused (typed verdict
        propagated) or none exist — fail fast, never a silent hang.

        ``sampling``: per-request :class:`SamplingParams` (or dict),
        carried through every placement INCLUDING failover re-decodes —
        the per-request determinism law (same seed/params/prompt ->
        same tokens) is what keeps the at-most-once journal sound for
        sampled requests exactly as for greedy ones.

        The request-scope trace id is minted HERE (the fleet
        front-door): every engine it touches — the first placement, a
        spread after a shed refusal, a failover re-decode — records its
        lifecycle events under this one id."""
        rr = RouterRequest(self._next_rid, prompt, max_new, deadline_s)
        rr.trace = _telemetry.mint_trace()
        rr.sampling = SamplingParams.from_doc(sampling)
        rr.spec_k = None if spec_k is None else int(spec_k)
        self._next_rid += 1
        self._prune_journal()
        self._journal[rr.rid] = rr
        _telemetry.counter("router.requests").inc()
        _telemetry.note_request_event(
            rr.trace, "submit",
            args={"router": True, "rid": rr.rid,
                  "prompt_len": int(_np_size(prompt)),
                  "max_new": int(max_new), "deadline_s": deadline_s,
                  "sampling": (None if rr.sampling is None
                               else rr.sampling.to_doc())})
        self._place(rr)
        return rr

    def _close_trace(self, rr, live=None):
        """The one FINAL verdict event per trace — the Router owns
        fleet-level terminality (engine-level verdicts under a
        router-minted trace are hops: a shed refusal mid-spread, a
        victim's abandoned decode).  ``live`` (the engine Request at
        completion) contributes the latency stamps."""
        if rr.trace is None:
            return
        args = {"verdict": rr.verdict, "final": True, "router": True,
                "rid": rr.rid, "retries": rr.retries,
                "tokens": 0 if rr.tokens is None else len(rr.tokens)}
        if rr.replica_id is not None:
            args["replica"] = str(rr.replica_id)
        if live is not None:
            # duck-typed replicas (RPC proxies, test stubs) may not
            # carry the latency stamps — include what exists
            for key in ("ttft_s", "queue_wait_s", "tpot_s"):
                v = getattr(live, key, None)
                if v is not None:
                    args[key] = round(v, 6)
        if rr.error:
            args["error"] = str(rr.error)[:200]
        _telemetry.note_request_event(rr.trace, "verdict", args=args)

    def _prune_journal(self):
        """Evict the oldest TERMINAL entries once the in-memory journal
        doubles its retention cap (amortized: one O(n log n) sweep per
        ``journal_retention`` submissions).  In-flight entries — the
        at-most-once authority — are never evicted; callers holding a
        RouterRequest handle keep it alive regardless."""
        cap = self.journal_retention
        if cap is None or len(self._journal) < 2 * cap:
            return
        for rid in sorted(self._journal):
            if len(self._journal) <= cap:
                break
            if rid in self._inflight:
                continue
            rr = self._journal[rid]
            # live handles are never evicted; an "accepted" entry with
            # NO engine handle is a replay_journal record of a request
            # a previous incarnation lost mid-flight — history, not
            # live state, and it must age out like any terminal entry
            # (or crash/replay cycles grow the journal without bound)
            if rr.state in ("submitted", "accepted") and \
                    (rr._live is not None or rr._home is not None):
                continue
            del self._journal[rid]

    def _place(self, rr):
        """Try every live, non-draining replica in load order until one
        ACCEPTS (bounded spread — one pass, no retry loop).  A typed
        refusal from every candidate propagates the LAST refusal's
        verdict to the caller."""
        self._inflight.discard(rr.rid)
        candidates = sorted(
            (r for r in self._live() if not r.draining),
            key=lambda r: r.load)
        # remaining budget relative to the ORIGINAL submission — an
        # already-blown deadline goes through as ~0 so the engine's
        # sweep expires it with the typed verdict, not a silent drop
        remaining = (None if rr.deadline_t is None
                     else rr.deadline_t - time.perf_counter())
        refusal = None
        # sampling is passed only when set: duck-typed replicas (test
        # stubs, older proxies) that predate per-request sampling keep
        # working for the greedy default
        kw = {} if rr.sampling is None else {"sampling": rr.sampling}
        if rr.spec_k is not None:
            kw["spec_k"] = rr.spec_k
        for r in candidates:
            try:
                req = r.submit(rr.prompt, rr.max_new,
                               deadline_s=remaining, trace=rr.trace,
                               **kw)
            except ReplicaLost:
                continue
            except ValueError as e:
                # infeasible everywhere by construction (engine-config
                # bound): terminal immediately, with the same typed
                # verdict an engine-level handle carries
                rr.state, rr.verdict = "failed", VERDICT_REJECTED
                rr.error = str(e)
                self._log("reject", rr)
                self._close_trace(rr)
                return
            if req.state == SHED:
                refusal = req
                continue
            rr._live = req
            rr._home = r
            rr.replica_id = r.replica_id
            rr.state = "accepted"
            # the fencing token: every placement is stamped with the
            # target's incarnation (None for in-process replicas) and
            # journaled under the slot's CURRENT fencing epoch — the
            # audit record of which boot was entitled to this work
            rr._placed_inc = getattr(r, "incarnation", None)
            self._inflight.add(rr.rid)
            self._log("accept", rr,
                      incarnation=rr._placed_inc,
                      fence_epoch=self._fence_epoch.get(
                          self._slot_key(r), 0))
            return
        rr.state = "refused"
        rr.verdict = refusal.verdict if refusal is not None \
            else VERDICT_NO_REPLICAS
        rr.error = (refusal.error if refusal is not None
                    else "no live replica to place on")
        _telemetry.counter("router.refused").inc()
        self._log("refuse", rr)
        self._close_trace(rr)

    # -- the serving loop --------------------------------------------------
    def step(self):
        """Step every live replica, failing over on ReplicaLost, then
        harvest finished requests into the journal.  Returns tokens
        produced this iteration."""
        produced = 0
        for r in list(self._replicas):
            if not r.alive:
                continue
            try:
                produced += r.step()
            except ReplicaLost:
                self._failover(r)
        self._harvest()
        self._sweep_fenced()
        self.collect_telemetry()
        return produced

    def collect_telemetry(self, force=False):
        """Pull every live RPC replica's telemetry into
        ``telemetry_dir`` (no-op without one, or between intervals
        unless ``force``).  Per replica: resume from the client-held
        cursor, append each returned line whole (single O_APPEND
        ``os.write`` — the emitter's torn-line discipline), loop while
        the worker declares ``more`` (bounded, so a firehose replica
        cannot wedge the serving loop — the cursor resumes next round).
        In-process replicas (no ``pull_telemetry``) are skipped: their
        emitter already writes locally.  A failed pull is counted and
        skipped — observability must never take the serving loop down.
        Returns the number of lines appended."""
        if not self.telemetry_dir:
            return 0
        now = time.monotonic()
        if not force and now < self._next_tel_pull:
            return 0
        self._next_tel_pull = now + self.telemetry_interval_s
        try:
            os.makedirs(self.telemetry_dir, exist_ok=True)
        except OSError:
            return 0
        lines = 0
        for r in list(self._replicas):
            pull = getattr(r, "pull_telemetry", None)
            if pull is None or not getattr(r, "alive", False):
                continue
            rid = str(r.replica_id).replace(os.sep, "_")
            path = os.path.join(self.telemetry_dir,
                                "stream-%s.jsonl" % rid)
            try:
                cursor = self._tel_cursors.get(rid)
                for _ in range(8):
                    reply = pull(cursor=cursor)
                    cursor = reply["cursor"]
                    data = (json.dumps(reply["line"])
                            + "\n").encode("utf-8")
                    fd = os.open(path, os.O_WRONLY | os.O_APPEND
                                 | os.O_CREAT, 0o644)
                    try:
                        os.write(fd, data)
                    finally:
                        os.close(fd)
                    lines += 1
                    if not reply.get("more"):
                        break
                self._tel_cursors[rid] = cursor
            except Exception:
                _telemetry.counter(
                    "router.telemetry_pull_errors").inc()
        return lines

    @staticmethod
    def _slot_key(replica):
        """The SLOT a replica occupies — the unit fencing epochs are
        scoped to.  An explicit ``slot`` attribute wins; otherwise the
        replica_id with its ``+attempt`` incarnation suffix stripped
        (the launcher fleet convention: slot0, slot0+1, ... share a
        slot)."""
        slot = getattr(replica, "slot", None)
        if slot is not None:
            return str(slot)
        return str(replica.replica_id).split("+", 1)[0]

    def _sweep_fenced(self):
        """Observe the zombie watch: poll each fenced-out incarnation's
        abandoned handles (best-effort, breaker-free) and REJECT any
        late completion with the typed ``fenced`` verdict event +
        journal line — at-most-once made auditable when the 'dead'
        replica was alive behind a partition.  Watches expire after
        ``fence_watch_s`` or when the handle terminates without
        finishing."""
        if not self._fenced:
            return
        now = time.monotonic()
        keep = []
        for w in self._fenced:
            poll = getattr(w["proxy"], "fenced_poll", None)
            if poll is not None:
                try:
                    poll()
                except Exception:
                    pass  # a zombie watch must never hurt the router
            m = w["mirror"]
            if getattr(m, "state", None) == FINISHED:
                rr = w["rr"]
                toks = len(getattr(m, "tokens", None) or [])
                _telemetry.counter("rpc.fenced_results").inc()
                # the journal line carries the FENCED incarnation's
                # identity and the epoch that fenced it out; replay
                # treats it as non-terminal (the request's own state
                # is told by its accept/retry/complete lines)
                self._log("fenced", rr, state="fenced",
                          verdict="fenced",
                          replica=w["replica_id"],
                          incarnation=w["incarnation"],
                          fence_epoch=w["epoch"],
                          tokens_rejected=toks)
                # engine-scope event (trace in args): the trace's own
                # lifecycle already closed — or will — with its ONE
                # final verdict; the rejection is fleet news, not a
                # lifecycle hop
                _telemetry.note_request_event(
                    "", "fenced",
                    args={"replica": str(w["replica_id"]),
                          "trace": rr.trace, "rid": rr.rid,
                          "fence_epoch": w["epoch"],
                          "tokens": toks})
                continue
            if getattr(m, "done", False) or now > w["expires"]:
                continue
            keep.append(w)
        self._fenced = keep

    def _harvest(self):
        """Move terminal engine states into the journal.  Completion is
        recorded EXACTLY once per rid — the at-most-once authority the
        failover path consults.  Scans only the in-flight set, not the
        all-time journal: a long-lived router must not pay O(requests
        ever served) per step."""
        for rid in list(self._inflight):
            rr = self._journal[rid]
            live = rr._live
            if rr.state != "accepted" or live is None:
                self._inflight.discard(rid)
                continue
            if live.state == FINISHED:
                rr.tokens = [int(t) for t in live.tokens]
                rr.state = "completed"
                rr.verdict = live.verdict or "completed"
                self._inflight.discard(rid)
                self._log("complete", rr, tokens=len(rr.tokens))
                self._close_trace(rr, live=live)
            elif live.state in _TERMINAL_FAILURES:
                rr.state = "failed"
                rr.verdict = live.verdict or live.state
                rr.error = live.error
                self._inflight.discard(rid)
                self._log("fail", rr)
                self._close_trace(rr, live=live)

    def _failover(self, replica):
        """A replica died: journal-driven failover.  Completed requests
        are untouched (at-most-once); incomplete accepted ones are
        re-placed on live replicas (partial tokens discarded — greedy
        decode regenerates them bit-identically), bounded by
        ``max_retries``.  A ``spawn`` callback, if any, brings up the
        replacement FIRST so the victims have somewhere to land.  The
        dead replica is then PRUNED: its watchdog lease is released
        (an abandoned lease would age into a process-wide stall kill)
        and it leaves ``_replicas``, dropping its engine — and with it
        a full KV page pool per failover that would otherwise pin
        memory for the router's lifetime."""
        abandon = getattr(replica, "abandon", None)
        if abandon is not None:
            try:
                abandon()
            except Exception:
                pass  # best-effort: the replica is already dead
        replica.alive = False
        self.failovers += 1
        _telemetry.counter("router.failovers").inc()
        # fence the slot: bump its epoch BEFORE re-placing — anything
        # the dead incarnation still returns is fenced out from here on
        fence_key = self._slot_key(replica)
        fence_epoch = self._fence_epoch.get(fence_key, 0) + 1
        self._fence_epoch[fence_key] = fence_epoch
        # why the failover ran, named by the liveness machine (RPC
        # proxies); in-process replicas raise ReplicaLost directly
        confirm_reason = getattr(replica, "confirmed_reason", None)
        self._harvest()   # completions from earlier steps stay completed
        if self._spawn is not None:
            try:
                fresh = self._spawn()
            except Exception as e:
                import logging
                logging.warning(
                    "mxnet_tpu.serving.router: replacement spawn failed "
                    "(%s: %s); continuing on survivors",
                    type(e).__name__, e)
            else:
                self._replicas.append(fresh)
                _telemetry.counter("router.replacements").inc()
        # victims matched by replica IDENTITY (the object), never by
        # replica_id — ids are caller-supplied and may collide, and an
        # id match would "fail over" healthy requests still decoding
        # fine on a live replica (double execution)
        victims = [self._journal[rid] for rid in sorted(self._inflight)
                   if self._journal[rid].state == "accepted"
                   and self._journal[rid]._home is replica]
        for rr in victims:
            # enroll the abandoned handle in the zombie watch: if the
            # fenced-out incarnation finishes it behind a partition,
            # the late completion is observed and rejected (typed
            # ``fenced``), never silently unread
            if rr._live is not None:
                self._fenced.append({
                    "rr": rr, "mirror": rr._live, "proxy": replica,
                    "replica_id": replica.replica_id,
                    "incarnation": getattr(replica, "incarnation",
                                           None),
                    "epoch": fence_epoch,
                    "expires": time.monotonic() + self.fence_watch_s})
            rr.retries += 1
            rr._live = None
            rr._home = None
            if rr.retries > self.max_retries:
                rr.state = "failed"
                rr.verdict = VERDICT_RETRIES_EXHAUSTED
                rr.error = ("replica %s lost; retry budget (%d) "
                            "exhausted" % (replica.replica_id,
                                           self.max_retries))
                self._inflight.discard(rr.rid)
                self._log("drop", rr)
                self._close_trace(rr)
                continue
            _telemetry.counter("router.retries").inc()
            self._log("retry", rr, from_replica=replica.replica_id,
                      reason=confirm_reason, fence_epoch=fence_epoch)
            # the failover arc: same trace, victim named, confirmation
            # reason carried — the survivor's `place`/`admit` events
            # continue it, and serve_report charges the re-decode
            # window to this replica AND names why the arc ran
            _telemetry.note_request_event(
                rr.trace, "retry",
                args={"from": str(replica.replica_id),
                      "retries": rr.retries, "rid": rr.rid,
                      "reason": confirm_reason})
            self._place(rr)
        # prune: journal entries survive; the dead replica (and its
        # engine's page pools) do not
        self._replicas = [r for r in self._replicas if r is not replica]
        self._gauge_live()

    # -- drive -------------------------------------------------------------
    @property
    def idle(self):
        """Nothing left to decode: every live replica is idle.  Every
        accepted request lives on some replica's queue/slots (failover
        re-places or terminally fails victims synchronously), so
        replica idleness covers the journal too."""
        return all(r.idle for r in self._live())

    def run_until_idle(self, max_steps=100000):
        for _ in range(max_steps):
            if self.idle:
                return
            self.step()
        raise MXNetError("router did not drain in %d steps" % max_steps)

    def drain(self):
        """Fleet drain: every live replica stops admitting, residents
        finish, then each replica reports its drain exit code.
        Returned as ``[(replica_id, rc)]`` pairs — ids are
        caller-supplied and may collide, so a dict would silently drop
        results."""
        out = []
        for r in self._live():
            out.append((r.replica_id, r.drain()))
        # the drains finished every accepted request on their engines;
        # harvest moves those completions into the journal NOW — the
        # replicas are dead after drain(), so no later step() would
        self._harvest()
        self._gauge_live()
        return out
