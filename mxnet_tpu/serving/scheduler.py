"""Admission queue + continuous-batching scheduler.

Fixed-capacity decode SLOTS (static ``num_slots`` — the decode program
compiles once, for one shape) with dynamic OCCUPANCY: requests join a
free slot between decode steps (one prefill dispatch fills their pages;
for a model whose prefill is chunked, as many dispatches as the prompt
needs, a bounded number an engine step, the slot PREFILLING meanwhile:
``Request.prefilling``) and leave the instant they finish (pages released, slot free for the
next queued request).  No recompiles, no barrier on the longest
sequence — the continuous-batching scheme of Orca/vLLM applied to the
predictor path (ROADMAP item 2).

Admission is FIFO and OOM-aware: the head of the queue is admitted only
when (a) a slot is free and (b) the paged allocator can reserve its
worst case (``prompt + max_new`` tokens) up front — see
kv_cache.PagedKVAllocator.  Head-of-line blocking is deliberate: FIFO
keeps per-request latency predictable and starvation impossible, the
usual serving trade.

Survivability additions (ISSUE 11): per-request deadlines (total
budget, queue + decode) with expiry sweeps the engine runs each step,
typed terminal verdicts on every non-success exit (fail fast — a
handle is live or terminal, never hung), and :meth:`shed` for the
SLO/drain refusals.  Every resident exit routes through
:meth:`finish`, so pages can never leak on a failure path.

Host-side control plane only; the engine owns every device object.
"""
from __future__ import annotations

import collections
import time

import numpy as _np

from .kv_cache import PagedKVAllocator, SCRATCH_PAGE

__all__ = ["Request", "SamplingParams", "ContinuousBatchingScheduler"]

#: request lifecycle states.  FINISHED/REJECTED/EXPIRED/FAILED/SHED are
#: terminal; every terminal request carries a typed ``verdict`` (and an
#: ``error`` message for the failure classes) so a caller never has to
#: poll a hung handle to learn its fate — fail fast is the contract
#: (ISSUE 11).
QUEUED, RUNNING, FINISHED, REJECTED, EXPIRED, FAILED, SHED = \
    "queued", "running", "finished", "rejected", "expired", "failed", \
    "shed"
#: terminal state for client-initiated teardown (ISSUE 19): an explicit
#: ``cancel`` or an orphan reclaim (vanished streaming client) — the
#: verdict (``cancelled`` vs ``abandoned``) says which.
CANCELLED = "cancelled"

#: typed verdicts a terminal request can carry
VERDICT_COMPLETED = "completed"                # every token produced
VERDICT_EXPIRED_QUEUE = "expired_queue"        # deadline passed in queue
VERDICT_EXPIRED_DECODE = "expired_decode"      # deadline passed resident
VERDICT_SHED = "shed"                          # SLO shed at admission
VERDICT_DRAINING = "draining"                  # replica refusing intake
VERDICT_REJECTED = "rejected_infeasible"       # can never run here
VERDICT_PREFILL_ERROR = "prefill_error"        # admission dispatch failed
VERDICT_CANCELLED = "cancelled"                # client asked for teardown
VERDICT_ABANDONED = "abandoned"                # poller vanished; reclaimed


class SamplingParams:
    """Per-request decode sampling (ISSUE 15): ``temperature <= 0`` is
    greedy argmax (bit-identical to the sampling-free engine);
    otherwise tokens are drawn from the temperature-scaled, top-k-
    and/or nucleus-filtered distribution with a PRNG keyed by ``seed``
    and advanced functionally per token — so the SAME (seed, params,
    prompt) always yields the SAME tokens, regardless of batch
    composition, join/leave, hot-swap, or a failover re-decode (the
    per-request determinism law, test-pinned).  These are ordinary
    decode-program INPUTS (a per-slot array), never a recompile."""

    __slots__ = ("temperature", "top_k", "top_p", "seed")

    def __init__(self, temperature=None, top_k=0, top_p=0.0, seed=0):
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = int(seed)
        if temperature is None:
            # a filter knob with NO temperature means temperature 1.0:
            # temp 0 would silently argmax past the caller's filter.
            # An EXPLICIT temperature=0 still wins (greedy).  Same rule
            # for every configuration path — constructor, dict/RPC
            # docs, and the MXTPU_SERVE_* env defaults.
            temperature = 1.0 if (self.top_k or self.top_p) else 0.0
        self.temperature = float(temperature)
        if self.temperature < 0:
            raise ValueError("temperature must be >= 0")
        if self.top_k < 0:
            raise ValueError("top_k must be >= 0")
        if not 0.0 <= self.top_p <= 1.0:
            raise ValueError("top_p must be in [0, 1]")

    @property
    def greedy(self):
        return self.temperature <= 0.0

    def to_doc(self):
        """JSON-able form (the RPC/journal wire format)."""
        return {"temperature": self.temperature, "top_k": self.top_k,
                "top_p": self.top_p, "seed": self.seed}

    @classmethod
    def from_doc(cls, doc):
        """Accepts None, an existing instance, or a dict."""
        if doc is None or isinstance(doc, cls):
            return doc
        return cls(temperature=doc.get("temperature"),
                   top_k=doc.get("top_k", 0),
                   top_p=doc.get("top_p", 0.0),
                   seed=doc.get("seed", 0))

    def __repr__(self):
        return ("SamplingParams(temperature=%g, top_k=%d, top_p=%g, "
                "seed=%d)" % (self.temperature, self.top_k, self.top_p,
                              self.seed))


class Request:
    """One inference request: a prompt plus a decode budget, an optional
    deadline, and the latency stamps the serving histograms are built
    from.  ``deadline_s`` is the TOTAL budget from submit — queue wait
    plus decode — so an expired request fails with a typed verdict
    instead of occupying a slot (or the queue) forever."""

    __slots__ = ("rid", "prompt", "max_new", "submit_t", "admit_t",
                 "first_token_t", "finish_t", "tokens", "state", "slot",
                 "pages", "logits_trace", "token_times", "deadline_s",
                 "deadline_t", "verdict", "error", "trace",
                 "trace_owned", "sampling", "prefix_len",
                 "shared_count", "cow_src", "cow_dst", "spec_k",
                 "last_poll_t", "prefilled")

    def __init__(self, rid, prompt, max_new, deadline_s=None):
        self.rid = rid
        self.prompt = _np.asarray(prompt, _np.int32).reshape(-1)
        if self.prompt.size == 0:
            raise ValueError("empty prompt")
        self.max_new = int(max_new)
        if self.max_new < 1:
            raise ValueError("max_new must be >= 1")
        self.submit_t = time.perf_counter()
        self.admit_t = None
        self.first_token_t = None
        self.finish_t = None
        self.tokens = []          # generated token ids (ints)
        self.token_times = []     # perf_counter per generated token
        self.state = QUEUED
        self.slot = None
        self.pages = None
        self.logits_trace = None  # engine fills when record_logits=True
        self.deadline_s = None if deadline_s is None else float(deadline_s)
        self.deadline_t = (None if deadline_s is None
                           else self.submit_t + float(deadline_s))
        self.verdict = None       # typed terminal verdict
        self.error = None         # human-readable failure detail
        # request-scope tracing (ISSUE 13): the lifecycle trace id this
        # request's events are recorded under (the engine mints one, or
        # the Router passes its own through so a failover re-decode on
        # another replica stays ONE trace).  ``trace_owned`` says who
        # closes it: True — the engine's terminal verdict event is
        # final; False — the Router owns fleet-level terminality.
        self.trace = None
        self.trace_owned = True
        # per-request sampling (ISSUE 15; None = greedy argmax)
        self.sampling = None
        # per-request speculative-decoding cap (ISSUE 16; None = the
        # engine's spec_k, 0 = no drafting for this request)
        self.spec_k = None
        # prefix-cache placement facts, stamped at admission:
        # ``prefix_len`` tokens of the prompt whose K/V was already
        # cached (0 = miss), ``shared_count`` whole pages mapped
        # shared, ``cow_src``/``cow_dst`` the copy-on-write pair (None
        # when the shared prefix ends on a page boundary)
        self.prefix_len = 0
        self.shared_count = 0
        self.cow_src = None
        self.cow_dst = None
        # streaming delivery (ISSUE 19): perf_counter stamp of the last
        # successful ``poll`` against this request.  None means no
        # client ever streamed it — a unary request, which the orphan
        # sweep must NEVER reclaim (only a poller that started and then
        # went silent counts as vanished).
        self.last_poll_t = None
        # prompt tokens whose prefill has been SENT (chunked prefill): a
        # resident request below its prompt's length is PREFILLING and
        # sits decode steps out; at it, the request decodes
        self.prefilled = 0

    @property
    def prefilling(self):
        """Resident with part of its prompt still to prefill: the third
        state of a slot, between free and decoding."""
        return self.state == RUNNING and self.prefilled < self.prompt.size

    @property
    def done(self):
        """Terminal: no further tokens will ever appear on this handle
        (success or any typed failure) — the fail-fast polling target."""
        return self.state not in (QUEUED, RUNNING)

    @property
    def ttft_s(self):
        if self.first_token_t is None:
            return None
        return self.first_token_t - self.submit_t

    @property
    def queue_wait_s(self):
        if self.admit_t is None:
            return None
        return self.admit_t - self.submit_t

    @property
    def tpot_s(self):
        """Mean time per output token AFTER the first (decode cadence);
        None until two tokens exist."""
        if len(self.token_times) < 2:
            return None
        span = self.token_times[-1] - self.token_times[0]
        return span / (len(self.token_times) - 1)


class ContinuousBatchingScheduler:
    def __init__(self, num_slots, allocator, max_pages_per_seq,
                 max_seq_len=None, prefix_cache=None, spec_k=0):
        if num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if not isinstance(allocator, PagedKVAllocator):
            raise TypeError("allocator must be a PagedKVAllocator")
        self.num_slots = int(num_slots)
        self.alloc = allocator
        # speculative decoding (ISSUE 16): every admission's worst-case
        # reservation extends by ``spec_k`` tokens — a spec-decode step
        # may scatter up to k draft positions BEYOND the sequence's
        # final committed length, and those writes must land in pages
        # the request owns (never a neighbor's).  Acceptance variance
        # itself is an occupancy/length concern (masks, not shapes),
        # so this one static pad is the whole allocator story.
        self.spec_k = int(spec_k)
        #: optional serving.prefix_cache.PrefixCache — admission matches
        #: each prompt's longest cached prefix and maps the shared pages
        #: into the block table instead of allocating + re-prefilling
        self.prefix = prefix_cache
        self.max_pages_per_seq = int(max_pages_per_seq)
        self.max_seq_len = (int(max_seq_len) if max_seq_len is not None
                            else self.max_pages_per_seq
                            * allocator.page_size)
        self._queue = collections.deque()
        self._slots = [None] * self.num_slots   # slot -> Request | None
        self._next_rid = 0
        # block tables live here (the scheduler owns placement); the
        # engine uploads this array every step.  SCRATCH_PAGE everywhere
        # a slot holds no real page — masked reads/writes route there.
        self.block_tables = _np.full(
            (self.num_slots, self.max_pages_per_seq), SCRATCH_PAGE,
            _np.int32)

    # -- intake ------------------------------------------------------------
    def submit(self, prompt, max_new, deadline_s=None):
        """Enqueue a request (never blocks, never rejects for load — the
        queue is the backpressure; the ENGINE's SLO controller is what
        sheds for load, via :meth:`shed`).  Rejects only requests that
        can NEVER run: worst case beyond the per-sequence page budget.
        Rejection is deterministic and terminal — the request carries a
        typed verdict BEFORE the raise, reserves nothing, and is never
        requeued (a never-fit request at the queue head would deadlock
        FIFO admission forever)."""
        req = Request(self._next_rid, prompt, max_new, deadline_s)
        self._next_rid += 1
        err = self.feasibility_error(req.prompt.size, req.max_new)
        if err is not None:
            self._reject(req, err)
        self._queue.append(req)
        return req

    def feasibility_error(self, prompt_size, max_new):
        """Why a (prompt_size, max_new) request can NEVER run here, or
        None when it can.  The one home of the infeasibility rules —
        the engine consults it BEFORE its shed/drain branches so an
        impossible request always gets the terminal ValueError, never a
        retryable-looking refusal."""
        worst = int(prompt_size) + int(max_new)
        if worst > self.max_seq_len:
            return ("request needs %d tokens (prompt %d + max_new %d) "
                    "but the engine serves at most %d per sequence"
                    % (worst, prompt_size, max_new, self.max_seq_len))
        need = self.alloc.pages_for(worst + self.spec_k)
        if need > self.alloc.num_pages - 1:
            # admission could never reserve this many pages even with
            # the pool idle — queueing it would deadlock the queue head
            return ("request needs %d KV pages but the pool only has "
                    "%d usable — enlarge num_pages or lower max_new"
                    % (need, self.alloc.num_pages - 1))
        return None

    def _reject(self, req, msg):
        """Terminal infeasible-rejection: typed verdict, no reservation,
        no requeue — then the (compat-kept) ValueError."""
        req.state = REJECTED
        req.verdict = VERDICT_REJECTED
        req.error = msg
        req.finish_t = time.perf_counter()
        raise ValueError(msg)

    def shed(self, prompt, max_new, verdict=VERDICT_SHED, error=None):
        """Refuse a request up front with a typed verdict (SLO shed /
        draining replica): the handle comes back terminal — state SHED,
        never queued, nothing reserved — so an overloaded replica fails
        fast instead of queuing unboundedly."""
        req = Request(self._next_rid, prompt, max_new)
        self._next_rid += 1
        req.state = SHED
        req.verdict = verdict
        req.error = error
        req.finish_t = time.perf_counter()
        return req

    # -- deadlines ---------------------------------------------------------
    def expire_queued(self, now=None):
        """Drop queued requests whose deadline has passed (verdict
        ``expired_queue``) and return them.  They hold no slot and no
        pages, so expiry is pure bookkeeping — FIFO order of the
        survivors is preserved."""
        if now is None:
            now = time.perf_counter()
        if not any(r.deadline_t is not None and now > r.deadline_t
                   for r in self._queue):
            return []
        expired, keep = [], collections.deque()
        for req in self._queue:
            if req.deadline_t is not None and now > req.deadline_t:
                req.state = EXPIRED
                req.verdict = VERDICT_EXPIRED_QUEUE
                req.error = ("deadline %.3fs passed after %.3fs in queue"
                             % (req.deadline_s, now - req.submit_t))
                req.finish_t = now
                expired.append(req)
            else:
                keep.append(req)
        self._queue = keep
        return expired

    def cancel_queued(self, req, verdict=VERDICT_CANCELLED, error=None,
                      now=None):
        """Terminal teardown for a QUEUED request (ISSUE 19): it holds
        no slot and no pages, so cancellation is pure bookkeeping — the
        request leaves the FIFO (survivor order preserved) with a typed
        verdict.  Residents go through :meth:`finish` instead, which
        also releases slot + pages."""
        if now is None:
            now = time.perf_counter()
        assert req.state == QUEUED, req.state
        keep = collections.deque(r for r in self._queue if r is not req)
        assert len(keep) == len(self._queue) - 1, "request not queued"
        self._queue = keep
        req.state = CANCELLED
        req.verdict = verdict
        if error is not None:
            req.error = error
        req.finish_t = now
        return req

    def expired_running(self, now=None):
        """Residents whose deadline has passed — the engine finishes
        them (releasing slot + pages) before the next decode dispatch,
        so an expired request never consumes another token's FLOPs."""
        if now is None:
            now = time.perf_counter()
        return [r for r in self._slots
                if r is not None and r.deadline_t is not None
                and now > r.deadline_t]

    @property
    def oldest_queue_wait(self):
        """Seconds the queue head has waited (None when empty) — the
        SLO controller's forward-looking overload signal: the admission-
        time p99 only updates when something IS admitted, but a wedged
        queue head means new intake is already doomed to violate."""
        if not self._queue:
            return None
        return time.perf_counter() - self._queue[0].submit_t

    # -- placement ---------------------------------------------------------
    def _match_prefix(self, head):
        """Consult the prefix cache for the queue head: returns
        ``(shared_nodes, cow_node, prefix_len)``.  The shared prefix is
        capped at ``prompt - 1`` tokens — the LAST prompt position must
        run through the model to produce the first output token, so a
        fully-cached prompt still prefills (at least) one token; the
        cap can turn the final shared page into a copy-on-write
        partial."""
        ps = self.alloc.page_size
        path, partial, overlap = self.prefix.match(head.prompt)
        prefix_len = min(len(path) * ps + overlap,
                         int(head.prompt.size) - 1)
        m, o = prefix_len // ps, prefix_len % ps
        cow = None
        if o > 0:
            cow = path[m] if m < len(path) else partial
        return path[:m], cow, prefix_len

    def admit(self):
        """Move queued requests into free slots while both a slot AND
        the worst-case page reservation are available (FIFO; stops at
        the first request that doesn't fit — no reordering).  With a
        prefix cache, the reservation counts ONLY un-shared pages
        (shared prefix pages are mapped by reference), and admission
        pressure evicts LRU cache entries before giving up.  Returns
        the newly-placed requests; the engine prefills each (suffix
        only, on a hit)."""
        placed = []
        while self._queue:
            slot = self._free_slot()
            if slot is None:
                break
            head = self._queue[0]
            # +spec_k: speculative draft positions may spill past the
            # final committed length — the tail pages must be OWNED
            total = self.alloc.pages_for(head.prompt.size + head.max_new
                                         + self.spec_k)
            # match + reserve, re-matching after every eviction round:
            # evict_for may drop the very nodes just matched (freeing
            # their pages), and acting on that stale match would retain
            # a freed/re-allocated page — the match must describe the
            # index as it stands when pages are taken.  Terminates:
            # each round either reserves or shrinks the cache by >= 1.
            while True:
                shared_nodes, cow, prefix_len = ([], None, 0)
                if self.prefix is not None:
                    shared_nodes, cow, prefix_len = \
                        self._match_prefix(head)
                need = total - len(shared_nodes)
                if self.alloc.can_reserve(need):
                    break
                # cached-but-idle pages are the one reclaimable reserve
                # (LRU leaves first).  A page some resident still maps
                # is only un-pinned, not freed.
                if self.prefix is None or \
                        self.prefix.evict_for(need) == 0:
                    shared_nodes = None
                    break
            if shared_nodes is None:
                break  # OOM-aware admission: wait, don't evict residents
            self._queue.popleft()
            owned = self.alloc.allocate(need)
            shared = [n.page for n in shared_nodes]
            if shared:
                self.alloc.retain(shared)
            head.pages = shared + owned
            head.prefix_len = prefix_len
            head.shared_count = len(shared)
            if cow is not None:
                # the request holds a reference on the DONOR page too:
                # an eviction between admission and the prefill dispatch
                # must not free the page the copy-on-write reads from
                self.alloc.retain([cow.page])
                head.pages = head.pages + [cow.page]
                head.cow_src = cow.page
                head.cow_dst = owned[0]
            else:
                head.cow_src = head.cow_dst = None
            head.slot = slot
            head.admit_t = time.perf_counter()
            head.state = RUNNING
            self._slots[slot] = head
            row = self.block_tables[slot]
            row[:] = SCRATCH_PAGE
            row[:len(shared)] = shared
            row[len(shared):len(shared) + len(owned)] = owned
            placed.append(head)
        return placed

    def finish(self, req, state=FINISHED, verdict=None, error=None):
        """Release a request's slot + pages (leave-between-steps) and
        stamp its typed verdict.  EVERY resident exit routes through
        here — completion, deadline expiry, prefill failure — so pages
        can never leak on a failure path (assert_conservation pins
        it)."""
        assert self._slots[req.slot] is req
        self._slots[req.slot] = None
        self.block_tables[req.slot, :] = SCRATCH_PAGE
        self.alloc.release(req.pages)
        req.pages = None
        req.state = state
        req.verdict = verdict or (VERDICT_COMPLETED if state == FINISHED
                                  else state)
        if error is not None:
            req.error = error
        req.finish_t = time.perf_counter()

    def _free_slot(self):
        for i, r in enumerate(self._slots):
            if r is None:
                return i
        return None

    # -- views -------------------------------------------------------------
    @property
    def running(self):
        return [r for r in self._slots if r is not None]

    @property
    def prefilling(self):
        """Residents whose prompt is not all prefilled yet, in the order
        they were admitted."""
        return sorted((r for r in self._slots
                       if r is not None and r.prefilling),
                      key=lambda r: r.rid)

    @property
    def queued(self):
        return len(self._queue)

    @property
    def occupancy(self):
        return sum(1 for r in self._slots if r is not None)

    def slot_request(self, slot):
        return self._slots[slot]

    @property
    def idle(self):
        return not self._queue and self.occupancy == 0
