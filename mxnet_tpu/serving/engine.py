"""ServingEngine: continuous batching + paged KV cache on one model.

The inference counterpart of the fused train step (PR 1): every decode
step is ONE donated XLA program that advances EVERY resident sequence by
one token —

    (params, kv_pages*, tokens, positions, active, block_tables)
        -> (logits, next_tokens, kv_pages')        [* donated]

with the paged-attention Pallas kernel (ops/pallas/paged_attention.py)
doing the ragged gather inside.  Requests join between steps via one
prefill dispatch (static padded prompt shape, traced length — no
per-length recompiles) and leave by releasing pages; occupancy is a
mask, never a shape, so request churn causes ZERO recompiles.

The KV page pools are ``[num_pages, page_size, K_kv * D]`` per layer
(``_init_pages``): with ``K_kv * D`` a multiple of 128 the TPU keeps
that shape row-major with no lane padding, so the stored layout, the
programs' scatters and the paged kernel's page block agree and a
donated pool is updated in place (SERVING.md §2; a width that is no
multiple of 128, multi-query attention at ``D`` 64, keeps a layout
copy).

Donation discipline (ROBUSTNESS.md §8): the KV page pools are donated
every step, so

- every lazily-compiling path is wrapped in
  ``aot_cache.donation_cache_guard`` and every eager compile runs under
  ``bypass_persistent_cache`` — a donated program must never be replayed
  from jax's persistent cache on the hazard (CPU) backends;
- the pools are born as jitted-zeros outputs — fresh XLA-owned buffers
  by construction; anything ever restored into them from host data must
  go through ``parallel.sharding.fresh_device_put`` instead (the eager
  device_put aliasing hazard, ROBUSTNESS.md §8c).

AOT warm-start (the PR-5/PR-6 machinery applied to the predictor path):
both serving programs (prefill, decode) run through ``aot_cache`` —
keyed by runtime fingerprint + full input tree + an engine-config hash —
so a serving replica restarted with ``MXTPU_AOT_CACHE_DIR`` reaches its
first token with 0 foreground compiles (on CPU via the donation-free
twin + background hot-swap, exactly like executor.make_fit_step).

Telemetry (OBSERVABILITY.md §9): ``serving.ttft`` / ``serving.tpot`` /
``serving.queue_wait`` histograms, ``serving.batch_occupancy`` /
``serving.kv_pages_free`` gauges, ``serving.requests`` /
``serving.tokens`` / ``serving.prefills`` counters, and one flight-
recorder record per decode step (``where="serve_step"``) so a crashed
replica's postmortem carries its recent decode cadence.

Survivability plane (ISSUE 11):

- **deadlines** — per-request total budget (queue + decode,
  ``submit(..., deadline_s=)`` / ``MXTPU_SERVE_DEADLINE_S``); expired
  requests exit with typed verdicts (``expired_queue`` /
  ``expired_decode``) before the next decode dispatch, releasing slot
  and pages, never consuming another token's FLOPs;
- **SLO shedding** — an :class:`~mxnet_tpu.serving.slo.SLOController`
  refuses NEW intake (state ``shed``, fail-fast) when the queue-wait
  p99 breaches its target, instead of queuing unboundedly;
- **watchdog lease** — every completed step renews the ``serve_step``
  progress lease and each prefill dispatch runs under a
  ``serve.prefill`` scoped guard, so a wedged decode dispatch trips the
  PR-4 stall watchdog (exit 75) and the postmortem carries this
  engine's serving snapshot (:func:`live_snapshot`: resident slots,
  free pages, queue depth) instead of dying silently;
- **fault sites** — ``serve.decode.stall`` (lease-less wedge right
  before the decode dispatch) and ``serve.prefill.error`` (admission
  dispatch fails: the request exits ``prefill_error`` with its pages
  released — deterministically, no requeue loop);
- **live weight hot-swap** — :meth:`swap_params` installs a new decode
  param tree between decode steps (same shapes: zero recompiles) after
  a finite-logits canary prefill aimed entirely at the scratch page, so
  the swap is invisible to resident sequences; a failed canary rolls
  back to the prior weights (serving/replica.py drives this from
  CheckpointManager publications).

Capacity multipliers (ISSUE 15):

- **refcounted prefix caching** (on by default;
  ``MXTPU_SERVE_PREFIX_CACHE=0`` disables) — admission matches each
  prompt's longest page-aligned cached prefix
  (serving/prefix_cache.py), maps the shared pages into the block
  table by reference (``PagedKVAllocator`` refcounts), copy-on-writes
  a prefix that ends mid-page, and prefills ONLY the un-cached suffix
  (``gpt.paged_prefill``, one program for every hit length —
  ``prefix_len`` is traced).  Registration happens after a SUCCESSFUL
  prefill; the ``serve.prefix.evict`` fault site force-drops the index
  between steps (victims fall back to a full prefill with correct
  tokens).  The headline win is ADMISSION CAPACITY (shared pages are
  not re-stored) plus the prompt-quadratic prefill FLOPs skipped at
  real prompt lengths; on the CPU interpret path a hit's wall time is
  NOT lower than a miss's (the static-pad suffix window still runs
  every position, plus the prefix gather).  Telemetry:
  ``serving.prefix.{hits,miss,shared_pages,cow_copies,evictions}`` +
  ``serving.prefill_tokens`` (logical tokens prefilled);
- **grouped-query attention** (``kv_heads=`` / ``MXTPU_SERVE_KV_HEADS``)
  — page pools shaped ``[num_pages, page_size, K_kv * D]`` with
  ``K_kv <= H`` (decode_params mean-pools the K/V projections), so KV
  bytes per resident token shrink ``H / K_kv``-fold and the same pool
  bytes hold proportionally more sequences;
- **quantized KV pages** (``kv_dtype=`` / ``MXTPU_SERVE_KV_DTYPE``,
  ISSUE 20) — ``bf16`` halves and ``int8`` quarters the page payload
  vs fp32 (int8 adds per-page-per-KV-head fp32 absmax scales:
  quantize-on-scatter in the programs, dequant inside the paged
  kernels; scores/softmax/output stay fp32).  Composes
  multiplicatively with GQA and prefix sharing.  Quantized greedy
  streams are pinned to THEMSELVES across churn/hot-swap/failover —
  NOT bit-identical to fp32 (the kernel-vs-oracle tolerance and
  ``check_kvq_greedy_match_rate_vs_fp`` in tests/serving_driver.py
  pin the error).  int8 decode carries a
  per-slot finite mask — the divergence guard behind the
  ``serve.kv.scale_poison`` drill (victims re-prefill in place).
  Telemetry: ``serving.kv.{dtype,bytes_per_token}`` gauges +
  ``serving.kv.scale_repairs``;
- **per-request sampling decode** — temperature/top-k/top-p as
  per-SLOT program inputs plus a seeded per-slot PRNG key advanced
  functionally inside the donated step: same (seed, params, prompt) ->
  same tokens regardless of batch composition, join/leave, hot-swap,
  or failover re-decode (greedy = temp 0 stays bit-identical).

Request-scope tracing (ISSUE 13, OBSERVABILITY.md §12): every request
carries a trace id (minted here, or passed through from the Router so a
failover re-decode stays ONE trace) and leaves a lifecycle event at each
transition — ``submit``/``place``, ``admit`` (slot + queue wait),
``prefill`` (dispatch/sync wall), one ``token`` event per prefill first
token, ONE batched ``tokens`` event per decode step naming every
advanced trace (hot-path: a single tuple append, same discipline as the
flight recorder), a ``swap`` pause event naming the resident traces it
interrupted, and exactly one terminal ``verdict`` event (``final`` when
this engine owns the trace).  ``serving.goodput`` counts tokens on
requests that COMPLETED within deadline (vs raw ``serving.tokens``).
"""
from __future__ import annotations

import collections
import functools
import itertools
import os
import time
import weakref

import numpy as _np

from .. import aot_cache as _aot
from .. import fault as _fault
from .. import profiler as _profiler
from .. import telemetry as _telemetry
from .. import watchdog as _watchdog
from ..base import MXNetError
from .kv_cache import PagedKVAllocator, SCRATCH_PAGE, normalize_kv_dtype
from .prefix_cache import PrefixCache
from .programs import KVPages, LatentPages, SlotState
from .scheduler import (CANCELLED, ContinuousBatchingScheduler, EXPIRED,
                        FAILED, FINISHED, QUEUED, RUNNING,
                        SamplingParams, VERDICT_ABANDONED,
                        VERDICT_CANCELLED, VERDICT_COMPLETED,
                        VERDICT_DRAINING, VERDICT_EXPIRED_DECODE,
                        VERDICT_PREFILL_ERROR, VERDICT_REJECTED)
from .slo import SLOController

__all__ = ["ServingEngine", "live_snapshot", "ngram_draft"]

# every live engine, weakly held: the crash postmortem
# (telemetry.dump_postmortem) folds live_snapshot() in so a stalled or
# dying replica's record says what it was serving, not just that it died
_ENGINES = weakref.WeakSet()
_engine_seq = itertools.count()


def live_snapshot():
    """Serving snapshots of every live engine in this process (the
    postmortem's ``serving`` block); [] when none exist."""
    out = []
    for eng in list(_ENGINES):
        try:
            out.append(eng.snapshot())
        except Exception:
            pass  # a half-constructed engine must not break a postmortem
    return out


def _env_float(name):
    try:
        v = float(os.environ.get(name, "0"))
    except ValueError:
        return None
    return v if v > 0 else None


def _fetch_async(*arrays):
    """Start the device-to-host copy of every array that is one (None
    and host values pass): the ``np.asarray`` / ``int()`` that follows
    then finds the bytes on their way and waits once, not once an
    array."""
    for a in arrays:
        start = getattr(a, "copy_to_host_async", None)
        if start is not None:
            start()


@functools.lru_cache(maxsize=None)
def _zeros_program(shape, dtype):
    """A jitted zeros of one shape, compiled once however many layers
    ask for it.  Every call returns a FRESH XLA-owned buffer."""
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda: jnp.zeros(shape, dtype))


#: what a cache kind other than paged K/V is called, and why it cannot
#: share a prefix, roll a rejected draft back or hold int8 pages
_KIND_REFUSALS = {
    "latent": {"name": "latent pages",
               "prefix": "a latent row is written by one prefill from "
                         "position 0",
               "spec": "a latent pool has no verify program",
               "int8": "a latent row has no per-page scale"},
    "state": {"name": "per-slot recurrent state",
              "prefix": "a recurrent layer's prefix is a state, not a "
                        "list of pages",
              "spec": "a recurrent state cannot be rolled back",
              "int8": "a recurrent state has no per-page scale"},
    "ring": {"name": "per-slot window rings",
             "prefix": "a cached prefix has no ring",
             "spec": "a rejected draft has overwritten ring rows",
             "int8": "a ring row has no per-page scale"}}


def _refusal(kinds, what):
    """``(names, reasons)`` of the declared kinds other than paged K/V:
    what the model keeps, and why each kind refuses ``what`` ("prefix"
    reuse, "spec"ulative decoding or "int8" pages)."""
    found = []
    for kind in kinds or ():
        name = "latent" if isinstance(kind, LatentPages) \
            else kind.role if isinstance(kind, SlotState) else None
        if name and name not in found:
            found.append(name)
    return (" and ".join(_KIND_REFUSALS[n]["name"] for n in found),
            "; ".join(_KIND_REFUSALS[n][what] for n in found))


def ngram_draft(context, k, max_n=3):
    """Model-free n-gram drafter (prompt-lookup decoding): propose the
    continuation of the LAST earlier occurrence of the context's
    length-``n`` suffix, longest ``n`` first (``max_n`` .. 1).  Returns
    up to ``k`` token ids, or ``[]`` when no suffix recurs — an honest
    "no proposal" beats a random one (every rejected draft costs a
    verify position).  Pure host-side numpy on ints; this is the default
    ``spec_drafter`` and the reference signature for a plugged-in draft
    net: ``(context int32[L], k) -> sequence of <= k token ids``."""
    ctx = _np.asarray(context, _np.int64).reshape(-1)
    n_ctx = int(ctx.size)
    if k < 1 or n_ctx < 2:
        return []
    for n in range(min(int(max_n), n_ctx - 1), 0, -1):
        suffix = ctx[n_ctx - n:]
        # vectorized window-equality over every earlier start (the
        # suffix's own start is excluded by the window count)
        hit = _np.ones(n_ctx - n, _np.bool_)
        for t in range(n):
            hit &= ctx[t:t + n_ctx - n] == suffix[t]
        starts = _np.flatnonzero(hit)
        if starts.size:
            # prefer the LATEST occurrence that still has a full-k
            # continuation before the context's end (a periodic context
            # shorter than its last period would otherwise truncate the
            # draft to the cycle remainder); fall back to the latest
            # occurrence overall for a partial draft
            full = starts[starts + n + int(k) <= n_ctx]
            j = int(full[-1]) if full.size else int(starts[-1])
            cont = ctx[j + n:j + n + int(k)]
            if cont.size:
                return [int(t) for t in cont]
    return []


class ServingEngine:
    """Continuous-batching greedy-decode server over a model-zoo net
    that answers ``serving_programs()`` (serving/programs.py: its
    programs and each layer's cache kind).

    ``num_slots`` decode slots, a shared pool of ``num_pages`` KV pages
    of ``page_size`` tokens; prompts are padded to ``max_prefill_len``
    (one prefill program) and ``prompt + max_new <= max_seq_len`` per
    request.  A model whose prefill is CHUNKED
    (``ServingPrograms.chunked_prefill``) admits longer prompts: the one
    program runs a chunk of ``max_prefill_len`` rows at a time against
    the slot's own pages, one run an engine step for the prefilling
    slot admitted first, while the other slots go on decoding
    (SERVING.md section 3).  Greedy argmax decoding (deterministic — the join/leave
    bit-exactness invariant is testable), optional ``eos_id`` early
    stop.

    ``record_logits=True`` keeps every request's per-token logits rows
    (tests bit-check them across occupancy changes); off in production.

    ``decode_ahead=n`` keeps ``n`` more decode dispatches on the device
    than the step reads (SERVING.md section 3): they, and an admission's
    prefill, go out before the tokens they follow are read.  Same
    tokens, no device idle between programs.
    """

    def __init__(self, net, num_slots=4, page_size=16, num_pages=None,
                 max_prefill_len=32, max_seq_len=None, eos_id=None,
                 record_logits=False, slo=None, default_deadline_s=None,
                 kv_heads=None, prefix_cache=None, spec_k=None,
                 spec_drafter=None, kv_dtype=None, decode_ahead=False):
        # everything model-shaped comes from this one object: the
        # programs and, layer by layer, the kind of cache they keep
        self._model = net.serving_programs()
        self._net = net
        self._n_heads = self._model.n_heads
        max_len = self._model.max_len
        paged_kv = self._model.cache_kinds is None
        if not paged_kv and kv_heads is not None:
            raise ValueError(
                "kv_heads regroups the K/V heads of a model of paged K/V "
                "alone; this model declares its own caches (%s)"
                % (_refusal(self._model.cache_kinds, "name")[0]
                   or "K/V pages at its own head count"))
        # grouped-query serving (ISSUE 15): K_kv <= H KV heads shrink
        # the page pools H/K_kv-fold -> proportionally more resident
        # sequences for the same pool bytes.  Explicit arg wins; env
        # opt-in via MXTPU_SERVE_KV_HEADS; default = the model's H
        # (bit-identical to the pre-GQA engine).
        if kv_heads is None:
            kv_heads = int(os.environ.get("MXTPU_SERVE_KV_HEADS", "0")) \
                or self._n_heads
        self.kv_heads = int(kv_heads)
        # quantized KV pages (ISSUE 20): ``kv_dtype`` picks the page
        # pools' storage — fp32 (default, bit-identical), bf16 (half
        # the payload bytes, cast on scatter), or int8 (quarter the
        # bytes: absmax quantize-on-scatter in the programs + per-page-
        # per-KV-head fp32 scale pools dequantized inside the paged
        # kernels).  Composes multiplicatively with GQA and prefix
        # sharing.  Quantized greedy streams are pinned to THEMSELVES
        # across churn/hot-swap/failover — bit-identity to the fp32
        # path is explicitly NOT the law (a kernel-vs-oracle tolerance
        # and a token-match-rate test pin the error instead).
        # Explicit arg wins; env opt-in via MXTPU_SERVE_KV_DTYPE.
        if kv_dtype is None:
            kv_dtype = os.environ.get("MXTPU_SERVE_KV_DTYPE") or None
        elif hasattr(kv_dtype, "kv_dtype"):
            # a mxnet_tpu.precision.PrecisionPolicy: the serving page
            # dtype is one field of the general policy
            kv_dtype = kv_dtype.kv_dtype
        self.kv_dtype = normalize_kv_dtype(kv_dtype)
        if not paged_kv and self.kv_dtype == "int8":
            raise ValueError(
                "int8 pages are defined for a model of paged K/V pools "
                "alone; this model keeps %s: %s (use bf16 or fp32)"
                % _refusal(self._model.cache_kinds, "int8"))
        self._p = self._model.decode_params(net, kv_heads=self.kv_heads)
        self._n_layers = len(self._p["layers"])
        self._units = int(self._p["wte"].shape[1])
        self._vocab = int(self._p["wte"].shape[0])
        self._head_dim = self._units // self._n_heads
        self.num_slots = int(num_slots)
        self.page_size = int(page_size)
        self.max_prefill_len = int(max_prefill_len)
        self.max_seq_len = int(max_seq_len if max_seq_len is not None
                               else max_len)
        if self.max_seq_len > max_len:
            raise ValueError("max_seq_len %d exceeds the model's "
                             "max_len %d" % (self.max_seq_len, max_len))
        if self.max_prefill_len > self.max_seq_len:
            raise ValueError("max_prefill_len > max_seq_len")
        self._chunked = self._model.chunked_prefill
        # speculative decoding (ISSUE 16): up to ``spec_k`` host-drafted
        # tokens per slot are VERIFIED by the same single donated decode
        # dispatch (no second program, no shape churn — k is a compile-
        # time width, acceptance is a mask).  0 = off, the pre-spec
        # engine bit-for-bit.  Explicit arg wins; env opt-in via
        # MXTPU_SERVE_SPEC_K; ``spec_drafter`` plugs in a custom
        # proposer (default: the model-free n-gram drafter above).
        if spec_k is None:
            spec_k = int(os.environ.get("MXTPU_SERVE_SPEC_K", "0") or 0)
        self.spec_k = int(spec_k)
        if self.spec_k < 0:
            raise ValueError("spec_k must be >= 0")
        if self.spec_k and not paged_kv:
            raise ValueError(
                "speculative decoding rolls rejected drafts back by "
                "masking pages; this model keeps %s: %s, so spec_k must "
                "be 0 for this model"
                % _refusal(self._model.cache_kinds, "spec"))
        if self.spec_k and \
                self.max_seq_len + self.spec_k > max_len:
            raise ValueError(
                "speculative decoding needs max_seq_len + spec_k <= "
                "the model's max_len (draft positions run past the "
                "last committed token): %d + %d > %d — lower "
                "max_seq_len or spec_k"
                % (self.max_seq_len, self.spec_k, max_len))
        self._drafter = (spec_drafter if spec_drafter is not None
                         else ngram_draft)
        # ``decode_ahead`` = n: a step sends decode dispatches until
        # 1 + n are unread, THEN waits for the oldest, so the device
        # finds its next program queued while the host fetches, emits
        # and admits.  The tokens and keys a dispatch follows stay on
        # the device (the program selects them over the host's rows); a
        # request whose last token is on its way sits a dispatch out; an
        # admission's prefill joins the same queue and its first token
        # is read with the decode that follows it.  Tokens are the same;
        # what moves: an admission's programs run after the n already sent,
        # and an early stop (EOS, cancel, deadline) costs its slot up
        # to n unread decodes.  0: the engine as it was, program for
        # program.
        self._decode_ahead = int(decode_ahead)
        if self._decode_ahead < 0:
            raise ValueError("decode_ahead must be >= 0")
        if self._decode_ahead and (self.spec_k
                                   or self.kv_dtype == "int8"):
            raise ValueError(
                "decode_ahead sends a step's successor before its "
                "tokens are read: a speculative step's accepted length "
                "and an int8 step's repair decide the successor's "
                "inputs on the host, so spec_k must be 0 and kv_dtype "
                "fp32 or bf16")
        #: dispatches sent and not read yet, oldest first (each a record
        #: of ``_send_decode`` / ``_send_prefill``); empty between steps
        #: unless ``decode_ahead``
        self._unread = collections.deque()
        # draft positions may spill past max_seq_len by up to spec_k
        # tokens: the per-sequence page budget covers the worst case so
        # a draft write can never land outside the request's own pages
        self.max_pages_per_seq = -(-(self.max_seq_len + self.spec_k)
                                   // self.page_size)
        if num_pages is None:
            # full capacity + scratch: every slot can hold a max-length
            # sequence.  Pass a smaller pool to get real admission
            # pressure (the OOM-aware path).
            num_pages = self.num_slots * self.max_pages_per_seq + 1
        self.eos_id = None if eos_id is None else int(eos_id)
        self._record_logits = bool(record_logits)

        # one kind of cache a layer (serving/programs.py); a model that
        # names none keeps paged K/V pools everywhere
        self._kinds = self._model.cache_kinds or tuple(
            KVPages(self.kv_heads, self._head_dim)
            for _ in range(self._n_layers))
        self._slot_state = any(isinstance(k, SlotState)
                               for k in self._kinds)
        self.alloc = PagedKVAllocator(
            num_pages, self.page_size, kv_dtype=self.kv_dtype,
            slot_state_bytes=self.state_bytes_per_slot)
        # refcounted prefix caching (ISSUE 15): on by default
        # (MXTPU_SERVE_PREFIX_CACHE=0 / prefix_cache=False disables).
        # Admission maps a prompt's longest page-aligned cached prefix
        # into the block table by reference and prefills only the
        # suffix — system-prompt-heavy traffic turns shared pages into
        # a direct admission-capacity and TTFT multiplier.
        # A cached prefix is a list of pages; a recurrent layer's
        # prefix is a state, and a latent row is written by one prefill
        # program from position 0: prefix reuse is OFF for such a model
        # (asking for it is an error; snapshots are ROADMAP's item).
        if not paged_kv:
            if prefix_cache:
                raise ValueError(
                    "the prefix cache shares K/V pages; this model "
                    "keeps %s: %s"
                    % _refusal(self._model.cache_kinds, "prefix"))
            prefix_cache = False
        if prefix_cache is None:
            prefix_cache = os.environ.get(
                "MXTPU_SERVE_PREFIX_CACHE", "1") not in ("0", "off", "")
        self._prefix = PrefixCache(self.alloc) if prefix_cache else None
        self.sched = ContinuousBatchingScheduler(
            self.num_slots, self.alloc, self.max_pages_per_seq,
            max_seq_len=self.max_seq_len, prefix_cache=self._prefix,
            spec_k=self.spec_k)
        # host-side spec accounting (bench reconciles these against the
        # serving.spec.* counters and the raw token counts):
        # ``spec_slot_steps`` — active-slot decode participations;
        # ``spec_discarded`` — accepted tokens dropped host-side by the
        # max_new / EOS truncation (committed K/V, uncounted tokens)
        self.spec_slot_steps = 0
        self.spec_discarded = 0
        # per-request sampling decode (ISSUE 15): per-SLOT params
        # arrays + functionally-advanced PRNG keys are ordinary decode
        # program inputs — never a recompile.  Greedy slots (temp 0)
        # take the argmax path bit-identically.  Env defaults apply to
        # submits that pass no SamplingParams.
        self._temps = _np.zeros(self.num_slots, _np.float32)
        self._top_ks = _np.zeros(self.num_slots, _np.int32)
        self._top_ps = _np.zeros(self.num_slots, _np.float32)
        self._keys = _np.zeros((self.num_slots, 2), _np.uint32)
        self.default_sampling = self._env_sampling()

        # survivability plane (ISSUE 11): SLO shed controller (explicit
        # arg wins; env opt-in via MXTPU_SERVE_SLO_P99_S; None = the
        # queue-forever behavior), default request deadline, drain flag
        self._slo = slo if slo is not None else SLOController.from_env()
        self.default_deadline_s = (default_deadline_s
                                   if default_deadline_s is not None
                                   else _env_float("MXTPU_SERVE_DEADLINE_S"))
        # streamed token delivery (ISSUE 19): every placed request is
        # reachable by trace id for cursor polls; terminal requests stay
        # registered (their token buffer is the re-poll recovery store)
        # until terminal + MXTPU_SERVE_STREAM_TTL_S.  A request whose
        # last poll is older than MXTPU_SERVE_ABANDON_S (unset = off —
        # unary clients never poll and must never be reclaimed) is
        # reclaimed with verdict ``abandoned`` before admission, like
        # the deadline sweeps.
        self._streams = {}          # trace -> Request
        self._waiting = set()       # traces whose last poll got 0 tokens
        self.abandoned = 0          # orphans reclaimed by THIS engine
        ttl = _env_float("MXTPU_SERVE_STREAM_TTL_S")
        self.stream_ttl_s = 60.0 if ttl is None else ttl
        self.abandon_s = _env_float("MXTPU_SERVE_ABANDON_S")
        self.stream_chunk = int(
            os.environ.get("MXTPU_SERVE_STREAM_CHUNK", "0") or 0) or 64
        self.draining = False
        self.swaps = 0
        # distinct watchdog lease key per engine in this process: one
        # engine going idle (release) must not retire the lease another
        # still-decoding engine depends on.  Production replicas hold
        # one engine, whose lease is plain "serve_step".
        seq = next(_engine_seq)
        self._lease = "serve_step" if seq == 0 else "serve_step@%d" % seq
        # request-scope tracing identity: serve_report attributes every
        # event to this tag (a ServingReplica overwrites it with its
        # replica_id, so fleet views name replicas, not engine ordinals)
        self.trace_tag = "engine%d" % seq
        #: checkpoint epoch currently serving (set by swap_params; the
        #: periodic serving status line carries it)
        self.weights_epoch = None
        self._kv = self._init_pages()
        #: what the last prefill / decode dispatch returned beside its
        #: tokens, still on the device: ``(logits, aux)`` of a model
        #: whose programs report ``aux`` (None otherwise)
        self.last_prefill = self.last_decode = None
        #: running sums of the counts the programs report
        #: (``ServingPrograms.decode_stats``), by program
        self.stat_totals = {"decode": {}, "prefill": {}}
        self.decode_steps = 0
        self.prefills = 0
        #: runs of the prefill program for a chunked model (``prefills``
        #: counts the prompts whose last chunk was read)
        self.prefill_chunks = 0
        # scale-poison repairs per resident request (rid -> count): the
        # divergence-guard recovery below re-prefills a victim at most
        # a few times before declaring its state unrecoverable
        self._kv_repairs = {}
        self._build_programs()
        _ENGINES.add(self)
        _telemetry.gauge("serving.kv_pages_free").set(
            self.alloc.free_pages)
        _telemetry.gauge("serving.batch_occupancy").set(0)
        # storage-mode gauges (ISSUE 20): bits per stored K/V value and
        # all-layer KV bytes one committed token costs (scale overhead
        # amortized per page); serve_report / fleet_top surface both
        _telemetry.gauge("serving.kv.dtype").set(
            8 * self.alloc.kv_itemsize)
        _telemetry.gauge("serving.kv.bytes_per_token").set(
            self.kv_bytes_per_token)
        if not paged_kv:
            # a model of mixed kinds: what a token costs in pages, and
            # what the slots' window rings hold whatever their lengths
            _telemetry.gauge("serving.cache.page_bytes_per_token").set(
                self.kv_bytes_per_token)
            _telemetry.gauge("serving.cache.ring_bytes").set(
                self.num_slots * self._slot_bytes("ring"))
        #: pages the paged kernel reads a block, for the block_fill
        #: gauge (None: the model's decode does not run that kernel)
        self._pages_per_block = None
        if paged_kv:
            from ..ops.pallas.paged_attention import pages_per_block
            pool = self._kv[0][0]
            self._pages_per_block = pages_per_block(
                self.page_size, pool.shape[2], pool.dtype,
                self.max_pages_per_seq)

    @staticmethod
    def _env_sampling():
        """Fleet-wide sampling defaults (SERVING.md env table):
        MXTPU_SERVE_TEMPERATURE / MXTPU_SERVE_TOP_K / MXTPU_SERVE_TOP_P
        / MXTPU_SERVE_SEED.  All unset -> None (greedy), matching the
        pre-ISSUE-15 contract bit-for-bit.  A filter knob (top-k/top-p)
        with NO temperature set means temperature 1.0 — temp 0 would
        silently argmax past the operator's filter."""
        raw_temp = os.environ.get("MXTPU_SERVE_TEMPERATURE")
        top_k = int(os.environ.get("MXTPU_SERVE_TOP_K", "0"))
        top_p = float(os.environ.get("MXTPU_SERVE_TOP_P", "0"))
        if raw_temp is None and top_k == 0 and top_p == 0:
            return None
        s = SamplingParams(
            temperature=None if raw_temp is None else float(raw_temp),
            top_k=top_k, top_p=top_p,
            seed=int(os.environ.get("MXTPU_SERVE_SEED", "0")))
        return None if s.greedy and not (top_k or top_p) else s

    def params_from_net(self, net):
        """The decode-param tree for THIS engine's configuration (the
        hot-swap entry point: a GQA engine needs the same K/V head
        pooling applied to the incoming weights, or swap_params would
        rightly reject the shape mismatch)."""
        return self._model.decode_params(net, kv_heads=self.kv_heads)

    # -- device state ------------------------------------------------------
    def _init_pages(self):
        """Per-layer (k_pages, v_pages) pools as FRESH XLA-owned buffers
        — they are donated every step, and a donated buffer must not
        alias anything a caller still references (ROBUSTNESS.md §8c).
        A jitted zeros program guarantees that by construction (each
        execution allocates fresh outputs); anything ever RESTORED into
        pages from host data must instead go through
        ``parallel.sharding.fresh_device_put`` — an eager device_put can
        alias its source, and donating the alias frees the source's
        memory out from under it."""
        import jax
        import jax.numpy as jnp

        if self.kv_dtype != "int8":
            return [self._init_cache(kind) for kind in self._kinds]
        # int8 payload + per-page-per-KV-head fp32 absmax scales
        # (gpt._quant_scatter resets a fresh page's scale before
        # writing, so the zero init is never load-bearing)
        shape = (self.alloc.num_pages, self.page_size,
                 self.kv_heads * self._head_dim)
        sshape = (self.alloc.num_pages, self.kv_heads)
        mk = jax.jit(lambda: (jnp.zeros(shape, jnp.int8),
                              jnp.zeros(sshape, jnp.float32)))
        out = []
        for _ in range(self._n_layers):
            kc, ks = mk()
            vc, vs = mk()
            out.append((kc, vc, ks, vs))
        return out

    def _init_cache(self, kind):
        """One layer's cache of a declared kind, as fresh XLA-owned
        buffers: ``(k, v)`` pools, one latent pool, or the per-slot
        arrays (one row a slot and a scratch row, which a weight-swap
        canary writes)."""
        dt = "bfloat16" if self.kv_dtype == "bf16" else "float32"

        def zeros(shape, dtype):
            return _zeros_program(tuple(shape), str(dtype))()

        pages = (self.alloc.num_pages, self.page_size)
        if isinstance(kind, KVPages):
            # a token's KV heads side by side on the minor axis: with
            # K_kv * D a multiple of 128 the chip keeps this row-major
            # with no lane padding, which is what the programs' scatters
            # and the paged kernel address
            shape = pages + (kind.heads * kind.head_dim,)
            return (zeros(shape, dt), zeros(shape, dt))
        if isinstance(kind, LatentPages):
            return tuple(zeros(pages + (w,), dt) for w in kind.widths)
        return tuple(zeros((self.num_slots + 1,) + tuple(shape),
                           dtype or dt)
                     for _, shape, dtype in kind.arrays)

    @property
    def kv_bytes_per_token(self):
        """All-layer paged-cache bytes one committed token occupies
        under this engine's ``kv_dtype`` (per-page scale overhead
        amortized over the page) — the SERVING.md §2d sizing unit.  A
        per-slot state costs no bytes a token (``state_bytes_per_slot``)."""
        per_page = 0
        for kind in self._kinds:
            if isinstance(kind, KVPages):
                per_page += self.alloc.page_bytes(kind.heads,
                                                  kind.head_dim)
            elif isinstance(kind, LatentPages):
                per_page += self.alloc.latent_page_bytes(sum(kind.widths))
        return per_page / float(self.page_size)

    @property
    def state_bytes_per_slot(self):
        """All-layer bytes of per-slot arrays (recurrent state, window
        rings) one resident sequence holds, whatever its length."""
        return self._slot_bytes()

    def _slot_bytes(self, role=None):
        """Bytes a slot of the :class:`SlotState` layers' arrays, all
        of them or those of one ``role``."""
        item = 4 if self.kv_dtype == "fp32" else 2
        return sum(
            int(_np.prod(shape)) * (_np.dtype(dtype).itemsize
                                    if dtype else item)
            for kind in self._kinds if isinstance(kind, SlotState)
            and role in (None, kind.role)
            for _, shape, dtype in kind.arrays)

    # -- program construction ---------------------------------------------
    def _config_hash(self):
        """Everything about this engine that changes the traced programs
        but not the input shapes — goes into the AOT cache key the way
        Module passes its symbol/optimizer hash."""
        # NOTE: the prefix-cache flag is deliberately NOT in the key —
        # cache-on and cache-off engines compile the SAME two programs
        # (a miss/off prefill is the cond's dense branch), so they
        # share AOT entries and the in-process memo
        h = ("serve|L%d|h%d|kv%d|u%d|v%d|ps%d|np%d|slots%d|mp%d|"
             "pf%d|%s"
             % (self._n_layers, self._n_heads, self.kv_heads,
                self._units, self._vocab, self.page_size,
                self.alloc.num_pages, self.num_slots,
                self.max_pages_per_seq, self.max_prefill_len,
                type(self._net).__name__))
        if self.spec_k:
            # appended only when ON: spec-off engines keep their
            # pre-ISSUE-16 keys (and every AOT entry already on disk)
            h += "|spec%d" % self.spec_k
        if self.kv_dtype != "fp32":
            # same discipline (ISSUE 20): fp32 engines keep their
            # existing keys; bf16/int8 re-key (their input trees also
            # differ — pool dtypes, int8's scale pools, and the int8
            # programs' extra finite-mask output)
            h += "|kvq:%s" % self.kv_dtype
        if self._model.cache_kinds is not None:
            # declared cache kinds re-key; paged-K/V models keep theirs
            h += "|kinds:%r|%s" % (self._kinds, self._model.config_key)
        if self._decode_ahead:
            h += "|ahead"
        return h

    def _build_programs(self):
        import jax

        gpt = self._model
        n_heads = self._n_heads
        # int8 engines (ISSUE 20) append a per-slot finite mask over
        # the step's logits to the decode outputs: the divergence guard
        # for quantized storage (a poisoned/NaN page scale surfaces as
        # non-finite logits for exactly the slots reading that page;
        # step() re-prefills the victims with their correct tokens).
        # fp32/bf16 programs keep their exact pre-ISSUE-20 signatures.
        quant = self.kv_dtype == "int8"

        def _finite(logits):
            import jax.numpy as jnp
            axes = tuple(range(1, logits.ndim))
            return jnp.isfinite(logits).all(axes)

        if self.spec_k:
            # the spec-decode program: the SAME single donated dispatch
            # per step, now scoring 1 + spec_k query positions per slot
            # (the multi-query-position verify kernel) and returning
            # the accepted token run per slot
            def decode(p, kv_pages, tokens, positions, active,
                       draft_len, block_tables, temps, top_ks, top_ps,
                       keys):
                out = gpt.spec_decode_step(
                    p, tokens, positions, active, draft_len, kv_pages,
                    block_tables, n_heads,
                    sampling=(temps, top_ks, top_ps, keys))
                return out + (_finite(out[0]),) if quant else out
        elif self._decode_ahead:
            # a slot CARRIED from the dispatch before takes that
            # dispatch's token and key, which never left the device
            def decode(p, kv_pages, tokens, positions, active,
                       block_tables, temps, top_ks, top_ps, keys,
                       carried, prev_tokens, prev_keys):
                import jax.numpy as jnp
                tokens = jnp.where(carried, prev_tokens, tokens)
                keys = jnp.where(carried[:, None], prev_keys, keys)
                return gpt.decode_step(
                    p, tokens, positions, active, kv_pages,
                    block_tables, n_heads,
                    sampling=(temps, top_ks, top_ps, keys))
        else:
            def decode(p, kv_pages, tokens, positions, active,
                       block_tables, temps, top_ks, top_ps, keys):
                out = gpt.decode_step(
                    p, tokens, positions, active, kv_pages,
                    block_tables, n_heads,
                    sampling=(temps, top_ks, top_ps, keys))
                return out + (_finite(out[0]),) if quant else out

        # ONE prefill program whether the prefix cache is on or off: a
        # traced prefix_len of 0 (every admission with the cache off,
        # every miss with it on) takes gpt.paged_prefill's plain causal
        # branch — bit-identical to the pre-prefix-cache prefill; only
        # hits pay the attention over the gathered prefix.  Samples the
        # request's FIRST token under its params.
        # a model with per-slot state is told which slot it admits into
        if self._decode_ahead:
            # the first token and key also join the device's per-slot
            # rows, which the next decode takes its carried slots from
            slot_state = self._slot_state

            def prefill(p, kv_pages, tokens, prompt_len, prefix_len,
                        bt_row, cow_src, cow_dst, temp, top_k, top_p,
                        key, slot, row_tokens, row_keys):
                out = gpt.prefill(
                    p, tokens, prompt_len, prefix_len, bt_row, cow_src,
                    cow_dst, kv_pages, n_heads,
                    sampling=(temp, top_k, top_p, key),
                    **({"slot": slot} if slot_state else {}))
                return out + ((row_tokens.at[slot].set(out[1]),
                               row_keys.at[slot].set(out[2])),)
        elif self._slot_state:
            def prefill(p, kv_pages, tokens, prompt_len, prefix_len,
                        bt_row, cow_src, cow_dst, temp, top_k, top_p,
                        key, slot):
                return gpt.prefill(
                    p, tokens, prompt_len, prefix_len, bt_row, cow_src,
                    cow_dst, kv_pages, n_heads,
                    sampling=(temp, top_k, top_p, key), slot=slot)
        else:
            def prefill(p, kv_pages, tokens, prompt_len, prefix_len,
                        bt_row, cow_src, cow_dst, temp, top_k, top_p,
                        key):
                return gpt.prefill(
                    p, tokens, prompt_len, prefix_len, bt_row, cow_src,
                    cow_dst, kv_pages, n_heads,
                    sampling=(temp, top_k, top_p, key))

        def sds(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)

        p_ex = jax.tree_util.tree_map(sds, self._p)
        kv_ex = jax.tree_util.tree_map(sds, self._kv)
        s, mp, tp = self.num_slots, self.max_pages_per_seq, \
            self.max_prefill_len
        i32, f32, u32 = _np.int32, _np.float32, _np.uint32
        if self.spec_k:
            k1 = self.spec_k + 1
            decode_ex = (p_ex, kv_ex,
                         jax.ShapeDtypeStruct((s, k1), i32),
                         jax.ShapeDtypeStruct((s, k1), i32),
                         jax.ShapeDtypeStruct((s,), _np.bool_),
                         jax.ShapeDtypeStruct((s,), i32),
                         jax.ShapeDtypeStruct((s, mp), i32),
                         jax.ShapeDtypeStruct((s,), f32),
                         jax.ShapeDtypeStruct((s,), i32),
                         jax.ShapeDtypeStruct((s,), f32),
                         jax.ShapeDtypeStruct((s, 2), u32))
        else:
            decode_ex = (p_ex, kv_ex,
                         jax.ShapeDtypeStruct((s,), i32),
                         jax.ShapeDtypeStruct((s,), i32),
                         jax.ShapeDtypeStruct((s,), _np.bool_),
                         jax.ShapeDtypeStruct((s, mp), i32),
                         jax.ShapeDtypeStruct((s,), f32),
                         jax.ShapeDtypeStruct((s,), i32),
                         jax.ShapeDtypeStruct((s,), f32),
                         jax.ShapeDtypeStruct((s, 2), u32))
        if self._decode_ahead:
            decode_ex += (jax.ShapeDtypeStruct((s,), _np.bool_),
                          jax.ShapeDtypeStruct((s,), i32),
                          jax.ShapeDtypeStruct((s, 2), u32))
        samp_ex = (jax.ShapeDtypeStruct((), f32),
                   jax.ShapeDtypeStruct((), i32),
                   jax.ShapeDtypeStruct((), f32),
                   jax.ShapeDtypeStruct((2,), u32))
        prefill_ex = (p_ex, kv_ex,
                      jax.ShapeDtypeStruct((tp,), i32),
                      jax.ShapeDtypeStruct((), i32),
                      jax.ShapeDtypeStruct((), i32),
                      jax.ShapeDtypeStruct((mp,), i32),
                      jax.ShapeDtypeStruct((), i32),
                      jax.ShapeDtypeStruct((), i32)) + samp_ex
        if self._slot_state or self._decode_ahead:
            prefill_ex += (jax.ShapeDtypeStruct((), i32),)
        if self._decode_ahead:
            prefill_ex += (jax.ShapeDtypeStruct((s,), i32),
                           jax.ShapeDtypeStruct((s, 2), u32))
        extra = self._config_hash()
        self._decode = self._compile("decode", decode, decode_ex, extra)
        self._prefill = self._compile("prefill", prefill, prefill_ex,
                                      extra)

    def _compile(self, name, fn, examples, extra):
        """AOT-compile one serving program through the executable cache
        (the executor._aot_fit_step tiers, serving flavor):

        - memo hit: same-process rebuild, the original compiled object;
        - disk hit, donated variant (TPU-class): deserialize + run;
        - disk hit, plain variant (CPU): run the donation-free twin now,
          hot-swap the donated program in when its background compile
          lands — first token never waits on XLA;
        - miss: compile the donated program (outside jax's persistent
          cache on hazard backends), then store this backend's
          consumable variant off the hot path.

        Every tier returns a ``profiler.instrument``-wrapped callable so
        steady-state dispatch/recompile accounting holds engine-wide,
        and notes the program it serves on for good
        (``telemetry.note_program``: its text says which scope each
        instruction of a device trace lies in; the twin of a hot-swap
        serves a few steps and the lazy jit has no text: neither is
        noted).
        Any cache failure falls back to guarded lazy jit — the cache can
        make spin-up faster, never break serving."""
        import jax

        def mk_jit(donated=True):
            return jax.jit(fn, donate_argnums=(1,) if donated else ())

        try:
            tag = "serve_" + name
            key = _aot.cache_key(tag, examples, extra=extra)
            memo = _aot.memo_get(key)
            if memo is not None:
                _telemetry.note_program(tag, memo)
                return _profiler.instrument(memo,
                                            first_call_compiles=False)
            if _aot.enabled():
                loaded = _aot.load(key)
                if loaded is not None:
                    compiled, var, _meta = loaded
                    from .. import watchdog as _watchdog
                    _watchdog.note_warm_start()
                    if var == _aot.VARIANT_DONATED:
                        _aot.memo_put(key, compiled)
                        _telemetry.note_program(tag, compiled)
                        return _profiler.instrument(
                            compiled, first_call_compiles=False)
                    # warm hazard-backend spin-up: serve on the twin
                    # now, hot-swap the donated program in when its
                    # background compile lands (§8 shared machinery)
                    return _profiler.instrument(
                        _aot.twin_hotswap_cell(mk_jit, examples, key,
                                               compiled,
                                               where="mxnet_tpu.serving"),
                        first_call_compiles=False)
            with _telemetry.span("serving.compile", cat="serving"):
                with _aot.bypass_persistent_cache():
                    compiled = mk_jit().lower(*examples).compile()
            _aot.memo_put(key, compiled)
            _telemetry.note_program(tag, compiled)
            if _aot.enabled():
                _aot.spawn_variant_store(mk_jit, examples, key,
                                         compiled,
                                         where="mxnet_tpu.serving")
            # the compile happened HERE (eagerly), so the instrumented
            # first call must not charge a second phantom compile
            _profiler.count_compile()
            return _profiler.instrument(compiled,
                                        first_call_compiles=False)
        except Exception as e:
            import logging
            logging.warning(
                "mxnet_tpu.serving: AOT path unavailable for %s "
                "(%s: %s); using guarded lazy jit", name,
                type(e).__name__, e)
            return _profiler.instrument(
                _aot.donation_cache_guard(mk_jit()))

    # -- request intake ----------------------------------------------------
    def submit(self, prompt, max_new, deadline_s=None, trace=None,
               sampling=None, spec_k=None):
        """Enqueue one request (prompt: 1-d int token array).  Returns
        the Request handle; tokens appear on it as the engine steps.

        ``deadline_s``: total budget from now (queue wait + decode);
        defaults to the engine's ``default_deadline_s`` (None = no
        deadline).  The handle can come back ALREADY terminal with a
        typed verdict — ``shed`` when the SLO controller is refusing
        intake, ``draining`` while the replica drains — so callers fail
        fast instead of waiting on a queue that will never serve them.
        Infeasible requests (can never fit) still raise ValueError.

        ``sampling``: a :class:`SamplingParams` (or its dict form) for
        per-request temperature/top-k/top-p decode with a seeded
        per-slot PRNG — same (seed, params, prompt) -> same tokens
        regardless of batch composition (the determinism law).  None
        uses the engine's env default (greedy when unset, bit-identical
        to the sampling-free engine).

        ``trace``: request-scope trace id.  None (direct callers) mints
        one here and this engine's terminal verdict event is FINAL; the
        Router passes its own id through so a failover re-decode on a
        survivor replica continues the same trace, and fleet-level
        terminality stays the Router's to stamp.

        ``spec_k``: per-request speculative-decoding cap — None uses
        the engine's ``spec_k``, 0 disables drafting for THIS request
        (it still rides the spec program, with an empty draft), any
        positive value caps the per-step draft at
        ``min(engine.spec_k, spec_k)``.  Serialized over RPC like
        sampling; it changes scheduling only, never the token stream
        (acceptance is exact, so fewer drafts mean more steps for the
        SAME tokens)."""
        prompt = _np.asarray(prompt, _np.int32).reshape(-1)
        sampling = SamplingParams.from_doc(sampling)
        if spec_k is not None and int(spec_k) < 0:
            raise ValueError("spec_k must be >= 0")
        if sampling is None:
            sampling = self.default_sampling
        # malformed-argument raises (the scheduler's Request rules)
        # happen BEFORE any trace event: they produce no handle, so
        # they must open no trace a verdict would then never close
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if int(max_new) < 1:
            raise ValueError("max_new must be >= 1")
        owned = trace is None
        if owned:
            trace = _telemetry.mint_trace()
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        _telemetry.note_request_event(
            trace, "submit" if owned else "place",
            args={"replica": self.trace_tag,
                  "prompt_len": int(prompt.size),
                  "max_new": int(max_new), "deadline_s": deadline_s,
                  "sampling": (None if sampling is None
                               else sampling.to_doc())})
        if prompt.size > self.max_prefill_len and not self._chunked:
            self._close_unplaced(trace, owned, VERDICT_REJECTED)
            raise ValueError(
                "prompt length %d exceeds max_prefill_len %d"
                % (prompt.size, self.max_prefill_len))
        # infeasibility is checked BEFORE the shed/drain branches: a
        # request that can NEVER run must get the terminal ValueError,
        # not a retryable-looking refusal a router would bounce forever
        err = self.sched.feasibility_error(prompt.size, max_new)
        if err is not None:
            self._close_unplaced(trace, owned, VERDICT_REJECTED,
                                 error=err)
            raise ValueError(err)
        if self.draining:
            _telemetry.counter("serving.drain_rejects").inc()
            req = self.sched.shed(
                prompt, max_new, verdict=VERDICT_DRAINING,
                error="replica is draining: finishing residents, "
                      "admitting nothing new")
            return self._trace_refusal(req, trace, owned)
        if self._slo is not None and self._slo.should_shed(
                self.sched.oldest_queue_wait):
            _telemetry.counter("serving.shed").inc()
            req = self.sched.shed(
                prompt, max_new,
                error="shed: queue-wait p99 %.3fs over SLO target %.3fs"
                      % (self._slo.windowed_p99(),
                         self._slo.target_p99_s))
            return self._trace_refusal(req, trace, owned)
        req = self.sched.submit(prompt, max_new, deadline_s)
        req.trace = trace
        req.trace_owned = owned
        self._streams[trace] = req
        req.sampling = sampling
        req.spec_k = None if spec_k is None else int(spec_k)
        if sampling is not None and not sampling.greedy:
            _telemetry.counter("serving.sampling.requests").inc()
        if self._record_logits:
            req.logits_trace = []
        _telemetry.counter("serving.requests").inc()
        return req

    # -- request-scope trace plumbing --------------------------------------
    def _close_unplaced(self, trace, owned, verdict, error=None):
        """Terminal verdict event for a request that never produced a
        scheduler handle (infeasible submit): the trace still closes."""
        args = {"verdict": verdict, "final": bool(owned),
                "replica": self.trace_tag, "tokens": 0}
        if error:
            args["error"] = str(error)[:200]
        _telemetry.note_request_event(trace, "verdict", args=args)

    def _trace_refusal(self, req, trace, owned):
        """Stamp trace identity on a shed/draining refusal handle and
        close (or, router-owned, annotate) its trace — a refused request
        still reaches a verdict span (no trace is ever left open)."""
        req.trace = trace
        req.trace_owned = owned
        self._close_trace(req)
        return req

    def _close_trace(self, req):
        """The terminal verdict event: verdict + the latency stamps the
        fleet percentiles split on.  ``final`` is False for router-owned
        traces (an engine-level shed may be just one hop of a spread;
        the Router emits the one FINAL verdict per trace)."""
        if req.trace is None:
            return
        args = {"verdict": req.verdict, "final": bool(req.trace_owned),
                "replica": self.trace_tag, "rid": req.rid,
                "tokens": len(req.tokens)}
        if req.ttft_s is not None:
            args["ttft_s"] = round(req.ttft_s, 6)
        if req.queue_wait_s is not None:
            args["queue_wait_s"] = round(req.queue_wait_s, 6)
        if req.tpot_s is not None:
            args["tpot_s"] = round(req.tpot_s, 6)
        if req.error:
            args["error"] = str(req.error)[:200]
        _telemetry.note_request_event(req.trace, "verdict", args=args)

    def _finish(self, req, state=FINISHED, verdict=None, error=None):
        """Every resident exit routes through here: the scheduler's
        finish (slot + pages released) plus the trace close and the
        goodput accounting — ``serving.goodput`` counts only tokens on
        requests that COMPLETED (reached every token within deadline),
        the numerator of the goodput-vs-raw-tokens split."""
        slot = req.slot
        self.sched.finish(req, state, verdict=verdict, error=error)
        self._kv_repairs.pop(req.rid, None)
        # clear the slot's sampling rows: a stale temp > 0 would make
        # every later ALL-GREEDY decode step pay the sampling math
        # (the lax.cond predicate reads these rows)
        if slot is not None:
            self._temps[slot] = 0.0
            self._top_ks[slot] = 0
            self._top_ps[slot] = 0.0
        if req.verdict == VERDICT_COMPLETED:
            _telemetry.counter("serving.goodput").inc(len(req.tokens))
        self._close_trace(req)

    # -- the serving loop --------------------------------------------------
    def _expire_deadlines(self):
        """The per-step deadline sweep: queued requests past deadline
        leave with ``expired_queue`` (no slot, no pages — pure
        bookkeeping); residents past deadline are finished with
        ``expired_decode`` BEFORE the decode dispatch, releasing slot +
        pages, so an expired request never burns another token."""
        for req in self.sched.expire_queued():
            _telemetry.counter("serving.expired_queue").inc()
            self._close_trace(req)
        now = time.perf_counter()
        for req in self.sched.expired_running(now):
            self._finish(
                req, EXPIRED, verdict=VERDICT_EXPIRED_DECODE,
                error="deadline %.3fs passed mid-decode after %d of %d "
                      "tokens" % (req.deadline_s, len(req.tokens),
                                  req.max_new))
            _telemetry.counter("serving.expired_decode").inc()

    # -- streamed delivery (ISSUE 19) --------------------------------------
    def poll(self, trace, cursor=0, max_tokens=None):
        """One cursor pull against a request's emitted-token buffer:
        returns the tokens after ``cursor`` (bounded chunk) plus the
        terminal verdict / ``more`` flag, or None for an unknown trace
        (never placed here, or already swept after terminal +
        ``stream_ttl_s``).  Stateless and idempotent — the client holds
        the cursor, so a dropped reply is recovered by re-polling the
        SAME cursor and the integer index can never deliver a token
        twice or skip one.  ``req.tokens`` is append-only until
        terminal, which is what makes the slice law safe.  A successful
        poll stamps ``last_poll_t`` — the orphan sweep's liveness
        evidence."""
        req = self._streams.get(trace)
        if req is None:
            return None
        now = time.perf_counter()
        req.last_poll_t = now
        cursor = max(0, int(cursor))
        chunk = (self.stream_chunk if max_tokens is None
                 else max(1, int(max_tokens)))
        toks = [int(t) for t in req.tokens[cursor:cursor + chunk]]
        new_cursor = cursor + len(toks)
        more = (not req.done) or new_cursor < len(req.tokens)
        _telemetry.counter("serving.stream.polls").inc()
        if toks:
            self._waiting.discard(trace)
            _telemetry.counter("serving.stream.delivered").inc(
                len(toks))
            # one trace-less ``poll`` event per DELIVERING poll: the
            # serve_report delivery phase joins emit stamps to first-
            # coverage stamps through these (empty polls carry no new
            # coverage, so they stay off the event stream)
            _telemetry.note_request_event(
                "", "poll",
                args={"replica": self.trace_tag, "trace": req.trace,
                      "rid": req.rid, "cursor": new_cursor})
        elif not req.done:
            self._waiting.add(trace)
        return {"trace": req.trace, "rid": req.rid,
                "cursor": new_cursor, "tokens": toks, "more": more,
                "state": req.state, "verdict": req.verdict,
                "error": req.error, "done": req.done}

    def cancel(self, trace):
        """Client-initiated teardown: lands the typed terminal verdict
        ``cancelled`` between decode steps (this is called from the
        dispatch gaps — RPC handling and router harvests both sit
        between ``step()`` calls), releasing slot + pages through the
        one `_finish` exit path.  Idempotent: cancelling a terminal
        request reports its existing verdict; unknown traces return
        None."""
        req = self._streams.get(trace)
        if req is None:
            return None
        if not req.done:
            if req.state == RUNNING:
                self._finish(req, CANCELLED, verdict=VERDICT_CANCELLED,
                             error="cancelled by client after %d of %d "
                                   "tokens" % (len(req.tokens),
                                               req.max_new))
            else:
                self.sched.cancel_queued(
                    req, error="cancelled by client while queued")
                self._close_trace(req)
            self._waiting.discard(trace)
            _telemetry.counter("serving.stream.cancelled").inc()
        return {"trace": req.trace, "rid": req.rid,
                "state": req.state, "verdict": req.verdict,
                "tokens": len(req.tokens)}

    def sweep_streams(self):
        """The pre-admission stream sweep (runs with the deadline
        sweeps): (a) reclaim orphans — any request a client STARTED
        streaming (``last_poll_t`` set) and then went silent on for
        more than ``abandon_s`` exits with verdict ``abandoned``,
        releasing slot + pages, so a vanished client can never pin the
        KV pool; (b) drop terminal buffers older than terminal +
        ``stream_ttl_s`` (after which a poll is a declared unknown, not
        a silent gap)."""
        now = time.perf_counter()
        if self.abandon_s is not None:
            for req in list(self.sched.running):
                if req.last_poll_t is not None and \
                        now - req.last_poll_t > self.abandon_s:
                    self._finish(
                        req, CANCELLED, verdict=VERDICT_ABANDONED,
                        error="no poll for %.3fs (abandon_s %.3fs); "
                              "orphan reclaimed after %d of %d tokens"
                              % (now - req.last_poll_t, self.abandon_s,
                                 len(req.tokens), req.max_new))
                    self.abandoned += 1
                    _telemetry.counter("serving.stream.abandoned").inc()
            for req in [r for r in self._streams.values()
                        if r.state == QUEUED]:
                if req.last_poll_t is not None and \
                        now - req.last_poll_t > self.abandon_s:
                    self.sched.cancel_queued(
                        req, verdict=VERDICT_ABANDONED,
                        error="no poll for %.3fs while queued; orphan "
                              "reclaimed" % (now - req.last_poll_t))
                    self._close_trace(req)
                    self.abandoned += 1
                    _telemetry.counter("serving.stream.abandoned").inc()
        dead = [tr for tr, r in self._streams.items()
                if r.done and r.finish_t is not None
                and now - r.finish_t > self.stream_ttl_s]
        for tr in dead:
            del self._streams[tr]
            self._waiting.discard(tr)
            _telemetry.counter("serving.stream.expired").inc()

    def _arm_slot_sampling(self, req):
        """Install the request's sampling params into its slot's rows
        of the per-slot decode arrays and seed the slot's PRNG key.
        Greedy requests (or None) zero the row — the decode program's
        ``temp > 0`` select takes the argmax path for them.  Returns
        the scalar (temp, top_k, top_p, key) the prefill consumes."""
        import jax
        s = req.sampling
        slot = req.slot
        if s is None or s.greedy:
            self._temps[slot] = 0.0
            self._top_ks[slot] = 0
            self._top_ps[slot] = 0.0
            self._keys[slot] = 0
        else:
            self._temps[slot] = s.temperature
            self._top_ks[slot] = s.top_k
            self._top_ps[slot] = s.top_p
            self._keys[slot] = _np.asarray(
                jax.random.PRNGKey(s.seed), _np.uint32)
        return (_np.float32(self._temps[slot]),
                _np.int32(self._top_ks[slot]),
                _np.float32(self._top_ps[slot]),
                self._keys[slot].copy())

    def _note_prefix_admission(self, req):
        """The prefix-cache accounting for one admission (hit/miss
        split, shared-page and COW counters, prefilled-token counter:
        ``check_prefix_sharing_and_cow`` pins a hit to its suffix)."""
        suffix = int(req.prompt.size) - req.prefix_len
        _telemetry.counter("serving.prefill_tokens").inc(suffix)
        if self._prefix is None:
            return
        if req.prefix_len > 0:
            _telemetry.counter("serving.prefix.hits").inc()
            _telemetry.counter("serving.prefix.shared_pages").inc(
                req.shared_count)
            if req.cow_src is not None:
                _telemetry.counter("serving.prefix.cow_copies").inc()
        else:
            _telemetry.counter("serving.prefix.miss").inc()

    def _admit_and_prefill(self):
        """Join phase: place queued requests into free slots and run one
        prefill dispatch each (pages donated through; the request's
        first generated token comes back with it).  On a prefix-cache
        hit only the UN-CACHED suffix prefills (shared pages were
        mapped by reference at admission; a prefix ending mid-page is
        copy-on-written inside the same dispatch).  Each dispatch runs
        under a ``serve.prefill`` watchdog guard (a wedged prefill is a
        diagnosable stall, not a silent hang); an injected
        ``serve.prefill.error`` fails THAT request deterministically —
        typed ``prefill_error`` verdict, slot + every reserved page
        released, never requeued — and the loop moves on.  Returns the
        tokens produced (with ``decode_ahead`` none yet: the prefills
        wait in ``_unread``)."""
        produced = 0
        with _telemetry.span("serve.admit", "serving") as sp:
            admitted = self.sched.admit()
            sp.set(admitted=len(admitted))
        for req in admitted:
            _telemetry.histogram("serving.queue_wait").observe(
                req.queue_wait_s)
            _telemetry.note_request_event(
                req.trace, "admit",
                args={"replica": self.trace_tag, "slot": req.slot,
                      "rid": req.rid,
                      "queue_wait_s": round(req.queue_wait_s, 6),
                      "pages": len(req.pages),
                      "prefix_hit": req.prefix_len > 0,
                      "prefix_len": req.prefix_len,
                      "shared_pages": req.shared_count})
            if self._slo is not None:
                self._slo.observe(req.queue_wait_s)
            try:
                _fault.check("serve.prefill.error",
                             "prefill failed for request %d" % req.rid)
            except _fault.FaultInjected as e:
                self._finish(req, FAILED,
                             verdict=VERDICT_PREFILL_ERROR,
                             error=str(e))
                _telemetry.counter("serving.prefill_errors").inc()
                continue
            if self._chunked:
                # its chunks go out below, one an engine step
                continue
            produced += self._prefill_run(req)
        if self._chunked:
            # one chunk run a step, for the slot admitted first
            req = next(iter(self.sched.prefilling), None)
            if req is not None:
                produced += self._prefill_run(req)
        return produced

    def _prefill_run(self, req):
        """One run of the prefill program for ``req``: its whole prompt,
        or for a chunked model its next ``max_prefill_len`` rows.
        Returns the tokens produced (none with ``decode_ahead``: the run
        waits in ``_unread``)."""
        # one span a run: a device gap under it is that request's (rid
        # and trace tie it to the request events)
        with _telemetry.span(
                "serve_prefill", "serving", rid=req.rid,
                trace=req.trace, prompt=int(req.prompt.size),
                prefix_len=req.prefix_len, offset=req.prefilled,
                queue_wait_us=int(req.queue_wait_s * 1e6)):
            sent = self._send_prefill(req)
            if self._decode_ahead:
                # read when its turn comes (``_step``)
                self._unread.append(sent)
                return 0
            return self._emit_prefill(sent)

    def _send_prefill(self, req):
        """One prefill dispatch of an admitted request; nothing is
        waited for.  A chunked model's covers the prompt's rows from
        ``req.prefilled`` on, as many as the program holds.  With
        ``decode_ahead`` the program also sets the first token and the
        key in the device's per-slot rows, as the newest unread
        dispatch left them, and the next decode takes them from there.
        Returns the dispatch's record."""
        with _watchdog.guard("serve.prefill"):
            with _telemetry.stamp_span("serve_prefill.dispatch") as disp:
                samp = self._arm_slot_sampling(req)
                toks = _np.zeros(self.max_prefill_len, _np.int32)
                # req.prefix_len is 0 with the cache off or on a miss:
                # the suffix is then the whole prompt and the program's
                # dense branch runs.  A chunk's first row stands where a
                # cached prefix would end
                start = req.prefilled if self._chunked else req.prefix_len
                end = min(int(req.prompt.size),
                          start + self.max_prefill_len)
                toks[:end - start] = req.prompt[start:end]
                logits, first, new_key, stats, rows = self._run_prefill(
                    toks, end, start,
                    self.sched.block_tables[req.slot].copy(),
                    req.cow_src if req.cow_src is not None
                    else SCRATCH_PAGE,
                    req.cow_dst if req.cow_dst is not None
                    else SCRATCH_PAGE, samp, req.slot)
                req.prefilled = end
                _fetch_async(first, new_key, stats)
        # only the run that ends the prompt yields the request's token
        last = end == req.prompt.size
        return {"reqs": [req] if last else [], "req": req, "last": last,
                "span": (start, end), "logits": logits, "first": first,
                "new_key": new_key, "stats": stats, "rows": rows,
                "disp": disp}

    def _emit_prefill(self, sent):
        """Wait for one prefill's first token and do the bookkeeping
        that commits it.  Returns the tokens emitted."""
        req, disp = sent["req"], sent["disp"]
        with _watchdog.guard("serve.prefill"):
            with _telemetry.stamp_span("serve_prefill.sync") as sync:
                first = int(sent["first"])          # device sync
                if sent["stats"] is not None:
                    self._note_stats("prefill", _np.asarray(sent["stats"]))
        if self._chunked:
            start, end = sent["span"]
            self.prefill_chunks += 1
            _telemetry.counter("serving.prefill.chunks").inc()
            _telemetry.counter("serving.prefill.chunk_rows").inc(
                end - start)
            _telemetry.counter("serving.prefill.chunk_rows_padded").inc(
                self.max_prefill_len)
            _telemetry.note_request_event(
                req.trace, "prefill_chunk", t_ns=sync.t1,
                args={"offset": start, "rows": end - start,
                      "last": sent["last"]})
            if not sent["last"]:
                return 0
        # a prefill read after its step: its two phases are noted back
        # to back, each at its own length
        t1 = sync.t0 if self._decode_ahead else disp.t1
        t0, t2 = t1 - (disp.t1 - disp.t0), sync.t1
        self.prefills += 1
        _telemetry.counter("serving.prefills").inc()
        if req.done:
            # it left (cancel, deadline) while its prefill was unread
            return 0
        # prefix/prefill-token accounting AFTER the dispatch landed: a
        # prefill that failed (fault above) must not count tokens that
        # were never prefilled
        self._note_prefix_admission(req)
        self._keys[req.slot] = _np.asarray(sent["new_key"], _np.uint32)
        if self._prefix is not None:
            # register the prompt's full pages under their content keys
            # — ONLY now, after the prefill landed: a failed prefill
            # must never leave the index naming pages whose contents
            # never materialized (the cache stamps the cached_pages
            # gauge itself)
            self._prefix.insert(req.prompt,
                                self.sched.block_tables[req.slot])
        _telemetry.note_train_step(t0, t1, t2, where="serve_prefill")
        _telemetry.note_request_event(
            req.trace, "prefill", t_ns=t0,
            args={"dispatch_s": round((t1 - t0) * 1e-9, 9),
                  "sync_s": round((t2 - t1) * 1e-9, 9),
                  "prefill_tokens":
                      int(req.prompt.size) - req.prefix_len})
        # the prefill's first token: one ``token`` event, stamped BEFORE
        # _note_token so a finish-on-first-token (max_new=1) orders
        # token -> verdict in the trace
        _telemetry.note_request_event(req.trace, "token", t_ns=t2)
        self._note_token(req, first,
                         _np.asarray(sent["logits"])
                         if self._record_logits else None)
        return 1

    def _device_rows(self):
        """The per-slot ``(tokens, keys)`` the newest unread dispatch
        left on the device; the host's own with nothing unread."""
        if self._unread:
            return self._unread[-1]["rows"]
        return (_np.zeros(self.num_slots, _np.int32), self._keys.copy())

    def _run_prefill(self, toks, prompt_len, prefix_len, bt_row, cow_src,
                     cow_dst, samp, slot):
        """One dispatch of the prefill program; the caches come back
        donated-through.  A model with per-slot state also gets the
        slot (``num_slots`` is the scratch row), and so does the
        ``decode_ahead`` program, with the device's per-slot rows.
        Returns ``(logits, first token, new key, the program's counts
        or None, the rows with this slot's set or None)``, all still on
        the device."""
        args = (toks, _np.int32(prompt_len), _np.int32(prefix_len),
                bt_row, _np.int32(cow_src), _np.int32(cow_dst)) \
            + tuple(samp)
        if self._slot_state or self._decode_ahead:
            args += (_np.int32(slot),)
        if self._decode_ahead:
            args += tuple(self._device_rows())
        out = self._prefill(self._p, self._kv, *args)
        rows = None
        if self._decode_ahead:
            *out, rows = out
        stats = None
        if self._model.has_aux:
            # the program's own report of the dispatch (routing), kept
            # on the device for whoever asks (:attr:`last_prefill`)
            *out, aux = out
            self.last_prefill = (out[0], aux)
            stats = aux["stats"]
        logits, first, new_key, self._kv = out
        return logits, first, new_key, stats, rows

    def _note_token(self, req, token, logits_row=None):
        now = time.perf_counter()
        req.tokens.append(int(token))
        req.token_times.append(now)
        if req.first_token_t is None:
            req.first_token_t = now
            _telemetry.histogram("serving.ttft").observe(req.ttft_s)
        else:
            _telemetry.histogram("serving.tpot").observe(
                now - req.token_times[-2])
        _telemetry.counter("serving.tokens").inc()
        if self._record_logits and logits_row is not None:
            req.logits_trace.append(_np.array(logits_row, _np.float32))
        if len(req.tokens) >= req.max_new or \
                (self.eos_id is not None and int(token) == self.eos_id):
            self._finish(req, FINISHED)

    def step(self):
        """One serving iteration: deadline sweep, admit+prefill joins,
        then ONE donated decode dispatch advancing every resident slot.
        Returns the number of tokens produced (0 == idle).

        Hang defense: a completed step renews the ``serve_step``
        progress lease; going idle releases it (an idle replica is not
        stalled).  The ``serve.decode.stall`` fault site wedges right
        before the decode dispatch WITHOUT renewing — exactly the
        production failure (a hung XLA dispatch / device lockup) the
        watchdog's exit-75 path exists for."""
        with _telemetry.span("serve.step", "serving",
                             step=self.decode_steps,
                             live=self.sched.occupancy,
                             queued=self.sched.queued):
            return self._step()

    def _step(self):
        with _telemetry.span("serve.sweep", "serving"):
            # the ``serve.prefix.evict`` drill: force-drop the whole
            # prefix index between steps — victims fall back to a full
            # prefill with correct tokens (the cache is a capacity
            # optimization, NEVER a correctness dependency; test-pinned)
            if self._prefix is not None and _fault.trigger(
                    "serve.prefix.evict"):
                self.drop_prefix_cache()
            # the ``serve.kv.scale_poison`` drill (ISSUE 20, int8
            # pools): NaN-poison one resident page's scale row between
            # steps — the quantized divergence guard must catch the
            # victim's non-finite logits on the next decode and
            # re-prefill it with its correct tokens, leaving every other
            # resident's stream untouched
            if self.kv_dtype == "int8" and self.sched.running and \
                    _fault.trigger("serve.kv.scale_poison"):
                self._poison_page_scale()
            self._expire_deadlines()
            self.sweep_streams()
        # every placed request produces exactly one token in its prefill
        produced = self._admit_and_prefill()
        running = self.sched.running
        if not running:
            # what is unread has nobody left to read it
            while self._unread:
                produced += self._emit(self._unread.popleft())
            if produced:
                _watchdog.renew(self._lease, step=self.decode_steps,
                                phase="serve_step")
            if self.sched.idle:
                _watchdog.release(self._lease)
            self._publish_gauges()
            return produced
        # arm the lease BEFORE the dispatch (auxiliary — it must not end
        # the startup-grace window that covers a lazily-compiling first
        # dispatch): a decode that wedges right here, including the very
        # first one, ages this lease with no renewal coming — exactly
        # what the watchdog exists to catch.  The post-decode renewal
        # below is the primary "real progress" mark.
        _watchdog.renew(self._lease, step=self.decode_steps,
                        phase="serve_step", primary=False)
        _fault.stall_if("serve.decode.stall")

        if self.spec_k:
            return produced + self._spec_decode_once(running)

        # this step's successors go out before its own tokens are
        # waited for: the device finds them queued when this one ends
        unread = self._unread
        while sum("nxt" in d for d in unread) <= self._decode_ahead:
            sent = self._send_decode(running)
            if sent is None:
                break
            unread.append(sent)
        # ONE decode dispatch is read a step.  A prefill sent before it
        # is read with it, once the decode's tokens are there: the host
        # never sits waiting on a prefill whose successor is queued
        prefills = []
        while unread and "nxt" not in unread[0]:
            prefills.append(unread.popleft())
        if unread:
            return produced + self._emit_decode(unread.popleft(), prefills)
        return produced + sum(self._emit_prefill(d) for d in prefills)

    def _emit(self, sent):
        return (self._emit_decode if "nxt" in sent
                else self._emit_prefill)(sent)

    def _send_decode(self, running):
        """Pack and dispatch ONE decode step for ``running`` and start
        its results' way home; nothing is waited for.  A request in an
        unread dispatch (``decode_ahead`` only) continues from the token
        on the device, as many positions on as dispatches hold it,
        unless its last token by count is among them.  Returns the
        dispatch's record, or None with nobody to advance."""
        s = self.num_slots
        unread = collections.Counter(
            r.rid for d in self._unread for r in d["reqs"])
        with _telemetry.span("serve.decode.pack", "serving",
                             live=len(running)):
            tokens = _np.zeros(s, _np.int32)
            positions = _np.zeros(s, _np.int32)
            active = _np.zeros(s, _np.bool_)
            carried = _np.zeros(s, _np.bool_)
            reqs = []
            for req in running:
                n = len(req.tokens) + unread[req.rid]
                if n >= req.max_new or req.prefilling:
                    continue
                if unread[req.rid]:
                    carried[req.slot] = True
                else:
                    tokens[req.slot] = req.tokens[-1]
                # context already in pages: prompt + generated-but-last;
                # the last generated token is what this step feeds in,
                # at position prompt_len + (n_generated - 1)
                positions[req.slot] = req.prompt.size + n - 1
                active[req.slot] = True
                reqs.append(req)
            if not reqs:
                return None
            self._note_block_fill(positions[active])
            args = (tokens, positions, active,
                    self.sched.block_tables.copy(), self._temps.copy(),
                    self._top_ks.copy(), self._top_ps.copy(),
                    self._keys.copy())
            if self._decode_ahead:
                args += (carried,) + tuple(self._device_rows())

        with _telemetry.stamp_span("serve_step.dispatch") as disp:
            res = self._decode(self._p, self._kv, *args)
            aux = None
            if self._model.has_aux:
                # the step's own counts come back with its tokens: one
                # small vector, fetched after the token sync
                *res, aux = res
            if self.kv_dtype == "int8":
                logits, nxt, new_keys, self._kv, ok_dev = res
            else:
                logits, nxt, new_keys, self._kv = res
                ok_dev = None
            # everything the host reads back from this step starts its
            # way home now, together: each blocking fetch would
            # otherwise pay its own round trip after the program ends
            _fetch_async(nxt, new_keys, aux and aux["stats"], ok_dev)
        return {"reqs": reqs, "logits": logits, "nxt": nxt,
                "new_keys": new_keys, "rows": (nxt, new_keys), "aux": aux,
                "ok_dev": ok_dev, "disp": disp}

    def _emit_decode(self, sent, prefills=()):
        """Wait for one dispatch's tokens and hand each to its request
        (one that left since the dispatch went out is passed over),
        after the first tokens of ``prefills``, which ran before it.
        Returns the tokens emitted."""
        disp, aux, ok_dev = sent["disp"], sent["aux"], sent["ok_dev"]
        with _telemetry.stamp_span("serve_step.sync") as sync:
            nxt = _np.asarray(sent["nxt"])   # device sync barrier
            if aux is not None:
                self.last_decode = (sent["logits"], aux)
                self._note_stats("decode", _np.asarray(aux["stats"]))
        first = sum(self._emit_prefill(d) for d in prefills)
        running = [r for r in sent["reqs"] if not r.done]
        with _telemetry.span("serve.decode.emit", "serving") as emit:
            # per-slot PRNG state advances FUNCTIONALLY inside the
            # donated program; the host copy is the only carry between
            # steps (np.array, not asarray: a jax-backed view is
            # read-only and admission writes per-slot rows)
            keys_prev = self._keys
            self._keys = _np.array(sent["new_keys"], _np.uint32)
            if self._decode_ahead:
                # a slot admitted since the dispatch went out holds its
                # prefill's key: only the advanced slots' rows are news
                rows = [r.slot for r in running]
                keys_prev[rows] = self._keys[rows]
                self._keys = keys_prev
            victims = ()
            if ok_dev is not None:
                okm = _np.asarray(ok_dev)
                victims = tuple(r for r in running if not okm[r.slot])
            # a dispatch sent ahead ended a step ago: its two phases
            # are noted back to back, each at its own length
            t1 = sync.t0 if self._decode_ahead else disp.t1
            _telemetry.note_train_step(t1 - (disp.t1 - disp.t0), t1,
                                       sync.t1, where="serve_step")
            # ONE batched ``tokens`` event per decode step naming every
            # advanced trace (all residents share the step's sync stamp
            # anyway) — per-token tracing at flight-recorder cost; the
            # per-trace token count is len-weighted at read time and
            # must equal the serving.tokens delta bit-exactly
            # (test-pinned)
            _telemetry.note_request_event(
                "", "tokens", t_ns=sync.t1,
                args={"replica": self.trace_tag,
                      "step": self.decode_steps,
                      "traces": [r.trace for r in running
                                 if r not in victims]})
            self.decode_steps += 1
            _watchdog.renew(self._lease, step=self.decode_steps,
                            phase="serve_step")
            logits_np = _np.asarray(sent["logits"]) \
                if self._record_logits else None
            made = 0
            for req in running:
                if req in victims:
                    continue
                self._note_token(
                    req, nxt[req.slot],
                    None if logits_np is None else logits_np[req.slot])
                made += 1
            if victims:
                self._repair_quant_victims(victims, keys_prev)
            if self.sched.idle:
                _watchdog.release(self._lease)
            self._publish_gauges()
            emit.set(tokens=made)
        return first + made

    # -- speculative decoding (ISSUE 16) -----------------------------------
    def _draft_for(self, req):
        """Host-side draft proposal for one resident, capped so no
        accepted run can overshoot the request's budget by more than
        the EOS/truncation slack (``max_new - produced - 1`` leaves
        room for the bonus token).  The ``serve.spec.poison`` drill
        corrupts the proposal BETWEEN draft and verify — verification
        must then reject every poisoned position and the emitted stream
        stay exactly the non-speculative one (self-correction is the
        safety property the drill pins)."""
        k = self.spec_k if req.spec_k is None \
            else min(self.spec_k, int(req.spec_k))
        cap = min(int(k), req.max_new - len(req.tokens) - 1)
        if cap <= 0:
            return []
        ctx = _np.concatenate(
            [req.prompt, _np.asarray(req.tokens, _np.int32)])
        # clamp a buggy custom drafter into vocab: an out-of-range
        # draft would index the embedding OOB inside the program
        drafts = [int(t) % self._vocab
                  for t in self._drafter(ctx, cap)][:cap]
        if drafts and _fault.trigger("serve.spec.poison"):
            drafts = [(d + 1) % self._vocab for d in drafts]
        return drafts

    def _spec_decode_once(self, running):
        """The speculative decode dispatch: ONE donated program scores
        each slot's last committed token plus up to ``spec_k`` drafted
        tokens and commits the longest accepted prefix (+ the bonus
        token from the last accepted position's distribution).  Greedy
        slots accept by exact argmax match — the emitted stream is the
        greedy chain itself, bit-identical to spec-off; sampled slots
        verify by rejection sampling against the slot's functional PRNG
        — one key advance per EMITTED token, so the per-request
        determinism law (same seed -> same stream) survives any draft
        quality, batch composition, or failover re-decode.  Pages past
        the committed position hold only draft K/V during the dispatch
        and are marked speculative for the duration — a release that
        beats the commit/rollback is caught by the allocator, and
        ``assert_conservation`` audits the marks.  Returns tokens
        produced."""
        s, k1 = self.num_slots, self.spec_k + 1
        ps = self.page_size
        with _telemetry.span("serve.decode.pack", "serving",
                             live=len(running)):
            tokens = _np.zeros((s, k1), _np.int32)
            positions = _np.zeros((s, k1), _np.int32)
            active = _np.zeros(s, _np.bool_)
            draft_len = _np.zeros(s, _np.int32)
            drafted = 0
            marked = []
            for req in running:
                drafts = self._draft_for(req)
                base = int(req.prompt.size) + len(req.tokens) - 1
                tokens[req.slot, 0] = req.tokens[-1]
                if drafts:
                    tokens[req.slot, 1:1 + len(drafts)] = drafts
                positions[req.slot] = base + _np.arange(k1)
                draft_len[req.slot] = len(drafts)
                active[req.slot] = True
                drafted += len(drafts)
                # pages strictly past the one holding the committed
                # position receive ONLY draft K/V this dispatch
                row = self.sched.block_tables[req.slot]
                for li in range(base // ps + 1,
                                (base + len(drafts)) // ps + 1):
                    marked.append(int(row[li]))
            if marked:
                self.alloc.mark_speculative(marked)
            if drafted:
                _telemetry.counter("serving.spec.draft_tokens").inc(
                    drafted)
            self._note_block_fill((positions[:, 0] + draft_len)[active])
            args = (tokens, positions, active, draft_len,
                    self.sched.block_tables.copy(), self._temps.copy(),
                    self._top_ks.copy(), self._top_ps.copy(),
                    self._keys.copy())

        try:
            with _telemetry.stamp_span("serve_step.dispatch") as disp:
                res = self._decode(self._p, self._kv, *args)
                if self.kv_dtype == "int8":
                    logits, out, n_new, new_keys, self._kv, ok_dev = res
                else:
                    logits, out, n_new, new_keys, self._kv = res
                    ok_dev = None
            with _telemetry.stamp_span("serve_step.sync") as sync:
                out = _np.asarray(out)           # device sync barrier
                n_new = _np.asarray(n_new)
        finally:
            # acceptance is decided the moment the dispatch returns:
            # rejected positions are masked by every later read and
            # overwritten in place, so commit/rollback is bookkeeping
            # only — and a FAILED dispatch must not leave marks a later
            # release would trip over
            if marked:
                self.alloc.clear_speculative(marked)
        with _telemetry.span("serve.decode.emit", "serving") as emit:
            produced = self._spec_emit(
                running, out, n_new, draft_len, new_keys, ok_dev, logits,
                disp.t0, disp.t1, sync.t1)
            emit.set(tokens=produced)
        return produced

    def _spec_emit(self, running, out, n_new, draft_len, new_keys, ok_dev,
                   logits, t0, t1, t2):
        """Commit what one verified speculative dispatch accepted:
        keys, acceptance accounting, the step's record and ``tokens``
        event, then every emitted token."""
        keys_prev = self._keys
        self._keys = _np.array(new_keys, _np.uint32)
        victims = ()
        if ok_dev is not None:
            okm = _np.asarray(ok_dev)
            victims = tuple(r for r in running if not okm[r.slot])

        accepted = rejected = rollbacks = 0
        emitted = {}
        for req in running:
            if req in victims:
                # quantized divergence guard: the whole verified run is
                # garbage — discard it (no accept/reject accounting)
                emitted[req] = []
                continue
            n = int(n_new[req.slot])
            dl = int(draft_len[req.slot])
            accepted += n - 1
            rejected += dl - (n - 1)
            if n - 1 < dl:
                rollbacks += 1
            self.spec_slot_steps += 1
            take = [int(t) for t in
                    out[req.slot,
                        :min(n, req.max_new - len(req.tokens))]]
            if self.eos_id is not None and self.eos_id in take:
                take = take[:take.index(self.eos_id) + 1]
            # accepted-but-discarded tail: K/V committed, token counted
            # nowhere — tracked so bench's token identity reconciles
            self.spec_discarded += n - len(take)
            emitted[req] = take
        if accepted:
            _telemetry.counter("serving.spec.accepted").inc(accepted)
        if rejected:
            _telemetry.counter("serving.spec.rejected").inc(rejected)
        if rollbacks:
            _telemetry.counter("serving.spec.rollbacks").inc(rollbacks)
        _telemetry.note_train_step(t0, t1, t2, where="serve_step")
        # the batched ``tokens`` event: one trace OCCURRENCE per token
        # actually counted this step (serve_report len-weights
        # occurrences, so traced tokens == serving.tokens stays exact)
        _telemetry.note_request_event(
            "", "tokens", t_ns=t2,
            args={"replica": self.trace_tag, "step": self.decode_steps,
                  "traces": [r.trace for r in running
                             for _ in emitted[r]]})
        self.decode_steps += 1
        _watchdog.renew(self._lease, step=self.decode_steps,
                        phase="serve_step")
        logits_np = _np.asarray(logits) if self._record_logits else None
        produced = 0
        for req in list(running):
            rows = None if logits_np is None else logits_np[req.slot]
            for i, tok in enumerate(emitted[req]):
                self._note_token(req, tok,
                                 None if rows is None else rows[i])
                produced += 1
        if victims:
            self._repair_quant_victims(victims, keys_prev)
        if self.sched.idle:
            _watchdog.release(self._lease)
        self._publish_gauges()
        return produced

    # -- quantized-pool divergence guard (ISSUE 20) -------------------------
    def _poison_page_scale(self):
        """Body of the ``serve.kv.scale_poison`` drill: NaN the layer-0
        K-scale row of the first resident's FIRST page between steps.
        Every subsequent dequant of that page is non-finite, so the
        victim's next decode logits must trip the finite mask; the
        repair path below rewrites the page (bytes AND scales) from the
        request's own committed tokens.  Other residents never map the
        page, so their streams must be byte-identical to an undrilled
        run (test-pinned)."""
        req = self.sched.running[0]
        page = int(self.sched.block_tables[req.slot][0])
        kc, vc, ks, vs = self._kv[0]
        self._kv[0] = (kc, vc, ks.at[page].set(_np.nan), vs)

    def _repair_quant_victims(self, victims, keys_prev):
        """Recovery for residents whose decode logits came back
        non-finite under int8 pools: the page state is unrecoverable in
        place (a NaN absmax scale poisons every dequant of its page),
        so the step's output for the victim was DISCARDED — here its
        PRNG key rolls back and its committed context (prompt + every
        emitted token except the still-pending last one) re-prefills IN
        PLACE through the dense prefill branch.  That rewrites every
        page the request owns with freshly quantized bytes + scales, so
        the next decode step resumes the exact stream (greedy streams
        stay pinned to themselves — the determinism law survives the
        drill).  A victim whose committed context no longer fits the
        prefill window, or that stays non-finite after repeated
        repairs (torn weights, not torn pages), fails with the typed
        ``prefill_error`` verdict instead of looping forever."""
        for req in victims:
            self._keys[req.slot] = keys_prev[req.slot]
            n = self._kv_repairs.get(req.rid, 0) + 1
            self._kv_repairs[req.rid] = n
            ctx = _np.concatenate(
                [_np.asarray(req.prompt, _np.int32),
                 _np.asarray(req.tokens[:-1], _np.int32)])
            if n > 3 or ctx.size > self.max_prefill_len:
                self._finish(
                    req, FAILED, verdict=VERDICT_PREFILL_ERROR,
                    error="quantized KV state unrecoverable for "
                          "request %d (%d repairs, committed context "
                          "%d vs prefill window %d)"
                          % (req.rid, n, ctx.size,
                             self.max_prefill_len))
                continue
            toks = _np.zeros(self.max_prefill_len, _np.int32)
            toks[:ctx.size] = ctx
            # greedy sampling args: the repair NEVER consumes the
            # request's PRNG chain — its first token is discarded (the
            # real next token comes from the resumed decode steps)
            samp = (_np.float32(0), _np.int32(0), _np.float32(0),
                    _np.zeros(2, _np.uint32))
            with _watchdog.guard("serve.prefill"):
                self._run_prefill(
                    toks, ctx.size, 0,
                    self.sched.block_tables[req.slot].copy(),
                    SCRATCH_PAGE, SCRATCH_PAGE, samp, req.slot)
            _telemetry.counter("serving.kv.scale_repairs").inc()
            _telemetry.note_request_event(
                req.trace, "kv_repair",
                args={"replica": self.trace_tag, "rid": req.rid,
                      "repairs": n, "context": int(ctx.size)})

    def _note_stats(self, program, values):
        """Counters and gauges from the counts one dispatch returned
        with its tokens (``ServingPrograms.decode_stats``;
        OBSERVABILITY.md section 9): sums as ``serving.moe.<name>``
        counters (both programs), the decode step's per-layer means as
        gauges."""
        doc = dict(zip(self._model.decode_stats,
                       (int(round(float(v))) for v in values)))
        totals = self.stat_totals[program]
        for name, v in doc.items():
            totals[name] = totals.get(name, 0) + v
            # a count of another layer than the experts' names its
            # family: ``dsa.rows_attended`` -> serving.dsa.rows_attended
            family, _, leaf = name.rpartition(".")
            if family:
                _telemetry.counter("serving.%s.%s" % (family, leaf)).inc(v)
            else:
                _telemetry.counter("serving.moe.%s" % name).inc(v)
        if program != "decode":
            return
        layers = doc.get("expert_layers")
        if layers and doc.get("assignments"):
            _telemetry.gauge("serving.moe.local_share").set(
                doc["local_assignments"] / doc["assignments"])
            _telemetry.gauge("serving.moe.experts_hit_per_layer").set(
                doc["experts_hit"] / layers)
            _telemetry.gauge("serving.moe.max_tokens_per_expert").set(
                doc["max_tokens_per_expert"] / layers)
            _telemetry.gauge("serving.moe.mean_tokens_per_expert").set(
                doc["local_assignments"] / doc["held_experts"])

    def _note_block_fill(self, last_pos):
        """``serving.paged.block_fill`` of the decode step being sent:
        live pages over the pages of the blocks the paged kernel
        enters, from each live slot's last key position (the host's own
        numbers: nothing is fetched)."""
        if self._pages_per_block is None or not last_pos.size:
            return
        pages = last_pos // self.page_size + 1
        blocks = -(-pages // self._pages_per_block)
        _telemetry.gauge("serving.paged.block_fill").set(
            float(pages.sum()) / (int(blocks.sum())
                                  * self._pages_per_block))

    def _publish_gauges(self):
        _telemetry.gauge("serving.batch_occupancy").set(
            self.sched.occupancy)
        _telemetry.gauge("serving.kv_pages_free").set(
            self.alloc.free_pages)
        if self._model.cache_kinds is not None:
            live = self.sched.occupancy
            _telemetry.gauge("serving.state.live_slots").set(
                live if self._slot_state else 0)
            _telemetry.gauge("serving.state.live_bytes").set(
                self.alloc.state_bytes(live))
            _telemetry.gauge("serving.latent.live_pages").set(
                self.alloc.used_pages)

    def run_until_idle(self, max_steps=100000):
        """Drive step() until queue and slots are empty (tests and batch
        jobs; a live server would call step() forever)."""
        for _ in range(max_steps):
            if self.sched.idle:
                return
            self.step()
        raise MXNetError("serving loop did not drain in %d steps"
                         % max_steps)

    # -- live weight hot-swap (ISSUE 11) -----------------------------------
    def swap_params(self, params, verify=True, epoch=None):
        """Install a new decode-param tree between decode steps — the
        live weight hot-swap a serving replica runs when a training job
        publishes a fresh checkpoint (serving/replica.py drives it from
        CheckpointManager publications).

        The tree must match the current one in structure, shapes, and
        dtypes (the compiled programs take params as ORDINARY inputs, so
        a same-shape swap costs ZERO recompiles; a mismatched one would
        silently retrace, so it is rejected before touching anything).
        With ``verify`` the new weights must pass a **canary decode**
        first: one prefill dispatch whose block table points entirely at
        the scratch page (page 0 — where every masked write already
        goes), whose logits must come back finite.  Residents never see
        the canary: no real page is read or written, and the swap lands
        between decode steps by construction (the caller's loop).  A
        failed canary rolls the engine back to the prior weights and
        raises — the replica keeps serving what it was serving."""
        import jax

        old = self._p
        flat_new, td_new = jax.tree_util.tree_flatten(params)
        flat_old, td_old = jax.tree_util.tree_flatten(old)
        if td_new != td_old or len(flat_new) != len(flat_old) or any(
                tuple(n.shape) != tuple(o.shape) or n.dtype != o.dtype
                for n, o in zip(flat_new, flat_old)):
            raise MXNetError(
                "hot-swap rejected: new param tree does not match the "
                "serving tree in structure/shape/dtype — a mismatched "
                "swap would retrace the decode program mid-flight")
        # the swap is a decode-cadence PAUSE for every resident (the
        # canary prefill runs in the step gap): record it as one
        # engine-scope event naming the resident traces, so serve_report
        # can charge the pause to exactly the requests that felt it —
        # the "swap pause" term of the SLO breach blame decomposition
        t0 = time.perf_counter_ns()
        resident = [r.trace for r in self.sched.running
                    if r.trace is not None]
        self._p = params
        if verify:
            try:
                self._canary_decode()
            except BaseException:
                self._p = old
                _telemetry.counter("serving.swap_rollbacks").inc()
                _telemetry.note_request_event(
                    "", "swap", t_ns=t0,
                    args={"replica": self.trace_tag, "ok": False,
                          "epoch": epoch, "traces": resident,
                          "dur_s": round((time.perf_counter_ns() - t0)
                                         * 1e-9, 9)})
                raise
        # the prefix index names pages whose K/V was computed under the
        # OLD weights: a post-swap hit would splice stale activations
        # into a new-weights decode (silently wrong tokens).  Evict on
        # SUCCESS only — a rolled-back swap keeps serving the weights
        # the cache was built under, so the cache stays valid.
        self.drop_prefix_cache()
        self.swaps += 1
        if epoch is not None:
            self.weights_epoch = epoch
        _telemetry.counter("serving.swaps").inc()
        _telemetry.note_request_event(
            "", "swap", t_ns=t0,
            args={"replica": self.trace_tag, "ok": True, "epoch": epoch,
                  "traces": resident,
                  "dur_s": round((time.perf_counter_ns() - t0) * 1e-9,
                                 9)})

    def _canary_decode(self):
        """One prefill with an all-scratch block table (prompt_len=1):
        exercises the full transformer stack under the NEW weights
        without touching any resident's pages.  Non-finite logits mean
        the published weights are torn/corrupt — raise so swap_params
        rolls back."""
        toks = _np.zeros(self.max_prefill_len, _np.int32)
        bt = _np.full(self.max_pages_per_seq, SCRATCH_PAGE, _np.int32)
        samp = (_np.float32(0), _np.int32(0), _np.float32(0),
                _np.zeros(2, _np.uint32))
        with _telemetry.span("serving.swap_canary", cat="serving"):
            logits = self._run_prefill(
                toks, 1, 0, bt, SCRATCH_PAGE, SCRATCH_PAGE, samp,
                self.num_slots)[0]
            row = _np.asarray(logits)       # device sync
        if not _np.isfinite(row).all():
            raise MXNetError(
                "hot-swap canary decode produced non-finite logits — "
                "new weights are torn or corrupt, rolling back")

    # -- drain / introspection ---------------------------------------------
    def drop_prefix_cache(self):
        """Evict every cached prefix entry (telemetry stamped inside
        the cache's one eviction path).  The shared move of the
        ``serve.prefix.evict`` drill, a successful weight hot-swap
        (stale-K/V invalidation), and the replica drain's zero-pages
        audit.  Returns entries dropped (0 with the cache off)."""
        if self._prefix is None:
            return 0
        return self._prefix.evict_all()

    def start_drain(self):
        """Stop admitting: every subsequent submit comes back terminal
        with verdict ``draining``.  Residents and the already-accepted
        queue keep decoding — drive :meth:`step` (or
        ``run_until_idle``) to let them finish; serving/replica.py's
        ``drain()`` owns the full protocol including the exit code."""
        self.draining = True

    def snapshot(self):
        """JSON-able serving state for postmortems, replica health, and
        the PERIODIC serving status line (every telemetry ``report()``
        from a process with live engines carries this block): resident
        slots, queue depth, page accounting, drain flag, SLO controller
        state, and the checkpoint epoch currently serving."""
        running = self.sched.running
        return {
            "replica": self.trace_tag,
            "decode_steps": self.decode_steps,
            "prefills": self.prefills,
            "kv_heads": self.kv_heads,
            "kv_dtype": self.kv_dtype,
            "kv_bytes_per_token": round(self.kv_bytes_per_token, 3),
            "state_bytes_per_slot": self.state_bytes_per_slot,
            "prefix_cached_pages": (None if self._prefix is None
                                    else self._prefix.cached_pages),
            "shared_pages": self.alloc.shared_pages,
            "swaps": self.swaps,
            "occupancy": self.sched.occupancy,
            "num_slots": self.num_slots,
            "queued": self.sched.queued,
            "resident_rids": [r.rid for r in running],
            "resident_tokens": [len(r.tokens) for r in running],
            "free_pages": self.alloc.free_pages,
            "used_pages": self.alloc.used_pages,
            "num_pages": self.alloc.num_pages,
            "draining": self.draining,
            "spec_k": self.spec_k,
            "spec": (None if not self.spec_k else {
                "slot_steps": self.spec_slot_steps,
                "discarded": self.spec_discarded,
                "speculative_pages": self.alloc.speculative_pages}),
            "weights_epoch": self.weights_epoch,
            "stream": {
                "live": sum(1 for r in self._streams.values()
                            if not r.done and r.last_poll_t is not None),
                "waiting": len(self._waiting),
                "retained": sum(1 for r in self._streams.values()
                                if r.done),
                "abandoned": self.abandoned,
            },
            "shedding": (self._slo.shedding if self._slo is not None
                         else False),
            "slo": (self._slo.state() if self._slo is not None
                    else None),
        }

    # -- convenience -------------------------------------------------------
    def generate(self, prompts, max_new, sampling=None):
        """Batch convenience: submit everything, drain, return token
        lists (prompt excluded) in submit order.  ``sampling``: one
        SamplingParams for all, or a per-prompt list."""
        if not isinstance(sampling, (list, tuple)):
            sampling = [sampling] * len(prompts)
        elif len(sampling) != len(prompts):
            raise ValueError(
                "sampling list length %d != %d prompts (zip would "
                "silently drop the tail)" % (len(sampling),
                                             len(prompts)))
        reqs = [self.submit(p, max_new, sampling=s)
                for p, s in zip(prompts, sampling)]
        self.run_until_idle()
        return [r.tokens for r in reqs]
