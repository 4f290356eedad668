"""Serving-path probe: continuous batching vs the sequential predictor.

Synthetic OPEN-LOOP load generator (Poisson arrivals — the generator
never waits for the server, so queueing delay is measured, not hidden)
over mixed prompt/output lengths, driven through two servers built on
the SAME model with the SAME greedy workload:

- **continuous** — ``mxnet_tpu.serving.ServingEngine``: fixed decode
  slots, paged KV cache, ONE donated XLA program per decode step for
  all resident sequences (the tentpole path);
- **sequential** — the predictor discipline the serving stack replaces:
  one request at a time, each new token a full fixed-shape forward over
  the padded context (``Predictor.forward``'s compiled-program contract
  — no KV cache, no cross-request batching), tokens via the same greedy
  argmax.

Reported per side: tokens/s, TTFT and TPOT p50/p99, queue wait, mean
batch occupancy.  Hard contracts asserted by ``BENCH_MODE=serve``
(bench.py):

- exactly ONE decode dispatch per token step (all resident sequences
  advance in it) and one dispatch per admitted request's prefill —
  nothing else dispatches in the serving loop;
- ZERO steady-state recompiles across request churn (slots joining /
  leaving never change a program shape);
- both sides emit IDENTICAL tokens (greedy determinism: the paged
  engine is bit-equivalent to the dense forward);
- warm replica spin-up (``measure_spinup``, restart_probe pattern: two
  subprocesses sharing one AOT cache dir) reaches its first token with
  ZERO foreground serving-program compiles;
- **degraded mode** (``run_degraded``, ISSUE 11): the same workload
  through a 2-replica Router with one replica killed mid-probe
  (``serve.replica.lost``) — zero dropped accepted requests, tokens
  bit-identical to the unfaulted run, and the replacement replica
  spawns AOT-warm (0 foreground compiles).  Per-verdict accounting is
  pinned too: 0 ``failed``, and exactly the killed replica's in-flight
  count ``retried`` — the degraded contract covers verdicts, not just
  totals;
- **request-scope observability** (ISSUE 13): the degraded drill runs
  against a REAL artifact tree (telemetry stream + router journal in
  the run-dir layout) and ``serve_report.py`` must reconstruct every
  accepted request's lifecycle with exactly one terminal verdict, link
  each failed-over request across both replicas by trace id, name the
  killed replica in the blame section, emit a merged chrome trace that
  loads as one file, and reconcile traced tokens with the
  ``serving.tokens``/``serving.goodput`` counters bit-exactly;
  ``measure_trace_overhead`` microbenches the per-decode-step tracing
  cost in isolation (``MXTPU_SERVE_TRACE_BUDGET_US``, default 2);
- **fleet drill** (``run_fleet``, ISSUE 14): the same contracts across
  REAL process boundaries — serve_worker subprocesses behind the RPC
  plane, one replica armed ``rpc.drop`` (circuit breaker trips, then
  recovers via the half-open probe once the replica heals) and one
  armed ``serve.replica.sigkill`` (real SIGKILL mid-probe → confirmed
  death → journaled failover → a REPLACEMENT PROCESS spun on the
  shared AOT cache with 0 foreground compiles) — 0 dropped, tokens
  bit-identical to the unfaulted run, all hard-asserted;
- **partition drill** (``run_partition``, ISSUE 17): the same fleet
  with NO shared run dir (per-worker private tmp dirs, addr-pinned
  proxies, one bootstrap port-file read) — heartbeat-only loss raises
  suspicion but ZERO failovers; a real partition confirms
  ``fence_expiry``, fails over, and FENCES the zombie's late
  completions (0 double-delivered, bit-identical tokens,
  ``rpc.fenced_results`` >= 1), all hard-asserted;
- **telemetry plane** (ISSUE 18): the partition drill's router host
  assembles per-replica telemetry ONLY via the ``telemetry_pull`` RPC
  (the workers' private dirs hold no readable stream) and
  ``serve_report`` over that pull-only tree must be green — lawful
  lifecycles, bit-exact traced-vs-counter token accounting, >= 1
  default alert rule fired and rendered — while
  ``fleet_top.collect_matrix`` returns a complete live matrix;
  ``measure_collector_impact`` pulls after EVERY engine step and the
  hot-path contracts (1.0 decode dispatch/step, 0 steady-state
  recompiles) must survive, with the steady-state pull itself under
  ``MXTPU_TELEMETRY_PULL_BUDGET`` µs (default 2000);
- **streamed delivery** (``run_streaming``, ISSUE 19): a poll-per-step
  client plane over an open-loop trace — cursor-assembled
  streams bit-identical to the engine's token lists (exactly-once),
  1.0 decode dispatch/step and 0 recompiles WITH polling, streamed
  TTFT p50 < 0.5x the unary completion p50 on a decode-dominated
  trace (under a saturating burst queue wait dominates both classes
  equally — the ratio would measure the scheduler); a cancel drill (typed
  ``cancelled`` verdicts mid-decode AND queued, pages restored), the
  ``serve.client.vanish`` drill (silent pollers reclaimed
  ``abandoned``, conservation green, ``orphan_reclaim`` alert fired),
  and a kill-mid-stream fleet drill — a REAL SIGKILL injected only
  once the victim's streams have delivered tokens, the client cursor
  resuming over the survivor's bit-identical re-decode with no gap
  and no dup, plus ``serve.stream.drop`` re-poll recovery;
- **capacity multipliers** (``run_prefix`` / ``run_gqa``, ISSUE 15):
  a system-prompt-heavy Poisson mix with per-request sampling on half
  the requests, cache-on vs cache-off on the SAME workload — prefix
  hit-rate > 0, >= 30% fewer prefill tokens, tokens bit-identical,
  1.0 decode dispatch/step and 0 steady-state recompiles with cache +
  sampling enabled; and grouped-query attention at ``K_kv = H/2`` —
  kernel-vs-oracle equivalence at mixed ragged lengths plus >= 1.5x
  resident sequences in the same page-pool bytes.

Usage: JAX_PLATFORMS=cpu python tools/perf_probe/serve_probe.py
Prints one JSON object.  ``--no-fleet`` / ``--no-spinup`` skip the
subprocess-heavy sections.
"""
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from restart_probe import _pct  # noqa: E402 — shared percentile helper


def build_net(vocab=256, n_layer=2, d_model=128, n_head=4, max_len=64,
              seed=0):
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import gpt

    np.random.seed(seed)
    mx.random.seed(seed)
    net = gpt.GPTLM(vocab, n_layer, d_model, n_head, max_len=max_len)
    net.initialize()
    return net


def make_workload(n_requests=24, mean_interarrival_s=0.004,
                  prompt_lens=(4, 24), new_tokens=(8, 24), vocab=256,
                  seed=7):
    """[(arrival_offset_s, prompt int32[L], max_new)] — Poisson process
    (exponential inter-arrival), uniform mixed lengths.  Seeded: both
    servers replay the identical trace."""
    import numpy as np
    rng = np.random.RandomState(seed)
    t = 0.0
    out = []
    for _ in range(n_requests):
        t += float(rng.exponential(mean_interarrival_s))
        lo, hi = prompt_lens
        plen = int(rng.randint(lo, hi + 1))
        nlo, nhi = new_tokens
        out.append((t, rng.randint(0, vocab, plen).astype(np.int32),
                    int(rng.randint(nlo, nhi + 1))))
    return out


def _req_stats(ttfts, tpots, waits):
    ttfts, tpots, waits = sorted(ttfts), sorted(tpots), sorted(waits)
    return {
        "ttft_p50_ms": round(_pct(ttfts, 0.5) * 1e3, 3),
        "ttft_p99_ms": round(_pct(ttfts, 0.99) * 1e3, 3),
        "tpot_p50_ms": (round(_pct(tpots, 0.5) * 1e3, 3)
                        if tpots else None),
        "tpot_p99_ms": (round(_pct(tpots, 0.99) * 1e3, 3)
                        if tpots else None),
        "queue_wait_p50_ms": (round(_pct(waits, 0.5) * 1e3, 3)
                              if waits else None),
        "queue_wait_p99_ms": (round(_pct(waits, 0.99) * 1e3, 3)
                              if waits else None),
    }


def run_continuous(net, workload, num_slots=8, page_size=16,
                   max_prefill_len=32, max_seq_len=48, num_pages=None,
                   prefix_cache=None, sampling=None, spec_k=None,
                   kv_dtype=None):
    """Open-loop drive of the ServingEngine; returns throughput, latency
    percentiles, occupancy, and the dispatch/compile accounting —
    WITH request-scope tracing live (it is always on: the 1.0
    dispatch/step and recompile contracts below therefore hold with the
    tracing plane enabled, and goodput must equal raw tokens on this
    unfaulted run).

    ``prefix_cache``: forwarded to the engine (None = its default);
    ``sampling``: optional per-request SamplingParams list aligned with
    the workload (None entries = greedy); ``spec_k``: speculative
    decode depth (None = the engine's env default, 0 = off)."""
    from mxnet_tpu import profiler, telemetry
    from mxnet_tpu.serving import ServingEngine
    import numpy as np

    eng = ServingEngine(net, num_slots=num_slots, page_size=page_size,
                        max_prefill_len=max_prefill_len,
                        max_seq_len=max_seq_len, num_pages=num_pages,
                        prefix_cache=prefix_cache, spec_k=spec_k,
                        kv_dtype=kv_dtype)
    # warmup: both programs execute once (first-call overhead, twin
    # hot-swap settle) before the timed workload
    eng.generate([np.zeros(4, np.int32)], max_new=2)
    profiler.reset_step_stats()
    telemetry.reset()   # clean counter/trace baseline for the deltas
    base = profiler.step_stats()
    d0, c0 = base["dispatch_count"], base["compile_count"]
    steps0, prefills0 = eng.decode_steps, eng.prefills
    slot_steps0, discarded0 = eng.spec_slot_steps, eng.spec_discarded

    reqs = []
    pending = list(workload)
    samp = list(sampling) if sampling is not None else [None] * len(
        pending)
    t_start = time.perf_counter()
    while pending or not eng.sched.idle:
        now = time.perf_counter() - t_start
        while pending and pending[0][0] <= now:
            _, prompt, max_new = pending.pop(0)
            reqs.append(eng.submit(prompt, max_new,
                                   sampling=samp[len(reqs)]))
        if eng.step() == 0 and pending:
            # idle gap before the next arrival: wait it out off-device
            time.sleep(min(1e-4, max(0.0, pending[0][0] - now)))
    wall = time.perf_counter() - t_start

    stats = profiler.step_stats()
    decode_steps = eng.decode_steps - steps0
    prefills = eng.prefills - prefills0
    dispatches = stats["dispatch_count"] - d0
    total_tokens = sum(len(r.tokens) for r in reqs)
    decode_tokens = total_tokens - prefills  # 1 token/request from prefill
    # request-scope accounting on the unfaulted run: traced token
    # events and goodput must BOTH equal the raw token counter
    traced = telemetry.count_token_events(telemetry.request_events())
    out = {
        "tokens_counter": telemetry.counter("serving.tokens").value,
        "goodput_counter": telemetry.counter("serving.goodput").value,
        "traced_tokens": traced,
        "requests": len(reqs),
        "num_slots": num_slots,
        "total_tokens": total_tokens,
        "wall_s": round(wall, 4),
        "tokens_per_sec": round(total_tokens / wall, 2),
        "decode_steps": decode_steps,
        "prefill_dispatches": prefills,
        "total_dispatches": dispatches,
        # the tentpole contract: every decode step is ONE program for
        # ALL residents; the only other dispatches are one per prefill
        "decode_dispatches_per_step": round(
            (dispatches - prefills) / max(1, decode_steps), 4),
        "steady_state_compiles": stats["compile_count"] - c0,
        "mean_batch_occupancy": round(
            decode_tokens / max(1, decode_steps), 3),
        "tokens": [list(map(int, r.tokens)) for r in reqs],
        # prefix-cache accounting (counters were reset above, so these
        # are this run's deltas; all 0 with the cache off)
        "prefill_tokens":
            telemetry.counter("serving.prefill_tokens").value,
        "prefix_hits": telemetry.counter("serving.prefix.hits").value,
        "prefix_miss": telemetry.counter("serving.prefix.miss").value,
        "prefix_shared_pages":
            telemetry.counter("serving.prefix.shared_pages").value,
        "prefix_cow_copies":
            telemetry.counter("serving.prefix.cow_copies").value,
        "sampling_requests":
            telemetry.counter("serving.sampling.requests").value,
        # speculative-decode accounting (ISSUE 16; all 0 with spec off).
        # tokens_per_slot_step is the per-sequence multiplier — decode
        # tokens per slot participation — exactly 1.0 for a
        # non-speculative engine by construction
        "spec_k": eng.spec_k,
        "spec_draft_tokens":
            telemetry.counter("serving.spec.draft_tokens").value,
        "spec_accepted": telemetry.counter("serving.spec.accepted").value,
        "spec_rejected": telemetry.counter("serving.spec.rejected").value,
        "spec_rollbacks":
            telemetry.counter("serving.spec.rollbacks").value,
        "spec_slot_steps": eng.spec_slot_steps - slot_steps0,
        "spec_discarded": eng.spec_discarded - discarded0,
        "tokens_per_slot_step": round(
            decode_tokens / (eng.spec_slot_steps - slot_steps0), 4)
        if eng.spec_slot_steps > slot_steps0 else 1.0,
    }
    out.update(_req_stats([r.ttft_s for r in reqs],
                          [r.tpot_s for r in reqs
                           if r.tpot_s is not None],
                          [r.queue_wait_s for r in reqs]))
    return out


def run_sequential(net, workload, t_pad=48):
    """The baseline the ISSUE names: sequential per-request
    ``Predictor.forward`` — one fixed-shape compiled full forward per
    generated token, requests strictly one at a time in arrival order.
    Causal attention makes right-padding invisible to position
    ``len-1``, so greedy tokens match the cached engine bit-for-bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from mxnet_tpu.gluon.block import functionalize

    fn, params = functionalize(net, jnp.zeros((1, t_pad), jnp.int32))

    @jax.jit
    def fwd_next(params, toks, length):
        (logits,), _ = fn(params, toks)
        row = lax.dynamic_index_in_dim(logits[0], length - 1, 0,
                                       keepdims=False)
        return row.argmax(-1).astype(jnp.int32)

    # warmup compile outside the timed region (parity with continuous)
    np.asarray(fwd_next(params, jnp.zeros((1, t_pad), jnp.int32),
                        jnp.int32(1)))

    ttfts, tpots, waits, all_tokens = [], [], [], []
    total = 0
    t_start = time.perf_counter()
    for arrival, prompt, max_new in workload:
        now = time.perf_counter() - t_start
        if now < arrival:
            time.sleep(arrival - now)
        service_start = time.perf_counter()
        waits.append(max(0.0, service_start - t_start - arrival))
        toks = np.zeros((1, t_pad), np.int32)
        toks[0, :prompt.size] = prompt
        length = prompt.size
        produced = []
        stamps = []
        for _ in range(max_new):
            nxt = int(fwd_next(params, toks, np.int32(length)))
            stamps.append(time.perf_counter())
            produced.append(nxt)
            toks[0, length] = nxt
            length += 1
        total += len(produced)
        all_tokens.append(produced)
        ttfts.append(stamps[0] - (t_start + arrival))
        if len(stamps) > 1:
            tpots.append((stamps[-1] - stamps[0]) / (len(stamps) - 1))
    wall = time.perf_counter() - t_start
    out = {
        "requests": len(workload),
        "total_tokens": total,
        "wall_s": round(wall, 4),
        "tokens_per_sec": round(total / wall, 2),
        "tokens": all_tokens,
    }
    out.update(_req_stats(ttfts, tpots, waits))
    return out


# -- capacity multipliers: prefix caching + GQA (ISSUE 15) ------------------

def make_prefix_workload(n_requests=24, sys_len=24,
                         mean_interarrival_s=0.004, tail_lens=(2, 8),
                         new_tokens=(8, 16), vocab=256, seed=17):
    """A system-prompt-heavy Poisson mix: every request shares one
    ``sys_len``-token system prompt followed by a short unique tail —
    the workload shape prefix caching exists for."""
    import numpy as np
    rng = np.random.RandomState(seed)
    sysp = rng.randint(0, vocab, sys_len).astype(np.int32)
    t = 0.0
    out = []
    for _ in range(n_requests):
        t += float(rng.exponential(mean_interarrival_s))
        tail = rng.randint(0, vocab,
                           int(rng.randint(tail_lens[0],
                                           tail_lens[1] + 1))
                           ).astype(np.int32)
        out.append((t, np.concatenate([sysp, tail]),
                    int(rng.randint(new_tokens[0],
                                    new_tokens[1] + 1))))
    return out


def run_prefix(net, workload=None):
    """The prefix-caching contract (hard-asserted by BENCH_MODE=serve):
    on a prefix-heavy workload with per-request SAMPLING enabled,
    cache-on must (a) hit (> 0 hit-rate), (b) prefill >= 30% fewer
    tokens than cache-off on the SAME workload, (c) emit bit-identical
    tokens (per-request determinism makes sampled tokens comparable
    across engine configs), and (d) keep 1.0 decode dispatch/step with
    0 steady-state recompiles — the caching + sampling machinery rides
    the existing one-donated-program-per-step invariant."""
    from mxnet_tpu.serving import SamplingParams
    if workload is None:
        workload = make_prefix_workload()
    # every other request samples (seeded); the rest stay greedy — the
    # bit-identity contract must hold for BOTH decode modes
    sampling = [None if i % 2 == 0 else
                SamplingParams(temperature=0.8, top_k=24, top_p=0.95,
                               seed=4000 + i)
                for i in range(len(workload))]
    on = run_continuous(net, workload, sampling=sampling)
    off = run_continuous(net, workload, sampling=sampling,
                         prefix_cache=False)
    admissions = on["prefix_hits"] + on["prefix_miss"]
    reduction = (1.0 - on["prefill_tokens"] /
                 max(1, off["prefill_tokens"]))
    return {
        "requests": len(workload),
        "tokens_match_cache_off": on.pop("tokens") == off.pop("tokens"),
        "prefill_tokens_on": on["prefill_tokens"],
        "prefill_tokens_off": off["prefill_tokens"],
        "prefill_token_reduction": round(reduction, 4),
        "hit_rate": round(on["prefix_hits"] / max(1, admissions), 4),
        "prefix_hits": on["prefix_hits"],
        "shared_pages": on["prefix_shared_pages"],
        "cow_copies": on["prefix_cow_copies"],
        "sampling_requests": on["sampling_requests"],
        "decode_dispatches_per_step": on["decode_dispatches_per_step"],
        "steady_state_compiles": on["steady_state_compiles"],
        "tokens_per_sec_on": on["tokens_per_sec"],
        "tokens_per_sec_off": off["tokens_per_sec"],
        "ttft_p50_ms_on": on["ttft_p50_ms"],
        "ttft_p50_ms_off": off["ttft_p50_ms"],
    }


# -- speculative decoding (ISSUE 16) ---------------------------------------

def make_spec_workload(net, n_requests=16, mean_interarrival_s=0.004,
                       prompt_lens=(8, 14), new_tokens=(24, 40),
                       pregen=10, vocab=256, seed=29, num_slots=8,
                       page_size=16, max_prefill_len=16,
                       max_seq_len=56):
    """An acceptance-friendly Poisson workload for the speculative
    decoder: every prompt is a short random seed followed by the
    model's OWN greedy continuation (pre-generated once, untimed), so
    the decode chain is self-similar from the first step and the
    n-gram drafter has material to hit — the serving analog of
    templated/system-prompt text, which is what speculative decoding
    exists for.  Same trace for spec-on and spec-off."""
    import numpy as np
    from mxnet_tpu.serving import ServingEngine

    rng = np.random.RandomState(seed)
    seeds = [rng.randint(0, vocab, int(rng.randint(2, 5)))
             .astype(np.int32) for _ in range(n_requests)]
    pre = ServingEngine(net, num_slots=num_slots, page_size=page_size,
                        max_prefill_len=max_prefill_len,
                        max_seq_len=max_seq_len)
    conts = pre.generate(seeds, max_new=pregen)
    t = 0.0
    out = []
    for sd, cont in zip(seeds, conts):
        t += float(rng.exponential(mean_interarrival_s))
        plen = int(rng.randint(prompt_lens[0], prompt_lens[1] + 1))
        prompt = np.concatenate(
            [sd, np.asarray(cont, np.int32)])[:plen].astype(np.int32)
        out.append((t, prompt,
                    int(rng.randint(new_tokens[0], new_tokens[1] + 1))))
    return out


def run_spec(net=None, spec_k=6):
    """The speculative-decoding contract (hard-asserted by
    ``BENCH_MODE=serve``): spec-on vs spec-off on the SAME
    acceptance-friendly workload, same engine geometry, both arms
    driven twice (best wall per arm — single-pass wall on a shared
    box is noisy; tokens must be identical across passes regardless).

    What bench pins on this dict:

    - ``speedup_tokens_per_sec`` >= 1.5 — the tentpole multiplier;
    - ``tokens_per_slot_step`` > 1.3 — tokens per slot participation
      (1.0 == non-speculative by construction);
    - greedy bit-identity: spec-on tokens == spec-off tokens;
    - 1.0 decode dispatch/step and 0 steady-state recompiles with
      spec ON — drafts ride the SAME donated program;
    - counter identity: drafted == accepted + rejected and
      decode tokens == slot_steps + accepted - discarded;
    - sampled reproducibility: a mixed greedy/sampled spec-on run
      repeats bit-identically, and reproduces across a 2-replica
      router failover (``serve.replica.lost``) onto a spun-up
      replacement — the per-request determinism law survives the
      re-decode.

    The probe net is WIDER than the default (d_model 256): the
    speculative program spends extra FLOPs per dispatch to verify k
    drafts, so the win needs dispatch cost to be dominated by model
    compute, exactly as on the real accelerator where decode is
    bandwidth-bound.  See SERVING.md section 2c for when NOT to
    enable."""
    import numpy as np
    from mxnet_tpu import fault
    from mxnet_tpu.serving import (Router, SamplingParams,
                                   ServingEngine, ServingReplica)

    if net is None:
        net = build_net(d_model=256)
    kw = dict(num_slots=8, page_size=16, max_prefill_len=16,
              max_seq_len=56)
    workload = make_spec_workload(net, **kw)

    def arm(k):
        a = run_continuous(net, workload, spec_k=k, **kw)
        b = run_continuous(net, workload, spec_k=k, **kw)
        if a["tokens"] != b["tokens"]:
            raise AssertionError(
                "spec_k=%r emitted different tokens on identical "
                "back-to-back runs" % k)
        return a if a["tokens_per_sec"] >= b["tokens_per_sec"] else b

    on, off = arm(spec_k), arm(0)
    on_tokens, off_tokens = on.pop("tokens"), off.pop("tokens")

    # mixed greedy/sampled determinism: same workload, every other
    # request sampled; two identical runs, then the same requests
    # replayed through a 2-replica router with one replica killed
    # mid-flight — every stream must reproduce bit-exactly
    sampling = [None if i % 2 == 0 else
                SamplingParams(temperature=0.8, top_k=24, top_p=0.95,
                               seed=5000 + i)
                for i in range(len(workload))]
    r1 = run_continuous(net, workload, sampling=sampling,
                        spec_k=spec_k, **kw)
    r2 = run_continuous(net, workload, sampling=sampling,
                        spec_k=spec_k, **kw)
    repro_match = r1["tokens"] == r2["tokens"]

    def mk_replica(rid):
        return ServingReplica(
            ServingEngine(net, spec_k=spec_k, **kw), replica_id=rid)

    rt = Router([mk_replica("sa"), mk_replica("sb")],
                spawn=lambda: mk_replica("s-replacement"),
                max_retries=2)
    rrs = [rt.submit(p, m, sampling=sp)
           for (_, p, m), sp in zip(workload, sampling)]
    fault.configure("serve.replica.lost:1")
    try:
        steps = 0
        while not rt.idle and steps < 10000:
            rt.step()
            steps += 1
    finally:
        fault.reset()
    failover_tokens = [list(map(int, rr.tokens)) for rr in rrs]
    failover_match = failover_tokens == r1["tokens"]

    dec_on = on["total_tokens"] - on["prefill_dispatches"]
    return {
        "requests": len(workload),
        "spec_k": spec_k,
        "speedup_tokens_per_sec": round(
            on["tokens_per_sec"] / off["tokens_per_sec"], 3),
        "tokens_per_sec_on": on["tokens_per_sec"],
        "tokens_per_sec_off": off["tokens_per_sec"],
        "tokens_match_spec_off": on_tokens == off_tokens,
        "tokens_per_slot_step": on["tokens_per_slot_step"],
        "decode_steps_on": on["decode_steps"],
        "decode_steps_off": off["decode_steps"],
        "decode_dispatches_per_step": on["decode_dispatches_per_step"],
        "steady_state_compiles": on["steady_state_compiles"],
        "draft_tokens": on["spec_draft_tokens"],
        "accepted": on["spec_accepted"],
        "rejected": on["spec_rejected"],
        "rollbacks": on["spec_rollbacks"],
        "acceptance_rate": round(
            on["spec_accepted"] / max(1, on["spec_draft_tokens"]), 4),
        "counter_identity_draft": on["spec_draft_tokens"]
        == on["spec_accepted"] + on["spec_rejected"],
        "counter_identity_tokens": dec_on
        == on["spec_slot_steps"] + on["spec_accepted"]
        - on["spec_discarded"],
        "spec_off_drafted": off["spec_draft_tokens"],
        "sampled_repro_match": repro_match,
        "failover_completed": sum(1 for rr in rrs
                                  if rr.state == "completed"),
        "failover_failovers": rt.failovers,
        "failover_tokens_match": failover_match,
    }


def run_gqa(net, pool_pages=13):
    """The GQA capacity contract (hard-asserted by BENCH_MODE=serve):
    at ``K_kv = H/2`` the SAME page-pool byte budget holds >= 1.5x the
    resident sequences (page bytes scale with K_kv, so the budget buys
    2x pages), with kernel-vs-oracle equivalence at mixed lengths."""
    import numpy as np
    from mxnet_tpu.ops.pallas.paged_attention import (
        paged_attention, paged_attention_reference)
    from mxnet_tpu.serving import ServingEngine

    n_heads = net.blocks._children[0].attn._num_heads
    assert n_heads % 2 == 0, n_heads
    rng = np.random.RandomState(23)

    # kernel-vs-oracle at K_kv = H/2, mixed ragged lengths
    s, d, page, n_pages, mp = 5, 16, 8, 16, 4
    kv = n_heads // 2
    q = rng.randn(s, n_heads, d).astype(np.float32)
    # pools as the engine stores them: [num_pages, page, K_kv * D]
    kp = rng.randn(n_pages, page, kv * d).astype(np.float32)
    vp = rng.randn(n_pages, page, kv * d).astype(np.float32)
    perm = rng.permutation(n_pages - 1) + 1
    ctx_lens = [29, 5, 0, 17, 32]
    bt = np.zeros((s, mp), np.int32)
    k = 0
    for i in range(s):
        need = -(-max(1, ctx_lens[i]) // page)
        bt[i, :need] = perm[k:k + need]
        k += need
    ctx = np.asarray(ctx_lens, np.int32)
    out = np.asarray(paged_attention(q, kp, vp, bt, ctx))
    ref = np.asarray(paged_attention_reference(q, kp, vp, bt, ctx))
    kernel_err = float(np.abs(out - ref).max())

    # resident capacity at the same pool bytes: identical worst-case
    # requests, count concurrent residents (prefix cache off — unique
    # prompts are the honest capacity baseline)
    kw = dict(num_slots=16, page_size=16, max_prefill_len=32,
              max_seq_len=48, prefix_cache=False)

    def residents(kv_heads, num_pages):
        eng = ServingEngine(net, kv_heads=kv_heads,
                            num_pages=num_pages, **kw)
        pool_bytes = sum(kc.nbytes + vc.nbytes for kc, vc in eng._kv)
        for _ in range(16):
            eng.submit(rng.randint(0, 256, (32,)).astype(np.int32), 16)
        eng.step()
        occ = eng.sched.occupancy
        eng.run_until_idle()
        return occ, pool_bytes

    occ_mha, bytes_mha = residents(n_heads, pool_pages)
    occ_gqa, bytes_gqa = residents(n_heads // 2, 2 * pool_pages - 1)
    return {
        "kv_heads": n_heads // 2,
        "n_heads": n_heads,
        "kernel_max_err": kernel_err,
        "residents_mha": occ_mha,
        "residents_gqa": occ_gqa,
        "resident_multiplier": round(occ_gqa / max(1, occ_mha), 3),
        "pool_bytes_mha": bytes_mha,
        "pool_bytes_gqa": bytes_gqa,
        "kv_bytes_per_token_ratio": round(bytes_gqa / bytes_mha, 4),
    }


def run_kvq(net, workload, reference_tokens, pool_pages=13):
    """The quantized KV-page contract (ISSUE 20, hard-asserted by
    BENCH_MODE=serve): int8 pages + per-page-per-KV-head fp32 absmax
    scales vs bf16 pools on the SAME Poisson workload —

    - kernel-vs-oracle dequant error <= the pinned tolerance (the
      Pallas kernels and the jnp reference dequantize the SAME int8
      pools + scales; published as the ``serving.kv.quant_error``
      gauge);
    - >= 1.8x resident sequences in the same pool bytes at int8 vs
      bf16 (the scale rows cost ~K_kv*8 bytes/page against the
      2*page*K_kv*D payload halving);
    - greedy token match-rate >= 0.99 vs the fp32 reference (greedy
      under quantization is pinned to ITSELF — bit-identity to the fp
      path is explicitly NOT the law, the match-rate gate is);
    - 1.0 decode dispatch/step and 0 steady-state recompiles with
      int8 pools (quantize-on-scatter lives INSIDE the one donated
      program)."""
    import numpy as np
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops.pallas.paged_attention import (
        paged_attention, paged_attention_reference)
    from mxnet_tpu.serving import ServingEngine

    n_heads = net.blocks._children[0].attn._num_heads
    rng = np.random.RandomState(31)

    # kernel-vs-oracle on the SAME quantized pools: absmax-quantize
    # random fp pages per page per KV head, run both readers
    s, d, page, n_pages, mp = 5, 16, 8, 16, 4
    q = rng.randn(s, n_heads, d).astype(np.float32)

    def quantize(pool):
        # a stored [n_pages, page, K_kv * D] pool, scaled per KV head
        heads = pool.reshape(n_pages, page, n_heads, d)
        scale = (np.abs(heads).max(axis=(1, 3)) / 127.0).astype(
            np.float32)                      # [n_pages, K_kv]
        qp = np.clip(np.round(
            heads / np.maximum(scale, 1e-30)[:, None, :, None]),
            -127, 127).astype(np.int8)
        return qp.reshape(pool.shape), scale

    kq, ks = quantize(rng.randn(n_pages, page, n_heads * d)
                      .astype(np.float32))
    vq, vs = quantize(rng.randn(n_pages, page, n_heads * d)
                      .astype(np.float32))
    perm = rng.permutation(n_pages - 1) + 1
    ctx_lens = [29, 5, 0, 17, 32]
    bt = np.zeros((s, mp), np.int32)
    k = 0
    for i in range(s):
        need = -(-max(1, ctx_lens[i]) // page)
        bt[i, :need] = perm[k:k + need]
        k += need
    ctx = np.asarray(ctx_lens, np.int32)
    out = np.asarray(paged_attention(q, kq, vq, bt, ctx,
                                     k_scales=ks, v_scales=vs))
    ref = np.asarray(paged_attention_reference(q, kq, vq, bt, ctx,
                                               k_scales=ks,
                                               v_scales=vs))
    dequant_err = float(np.abs(out - ref).max())
    telemetry.gauge("serving.kv.quant_error").set(dequant_err)

    # resident capacity in the same pool bytes: identical worst-case
    # requests; the int8 pool buys ~2x the pages of the bf16 budget
    kw = dict(num_slots=16, page_size=16, max_prefill_len=32,
              max_seq_len=48, prefix_cache=False)

    def residents(kv_dtype, num_pages):
        eng = ServingEngine(net, kv_dtype=kv_dtype,
                            num_pages=num_pages, **kw)
        pool_bytes = sum(sum(a.nbytes for a in entry)
                        for entry in eng._kv)
        for _ in range(16):
            eng.submit(rng.randint(0, 256, (32,)).astype(np.int32), 16)
        eng.step()
        occ = eng.sched.occupancy
        eng.run_until_idle()
        return occ, pool_bytes, eng.kv_bytes_per_token

    occ_bf16, bytes_bf16, bpt_bf16 = residents("bf16", pool_pages)
    d_model = int(net.wte.shape[1])
    bf16_page = 2 * kw["page_size"] * d_model * 2
    int8_page = 2 * kw["page_size"] * d_model + 2 * n_heads * 4
    int8_pages = pool_pages * bf16_page // int8_page
    occ_int8, bytes_int8, bpt_int8 = residents("int8", int8_pages)

    # the same open-loop workload through an int8 engine: match-rate
    # vs the fp32 reference tokens + the hot-path contracts.  Pages of
    # 8 keep the absmax scale groups tight (one fp32 scale per 8 rows
    # per KV head); the fp reference stands across page sizes — greedy
    # fp tokens are page-layout-invariant (the paged kernel's
    # per-page partial sums reduce in fp32)
    cont = run_continuous(net, workload, page_size=8, kv_dtype="int8")
    matched = total = 0
    for got, want in zip(cont.pop("tokens"), reference_tokens):
        total += len(want)
        matched += sum(1 for a, b in zip(got, want) if a == b)
    return {
        "kv_dtype": "int8",
        "dequant_max_err": dequant_err,
        "residents_bf16": occ_bf16,
        "residents_int8": occ_int8,
        "resident_multiplier": round(occ_int8 / max(1, occ_bf16), 3),
        "pool_bytes_bf16": bytes_bf16,
        "pool_bytes_int8": bytes_int8,
        "bytes_per_token_bf16": round(bpt_bf16, 2),
        "bytes_per_token_int8": round(bpt_int8, 2),
        "bytes_per_token_ratio": round(bpt_int8 / bpt_bf16, 4),
        "token_match_rate": round(matched / max(1, total), 4),
        "tokens_per_sec": cont["tokens_per_sec"],
        "decode_dispatches_per_step":
            cont["decode_dispatches_per_step"],
        "steady_state_compiles": cont["steady_state_compiles"],
    }


# -- degraded mode: kill a replica mid-probe (ISSUE 11 + 13) ---------------

def run_degraded(net, workload, reference_tokens, num_slots=8,
                 page_size=16, max_prefill_len=32, max_seq_len=48,
                 kill_after_steps=3):
    """The survivability contract under replica loss: a 2-replica
    router serving the SAME workload, one replica killed mid-probe
    (``serve.replica.lost``).  Hard contracts asserted by
    ``BENCH_MODE=serve``:

    - ZERO dropped accepted requests — every one completes exactly once;
    - tokens bit-identical to the unfaulted continuous run (greedy
      determinism survives the failover re-decode);
    - the replacement replica spins up AOT-warm: 0 foreground compiles
      (in-process memo / shared AOT cache tier);
    - per-VERDICT deltas, not just totals: 0 ``failed``, and exactly
      the killed replica's in-flight count ``retried``;
    - the whole drill runs against a REAL artifact tree (telemetry
      stream + router journal, the launch.py run-dir layout) and
      ``serve_report`` must reconstruct it: every accepted request one
      terminal verdict, failed-over requests linked across both
      replicas by trace id, the killed replica named in the blame
      section, the merged chrome trace one loadable file, traced
      tokens == serving.tokens bit-exactly.
    """
    from mxnet_tpu import fault, profiler, telemetry
    from mxnet_tpu.serving import Router, ServingEngine, ServingReplica
    import serve_report

    kw = dict(num_slots=num_slots, page_size=page_size,
              max_prefill_len=max_prefill_len, max_seq_len=max_seq_len)
    spawn_compiles = []

    def spawn():
        c0 = profiler.step_stats()["compile_count"]
        rep = ServingReplica(ServingEngine(net, **kw),
                             replica_id="replacement")
        spawn_compiles.append(
            profiler.step_stats()["compile_count"] - c0)
        return rep

    # the run-dir artifact layout (tools/launch.py contract): stream +
    # router journal under <run-dir>/telemetry/
    tree = tempfile.mkdtemp(prefix="serve-degraded-")
    tdir = os.path.join(tree, "telemetry")
    os.makedirs(tdir)
    telemetry.reset()   # the earlier probe phases' events are not ours
    telemetry.start_emitter(os.path.join(tdir, "stream-slot0.jsonl"),
                            interval=0.25)
    replicas = [ServingReplica(ServingEngine(net, **kw),
                               replica_id="a"),
                ServingReplica(ServingEngine(net, **kw),
                               replica_id="b")]
    rt = Router(replicas, spawn=spawn, max_retries=2,
                journal_path=os.path.join(
                    tdir, "router-journal-slot0.jsonl"))
    t_start = time.perf_counter()
    rrs = []
    pending = list(workload)
    steps = 0
    killed = False
    victim_inflight = None
    while pending or not rt.idle:
        now = time.perf_counter() - t_start
        while pending and pending[0][0] <= now:
            _, prompt, max_new = pending.pop(0)
            rrs.append(rt.submit(prompt, max_new))
        if steps == kill_after_steps and not killed:
            # snapshot each replica's accepted in-flight count BEFORE
            # the killing step: the victim's count is exactly what the
            # router must retry (the per-verdict contract)
            inflight = {id(r): sum(1 for rr in rrs
                                   if rr.state == "accepted"
                                   and rr._home is r)
                        for r in replicas}
            fault.configure("serve.replica.lost:1")
            killed = True
        if rt.step() == 0 and pending:
            time.sleep(min(1e-4, max(0.0, pending[0][0] - now)))
        if killed and victim_inflight is None:
            dead = [r for r in replicas if not r.alive]
            if dead:
                victim_inflight = inflight[id(dead[0])]
                victim_id = dead[0].replica_id
        steps += 1
    fault.reset()
    wall = time.perf_counter() - t_start
    telemetry.stop_emitter()   # final line flushes remaining events
    completed = [rr for rr in rrs if rr.state == "completed"]
    tokens = [rr.tokens for rr in completed]

    # fleet reconstruction from the REAL artifacts
    rep = serve_report.analyze(tree)
    trace_path = os.path.join(tree, "serve-trace.json")
    doc, _t0 = serve_report.merged_trace(rep["data"], rep["requests"])
    with open(trace_path, "w") as f:
        json.dump(doc, f)
    try:
        trace_events = len(json.load(open(trace_path))["traceEvents"])
    except Exception:
        trace_events = 0
    acc = rep["accounting"]
    blamed = {b["replica"] for b in rep["blame"]}
    report = {
        "lifecycle_ok": rep["lifecycle"]["ok"],
        "violations": rep["lifecycle"]["violations"][:5],
        "open_traces": len(rep["lifecycle"]["open_traces"]),
        "arcs": len(rep["arcs"]),
        "linked_arcs": rep["linked_arcs"],
        "killed_replica": victim_id if victim_inflight is not None
        else None,
        "killed_replica_blamed": (victim_id in blamed
                                  if victim_inflight is not None
                                  else False),
        "trace_file_events": trace_events,
        "tokens_counter": acc["tokens"],
        "traced_tokens": acc["traced_tokens"],
        "goodput_counter": acc["goodput"],
        "token_accounting_exact": acc["tokens_match"],
    }
    shutil.rmtree(tree, ignore_errors=True)

    verdicts = {}
    for rr in rrs:
        verdicts[rr.verdict or rr.state] = \
            verdicts.get(rr.verdict or rr.state, 0) + 1
    return {
        "requests": len(rrs),
        "completed": len(completed),
        "dropped": len(rrs) - len(completed),
        "failovers": rt.failovers,
        "replacement_spawns": len(spawn_compiles),
        "replacement_foreground_compiles": sum(spawn_compiles),
        "tokens_match_unfaulted": tokens == reference_tokens,
        "wall_s": round(wall, 4),
        # per-verdict accounting (the degraded contract pins verdicts,
        # not just totals): nothing failed, and the retried count is
        # exactly the victim's in-flight count at the kill
        "verdicts": verdicts,
        "failed": sum(1 for rr in rrs if rr.state == "failed"),
        "retried": sum(1 for rr in rrs if rr.retries > 0),
        "expected_retried": victim_inflight,
        "report": report,
    }


# -- out-of-process fleet drill (ISSUE 14) ---------------------------------

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "..", "serve_worker.py")


def _spawn_worker(run_dir, cache, slot, attempt, extra_env=None):
    """One serve_worker subprocess for ``slot``: shared AOT cache
    (replacements spin up warm), port file under ``run_dir``.  The
    worker drains its variant stores before publishing the port file,
    so 'fleet discoverable' implies 'cache durable'."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "MXTPU_AOT_CACHE_DIR": cache,
        "JAX_COMPILATION_CACHE_DIR": os.path.join(cache, "xla"),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "MXTPU_WORKER_SLOT": str(slot),
        "MXTPU_WORKER_RANK": str(slot),
        "MXTPU_RESTART_ATTEMPT": str(attempt),
        "MXTPU_SERVE_PORT_FILE":
            os.path.join(run_dir, "serve-port-slot%d.json" % slot),
    })
    env.update(extra_env or {})
    return subprocess.Popen(
        [sys.executable, os.path.abspath(_WORKER),
         "--max-seconds", "600"], env=env)


def run_fleet(workload, reference_tokens):
    """The out-of-process fleet drill (``BENCH_MODE=serve`` hard
    contracts, ISSUE 14): REAL worker processes behind the RPC plane.

    Two phases over one spun-up fleet:

    1. **breaker drill** — worker b is armed ``rpc.drop:5`` from
       spawn: its first five RPC replies are blackholed, the proxy's
       calls time out, the circuit breaker TRIPS (placement skips b,
       requests complete on a), then — once the site exhausts — the
       half-open probe succeeds and the breaker CLOSES; post-recovery
       requests are served by b again.  Contracts: every request
       completes, ``trips >= 1``, final state ``closed``, b serves
       after recovery.
    2. **sigkill failover drill** — worker c is armed
       ``serve.replica.sigkill:1``: it dies a REAL SIGKILL on its
       first decode step (mid-probe, with accepted requests in
       flight).  The router confirms the death (pid probe), fails the
       victims over, and the spawn callback brings up a REPLACEMENT
       process on the shared AOT cache.  Contracts: ZERO dropped
       requests, tokens bit-identical to the unfaulted continuous
       run, >= 1 failover, replacement 0 foreground compiles.
    """
    from mxnet_tpu.serving import Router
    from mxnet_tpu.serving.rpc import (BREAKER_CLOSED,
                                       CircuitBreaker,
                                       RpcReplicaProxy,
                                       port_file_path, wait_port_file)

    run_dir = tempfile.mkdtemp(prefix="serve-fleet-")
    cache = os.path.join(run_dir, "aot")
    os.makedirs(cache)
    procs = {}
    try:
        procs["a"] = _spawn_worker(run_dir, cache, 0, 0)
        procs["b"] = _spawn_worker(
            run_dir, cache, 1, 0,
            {"MXTPU_FAULT": "rpc.drop:5",
             "MXTPU_FAULT_ATTEMPTS": "0"})
        procs["c"] = _spawn_worker(
            run_dir, cache, 2, 0,
            {"MXTPU_FAULT": "serve.replica.sigkill:1",
             "MXTPU_FAULT_ATTEMPTS": "0"})
        for slot in (0, 1, 2):
            wait_port_file(port_file_path(run_dir, slot), timeout=300)

        def proxy(slot, rid):
            return RpcReplicaProxy(
                rid, port_file=port_file_path(run_dir, slot),
                timeout_s=0.25, retries=0,
                breaker=CircuitBreaker(threshold=2, cooldown_s=0.4,
                                       name=rid))

        # ---- phase 1: breaker trip + recovery --------------------------
        pa, pb = proxy(0, "a"), proxy(1, "b")
        rt = Router([pa, pb])
        reqs = [rt.submit(p, n) for _t, p, n in workload[:6]]
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            rt.step()
            if all(r.done for r in reqs) and \
                    pb.breaker.state == BREAKER_CLOSED and \
                    pb.breaker.trips >= 1:
                break
            time.sleep(0.02)
        tripped, recovered = pb.breaker.trips, \
            pb.breaker.state == BREAKER_CLOSED
        post = [rt.submit(p, n) for _t, p, n in workload[6:10]]
        deadline = time.monotonic() + 60
        while not all(r.done for r in post) and \
                time.monotonic() < deadline:
            rt.step()
            time.sleep(0.02)
        breaker = {
            "completed": sum(1 for r in reqs + post
                             if r.state == "completed"),
            "requests": len(reqs) + len(post),
            "trips": tripped,
            "recovered": recovered,
            "final_state": pb.breaker.state,
            "served_by_b_after_recovery": sum(
                1 for r in post if r.state == "completed"
                and r.replica_id == "b"),
        }

        # ---- phase 2: SIGKILL one replica mid-probe --------------------
        pc = proxy(2, "c")
        spawn_compiles = []

        def spawn():
            # the real supervised-respawn move: a fresh worker process
            # for slot 2, then the successor proxy pinned to it
            procs["c2"] = _spawn_worker(run_dir, cache, 2, 1)
            fresh = pc.successor(replica_id="c2", timeout=300)
            # the 0-foreground-compile contract must be MEASURED, not
            # defaulted: an unreachable health probe is a failed
            # drill, never a silent 0
            compiles = None
            for _ in range(20):
                health = fresh.health()
                compiles = (health.get("remote")
                            or {}).get("serve_compiles")
                if compiles is not None:
                    break
                time.sleep(0.25)
            if compiles is None:
                raise RuntimeError(
                    "replacement health probe never answered — the "
                    "foreground-compile contract cannot be verified: "
                    "%r" % (health,))
            spawn_compiles.append(compiles)
            return fresh

        rt2 = Router([pa, pc], spawn=spawn, max_retries=2)
        rrs = []
        pending = list(workload)
        t_start = time.perf_counter()
        while pending or not rt2.idle:
            now = time.perf_counter() - t_start
            while pending and pending[0][0] <= now:
                _, prompt, max_new = pending.pop(0)
                rrs.append(rt2.submit(prompt, max_new))
            # reap exited children: a SIGKILLed worker must become a
            # ProcessLookupError for the proxy's death probe, not a
            # zombie that still answers kill(pid, 0)
            for p in procs.values():
                p.poll()
            if rt2.step() == 0 and pending:
                time.sleep(min(1e-4, max(0.0, pending[0][0] - now)))
            if time.perf_counter() - t_start > 300:
                raise RuntimeError("fleet drill did not drain")
        completed = [rr for rr in rrs if rr.state == "completed"]
        tokens = [rr.tokens for rr in completed]
        return {
            "requests": len(rrs),
            "completed": len(completed),
            "dropped": len(rrs) - len(completed),
            "failovers": rt2.failovers,
            "tokens_match_unfaulted": tokens == reference_tokens,
            "replacement_spawns": len(spawn_compiles),
            "replacement_foreground_compiles":
                sum(c or 0 for c in spawn_compiles),
            "retried": sum(1 for rr in rrs if rr.retries > 0),
            "breaker": breaker,
        }
    finally:
        for p in procs.values():
            try:
                p.kill()
                p.wait(timeout=10)
            except Exception:
                pass
        shutil.rmtree(run_dir, ignore_errors=True)


def run_partition(workload, reference_tokens):
    """The ISSUE-17 partition drill: RPC-native liveness over a fleet
    that shares NO run directory.  Every worker lives in a PRIVATE tmp
    dir — its port file, heartbeat file, and telemetry are invisible
    to its peers and to the router except for ONE bootstrap read of
    the port file (the out-of-band discovery stand-in); after that the
    proxies are addr-pinned and liveness rides the heartbeat RPC
    alone.  The only shared artifact is the router host's own journal
    — the multi-host seam.

    Phase A — **heartbeat-only loss** (``rpc.heartbeat.drop``, armed
    mid-run over the drill-plane ``inject`` RPC): worker b's heartbeat
    replies park while its data plane keeps answering.  Laws: the
    proxy records SUSPICION (``rpc.suspicions`` delta > 0), every
    request completes, suspicion CLEARS when the control plane heals,
    and there are ZERO failovers — breaker wobble or a cut control
    plane alone never kills a replica that is still doing work.

    Phase B — **real partition** (``rpc.partition``, a FINITE count so
    the link heals once the armed budget is parked away): worker b
    blackholes every inbound frame while holding accepted work.  The
    proxy suspects, then confirms ``fence_expiry`` (heartbeat AND
    progress silence past the lease); the router fails over, bumps the
    slot's fencing epoch, and re-places the victims on a.  The zombie
    keeps decoding behind the partition; when the link heals, its late
    completions are observed and REJECTED (``rpc.fenced_results``,
    journaled ``fenced`` lines).  Laws: >= 1 failover with the typed
    ``fence_expiry`` reason, >= 1 fenced result, EXACTLY one terminal
    journal line per rid (0 double-delivered), and the delivered
    tokens bit-identical to the unfaulted run.

    **Telemetry plane (ISSUE 18)** rides the same drill: the workers
    export no ``MXTPU_TELEMETRY`` (their private tmp dirs hold no
    stream files), so the ONLY way the router host assembles fleet
    telemetry is the ``telemetry_pull`` RPC — a collector loop in both
    phases appends each worker's pulled lines to
    ``<router_dir>/telemetry/stream-{a,b}.jsonl``, the router process
    runs the default alert rules locally (its proxies own the breaker
    and fence evidence, so ``breaker_open`` / ``replica_fenced`` fire
    HERE) and emits its own line into the same tree, and
    ``serve_report.analyze`` over that pull-only tree must be green:
    lawful lifecycles, traced-vs-counter token accounting bit-exact
    (the zombie's behind-the-partition decode included — its stream is
    pulled after the heal), and >= 1 fired alert in the alerts lane.
    ``fleet_top.collect_matrix`` against the live fleet must return a
    complete matrix (every row up with an engine block)."""
    import io

    import fleet_top as _ft
    import serve_report as _sr
    from mxnet_tpu import telemetry
    from mxnet_tpu.serving import Router
    from mxnet_tpu.serving.rpc import (CircuitBreaker, RpcReplicaProxy,
                                       collect_telemetry,
                                       port_file_path, rpc_call,
                                       wait_port_file)

    def cval(name):
        return telemetry.counter(name).value

    def inject(addr, spec, timeout=1.0):
        return rpc_call(tuple(addr), {"method": "inject",
                                      "spec": spec},
                        timeout, retries=0)

    # clean registry in the router process: the pulled tree gets the
    # router's OWN stream line too, and stale counters from earlier
    # in-process probes would break the bit-exact reconciliation
    telemetry.reset()
    cache = tempfile.mkdtemp(prefix="serve-part-aot-")
    router_dir = tempfile.mkdtemp(prefix="serve-part-router-")
    journal = os.path.join(router_dir, "router-journal.jsonl")
    tel_dir = os.path.join(router_dir, "telemetry")
    os.makedirs(tel_dir)
    tel_cursors = {}
    tel_stats = {"lines": 0, "errors": 0, "resets": 0}
    dirs, procs, addrs = {}, {}, {}

    def pull_workers(timeout=0.2):
        # the collector: cursor-resumed telemetry_pull per worker into
        # the router host's tree.  A partitioned worker's pull parks
        # (counted, never fatal) — the client-held cursor makes the
        # post-heal retry pick up exactly where the last one ended
        for tag, addr in addrs.items():
            path = os.path.join(tel_dir, "stream-%s.jsonl" % tag)
            try:
                res = collect_telemetry(
                    path, tuple(addr), cursor=tel_cursors.get(tag),
                    timeout_s=timeout)
                tel_cursors[tag] = res["cursor"]
                tel_stats["lines"] += res["lines"]
                tel_stats["resets"] += res["resets"]
            except Exception:
                tel_stats["errors"] += 1

    try:
        for slot, tag in ((0, "a"), (1, "b")):
            dirs[tag] = tempfile.mkdtemp(
                prefix="serve-part-w%d-" % slot)
            procs[tag] = _spawn_worker(
                dirs[tag], cache, slot, 0,
                {"MXTPU_RPC_ALLOW_INJECT": "1"})
        for slot, tag in ((0, "a"), (1, "b")):
            doc = wait_port_file(port_file_path(dirs[tag], slot),
                                 timeout=300)
            addrs[tag] = (doc.get("host", "127.0.0.1"),
                          int(doc["port"]))

        def proxy(tag):
            # addr-pinned: NO port-file watching after bootstrap —
            # liveness evidence is the heartbeat RPC only
            return RpcReplicaProxy(
                tag, addr=addrs[tag], timeout_s=0.25, retries=0,
                heartbeat_s=0.05, suspect_after_s=0.2,
                dead_after_s=0.8,
                breaker=CircuitBreaker(threshold=1, cooldown_s=100.0,
                                       name=tag))

        pa, pb = proxy("a"), proxy("b")
        rt = Router([pa, pb], journal_path=journal, max_retries=2)

        # ---- phase A: control plane cut, data plane healthy ----------
        base_susp = cval("rpc.suspicions")
        inject(addrs["b"], "rpc.heartbeat.drop:100000")
        reqs = [rt.submit(p, n) for _t, p, n in workload[:8]]
        suspected_seen = False
        next_pull = time.monotonic() + 1.0
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            rt.step()
            suspected_seen = suspected_seen or pb.suspected
            if time.monotonic() >= next_pull:
                next_pull = time.monotonic() + 1.0
                pull_workers()
                telemetry.check_alerts()
            if all(r.done for r in reqs) and suspected_seen:
                break
            time.sleep(0.01)
        inject(addrs["b"], "")          # heal the control plane
        deadline = time.monotonic() + 30
        while pb.suspected and time.monotonic() < deadline:
            rt.step()
            time.sleep(0.01)
        phase_a = {
            "requests": len(reqs),
            "completed": sum(1 for r in reqs
                             if r.state == "completed"),
            "suspicions": cval("rpc.suspicions") - base_susp,
            "suspect_cleared": not pb.suspected,
            "failovers": rt.failovers,
            "confirm_reason": pb.confirmed_reason,
        }

        # live fleet matrix between phases: both workers healthy again,
        # so every row must come back complete (up, engine block,
        # heartbeat RTT) — the fleet_top --once contract in-process
        matrix = _ft.collect_matrix(
            [(t, tuple(addrs[t])) for t in ("a", "b")], timeout_s=2.0)
        mbuf = io.StringIO()
        _ft.render_matrix(matrix, mbuf)
        fleet_top = {
            "rows": len(matrix["rows"]),
            "complete": all(r.get("up") and r.get("engine")
                            and r.get("hb_rtt_ms") is not None
                            for r in matrix["rows"]),
            "renders": "replica" in mbuf.getvalue(),
        }

        # ---- phase B: real partition + fenced failover ---------------
        base_fenced = cval("rpc.fenced_results")
        base_conf = cval("rpc.confirmations.fence_expiry")
        rrs = [rt.submit(p, n) for _t, p, n in workload]
        on_b = sum(1 for rr in rrs if rr.replica_id == "b")
        if on_b == 0:
            raise RuntimeError(
                "placement never used worker b — the partition would "
                "cut an idle link and drill nothing")
        # finite count: the partition heals once this budget is parked
        # away (heartbeats, the breaker's one probe, the fenced sweep's
        # polls, and the heal-spam below all burn it)
        inject(addrs["b"], "rpc.partition:100")
        healed = False
        next_pull = time.monotonic() + 1.0
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            rt.step()
            for p_ in procs.values():
                p_.poll()
            if time.monotonic() >= next_pull:
                next_pull = time.monotonic() + 1.0
                # b's pulls park while partitioned (each burns one of
                # the armed budget, same as any inbound frame) and
                # resume from the held cursor after the heal
                pull_workers()
                telemetry.check_alerts()
            done = all(rr.done for rr in rrs)
            if done and cval("rpc.fenced_results") - base_fenced >= 1:
                break
            if done and rt.failovers > phase_a["failovers"] \
                    and not healed:
                try:
                    inject(addrs["b"], "", timeout=0.1)
                    healed = True
                except Exception:
                    pass    # still partitioned: the attempt burned one
            time.sleep(0.01)
        completed = [rr for rr in rrs if rr.state == "completed"]
        tokens = [rr.tokens for rr in completed]

        # telemetry finale: make sure the link is healed, then pull
        # each worker to quiescence (cursor stops advancing) — the
        # zombie's behind-the-partition decode must be IN the tree or
        # the traced-vs-counter reconciliation below can't be exact
        try:
            inject(addrs["b"], "", timeout=0.5)
        except Exception:
            pass
        settle = time.monotonic() + 20
        while time.monotonic() < settle:
            before = {t: (tel_cursors.get(t) or {}).get("req_seq")
                      for t in addrs}
            pull_workers(timeout=1.0)
            after = {t: (tel_cursors.get(t) or {}).get("req_seq")
                     for t in addrs}
            if after == before and all(v is not None
                                       for v in after.values()):
                break
            time.sleep(0.2)
        telemetry.check_alerts()
        # the router host's own line joins the same tree: its registry
        # holds the fleet-level events (submits, finals, fenced, the
        # alerts its rules fired) the workers never see
        telemetry._emit_line(
            os.path.join(tel_dir, "stream-router.jsonl"), final=True)

        # serve_report over the PULL-ONLY tree (the workers' private
        # dirs were never read): green or the drill fails
        rep = _sr.analyze(router_dir)
        rbuf = io.StringIO()
        _sr.render(rep, rbuf)
        acc = rep["accounting"]
        telemetry_out = {
            "pulled_lines": tel_stats["lines"],
            "pull_errors": tel_stats["errors"],
            "cursor_resets": tel_stats["resets"],
            "streams": sorted(os.listdir(tel_dir)),
            "lifecycle_ok": rep["lifecycle"]["ok"],
            "accounting_exact": bool(acc["tokens_match"]),
            "tokens": acc["tokens"],
            "traced_tokens": acc["traced_tokens"],
            "alerts_fired": len(rep["alerts"]),
            "alert_rules": sorted({a["rule"] for a in rep["alerts"]
                                   if a["rule"]}),
            "report_renders": "fired alerts" in rbuf.getvalue(),
            "fleet_top": fleet_top,
        }

        # exactly-once off the journal: one terminal line per rid,
        # fenced lines are separate typed events, never deliveries
        terminal = {}
        fenced_lines = []
        with open(journal) as f:
            for ln in f:
                try:
                    doc = json.loads(ln)
                except ValueError:
                    continue
                if doc.get("event") == "fenced":
                    fenced_lines.append(doc)
                elif doc.get("event") == "complete":
                    terminal[doc["rid"]] = \
                        terminal.get(doc["rid"], 0) + 1
        return {
            "phase_a": phase_a,
            "requests": len(rrs),
            "completed": len(completed),
            "dropped": len(rrs) - len(completed),
            "failovers": rt.failovers,
            "confirm_reason": pb.confirmed_reason,
            "confirmations_fence_expiry":
                cval("rpc.confirmations.fence_expiry") - base_conf,
            "fenced_results":
                cval("rpc.fenced_results") - base_fenced,
            "fenced_journal_lines": len(fenced_lines),
            "double_delivered":
                sum(1 for v in terminal.values() if v > 1),
            "victims_on_partitioned": on_b,
            "tokens_match_unfaulted": tokens == reference_tokens,
            "telemetry": telemetry_out,
        }
    finally:
        for p in procs.values():
            try:
                p.kill()
                p.wait(timeout=10)
            except Exception:
                pass
        for d in list(dirs.values()) + [cache, router_dir]:
            shutil.rmtree(d, ignore_errors=True)


# -- streamed delivery drills (ISSUE 19) -----------------------------------

def run_streamed(net, workload, num_slots=8, page_size=16,
                 max_prefill_len=32, max_seq_len=48):
    """In-process streamed-delivery phase: an open-loop workload where
    every in-flight request is POLLED once per engine step (the
    client-pull cadence) and its tokens assembled strictly by cursor.
    What ``BENCH_MODE=serve`` pins on this dict:

    - exactly-once assembly: the cursor-assembled streams equal the
      engine's own token lists bit-for-bit (no gap, no dup);
    - the hot path survives streaming: 1.0 decode dispatch/step and 0
      steady-state recompiles WITH a poll per request per step — the
      delivery plane never forces a dispatch;
    - streamed TTFT p50 < 0.5x the unary completion p50: first-token
      latency is now a client-visible number, not a telemetry-only one
      (a unary client waits for completion).

    The latency split runs on a streaming-REPRESENTATIVE trace
    (arrival rate the slot pool absorbs, decode-dominated lengths):
    under a saturating burst, queue wait dominates BOTH classes
    equally and the ratio measures the scheduler, not the delivery
    plane — the throughput/queueing contracts already own that regime
    (``run_continuous`` and the fleet drill keep the original burst).
    """
    from mxnet_tpu import profiler, telemetry
    from mxnet_tpu.serving import ServingEngine
    import numpy as np

    eng = ServingEngine(net, num_slots=num_slots, page_size=page_size,
                        max_prefill_len=max_prefill_len,
                        max_seq_len=max_seq_len)
    eng.generate([np.zeros(4, np.int32)], max_new=2)
    profiler.reset_step_stats()
    telemetry.reset()
    base = profiler.step_stats()
    d0, c0 = base["dispatch_count"], base["compile_count"]
    steps0, prefills0 = eng.decode_steps, eng.prefills

    reqs, arrivals, assembled = [], [], []
    first_token_t, done_t = [], []
    polls = 0
    pending = list(workload)
    t_start = time.perf_counter()
    while pending or not eng.sched.idle:
        now = time.perf_counter() - t_start
        while pending and pending[0][0] <= now:
            arr, prompt, max_new = pending.pop(0)
            arrivals.append(arr)
            assembled.append([])
            first_token_t.append(None)
            done_t.append(None)
            reqs.append(eng.submit(prompt, max_new))
        if eng.step() == 0 and pending:
            time.sleep(min(1e-4, max(0.0, pending[0][0] - now)))
        # the client-pull cadence: one poll per in-flight stream per
        # step, tokens appended strictly at the held cursor
        for i, req in enumerate(reqs):
            if done_t[i] is not None:
                continue
            reply = eng.poll(req.trace, cursor=len(assembled[i]))
            polls += 1
            t_now = time.perf_counter() - t_start
            if reply["tokens"]:
                if first_token_t[i] is None:
                    first_token_t[i] = t_now
                assembled[i].extend(reply["tokens"])
            if reply["done"] and not reply["more"]:
                done_t[i] = t_now
    # drain the tail: terminal buffers answer re-polls until TTL
    for i, req in enumerate(reqs):
        while done_t[i] is None:
            reply = eng.poll(req.trace, cursor=len(assembled[i]))
            polls += 1
            if first_token_t[i] is None and reply["tokens"]:
                first_token_t[i] = time.perf_counter() - t_start
            assembled[i].extend(reply["tokens"])
            if reply["done"] and not reply["more"]:
                done_t[i] = time.perf_counter() - t_start

    stats = profiler.step_stats()
    decode_steps = eng.decode_steps - steps0
    prefills = eng.prefills - prefills0
    dispatches = stats["dispatch_count"] - d0
    streamed_ttft = sorted(t - a for t, a in zip(first_token_t,
                                                 arrivals))
    unary_done = sorted(t - a for t, a in zip(done_t, arrivals))
    engine_tokens = [[int(t) for t in r.tokens] for r in reqs]
    ttft_p50 = _pct(streamed_ttft, 0.5)
    unary_p50 = _pct(unary_done, 0.5)
    return {
        "requests": len(reqs),
        "polls": polls,
        "exactly_once": assembled == engine_tokens,
        "decode_dispatches_per_step": round(
            (dispatches - prefills) / max(1, decode_steps), 4),
        "steady_state_compiles": stats["compile_count"] - c0,
        "streamed_ttft_p50_ms": round(ttft_p50 * 1e3, 3),
        "streamed_ttft_p99_ms": round(
            _pct(streamed_ttft, 0.99) * 1e3, 3),
        "unary_completion_p50_ms": round(unary_p50 * 1e3, 3),
        "ttft_vs_unary_ratio": round(ttft_p50 / max(1e-9, unary_p50),
                                     4),
        "stream_polls_counter":
            telemetry.counter("serving.stream.polls").value,
        "delivered_counter":
            telemetry.counter("serving.stream.delivered").value,
    }


def run_cancel(net, num_slots=4, page_size=8, max_prefill_len=32,
               max_seq_len=48):
    """Cancellation drill: one request cancelled MID-DECODE, one
    cancelled while QUEUED (slots full), the rest served to
    completion.  Pins: both land the typed terminal verdict
    ``cancelled`` (between decode steps — slot + pages released), the
    survivors' tokens are untouched, cancel is idempotent, and the
    page pool conserves (audit green, all pages back in the free
    pool)."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.serving import ServingEngine
    import numpy as np

    rng = np.random.RandomState(41)
    eng = ServingEngine(net, num_slots=num_slots, page_size=page_size,
                        max_prefill_len=max_prefill_len,
                        max_seq_len=max_seq_len, prefix_cache=False)
    eng.generate([np.zeros(4, np.int32)], max_new=2)
    telemetry.reset()
    free0 = eng.alloc.free_pages
    prompts = [rng.randint(0, 256, 8).astype(np.int32)
               for _ in range(num_slots + 1)]
    # reference: the same prompts served with no cancellation
    ref = eng.generate(prompts, max_new=16)
    assert eng.alloc.free_pages == free0
    reqs = [eng.submit(p, 16) for p in prompts]
    eng.step()          # residents placed; the last request queues
    victim, queued = reqs[0], reqs[-1]
    assert queued.state == "queued", queued.state
    eng.step()
    mid = eng.cancel(victim.trace)          # mid-decode teardown
    que = eng.cancel(queued.trace)          # queued teardown
    again = eng.cancel(victim.trace)        # idempotent re-cancel
    eng.run_until_idle()
    eng.alloc.assert_conservation()
    survivors = [r for r in reqs if r is not victim and r is not queued]
    surv_ok = all(
        [int(t) for t in r.tokens] == [int(t) for t in ref[i + 1]]
        for i, r in enumerate(survivors))
    return {
        "mid_decode_verdict": mid["verdict"],
        "queued_verdict": que["verdict"],
        "idempotent": again["verdict"] == mid["verdict"],
        "victim_tokens_at_cancel": mid["tokens"],
        "survivors_completed": sum(1 for r in survivors
                                   if r.state == "finished"),
        "survivor_tokens_match": surv_ok,
        "cancelled_counter":
            telemetry.counter("serving.stream.cancelled").value,
        "pages_restored": eng.alloc.free_pages == free0,
        "conservation_ok": True,
    }


def run_vanish(net, num_slots=4, page_size=8, max_prefill_len=32,
               max_seq_len=48, abandon_s=0.05):
    """The ``serve.client.vanish`` drill: every request's poller runs
    for a few steps (the requests become STREAMS), then the armed
    fault silences two of them — their clients vanish without a
    cancel.  After ``MXTPU_SERVE_ABANDON_S`` of poll silence the
    engine reclaims both with the typed ``abandoned`` verdict; the
    drill pins the reclaim count, the verdicts, conservation (audit
    green + every page back in the free pool — a vanished client can
    NOT pin the KV pool), the surviving streams' bit-exact delivery,
    and the ``orphan_reclaim`` default alert rule firing on the
    counter."""
    from mxnet_tpu import fault, telemetry
    from mxnet_tpu.serving import ServingEngine
    import numpy as np

    rng = np.random.RandomState(43)
    os.environ["MXTPU_SERVE_ABANDON_S"] = str(abandon_s)
    try:
        eng = ServingEngine(net, num_slots=num_slots,
                            page_size=page_size,
                            max_prefill_len=max_prefill_len,
                            max_seq_len=max_seq_len,
                            prefix_cache=False)
    finally:
        del os.environ["MXTPU_SERVE_ABANDON_S"]
    eng.generate([np.zeros(4, np.int32)], max_new=2)
    telemetry.reset()
    free0 = eng.alloc.free_pages
    reqs = [eng.submit(rng.randint(0, 256, 8).astype(np.int32), 24)
            for _ in range(num_slots)]
    assembled = [[] for _ in reqs]
    vanished = set()
    fault.configure("serve.client.vanish:2")
    try:
        # a few polled steps first: every request becomes a stream
        for _ in range(3):
            eng.step()
            for i, r in enumerate(reqs):
                assembled[i].extend(
                    eng.poll(r.trace, cursor=len(assembled[i]))
                    ["tokens"])
        deadline = time.monotonic() + 60
        while not eng.sched.idle and time.monotonic() < deadline:
            eng.step()
            for i, r in enumerate(reqs):
                if i in vanished or r.done:
                    continue
                if fault.trigger("serve.client.vanish"):
                    vanished.add(i)   # this poller goes silent forever
                    continue
                assembled[i].extend(
                    eng.poll(r.trace, cursor=len(assembled[i]))
                    ["tokens"])
            # the reclaim clock is real time; the engine steps faster
            # than abandon_s on CPU, so give the sweep a chance to see
            # the silence age past the window
            time.sleep(abandon_s / 4)
    finally:
        fault.reset()
    eng.alloc.assert_conservation()
    fired = telemetry.check_alerts()
    survivors = [i for i in range(len(reqs)) if i not in vanished]
    for i in survivors:     # drain the survivors' stream tails
        reply = eng.poll(reqs[i].trace, cursor=len(assembled[i]))
        assembled[i].extend(reply["tokens"])
    snap = eng.snapshot()["stream"]
    return {
        "requests": len(reqs),
        "orphans": len(vanished),
        "abandoned_verdicts": sum(1 for i in vanished
                                  if reqs[i].verdict == "abandoned"),
        "abandoned_counter":
            telemetry.counter("serving.stream.abandoned").value,
        "snapshot_abandoned": snap["abandoned"],
        "survivors_completed": sum(
            1 for i in survivors if reqs[i].state == "finished"),
        "survivor_streams_exact": all(
            assembled[i] == [int(t) for t in reqs[i].tokens]
            for i in survivors),
        "pages_restored": eng.alloc.free_pages == free0,
        "conservation_ok": True,
        "alert_fired": any(a.get("rule") == "orphan_reclaim"
                           for a in fired),
    }


def run_stream_fleet(workload, reference_tokens):
    """The kill-mid-stream drill (the ISSUE 19 tentpole contract):
    REAL worker processes, clients streaming by cursor through the
    router, a REAL SIGKILL landed mid-stream (injected over the
    drill-plane RPC once tokens are flowing), plus ``serve.stream.drop``
    armed on the survivor to blackhole poll replies.  Hard contracts:

    - exactly-once delivery: every accepted request's cursor-assembled
      stream equals both its completed journal tokens and the
      unfaulted reference, bit-for-bit — NO gap and NO dup across the
      failover (the router maps the client cursor onto the survivor's
      bit-identical re-decode);
    - >= 1 stream had delivered tokens BEFORE the kill and resumed
      across it (the drill killed an ACTIVE stream, not an idle one);
    - a dropped poll reply recovers by an idempotent re-poll at the
      SAME cursor (observed as >= 1 direct proxy poll returning None,
      with the re-poll resuming contiguously);
    - zero dropped requests, >= 1 failover, cancel-free teardown."""
    from mxnet_tpu.serving import Router
    from mxnet_tpu.serving.rpc import (CircuitBreaker, RpcReplicaProxy,
                                       port_file_path, rpc_call,
                                       wait_port_file)

    run_dir = tempfile.mkdtemp(prefix="serve-stream-")
    cache = os.path.join(run_dir, "aot")
    os.makedirs(cache)
    procs, addrs = {}, {}

    def inject(addr, spec, timeout=1.0):
        return rpc_call(tuple(addr), {"method": "inject",
                                      "spec": spec}, timeout,
                        retries=0)

    try:
        procs["a"] = _spawn_worker(run_dir, cache, 0, 0,
                                   {"MXTPU_RPC_ALLOW_INJECT": "1"})
        procs["v"] = _spawn_worker(run_dir, cache, 1, 0,
                                   {"MXTPU_RPC_ALLOW_INJECT": "1"})
        for slot, tag in ((0, "a"), (1, "v")):
            doc = wait_port_file(port_file_path(run_dir, slot),
                                 timeout=300)
            addrs[tag] = (doc.get("host", "127.0.0.1"),
                          int(doc["port"]))

        def proxy(slot, rid):
            return RpcReplicaProxy(
                rid, port_file=port_file_path(run_dir, slot),
                timeout_s=0.25, retries=0,
                breaker=CircuitBreaker(threshold=4, cooldown_s=0.4,
                                       name=rid))

        pa, pv = proxy(0, "a"), proxy(1, "v")
        spawned = []

        def spawn():
            procs["v2"] = _spawn_worker(run_dir, cache, 1, 1)
            fresh = pv.successor(replica_id="v2", timeout=300)
            spawned.append(fresh)
            return fresh

        rt = Router([pa, pv], spawn=spawn, max_retries=2)
        rrs, assembled = [], []
        pending = list(workload)
        killed = False
        drop_armed = False
        drop_seen = 0
        drop_repoll_contiguous = None
        cursors_at_kill = None
        t_start = time.perf_counter()
        while pending or not rt.idle:
            now = time.perf_counter() - t_start
            while pending and pending[0][0] <= now:
                _, prompt, max_new = pending.pop(0)
                rrs.append(rt.submit(prompt, max_new))
                assembled.append([])
            for p in procs.values():
                p.poll()    # reap: SIGKILL must read as a dead pid
            rt.step()
            # the client poller plane: one cursor-pull per in-flight
            # stream per loop, tokens appended strictly at the cursor
            delivered_v = 0
            for i, rr in enumerate(rrs):
                reply = rt.poll(rr.rid, cursor=len(assembled[i]))
                if reply and reply["tokens"]:
                    assert reply["cursor"] == (len(assembled[i])
                                               + len(reply["tokens"]))
                    assembled[i].extend(reply["tokens"])
                if rr.replica_id == "v" and assembled[i]:
                    delivered_v += 1
            # arm the poll-reply blackhole on the survivor once its
            # streams flow: the next 2 direct polls park, the re-poll
            # at the SAME cursor must resume contiguously
            if not drop_armed and any(
                    a and rr.replica_id == "a" and not rr.done
                    for a, rr in zip(assembled, rrs)):
                idx = next(i for i, rr in enumerate(rrs)
                           if assembled[i] and rr.replica_id == "a"
                           and not rr.done)
                inject(addrs["a"], "serve.stream.drop:2")
                drop_armed = True
                cur = len(assembled[idx])
                for _ in range(8):
                    direct = pa.poll(rrs[idx].trace, cursor=cur)
                    if direct is None:
                        drop_seen += 1       # blackholed reply
                        continue
                    if direct.get("known") and direct.get("tokens"):
                        drop_repoll_contiguous = (
                            direct["cursor"]
                            == cur + len(direct["tokens"]))
                        assembled[idx].extend(direct["tokens"])
                    break
            # land the SIGKILL only once the victim is MID-stream:
            # some client cursor on v must already be past 0
            if not killed and delivered_v >= 1:
                cursors_at_kill = [len(a) for a in assembled]
                inject(addrs["v"], "serve.replica.sigkill:1",
                       timeout=0.5)
                killed = True
            if time.perf_counter() - t_start > 300:
                raise RuntimeError("stream fleet drill did not drain")
            time.sleep(0.005)
        # drain every stream tail to its terminal buffer
        for i, rr in enumerate(rrs):
            for _ in range(50):
                reply = rt.poll(rr.rid, cursor=len(assembled[i]))
                if reply is None:
                    break
                assembled[i].extend(reply["tokens"])
                if not reply["more"]:
                    break
        completed = [rr for rr in rrs if rr.state == "completed"]
        journal_tokens = [rr.tokens for rr in completed]
        resumed = sum(
            1 for i, rr in enumerate(rrs)
            if rr.retries > 0 and cursors_at_kill is not None
            and i < len(cursors_at_kill) and cursors_at_kill[i] > 0)
        return {
            "requests": len(rrs),
            "completed": len(completed),
            "dropped": len(rrs) - len(completed),
            "failovers": rt.failovers,
            "killed_mid_stream": killed,
            "streams_resumed_across_kill": resumed,
            "exactly_once": assembled == [rr.tokens for rr in rrs],
            "tokens_match_unfaulted":
                journal_tokens == reference_tokens,
            "drop_blackholed_replies": drop_seen,
            "drop_repoll_contiguous": drop_repoll_contiguous,
            "replacement_spawns": len(spawned),
        }
    finally:
        for p in procs.values():
            try:
                p.kill()
                p.wait(timeout=10)
            except Exception:
                pass
        shutil.rmtree(run_dir, ignore_errors=True)


def run_streaming(net, workload, reference_tokens, fleet=True):
    """The ISSUE 19 umbrella: in-process streamed phase + cancel drill
    + vanish drill (+ the out-of-process kill-mid-stream drill).  The
    fleet drill replays the caller's burst ``workload`` against its
    ``reference_tokens``; the streamed latency split gets its own
    decode-dominated trace (see ``run_streamed``) at the same engine
    config, so the AOT memo is shared."""
    stream_workload = make_workload(n_requests=24,
                                    mean_interarrival_s=0.02,
                                    new_tokens=(16, 24), seed=11)
    out = {
        "streamed": run_streamed(net, stream_workload),
        "cancel": run_cancel(net),
        "vanish": run_vanish(net),
    }
    if fleet:
        out["fleet"] = run_stream_fleet(workload, reference_tokens)
    return out


def measure_trace_overhead(slots=8, iters=2000, passes=5):
    """Isolated microbench of the per-decode-step tracing cost: one
    batched ``tokens`` event naming every resident trace (exactly what
    ``ServingEngine.step`` adds per decode step), timed hot, median of
    ``passes``.  ``BENCH_MODE=serve`` asserts it under
    ``MXTPU_SERVE_TRACE_BUDGET_US`` (default 2 µs/decode-step)."""
    from mxnet_tpu import telemetry

    telemetry.reset()
    traces = [telemetry.mint_trace() for _ in range(slots)]
    note = telemetry.note_request_event
    results = []
    for _ in range(passes):
        t0 = time.perf_counter_ns()
        for i in range(iters):
            # list built per step like the engine's comprehension over
            # its residents — the microbench pays what the hot path pays
            note("", "tokens", t_ns=t0,
                 args={"replica": "a", "step": i,
                       "traces": list(traces)})
        results.append((time.perf_counter_ns() - t0) / 1e3 / iters)
        telemetry.reset()
    return round(sorted(results)[len(results) // 2], 3)


def measure_collector_impact(net=None, n_requests=12, iters=200,
                             passes=5):
    """Collector-on-the-hot-path microbench (ISSUE 18): drives the
    engine open-loop while running ``telemetry.pull_snapshot`` — the
    entire ``telemetry_pull`` handler body minus the socket — after
    EVERY engine step, far denser than the supervisor's default 2 s
    interval, and checks the serving hot-path contracts survive
    (exactly 1.0 decode dispatch/step, 0 steady-state recompiles: the
    pull must never force a dispatch or a recompile).  Then times the
    steady-state pull itself hot (cursor caught up: the report
    snapshot dominates), median of ``passes``, for the
    ``MXTPU_TELEMETRY_PULL_BUDGET`` budget (µs, default 2000)."""
    from mxnet_tpu import profiler, telemetry
    from mxnet_tpu.serving import ServingEngine
    import numpy as np

    if net is None:
        net = build_net()
    workload = make_workload(n_requests=n_requests)
    eng = ServingEngine(net, num_slots=8, page_size=16,
                        max_prefill_len=32, max_seq_len=48)
    eng.generate([np.zeros(4, np.int32)], max_new=2)
    profiler.reset_step_stats()
    telemetry.reset()
    base = profiler.step_stats()
    d0, c0 = base["dispatch_count"], base["compile_count"]
    steps0, prefills0 = eng.decode_steps, eng.prefills

    cursor = {"req_seq": None, "step_seq": None}
    pulls = 0
    reqs, pending = [], list(workload)
    t_start = time.perf_counter()
    while pending or not eng.sched.idle:
        now = time.perf_counter() - t_start
        while pending and pending[0][0] <= now:
            _, prompt, max_new = pending.pop(0)
            reqs.append(eng.submit(prompt, max_new))
        if eng.step() == 0 and pending:
            time.sleep(min(1e-4, max(0.0, pending[0][0] - now)))
        _doc, cursor, more = telemetry.pull_snapshot(
            cursor.get("req_seq"), cursor.get("step_seq"))
        pulls += 1
        while more:     # chunked tail, same as a collector's loop
            _doc, cursor, more = telemetry.pull_snapshot(
                cursor.get("req_seq"), cursor.get("step_seq"))
            pulls += 1

    stats = profiler.step_stats()
    decode_steps = eng.decode_steps - steps0
    prefills = eng.prefills - prefills0
    dispatches = stats["dispatch_count"] - d0

    # isolated steady-state pull cost (caught-up cursor, warm registry)
    results = []
    for _ in range(passes):
        t0 = time.perf_counter_ns()
        for _i in range(iters):
            _doc, cursor, _more = telemetry.pull_snapshot(
                cursor.get("req_seq"), cursor.get("step_seq"))
        results.append((time.perf_counter_ns() - t0) / 1e3 / iters)
    return {
        "pulls": pulls,
        "decode_steps": decode_steps,
        "decode_dispatches_per_step": round(
            (dispatches - prefills) / max(1, decode_steps), 4),
        "steady_state_compiles": stats["compile_count"] - c0,
        "pull_us": round(sorted(results)[len(results) // 2], 1),
        "tokens": sum(len(r.tokens) for r in reqs),
    }


# -- AOT-warm replica spin-up (restart_probe pattern) ----------------------

def _spinup_child():
    """One fresh replica: backend-ready -> engine built -> first token.
    Prints foreground serving-program compiles (profiler counters: the
    engine's eager AOT-miss compiles + anything landing inside an
    instrumented serve call) and the time to first token."""
    import numpy as np
    import jax
    jax.devices()
    from mxnet_tpu import aot_cache, profiler, telemetry
    from mxnet_tpu.serving import ServingEngine

    net = build_net()
    profiler.reset_step_stats()
    t0 = time.perf_counter()
    eng = ServingEngine(net, num_slots=4, page_size=8,
                        max_prefill_len=32, max_seq_len=48)
    eng.generate([np.arange(6, dtype=np.int32)], max_new=2)
    ttft = time.perf_counter() - t0
    # background stores (twin serialization) must land before exit or
    # the warm attempt finds an empty cache
    aot_cache.drain(timeout=120)
    c = telemetry.report()["counters"]
    print(json.dumps({
        "ttfb_s": round(ttft, 3),
        "serve_compiles": profiler.step_stats()["compile_count"],
        "aot_hits": c.get("aot.cache_hits", 0),
        "aot_misses": c.get("aot.cache_misses", 0),
    }), flush=True)


def measure_spinup():
    """Cold vs warm replica spin-up sharing one AOT cache dir — what two
    launch.py restart attempts (or two replicas on one host) see."""
    cache = tempfile.mkdtemp(prefix="serve-probe-aot-")
    env = dict(os.environ)
    env.update({
        "MXTPU_AOT_CACHE_DIR": cache,
        "JAX_COMPILATION_CACHE_DIR": os.path.join(cache, "xla"),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_PLATFORMS": "cpu",
    })
    out = {}
    try:
        for label in ("cold", "warm"):
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--spinup-child"],
                env=env, capture_output=True, text=True, timeout=600)
            if r.returncode != 0:
                raise RuntimeError("spinup child (%s) failed rc=%d:\n%s"
                                   % (label, r.returncode,
                                      r.stderr[-2000:]))
            out[label] = json.loads(r.stdout.strip().splitlines()[-1])
    finally:
        shutil.rmtree(cache, ignore_errors=True)
    return {
        "cold_ttfb_s": out["cold"]["ttfb_s"],
        "warm_ttfb_s": out["warm"]["ttfb_s"],
        "cold_serve_compiles": out["cold"]["serve_compiles"],
        "warm_serve_compiles": out["warm"]["serve_compiles"],
        "warm_aot_hits": out["warm"]["aot_hits"],
    }


def run(spinup=True, degraded=True, fleet=True):
    net = build_net()
    workload = make_workload()
    cont = run_continuous(net, workload)
    seq = run_sequential(net, workload)
    cont_tokens = cont.pop("tokens")
    if cont_tokens != seq.pop("tokens"):
        raise AssertionError(
            "continuous and sequential servers emitted different greedy "
            "tokens for the same workload — the paged engine diverged "
            "from the dense forward")
    result = {
        "continuous": cont,
        "sequential": seq,
        "speedup_tokens_per_sec": round(
            cont["tokens_per_sec"] / seq["tokens_per_sec"], 2),
        "trace_overhead_us": measure_trace_overhead(),
        "collector": measure_collector_impact(net),
        "prefix": run_prefix(net),
        "gqa": run_gqa(net),
        "kvq": run_kvq(net, workload, cont_tokens),
        "spec": run_spec(),
        "stream": run_streaming(net, workload, cont_tokens,
                                fleet=fleet),
    }
    if degraded:
        result["degraded"] = run_degraded(net, workload, cont_tokens)
    if fleet:
        result["fleet"] = run_fleet(workload, cont_tokens)
        result["partition"] = run_partition(workload, cont_tokens)
    if spinup:
        result["spinup"] = measure_spinup()
    return result


if __name__ == "__main__":
    if "--spinup-child" in sys.argv:
        _spinup_child()
    else:
        print(json.dumps(run("--no-spinup" not in sys.argv,
                             fleet="--no-fleet" not in sys.argv)))
