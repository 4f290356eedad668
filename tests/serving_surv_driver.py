"""Standalone serving-survivability checks (ISSUE 11): deadlines, SLO
shedding, prefill-error verdicts, graceful drain, router failover with
at-most-once decode, live weight hot-swap with rollback — run in a
process of its own (as serving_driver.py) by
tests/test_serving_surv.py.

Usage: python serving_surv_driver.py
       [fast|lifecycle|router|swap|sampling|spec|prefix|stall|e2e]

- ``fast`` = lifecycle + router + swap + sampling + spec + prefix in
  ONE process (one jax import, engines share the AOT memo) — the
  tier-1 sibling of the slow e2e.
- ``stall`` expects the WATCHDOG to kill this process: the caller arms
  MXTPU_FAULT="serve.decode.stall:1" + MXTPU_STALL_TIMEOUT and asserts
  exit code 75 plus a postmortem carrying the serving snapshot.
- ``e2e`` is the slow combined drill (kill a replica mid-load under a
  decode-stall hiccup, zero dropped accepted requests bit-identically,
  shed under overload, AOT-warm replacement, mid-run hot-swap + torn
  rollback).

Prints SERVING_<SECTION>_OK markers on success.
"""
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import fault, profiler, telemetry  # noqa: E402
from mxnet_tpu.gluon.model_zoo import gpt  # noqa: E402

VOCAB, UNITS, HEADS, MAX_LEN = 128, 64, 2, 48
ENGINE_KW = dict(num_slots=3, page_size=8, max_prefill_len=16,
                 max_seq_len=32)


def _engine(net, **over):
    from mxnet_tpu.serving import ServingEngine
    kw = dict(ENGINE_KW)
    kw.update(over)
    return ServingEngine(net, **kw)


def _idle_pages_ok(eng):
    """Idle-engine page accounting: no leaks beyond the prefix index's
    own pins, conservation + index consistency intact."""
    eng.alloc.assert_conservation()
    cached = 0 if eng._prefix is None else eng._prefix.cached_pages
    assert eng.alloc.used_pages == cached, \
        (eng.alloc.used_pages, cached)
    if eng._prefix is not None:
        eng._prefix.assert_consistent()


def _net(seed=0):
    np.random.seed(seed)
    mx.random.seed(seed)
    n = gpt.GPTLM(VOCAB, 2, UNITS, HEADS, max_len=MAX_LEN)
    n.initialize()
    return n


def _ref(net, prompt, max_new):
    return list(gpt.generate(net, prompt[None], max_new)[0, len(prompt):])


def _prompts(rng, n, lo=3, hi=14):
    return [rng.randint(0, VOCAB, (rng.randint(lo, hi),)).astype(np.int32)
            for _ in range(n)]


# -- lifecycle: deadlines / shed / prefill error / drain --------------------

def check_deadline_verdicts(net):
    rng = np.random.RandomState(0)
    eng = _engine(net)
    longs = [eng.submit(p, 10) for p in _prompts(rng, 3)]
    # expires IN QUEUE: no free slot would matter — the deadline sweep
    # runs before admission, so this one never reserves anything
    doomed = eng.submit(rng.randint(0, VOCAB, (4,)).astype(np.int32), 5,
                        deadline_s=1e-4)
    time.sleep(0.005)
    eng.step()
    assert doomed.state == "expired" and \
        doomed.verdict == "expired_queue", (doomed.state, doomed.verdict)
    assert doomed.tokens == [] and doomed.done
    eng.run_until_idle()
    assert all(r.verdict == "completed" for r in longs)

    # expires MID-DECODE: partial tokens preserved, slot + pages back
    eng2 = _engine(net)
    used0 = eng2.alloc.used_pages
    r = eng2.submit(rng.randint(0, VOCAB, (5,)).astype(np.int32), 12,
                    deadline_s=30.0)
    eng2.step()
    eng2.step()
    got = len(r.tokens)
    assert got >= 2
    r.deadline_t = time.perf_counter() - 1.0   # deterministic expiry
    eng2.step()
    assert r.state == "expired" and r.verdict == "expired_decode", \
        (r.state, r.verdict)
    assert len(r.tokens) == got, "expired request decoded another token"
    assert eng2.alloc.used_pages == used0
    eng2.alloc.assert_conservation()
    # the freed slot serves the next request correctly
    p = rng.randint(0, VOCAB, (6,)).astype(np.int32)
    assert eng2.generate([p], 4)[0] == _ref(net, p, 4)


def check_shed_hysteresis(net):
    from mxnet_tpu.serving import SLOController
    rng = np.random.RandomState(1)
    slo = SLOController(target_p99_s=0.05, release_frac=0.5,
                        window_s=0.3, min_samples=3)
    eng = _engine(net, slo=slo)
    shed0 = telemetry.counter("serving.shed").value
    for _ in range(4):
        slo.observe(1.0)            # a burst of SLO-violating waits
    p = rng.randint(0, VOCAB, (4,)).astype(np.int32)
    r = eng.submit(p, 3)
    assert r.state == "shed" and r.verdict == "shed" and r.done, \
        (r.state, r.verdict)
    assert r.error and "SLO" in r.error
    assert telemetry.counter("serving.shed").value == shed0 + 1
    assert telemetry.gauge("serving.shed_active").value == 1
    time.sleep(0.35)                 # the window rolls past the burst
    r2 = eng.submit(p, 3)
    assert r2.state == "queued", "shed failed to release (hysteresis)"
    eng.run_until_idle()
    assert r2.tokens == _ref(net, p, 3)
    assert telemetry.gauge("serving.shed_active").value == 0


def check_prefill_error(net):
    rng = np.random.RandomState(2)
    eng = _engine(net)
    fault.configure("serve.prefill.error:1")
    try:
        pa, pb = _prompts(rng, 2)
        ra = eng.submit(pa, 4)
        rb = eng.submit(pb, 4)
        eng.step()   # FIFO: ra hits the armed site, rb prefills fine
        assert ra.state == "failed" and ra.verdict == "prefill_error", \
            (ra.state, ra.verdict)
        assert ra.error and "fault injection" in ra.error
        assert ra.pages is None     # every reserved page released
        eng.alloc.assert_conservation()
        eng.run_until_idle()
        assert rb.tokens == _ref(net, pb, 4)
        _idle_pages_ok(eng)
        assert telemetry.counter("serving.prefill_errors").value >= 1
    finally:
        fault.reset()


def check_drain(net):
    from mxnet_tpu.serving import ServingReplica, EXIT_SERVE_DRAIN
    rng = np.random.RandomState(3)
    eng = _engine(net)
    rep = ServingReplica(eng, replica_id="r0")
    accepted = [rep.submit(p, 5) for p in _prompts(rng, 4)]  # 3 slots+1q
    rep.step()
    eng.start_drain()
    refused = eng.submit(rng.randint(0, VOCAB, (4,)).astype(np.int32), 3)
    assert refused.state == "shed" and refused.verdict == "draining"
    # infeasibility outranks the drain refusal: an impossible request
    # must still get the terminal ValueError, never a retryable verdict
    try:
        eng.submit(np.zeros(16, np.int32), 32)
        raise AssertionError("infeasible request accepted while draining")
    except ValueError as e:
        assert "at most" in str(e)
    rc = rep.drain()
    assert rc == EXIT_SERVE_DRAIN == 80
    # zero dropped ACCEPTED requests: queued-but-unadmitted ones finish too
    assert all(r.verdict == "completed" and len(r.tokens) == 5
               for r in accepted)
    _idle_pages_ok(eng)
    assert not rep.alive
    hb = rep.health()
    assert hb["engine"]["draining"] and hb["engine"]["occupancy"] == 0


def section_lifecycle():
    net = _net()
    check_deadline_verdicts(net)
    check_shed_hysteresis(net)
    check_prefill_error(net)
    check_drain(net)
    print("SERVING_LIFECYCLE_OK")
    return net


# -- router: failover, at-most-once, AOT-warm replacement -------------------

def section_router(net=None):
    from mxnet_tpu.serving import Router, ServingReplica
    net = net or _net()
    rng = np.random.RandomState(4)
    prompts = _prompts(rng, 6)
    news = [int(rng.randint(3, 8)) for _ in prompts]
    refs = [_ref(net, p, n) for p, n in zip(prompts, news)]

    journal = os.path.join(tempfile.mkdtemp(prefix="surv-journal-"),
                           "journal.jsonl")
    spawn_compiles = []

    def spawn():
        c0 = profiler.step_stats()["compile_count"]
        rep = ServingReplica(_engine(net), replica_id="replacement")
        spawn_compiles.append(profiler.step_stats()["compile_count"] - c0)
        return rep

    reps = [ServingReplica(_engine(net), replica_id="a"),
            ServingReplica(_engine(net), replica_id="b")]
    rt = Router(reps, spawn=spawn, max_retries=2, journal_path=journal)
    rrs = [rt.submit(p, n) for p, n in zip(prompts, news)]
    assert all(rr.state == "accepted" for rr in rrs)
    # every request is also a STREAM: a client polls it through the
    # router after each step, by absolute cursor
    streams = {rr.rid: [] for rr in rrs}

    def step_and_poll():
        rt.step()
        for rr in rrs:
            got = streams[rr.rid]
            got += rt.poll(rr.rid, cursor=len(got))["tokens"]

    for _ in range(2):
        step_and_poll()
    completed_before = {rr.rid for rr in rrs if rr.state == "completed"}
    # what each replica holds in flight, and how far its streams have
    # been delivered, BEFORE the killing step
    inflight = {id(r): {rr.rid for rr in rrs
                        if rr.state == "accepted" and rr._home is r}
                for r in reps}
    cursor_at_kill = {rid: len(got) for rid, got in streams.items()}
    fault.configure("serve.replica.lost:1")
    try:
        while not rt.idle:
            step_and_poll()
    finally:
        fault.reset()
    assert rt.failovers == 1, rt.failovers
    assert telemetry.counter("router.replacements").value >= 1
    # the dead replica was pruned AND its watchdog lease released — an
    # abandoned lease would age into a process-wide exit-75 kill
    from mxnet_tpu import watchdog
    dead = [r for r in reps if not r.alive]
    assert len(dead) == 1 and dead[0] not in rt._replicas
    assert dead[0].engine._lease not in watchdog.snapshot()["leases"]
    # THE contract: every accepted request completes exactly once with
    # bit-identical greedy tokens, replica death notwithstanding
    for rr, ref in zip(rrs, refs):
        assert rr.state == "completed", (rr.rid, rr.state, rr.verdict)
        assert rr.tokens == ref, (rr.rid, rr.tokens, ref)
    # at-most-once: pre-death completions were never re-executed
    for rr in rrs:
        if rr.rid in completed_before:
            assert rr.retries == 0
    # the journal is the audit record: exactly one completion per rid
    with open(journal) as f:
        lines = [json.loads(ln) for ln in f]
    completes = [ln["rid"] for ln in lines if ln["event"] == "complete"]
    assert sorted(completes) == sorted(rr.rid for rr in rrs), completes
    retried = {ln["rid"] for ln in lines if ln["event"] == "retry"}
    assert retried, "the failover re-placed nothing?"
    # verdicts, not just totals: the retried set IS what the victim
    # held in flight, and nothing failed
    assert retried == inflight[id(dead[0])], (retried, inflight)
    assert {rr.rid for rr in rrs if rr.retries > 0} == retried
    assert not [rr for rr in rrs if rr.state == "failed"]
    # the kill landed mid-stream, and the cursors stayed valid across
    # it: the survivor's re-decode is bit-identical, so every polled
    # stream assembles to its reference with no gap and no duplicate
    assert any(cursor_at_kill[rid] > 0 for rid in retried), \
        cursor_at_kill
    for rr, ref in zip(rrs, refs):
        got = streams[rr.rid]
        got += rt.poll(rr.rid, cursor=len(got))["tokens"]
        assert got == ref, (rr.rid, got, ref)
        assert rt.poll(rr.rid, cursor=len(got))["more"] is False
    # replacement came up AOT-warm: 0 foreground compiles (memo tier)
    assert spawn_compiles == [0], spawn_compiles
    for rep in rt._replicas:
        if rep.alive:
            _idle_pages_ok(rep.engine)
    print("SERVING_ROUTER_OK")


# -- live weight hot-swap ---------------------------------------------------

def _publish(mgr, net, epoch, perturb=None):
    """Trainer-side publication: arg params by name, manifest last.
    ``perturb`` (a seed) adds per-element relative noise — a UNIFORM
    scale would be argmax-invariant through LayerNorm + the tied head,
    making "the swap took effect" vacuous."""
    args = {}
    prng = None if perturb is None else np.random.RandomState(perturb)
    for p in net.collect_params().values():
        d = p.data()
        if prng is not None:
            arr = d.asnumpy()
            d = mx.nd.array(arr * (1.0 + 0.5 * prng.standard_normal(
                arr.shape).astype(arr.dtype)))
        args[p.name] = d
    mgr.save(epoch, args, {}, mode="sync")


def section_swap(net=None):
    from mxnet_tpu.checkpoint import CheckpointManager
    from mxnet_tpu.serving import ServingReplica, CheckpointSubscriber
    net = net or _net()
    rng = np.random.RandomState(5)
    prefix = os.path.join(tempfile.mkdtemp(prefix="surv-pub-"), "pub")
    mgr = CheckpointManager(prefix)
    _publish(mgr, net, 1)

    # no-swap reference: a resident decoding with the initial weights
    probe = rng.randint(0, VOCAB, (5,)).astype(np.int32)
    ref_initial = _ref(net, probe, 8)

    sub = CheckpointSubscriber(prefix, net, epoch=1)
    rep = ServingReplica(_engine(net), replica_id="s0", subscriber=sub,
                        swap_poll_steps=1)
    r = rep.submit(probe, 8)
    rep.step()
    rep.step()
    # identical-weights publication mid-decode: the swap must be
    # BIT-invisible to the resident
    _publish(mgr, net, 2)
    while not r.done:
        rep.step()
    assert rep.engine.swaps == 1 and sub.applied_epoch == 2
    assert r.tokens == ref_initial, "identical-weights swap perturbed " \
        "a resident's tokens"

    # a REAL weight change: the next request decodes under epoch 3
    _publish(mgr, net, 3, perturb=3)
    r2 = rep.submit(probe, 8)
    while not r2.done:
        rep.step()
    assert sub.applied_epoch == 3 and rep.engine.swaps == 2
    # net now holds epoch-3 weights (load_params set them): the dense
    # reference must agree with what the paged engine served
    ref_ep3 = _ref(net, probe, 8)
    assert r2.tokens == ref_ep3
    assert r2.tokens != ref_initial, \
        "weight change did not take effect (test is vacuous)"

    # torn publication: canary catches the poisoned tree, ROLLS BACK,
    # and the replica keeps serving epoch 3
    rb0 = telemetry.counter("serving.swap_rollbacks").value
    _publish(mgr, net, 4, perturb=4)
    fault.configure("serve.swap.torn:1")
    try:
        r3 = rep.submit(probe, 8)
        while not r3.done:
            rep.step()
    finally:
        fault.reset()
    assert telemetry.counter("serving.swap_rollbacks").value == rb0 + 1
    assert sub.applied_epoch == 3 and sub.seen_epoch == 4
    assert rep.engine.swaps == 2, "torn swap counted as installed"
    assert r3.tokens == ref_ep3, "rollback did not restore weights"
    # the NET rolled back too: load_params mutates it in place, and a
    # torn epoch left in the net would resurface canary-free through
    # the next decode_params / replacement engine built on it
    assert _ref(net, probe, 8) == ref_ep3, \
        "net still holds the torn epoch after rollback"
    assert all(np.isfinite(t) for t in r3.tokens)
    rep.engine.alloc.assert_conservation()

    # ISSUE 15: a SUCCESSFUL swap must evict the prefix cache — its
    # pages hold K/V computed under the old weights, and a post-swap
    # hit would splice stale activations into a new-weights decode.
    # probe2 is >= one full page, so its prefix caches.
    probe2 = rng.randint(0, VOCAB, (10,)).astype(np.int32)
    assert rep.engine.generate([probe2], 6)[0] == _ref(net, probe2, 6)
    assert rep.engine._prefix.cached_pages >= 1
    _publish(mgr, net, 5, perturb=5)
    r5 = rep.submit(probe2, 6)
    while not r5.done:
        rep.step()
    assert sub.applied_epoch == 5
    ref5 = _ref(net, probe2, 6)          # net now holds epoch 5
    assert r5.tokens == ref5, \
        "post-swap decode served the prefix cache's stale pre-swap K/V"
    # (and the rolled-back torn swap above did NOT evict: the cache
    # stays valid for the weights actually serving)
    print("SERVING_SWAP_OK")


# -- per-request determinism law (ISSUE 15) --------------------------------

def section_sampling(net=None):
    """The per-request determinism law: same (seed, sampling params,
    prompt) -> same tokens, regardless of batch composition, across a
    join/leave, and across a router failover re-decode.  Greedy
    requests in a sampled batch still match the dense reference."""
    from mxnet_tpu.serving import Router, SamplingParams, ServingReplica
    net = net or _net()
    rng = np.random.RandomState(11)
    prompts = _prompts(rng, 5)
    samps = [SamplingParams(temperature=0.8, top_k=24, seed=100 + i)
             for i in range(3)] + [None,
                                   SamplingParams(temperature=0.6,
                                                  top_p=0.9, seed=55)]
    # solo references: each request decoded ALONE (occupancy 1)
    solo = _engine(net)
    refs = []
    for p, s in zip(prompts, samps):
        refs.append(solo.generate([p], 6, sampling=s)[0])

    # (a) different batch composition + join/leave churn: all five
    # resident together, joining over successive steps
    churn = _engine(net)
    handles = []
    for i, (p, s) in enumerate(zip(prompts, samps)):
        handles.append(churn.submit(p, 6, sampling=s))
        churn.step()                   # staggered joins; finishers leave
    churn.run_until_idle()
    for h, ref in zip(handles, refs):
        assert h.tokens == ref, (h.tokens, ref)
    # the greedy request equals the dense reference too
    assert handles[3].tokens == _ref(net, prompts[3], 6)
    _idle_pages_ok(churn)

    # (b) failover re-decode: a replica dies mid-decode; the survivor
    # re-decodes the victims BIT-identically (the at-most-once journal
    # stays sound for sampled requests exactly as for greedy)
    reps = [ServingReplica(_engine(net), replica_id="sa"),
            ServingReplica(_engine(net), replica_id="sb")]
    rt = Router(reps, max_retries=2)
    rrs = [rt.submit(p, 6, sampling=s)
           for p, s in zip(prompts, samps)]
    rt.step()
    fault.configure("serve.replica.lost:1")
    try:
        rt.run_until_idle()
    finally:
        fault.reset()
    assert rt.failovers == 1
    for rr, ref in zip(rrs, refs):
        assert rr.state == "completed", (rr.rid, rr.state)
        assert rr.tokens == ref, (rr.rid, rr.tokens, ref)
    assert telemetry.counter("serving.sampling.requests").value > 0
    # sanity: sampling actually samples (a hot temperature diverges
    # from greedy for at least one request — not vacuous)
    greedy_refs = [_ref(net, p, 6) for p in prompts[:3]]
    assert any(refs[i] != greedy_refs[i] for i in range(3)), \
        "sampled tokens identical to greedy — sampling is vacuous"
    print("SERVING_SAMPLING_OK")
    return net


# -- speculative decoding under churn/swap/failover (ISSUE 16) --------------

def section_spec(net=None):
    """The spec-decode determinism laws under survivability churn: a
    spec-on engine's greedy stream is the dense chain whatever the
    batch composition; SAMPLED spec streams reproduce for a fixed spec
    config across solo decode, join/leave churn, a mid-decode weight
    hot-swap (identical weights -> bit-invisible), and a router
    failover re-decode (spec-on sampled streams are pinned to
    THEMSELVES — only greedy is bit-pinned to spec-off); speculative
    page marks never survive a step, an idle engine, or a drain."""
    from mxnet_tpu.serving import (EXIT_SERVE_DRAIN, Router,
                                   SamplingParams, ServingReplica)
    net = net or _net()
    rng = np.random.RandomState(21)
    K = 3
    motif = rng.randint(0, VOCAB, (3,)).astype(np.int32)
    prompts = [np.resize(motif, 12),
               rng.randint(0, VOCAB, (5,)).astype(np.int32),
               np.resize(motif, 7),
               rng.randint(0, VOCAB, (9,)).astype(np.int32)]
    samps = [None,
             SamplingParams(temperature=0.8, top_k=24, seed=201),
             SamplingParams(temperature=0.7, top_p=0.9, seed=202),
             None]
    solo = _engine(net, spec_k=K)
    refs = [solo.generate([p], 6, sampling=sp)[0]
            for p, sp in zip(prompts, samps)]
    _idle_pages_ok(solo)
    assert solo.alloc.speculative_pages == 0
    # greedy members ARE the dense chain, drafts notwithstanding
    for i in (0, 3):
        assert refs[i] == _ref(net, prompts[i], 6), i
    # sampling actually sampled (non-vacuous law)
    assert any(refs[i] != _ref(net, prompts[i], 6) for i in (1, 2)), \
        "sampled spec tokens identical to greedy — sampling is vacuous"

    # (a) join/leave churn: staggered joins, same spec config
    acc0 = telemetry.counter("serving.spec.accepted").value
    churn = _engine(net, spec_k=K)
    handles = []
    for p, sp in zip(prompts, samps):
        handles.append(churn.submit(p, 6, sampling=sp))
        churn.step()
    churn.run_until_idle()
    for h, ref in zip(handles, refs):
        assert h.tokens == ref, (h.tokens, ref)
    assert telemetry.counter("serving.spec.accepted").value > acc0, \
        "nothing accepted across the churn run — spec is vacuous"
    _idle_pages_ok(churn)
    assert churn.alloc.speculative_pages == 0

    # (b) identical-weights hot-swap mid-decode: bit-invisible to a
    # speculative resident (greedy AND sampled)
    sw = _engine(net, spec_k=K)
    r0 = sw.submit(prompts[0], 6)
    r1 = sw.submit(prompts[1], 6, sampling=samps[1])
    sw.step()
    sw.swap_params(sw.params_from_net(net), epoch=2)
    sw.run_until_idle()
    assert sw.swaps == 1
    assert r0.tokens == refs[0] and r1.tokens == refs[1], \
        "identical-weights swap perturbed a speculative resident"

    # (c) failover re-decode: a replica dies mid-decode, the survivor
    # re-decodes victims bit-identically — sampled and greedy alike
    reps = [ServingReplica(_engine(net, spec_k=K), replica_id="ka"),
            ServingReplica(_engine(net, spec_k=K), replica_id="kb")]
    rt = Router(reps, max_retries=2)
    rrs = [rt.submit(p, 6, sampling=sp)
           for p, sp in zip(prompts, samps)]
    rt.step()
    fault.configure("serve.replica.lost:1")
    try:
        rt.run_until_idle()
    finally:
        fault.reset()
    assert rt.failovers == 1
    for rr, ref in zip(rrs, refs):
        assert rr.state == "completed", (rr.rid, rr.state)
        assert rr.tokens == ref, (rr.rid, rr.tokens, ref)
    for rep in reps:
        if rep.alive:
            _idle_pages_ok(rep.engine)
            assert rep.engine.alloc.speculative_pages == 0

    # (d) graceful drain of a speculative replica: every accepted
    # request completes, zero speculative marks left behind
    rep = ServingReplica(_engine(net, spec_k=K), replica_id="kd")
    hs = [rep.submit(p, 5) for p in prompts[:3]]
    rep.step()
    assert rep.drain() == EXIT_SERVE_DRAIN
    assert all(h.verdict == "completed" and len(h.tokens) == 5
               for h in hs)
    assert rep.engine.alloc.speculative_pages == 0
    _idle_pages_ok(rep.engine)
    print("SERVING_SPEC_OK")
    return net


# -- prefix-cache eviction drill (ISSUE 15) --------------------------------

def section_prefix_evict(net=None):
    """``serve.prefix.evict`` force-drops the cached prefix index
    between steps: the victim request falls back to a FULL prefill with
    correct tokens — the cache is a capacity optimization, never a
    correctness dependency."""
    net = net or _net()
    rng = np.random.RandomState(12)
    sysp = rng.randint(0, VOCAB, (8,)).astype(np.int32)   # one full page
    pa = np.concatenate([sysp, rng.randint(0, VOCAB, (3,))
                         .astype(np.int32)])
    pb = np.concatenate([sysp, rng.randint(0, VOCAB, (5,))
                         .astype(np.int32)])
    eng = _engine(net)
    assert eng._prefix is not None, "prefix cache should default ON"
    ra = eng.generate([pa], 4)[0]
    assert ra == _ref(net, pa, 4)
    assert eng._prefix.cached_pages >= 1
    hits0 = telemetry.counter("serving.prefix.hits").value
    fault.configure("serve.prefix.evict:1")
    try:
        rb = eng.submit(pb, 4)
        eng.run_until_idle()
        fired = fault.fire_count("serve.prefix.evict")
    finally:
        fault.reset()
    assert fired == 1, fired
    assert telemetry.counter("serving.prefix.evictions").value >= 1
    # the victim MISSED (the index was dropped before its admission)
    # and fell back to a full prefill with correct tokens
    assert rb.prefix_len == 0 and rb.shared_count == 0
    assert telemetry.counter("serving.prefix.hits").value == hits0
    assert rb.tokens == _ref(net, pb, 4)
    _idle_pages_ok(eng)
    # and the cache re-warms: the same prompt now hits
    rc = eng.submit(pb, 4)
    eng.run_until_idle()
    assert rc.prefix_len > 0 and rc.tokens == rb.tokens
    _idle_pages_ok(eng)
    print("SERVING_PREFIX_EVICT_OK")
    return net


# -- request-scope tracing laws (ISSUE 13) ---------------------------------

def _token_event_count(evs):
    """The token-accounting law's left-hand side — the one shared
    definition (telemetry owns the event schema)."""
    return telemetry.count_token_events(evs)


def _finals(evs, trace):
    return [e for e in evs
            if e["event"] == "verdict" and e["trace"] == trace
            and e["args"].get("final")]


def section_trace():
    """The lifecycle laws, against real engines (test-pinned contract
    of OBSERVABILITY.md §12):

    - every submitted request reaches EXACTLY ONE terminal verdict
      span, whatever its fate (completed / shed / expired in queue /
      expired mid-decode / prefill error / infeasible);
    - shed and expired requests still close their trace;
    - the trace id survives router failover: same id on both replicas,
      a ``retry`` span linking victim -> survivor;
    - traced token count == the serving.tokens counter delta,
      bit-exactly;
    - serve_report reconstructs all of it from a REAL artifact tree
      (stream + router journal) including the blame section and a
      loadable merged chrome trace.
    """
    import serve_report   # tools/perf_probe (path set in __main__)
    from mxnet_tpu.serving import Router, ServingReplica, SLOController

    net = _net()
    rng = np.random.RandomState(7)
    tree = tempfile.mkdtemp(prefix="surv-trace-")
    tdir = os.path.join(tree, "telemetry")
    os.makedirs(tdir)
    telemetry.reset()
    telemetry.start_emitter(os.path.join(tdir, "stream-slot0.jsonl"),
                            interval=0.2)

    # --- engine-level verdict variety (direct submits own their trace)
    eng = _engine(net)
    tok0 = telemetry.counter("serving.tokens").value
    expired_q = eng.submit(rng.randint(0, VOCAB, (4,)).astype(np.int32),
                           3, deadline_s=1e-5)
    time.sleep(0.002)
    fault.configure("serve.prefill.error:1")
    try:
        # FIFO: the doomed request expires in the sweep, then `pe` is
        # the queue head and eats the armed prefill fault
        pe = eng.submit(rng.randint(0, VOCAB, (4,)).astype(np.int32), 3)
        eng.step()
    finally:
        fault.reset()
    assert expired_q.verdict == "expired_queue"
    assert pe.verdict == "prefill_error"
    ok = eng.submit(rng.randint(0, VOCAB, (5,)).astype(np.int32), 4)
    mid = eng.submit(rng.randint(0, VOCAB, (5,)).astype(np.int32), 10,
                     deadline_s=60.0)
    eng.step()
    mid.deadline_t = time.perf_counter() - 1.0
    eng.run_until_idle()
    assert mid.verdict == "expired_decode"
    assert ok.verdict == "completed"
    try:
        eng.submit(np.zeros(16, np.int32), 32)
        raise AssertionError("infeasible request accepted")
    except ValueError:
        pass
    slo = SLOController(target_p99_s=0.01, min_samples=2)
    eng_slo = _engine(net, slo=slo)
    for _ in range(3):
        slo.observe(1.0)
    shed = eng_slo.submit(rng.randint(0, VOCAB, (4,)).astype(np.int32),
                          3)
    assert shed.verdict == "shed"

    evs = telemetry.request_events()
    # law: exactly one FINAL verdict per trace, and it is the last
    # per-trace event — for EVERY fate above (the infeasible submit
    # minted a trace too, closed before the raise)
    traces = {e["trace"] for e in evs if e["trace"]}
    for tr in traces:
        finals = _finals(evs, tr)
        assert len(finals) == 1, (tr, finals)
        per_trace = [e for e in evs if e["trace"] == tr]
        assert per_trace[-1]["event"] == "verdict", per_trace[-1]
    closed = {_finals(evs, e["trace"])[0]["args"]["verdict"]
              for e in evs if e["trace"]}
    for v in ("completed", "expired_queue", "expired_decode",
              "prefill_error", "rejected_infeasible", "shed"):
        assert v in closed, (v, closed)
    # law: traced tokens == serving.tokens delta, bit-exactly
    assert _token_event_count(evs) == \
        telemetry.counter("serving.tokens").value - tok0

    # --- failover: trace id survives onto the survivor ---------------
    tok1 = telemetry.counter("serving.tokens").value
    seen1 = len(telemetry.request_events())
    reps = [ServingReplica(_engine(net), replica_id="a"),
            ServingReplica(_engine(net), replica_id="b")]
    rt = Router(reps, spawn=lambda: ServingReplica(
        _engine(net), replica_id="c"), max_retries=2,
        journal_path=os.path.join(tdir, "router-journal-slot0.jsonl"))
    rrs = [rt.submit(p, 5) for p in _prompts(rng, 6)]
    rt.step()
    fault.configure("serve.replica.lost:1")
    try:
        rt.run_until_idle()
    finally:
        fault.reset()
    assert rt.failovers == 1
    assert all(rr.state == "completed" for rr in rrs)
    evs = telemetry.request_events()[seen1:]
    retried = [e for e in evs if e["event"] == "retry"]
    assert retried, "the failover traced no retry span"
    victim = retried[0]["args"]["from"]
    for e in retried:
        tr = e["trace"]
        # same id on BOTH replicas: victim placement before the retry,
        # survivor placement after, one final verdict at the end
        hops = [x["args"]["replica"] for x in evs
                if x["trace"] == tr and x["event"] in ("place", "admit")]
        assert victim in hops, (tr, hops)
        assert hops[-1] != victim, (tr, hops)
        assert len(_finals(evs, tr)) == 1
        assert _finals(evs, tr)[0]["args"]["verdict"] == "completed"
    # router-minted traces: engine-level verdicts along the way are
    # non-final hops; exactly one FINAL per trace overall
    for rr in rrs:
        assert len(_finals(evs, rr.trace)) == 1, rr.trace
    assert _token_event_count(evs) == \
        telemetry.counter("serving.tokens").value - tok1

    # --- the fleet report reconstructs it from the real artifacts ----
    telemetry.stop_emitter()
    rep = serve_report.analyze(tree)
    assert rep["lifecycle"]["ok"], rep["lifecycle"]
    assert rep["linked_arcs"] == len(retried) == len(rep["arcs"])
    assert any(b["replica"] == victim for b in rep["blame"]), \
        rep["blame"]
    assert rep["accounting"]["tokens_match"], rep["accounting"]
    assert rep["accounting"]["goodput_fraction"] is not None
    doc, _t0 = serve_report.merged_trace(rep["data"], rep["requests"])
    path = os.path.join(tree, "trace.json")
    with open(path, "w") as f:
        json.dump(doc, f)
    loaded = json.load(open(path))
    assert loaded["traceEvents"], "merged trace empty"
    assert any(e["ph"] == "s" for e in loaded["traceEvents"]), \
        "no failover flow arrows in the merged trace"
    print("SERVING_TRACE_OK")


# -- stall: the watchdog owns this process's death --------------------------

def section_stall():
    """Caller sets MXTPU_STALL_TIMEOUT (+ postmortem dir) and expects
    this process to die 75 with a serving snapshot in the postmortem —
    anything printed after the loop means detection FAILED.  The stall
    is armed AFTER one clean step: the realistic wedge is a decode that
    hangs mid-serving, past the startup-grace window (a wedged FIRST
    dispatch is covered too, on the same lease, but only after the
    longer compile-sized grace)."""
    net = _net()
    eng = _engine(net)
    eng.submit(np.arange(6, dtype=np.int32), 20)
    eng.step()
    fault.configure("serve.decode.stall:1")
    for _ in range(1000):
        eng.step()
    print("SERVING_STALL_NOT_DETECTED")


# -- e2e: the combined slow drill ------------------------------------------

def section_e2e():
    from mxnet_tpu.checkpoint import CheckpointManager
    from mxnet_tpu.serving import (Router, ServingReplica,
                                   CheckpointSubscriber, SLOController)
    net = _net()
    rng = np.random.RandomState(6)

    # phase 1+2: failover under a decode-stall hiccup — zero dropped
    # accepted requests, bit-identical vs the unfaulted dense reference
    prompts = _prompts(rng, 10)
    news = [int(rng.randint(4, 10)) for _ in prompts]
    refs = [_ref(net, p, n) for p, n in zip(prompts, news)]
    journal = os.path.join(tempfile.mkdtemp(prefix="surv-e2e-"),
                           "journal.jsonl")
    spawn_compiles = []

    def spawn():
        c0 = profiler.step_stats()["compile_count"]
        rep = ServingReplica(_engine(net), replica_id="replacement")
        spawn_compiles.append(profiler.step_stats()["compile_count"] - c0)
        return rep

    rt = Router([ServingReplica(_engine(net), replica_id="a"),
                 ServingReplica(_engine(net), replica_id="b")],
                spawn=spawn, max_retries=2, journal_path=journal)
    rrs = [rt.submit(p, n) for p, n in zip(prompts, news)]
    rt.step()
    os.environ["MXTPU_FAULT_STALL_SECS"] = "0.2"   # bounded hiccup
    fault.configure("serve.decode.stall:1;serve.replica.lost:1")
    try:
        rt.run_until_idle()
        stalled = fault.fire_count("serve.decode.stall")
        lost = fault.fire_count("serve.replica.lost")
    finally:
        fault.reset()
        os.environ.pop("MXTPU_FAULT_STALL_SECS", None)
    assert stalled == 1 and lost == 1, (stalled, lost)
    assert rt.failovers == 1
    for rr, ref in zip(rrs, refs):
        assert rr.state == "completed" and rr.tokens == ref, \
            (rr.rid, rr.state, rr.verdict)
    with open(journal) as f:
        lines = [json.loads(ln) for ln in f]
    completes = [ln["rid"] for ln in lines if ln["event"] == "complete"]
    assert sorted(completes) == sorted(rr.rid for rr in rrs)
    assert spawn_compiles == [0], \
        "replacement replica was not AOT-warm: %s" % spawn_compiles
    print("SERVING_E2E_FAILOVER_OK")

    # phase 3: overload → shed instead of unbounded queueing.  One slot,
    # a burst far beyond it, a tight SLO: intake is refused fast, the
    # accepted queue stays bounded, and shed RELEASES once drained.
    slo = SLOController(target_p99_s=0.002, release_frac=0.5,
                        window_s=1.5, min_samples=3)
    eng = _engine(net, num_slots=1, slo=slo)
    shed0 = telemetry.counter("serving.shed").value
    burst = _prompts(rng, 30, lo=3, hi=8)
    handles, max_queue = [], 0
    for i, p in enumerate(burst):
        handles.append(eng.submit(p, 6))
        if i >= 8:
            # arrivals keep outpacing the single slot: the queue head
            # ages past the (tight) SLO and intake must start shedding
            eng.step()
            time.sleep(0.004)
        max_queue = max(max_queue, eng.sched.queued)
    eng.run_until_idle()
    sheds = telemetry.counter("serving.shed").value - shed0
    accepted = [h for h in handles if h.verdict == "completed"]
    shed = [h for h in handles if h.state == "shed"]
    assert sheds > 0 and len(shed) == sheds, (sheds, len(shed))
    assert accepted, "shed everything — overload phase is vacuous"
    assert len(accepted) + len(shed) == len(handles)
    # bounded: the accepted queue-wait p99 cannot run away once intake
    # sheds — every accepted wait is below target + one burst window
    waits = sorted(h.queue_wait_s for h in accepted)
    p99 = waits[min(len(waits) - 1, int(0.99 * (len(waits) - 1) + 1))]
    assert p99 < 1.0, \
        "queue-wait p99 %.3fs unbounded under shed" % p99
    for h in accepted:
        i = handles.index(h)
        assert h.tokens == _ref(net, burst[i], 6)
    # hysteresis releases once the window rolls past the burst
    time.sleep(slo.window_s + 0.1)
    assert not slo.should_shed(eng.sched.oldest_queue_wait)
    print("SERVING_E2E_SHED_OK")

    # phase 4: mid-run hot-swap + torn rollback on a live replica
    prefix = os.path.join(tempfile.mkdtemp(prefix="surv-e2e-pub-"),
                          "pub")
    mgr = CheckpointManager(prefix)
    _publish(mgr, net, 1)
    sub = CheckpointSubscriber(prefix, net, epoch=1)
    rep = ServingReplica(_engine(net), replica_id="sw",
                        subscriber=sub, swap_poll_steps=1)
    probe = burst[0]
    ref_old = _ref(net, probe, 6)
    resident = rep.submit(probe, 12)
    rep.step()
    _publish(mgr, net, 2, perturb=2)
    while not resident.done:
        rep.step()
    assert resident.verdict == "completed"
    assert sub.applied_epoch == 2
    ref_new = _ref(net, probe, 6)
    assert rep.engine.generate([probe], 6) == [ref_new]
    fault.configure("serve.swap.torn:1")
    _publish(mgr, net, 3, perturb=3)
    try:
        r = rep.submit(probe, 6)
        while not r.done:
            rep.step()
    finally:
        fault.reset()
    assert sub.applied_epoch == 2 and r.tokens == ref_new
    assert ref_new != ref_old, "swap phase is vacuous"
    print("SERVING_E2E_SWAP_OK")


def main(section):
    if section in ("lifecycle", "fast"):
        net = section_lifecycle()
    else:
        net = None
    if section in ("router", "fast"):
        section_router(net)
    if section in ("swap", "fast"):
        section_swap(net)
    if section in ("sampling", "fast"):
        net = section_sampling(net)
    if section in ("spec", "fast"):
        net = section_spec(net)
    if section in ("prefix", "fast"):
        section_prefix_evict(net)
    if section == "trace":
        section_trace()
    if section == "stall":
        section_stall()
    if section == "e2e":
        section_e2e()


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "tools",
        "perf_probe"))
    main(sys.argv[1] if len(sys.argv) > 1 else "fast")
