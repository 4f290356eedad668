"""``ServingEngine(decode_ahead=n)``: a step sends the next n decode
dispatches, and an admission's prefill, before it waits for the tokens
they follow.  The tokens (and logits) are those of the engine as it
was, for a model with paged K/V pools and for one with latent pages and
per-slot state; what may move is WHEN a token shows, never which.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo import gpt, ling3
from mxnet_tpu.serving import ServingEngine
from mxnet_tpu.serving.scheduler import SamplingParams

PLAN = [(5, 4), (17, 1), (9, 12), (30, 3), (8, 9), (12, 2), (3, 7),
        (21, 6)]


@pytest.fixture(scope="module")
def nets():
    g = gpt.gpt2_tiny()
    g.initialize(mx.init.Xavier())
    # "gpt2-shared-prefix": the same net, served with the prefix cache
    # on and prompts that share pages (what gpt2m-serve-backlog's engine
    # is built with, and the chat cell's traffic hits)
    return {"gpt2": g, "gpt2-shared-prefix": g,
            "hybrid": ling3.ling3_tiny().init_seeded(7)}


def prompt(n, seed, shared=False):
    """``n`` seeded tokens; ``shared``: every prompt over 16 tokens opens
    on the same two pages of 8."""
    p = np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)
    if shared and n > 16:
        p[:16] = prompt(16, 7)
    return p


def engine(net, ahead, **kw):
    args = dict(num_slots=3, page_size=8, num_pages=64, max_prefill_len=40,
                max_seq_len=64, record_logits=True, prefix_cache=False,
                decode_ahead=ahead)
    args.update(kw)
    return ServingEngine(net, **args)


def serve(net, ahead, sampled=False, between=None, shared=False, **kw):
    """PLAN through three slots (every slot reused), one request arriving
    late; ``between(eng, reqs, step)`` runs in the gap after each step.
    ``shared``: the prefix cache on, over prompts that share pages."""
    eng = engine(net, ahead, prefix_cache=shared, **kw)
    reqs = [eng.submit(prompt(n, 100 + i, shared), new, trace="t%d" % i,
                       sampling=SamplingParams(temperature=0.8, top_k=20,
                                               seed=i) if sampled else None)
            for i, (n, new) in enumerate(PLAN)]
    made = steps = 0
    while not eng.sched.idle:
        made += eng.step()
        steps += 1
        if steps == 3:
            reqs.append(eng.submit(prompt(11, 999), 5, trace="late"))
        if between is not None:
            between(eng, reqs, steps)
        assert steps < 200
    assert made == sum(len(r.tokens) for r in reqs)
    assert eng.sched.occupancy == 0
    if not shared:      # a cached prefix keeps its pages
        assert eng.alloc.used_pages == 0
    return eng, reqs


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("ahead", [1, 2])
@pytest.mark.parametrize("model", ["gpt2", "gpt2-shared-prefix", "hybrid"])
def test_decode_ahead_gives_the_same_tokens_and_logits(nets, model, ahead,
                                                       sampled):
    shared = model == "gpt2-shared-prefix"
    _, want = serve(nets[model], 0, sampled, shared=shared)
    eng, got = serve(nets[model], ahead, sampled, shared=shared)
    for w, g in zip(want, got):
        assert g.done and len(g.tokens) == g.max_new
        assert g.tokens == w.tokens
        assert np.array_equal(np.stack(g.logits_trace),
                              np.stack(w.logits_trace))
        assert g.prefix_len == w.prefix_len
    if shared:          # the cache was hit, not just on
        assert sum(g.prefix_len for g in got) >= 32
    # every dispatch was read by somebody: a request whose last token
    # was on its way sat the ones sent ahead out
    assert not eng._unread


@pytest.mark.parametrize("ahead", [1, 2])
@pytest.mark.parametrize("model", ["gpt2", "hybrid"])
def test_decode_ahead_stops_at_eos_like_the_engine_as_it_was(nets, model,
                                                             ahead):
    _, plain = serve(nets[model], 0)
    mid = [t for r in plain for t in r.tokens[1:-1]]
    eos = mid[len(mid) // 2]
    _, want = serve(nets[model], 0, eos_id=eos)
    _, got = serve(nets[model], ahead, eos_id=eos)
    assert any(len(r.tokens) < r.max_new for r in want)
    assert [r.tokens for r in got] == [r.tokens for r in want]


@pytest.mark.parametrize("ahead", [1, 2])
@pytest.mark.parametrize("model", ["gpt2", "hybrid"])
def test_decode_ahead_passes_over_a_cancelled_request(nets, model, ahead):
    """A request cancelled in the gap was in the dispatches already sent:
    their tokens for it are dropped, its slot's next tenant starts clean,
    and nobody else's tokens move."""
    def cancel(eng, reqs, step):
        if step == 4:
            assert sum(any(r.trace == "t2" for r in d["reqs"])
                       for d in eng._unread) == eng._decode_ahead
            eng.cancel("t2")

    _, want = serve(nets[model], 0, between=cancel)
    _, got = serve(nets[model], ahead, between=cancel)
    assert got[2].verdict == "cancelled"
    assert 0 < len(got[2].tokens) < got[2].max_new
    assert [r.tokens for r in got] == [r.tokens for r in want]


def unread(eng):
    return [("decode" if "nxt" in d else "prefill",
             [r.rid for r in d["reqs"]]) for d in eng._unread]


def test_a_step_sends_its_successors_before_it_reads_its_tokens(nets):
    eng = engine(nets["hybrid"], 1)
    a = eng.submit(prompt(6, 1), 3)
    b = eng.submit(prompt(9, 2), 6)
    # both prefills and two decodes go out; the first decode is read,
    # and the prefills with it
    assert eng.step() == 4
    assert (len(a.tokens), len(b.tokens)) == (2, 2)
    assert unread(eng) == [("decode", [a.rid, b.rid])]
    eng.step()          # a's third token is its last: it sits 3 out
    assert a.done and len(a.tokens) == 3
    assert unread(eng) == [("decode", [b.rid])]
    assert eng.decode_steps == 2
    # an admission's prefill queues behind the decode already sent and
    # is never waited for: its first token is read with the tokens of
    # the decode that follows it
    c = eng.submit(prompt(5, 3), 2)
    assert eng.step() == 1
    assert unread(eng) == [("prefill", [c.rid]), ("decode", [c.rid, b.rid])]
    assert not c.tokens and eng.prefills == 2
    assert eng.step() == 3
    assert c.done and len(c.tokens) == 2 and eng.prefills == 3
    eng.run_until_idle()
    assert len(b.tokens) == 6 and not eng._unread
    assert eng.decode_steps == 5


def test_two_ahead_keeps_two_decodes_unread(nets):
    eng = engine(nets["gpt2"], 2)
    a = eng.submit(prompt(6, 1), 8)
    eng.step()
    assert len(a.tokens) == 2
    assert unread(eng) == [("decode", [a.rid])] * 2
    eng.step()
    assert len(a.tokens) == 3 and len(eng._unread) == 2
    eng.run_until_idle()
    assert len(a.tokens) == 8 and not eng._unread
    assert eng.decode_steps == 7


def test_the_engine_as_it_was_sends_nothing_ahead(nets):
    eng = engine(nets["gpt2"], 0)
    eng.submit(prompt(6, 1), 4)
    eng.step()
    assert not eng._unread
    assert "ahead" not in eng._config_hash()
    assert "ahead" in engine(nets["gpt2"], 1)._config_hash()


@pytest.mark.parametrize("kw", [dict(spec_k=2), dict(kv_dtype="int8")],
                         ids=["spec_k", "int8"])
def test_decode_ahead_refuses_what_decides_on_the_host(nets, kw):
    with pytest.raises(ValueError, match="decode_ahead"):
        engine(nets["gpt2"], 1, **kw)
