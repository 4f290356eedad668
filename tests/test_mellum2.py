"""Mellum 2's language model (gluon/model_zoo/mellum2.py) at its tiny
preset on the CPU, against the plain reference
(perfbench/reference/mellum2.py): the engine's CHUNKED prefill and its
decode through pages (the full layer) and rings (the sliding layers),
with a window of 8, pages of 4 and chunks of 12 so that rings wrap and
chunks cross pages; the window's edge; YaRN's attention factor; the
softmax router with no shared expert; the share; and the sigmoid
models' expert layer pinned to what it was.
"""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

from mxnet_tpu import telemetry                              # noqa: E402
from mxnet_tpu.gluon.model_zoo import mellum2 as mel         # noqa: E402
from mxnet_tpu.gluon.model_zoo import decoder_blocks         # noqa: E402
from mxnet_tpu.parallel import moe as pmoe                   # noqa: E402
from mxnet_tpu.serving import ServingEngine                  # noqa: E402
from reference import mellum2 as reference                   # noqa: E402

WINDOW, PAGE, CHUNK = 8, 4, 12
#: (prompt, new tokens): ragged over three chunks, every ring wrapped
#: three times and pages crossed; shorter than the window; one chunk
#: exactly
WORK = [(29, 5), (5, 4), (12, 3)]


@pytest.fixture(scope="module")
def net():
    return mel.mellum2_tiny().init_seeded(2 ** 31 + 9)


def tokens(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(0, vocab, n) \
        .astype(np.int32)


def reference_rows(eng, net, req, **kw):
    """The reference's logits at the positions whose tokens ``req`` got."""
    seq = np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)])[:-1]
    want, _ = reference.forward(
        eng._p, seq, net.cfg, rows=np.arange(req.prompt.size - 1, seq.size),
        **kw)
    return np.asarray(want)


@pytest.fixture(scope="module")
def served(net):
    """Three requests through two slots: a prompt of several chunks
    beside one shorter than the window, then a slot reused."""
    telemetry.reset()
    eng = ServingEngine(net, num_slots=2, page_size=PAGE, num_pages=48,
                        max_prefill_len=CHUNK, max_seq_len=80,
                        record_logits=True, decode_ahead=2)
    eng.shares = []
    note = eng._note_stats

    def noted(program, values):
        note(program, values)
        if program == "decode":
            eng.shares.append(
                telemetry.report()["gauges"]["serving.moe.local_share"])
    eng._note_stats = noted
    reqs = [eng.submit(tokens(n, 100 + n), new) for n, new in WORK]
    eng.run_until_idle()
    return eng, reqs


@pytest.mark.parametrize("i", range(len(WORK)))
def test_engine_agrees_with_the_reference_full_forward(net, served, i):
    eng, reqs = served
    r = reqs[i]
    assert r.done and len(r.tokens) == r.max_new
    got = np.stack(r.logits_trace)
    assert np.abs(got - reference_rows(eng, net, r)).max() < 2e-5
    assert (got.argmax(-1) == np.asarray(r.tokens)).all()


def test_engine_counts_rings_pages_and_window_rows(net, served):
    """One full layer in pages, three sliding ones in rings of 8 rows;
    ``kv.window_rows_read`` counts the ring rows each sliding layer read:
    a decoding slot's ``min(context, 8)``, a chunk's ``min(offset, 8)``."""
    eng, _ = served
    assert eng.kv_bytes_per_token == 2 * 2 * 16 * 4
    assert eng.state_bytes_per_slot == 3 * 2 * WINDOW * 2 * 16 * 4
    assert [a.shape for a in eng._kv[0]] == [(3, WINDOW, 32)] * 2
    assert [a.shape for a in eng._kv[3]] == [(48, PAGE, 32)] * 2
    decode = prefill = 0
    for n, new in WORK:
        decode += np.minimum(np.arange(n + 1, n + new), WINDOW).sum()
        prefill += sum(min(off, WINDOW) for off in range(0, n, CHUNK))
    got = {p: eng.stat_totals[p]["kv.window_rows_read"]
           for p in ("decode", "prefill")}
    assert got == {"decode": 3 * decode, "prefill": 3 * prefill}
    assert telemetry.report()["counters"]["serving.kv.window_rows_read"] \
        == 3 * (decode + prefill)


def test_a_decode_step_holding_every_expert_reads_a_local_share_of_100(
        served):
    """Every choice of every real row falls on a held expert (a decoding
    slot gives 4 layers x 4 choices, an empty slot's row none), so a step
    in which every slot decodes reads ``serving.moe.local_share`` 1."""
    eng, _ = served
    got = eng.stat_totals["decode"]
    assert got["local_assignments"] % (4 * 4) == 0
    assert 0 < got["local_assignments"] < got["assignments"]
    assert got["held_experts"] == 8 * got["expert_layers"]
    assert max(eng.shares) == 1.0 and min(eng.shares) > 0


def test_the_window_sees_seven_back_and_not_eight():
    """Query ``i`` sees key ``i - 7`` and not ``i - 8``: in a chunk
    (``attend_window``, the rows before it from the ring), at decode
    (``attend_ring``) and in the reference's mask.  Every key's value is
    its position's one-hot and every score equal, so a query's output is
    the mean over what it sees."""
    first, t, d = 16, 4, 32
    total = first + t
    q = jnp.zeros((total, 2, d), jnp.float32)
    k = jnp.zeros((total, 1, d), jnp.float32)
    v = jnp.eye(total, d, dtype=jnp.float32)[:, None, :]
    got = jax.jit(decoder_blocks.attend_window, static_argnums=(6,))(
        q[first:], k[first:], v[first:], k[first - WINDOW:first],
        v[first - WINDOW:first], first, WINDOW)[:, 0]
    for i, row in zip(range(first, total), np.asarray(got)):
        assert row[i - 7] == pytest.approx(1 / 8) and row[i - 8] == 0.0
    # decode: position 19's ring holds 12 .. 19 (19 % 8 is its own row)
    ring = np.zeros((1, WINDOW, d), np.float32)
    for p in range(12, 20):
        ring[0, p % WINDOW] = np.asarray(v[p, 0])
    o = jax.jit(lambda *a: decoder_blocks.attend_ring(
        *a, {"num_key_value_heads": 1, "head_dim": d}))(
        q[19:20], jnp.zeros_like(jnp.asarray(ring)), jnp.asarray(ring),
        jnp.asarray([19]), jnp.asarray([True]))
    row = np.asarray(o)[0, 0]
    assert row[12] == pytest.approx(1 / 8) and row[11] == 0.0
    assert bool(reference.visible(19, 12, reference.SLIDING, WINDOW))
    assert not bool(reference.visible(19, 11, reference.SLIDING, WINDOW))
    assert bool(reference.visible(19, 11, reference.FULL, WINDOW))


def test_yarn_attention_factor_scales_a_full_layers_scores(net):
    """At position 0 the rotation is the identity, so a full layer's
    ``q . k`` is ``attention_factor ** 2`` (1.27726 ** 2) times a plain
    layer's, in the program and in the reference; a sliding layer's is
    not scaled."""
    factor = net.cfg["rope_parameters"][mel.FULL]["attention_factor"]
    assert factor == pytest.approx(0.1 * np.log(16) + 1)
    lp = mel.decode_params(net)["layers"][3]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 64)),
                    jnp.float32)
    pos = jnp.zeros(1, jnp.int32)
    plain = dict(net.cfg, rope_parameters={
        mel.FULL: net.cfg["rope_parameters"][mel.SLIDING],
        mel.SLIDING: net.cfg["rope_parameters"][mel.SLIDING]})

    @jax.jit
    def scores(lp, x):
        out = []
        for cfg in (net.cfg, plain):
            for kind in (mel.FULL, mel.SLIDING):
                q, k, _ = mel._qkv(lp, x, pos, kind, cfg)
                out.append(jnp.sum(q[0, 0] * k[0, 0]))
        return out

    full, sliding, plain_full, plain_sliding = (
        float(a) for a in scores(lp, x))
    assert full == pytest.approx(factor ** 2 * plain_full, rel=1e-5)
    assert sliding == pytest.approx(plain_sliding, rel=1e-6)
    y = jnp.ones((1, 2, 16), jnp.float32)
    for kind, want in ((mel.FULL, factor), (mel.SLIDING, 1.0)):
        got = reference._rope(y, pos, net.cfg["rope_parameters"][kind])
        assert np.allclose(np.asarray(got), want, rtol=1e-6)
    # the program's YaRN frequencies are the reference's
    freqs, _ = mel.rope_of(dict(PUBLISHED_CFG), mel.FULL)
    want, _ = reference.inv_freq(
        PUBLISHED_CFG["rope_parameters"][mel.FULL], 128)
    assert np.allclose(np.asarray(freqs), want, rtol=1e-6)


PUBLISHED_CFG = dict(mel.PUBLISHED)


def test_softmax_route_is_top_k_of_softmax_renormalised_with_no_shared_term(
        net):
    lp = mel.decode_params(net)["layers"][0]["moe"]
    assert "sh_gu_w" not in lp and "router_b" not in lp
    x = jnp.asarray(np.random.default_rng(3).normal(size=(10, 64)),
                    jnp.float32)
    experts, weights = pmoe.softmax_topk_route(x, lp["router_w"], 4)
    logits = np.asarray(x, np.float64) @ np.asarray(lp["router_w"],
                                                    np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    top = np.argsort(-p, -1)[:, :4]
    assert (np.sort(np.asarray(experts), -1) == np.sort(top, -1)).all()
    chosen = np.take_along_axis(p, np.asarray(experts), -1)
    assert np.allclose(np.asarray(weights), chosen / chosen.sum(-1,
                                                                keepdims=True),
                       atol=1e-6)
    # the layer: the chosen experts' SwiGLUs weighed, and nothing else
    with jax.default_matmul_precision("highest"):
        y, got, _ = jax.jit(lambda lp, x: decoder_blocks.moe(
            lp, x, net.cfg))(lp, x)
    xs = np.asarray(x, np.float64)
    want = np.zeros_like(xs)
    for j in range(4):
        e = np.asarray(experts)[:, j]
        gu = np.einsum("tc,tcf->tf", xs, np.asarray(lp["gu_w"])[e])
        act = gu[:, :32] / (1 + np.exp(-gu[:, :32])) * gu[:, 32:]
        want += np.asarray(weights)[:, j, None] * np.einsum(
            "tf,tfc->tc", act, np.asarray(lp["down_w"])[e])
    assert np.array_equal(np.asarray(got), np.asarray(experts))
    assert np.abs(np.asarray(y) - want).max() < 1e-5


def test_two_half_shares_add_up_to_the_uncut_layer(net):
    """The partial outputs of two chips holding experts 0-3 and 4-7 sum
    to the uncut reference layer (model-configs guide, section 4); there
    is no shared expert to count once."""
    lp = mel.decode_params(net)["layers"][1]["moe"]
    x = jnp.asarray(np.random.default_rng(2).normal(size=(24, 64)),
                    jnp.float32)

    @jax.jit
    def parts(lp, x):
        with jax.default_matmul_precision("highest"):
            out = [reference.moe(lp, x, net.cfg)[0]]
            for first in (0, 4):
                share = dict(lp, gu_w=lp["gu_w"][first:first + 4],
                             down_w=lp["down_w"][first:first + 4])
                cfg = dict(net.cfg, experts_held=[first, 4])
                out += [decoder_blocks.moe(share, x, cfg)[0],
                        reference.moe(share, x, cfg)[0]]
            return out

    want, y0, ref0, y4, ref4 = (np.asarray(a) for a in parts(lp, x))
    for y, ref_share in ((y0, ref0), (y4, ref4)):
        assert np.abs(y - ref_share).max() < 1e-5
    assert np.abs(y0 + y4 - want).max() < 1e-5


@pytest.mark.parametrize("masked", [False, True])
def test_the_sigmoid_expert_layer_is_the_route_plus_the_shared_term(
        masked):
    """``decoder_blocks.moe`` for a sigmoid config with a shared expert
    (K-EXAONE's tiny preset) traces to the jaxpr of the sigmoid route,
    the held experts and the shared term written out directly."""
    from mxnet_tpu.gluon.model_zoo import exaone_moe
    from mxnet_tpu.telemetry import device_scope
    small = exaone_moe.exaone_moe_tiny()
    cfg = small.cfg
    assert "scoring_func" not in cfg
    lp = exaone_moe.param_tree(cfg, lambda path, shape: jax.ShapeDtypeStruct(
        shape, jnp.float32))["layers"][1]["moe"]
    x = jax.ShapeDtypeStruct((12, 64), jnp.float32)
    valid = jax.ShapeDtypeStruct((12,), jnp.bool_) if masked else None

    def direct(lp, x, valid):
        with device_scope("moe"):
            with device_scope("moe.route"):
                experts, weights = pmoe.grouped_topk_route(
                    x, lp["router_w"], lp["router_b"], cfg["n_group"],
                    cfg["topk_group"], cfg["num_experts_per_tok"],
                    cfg["routed_scaling_factor"])
                local = experts if valid is None \
                    else jnp.where(valid[:, None], experts, -1)
            y, stats = pmoe.held_experts_ffn(
                x, local, weights, lp["gu_w"], lp["down_w"],
                cfg["experts_held"][0], lp["router_w"].shape[1])
            with device_scope("moe.shared"):
                return y + decoder_blocks.swiglu(
                    x, lp["sh_gu_w"], lp["sh_down_w"]), experts, stats

    def layer(lp, x, valid):
        return decoder_blocks.moe(lp, x, cfg, valid)

    assert str(jax.make_jaxpr(layer)(lp, x, valid)) \
        == str(jax.make_jaxpr(direct)(lp, x, valid))


def test_an_unknown_router_is_refused(net):
    lp = mel.decode_params(net)["layers"][0]["moe"]
    with pytest.raises(ValueError, match="scoring_func"):
        decoder_blocks.moe(lp, jnp.zeros((2, 64)),
                           dict(net.cfg, scoring_func="softplus"))


def test_the_reference_in_a_lower_precision_fails_the_tiny_limits(net,
                                                                  served):
    """The cell's tiny limits lie between this program's error and the
    reference's when it computes in float8_e4m3."""
    with open(os.path.join(REPO, "perfbench", "workloads",
                           "mellum2-serve-codeassist.json")) as f:
        check = json.load(f)["tiny"]["correct"]
    eng, reqs = served
    r = reqs[0]
    got = np.stack(r.logits_trace)
    assert np.abs(got - reference_rows(eng, net, r)).max() \
        < check["tol_logit"] / 3
    low = reference_rows(eng, net, r, compute_as="float8_e4m3fn")
    assert np.abs(got - low).max() > check["tol_logit"]


def test_the_model_states_the_configuration_files_sizes():
    with open(os.path.join(REPO, "perfbench", "configs",
                           "mellum2-12b-a2.5b.json")) as f:
        cfg = json.load(f)
    for key, value in mel.PUBLISHED.items():
        if key == "num_hidden_layers":
            assert cfg["published"][key] == value
        elif key == "scoring_func":
            # config.json names none: the family's, stated as assumed
            assert key not in cfg and "softmax" in cfg["assumed"]["router"]
        else:
            assert cfg[key] == value, key
    assert mel.mellum2().cfg["layers"] == cfg["layers_kept"] \
        == list(range(8))
    assert mel.mellum2().cfg["experts_held"] == cfg["experts_held"] \
        == [0, 64]
    assert mel.DECODE_STATS[:len(decoder_blocks.MOE_STATS)] \
        == decoder_blocks.MOE_STATS
    assert mel._attend is decoder_blocks.attend
