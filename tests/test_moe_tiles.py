"""The expert layout's tile height (parallel/moe.py ``expert_tile_rows``)
and the held experts' layer at a tall tile, on the CPU: the rule over
the served programs' shapes, ``held_experts_ffn`` at 128 rows a tile
against a dense loop over experts under every skew of the counts, and
the count of weight tiles."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.parallel import moe


# rows of a program x choices a row, over the router's width
# (perfbench/workloads/*.json, perfbench/configs/*.json as published)
@pytest.mark.parametrize("program,rows,k,num_experts,want", [
    ("kexaone decode", 64, 8, 128, 16),
    ("ling3vl decode", 128, 8, 512, 16),
    ("dsv32 decode", 16, 8, 256, 16),
    ("tiny preset decode", 2, 4, 16, 16),
    ("ling3vl prefill", 1024, 8, 512, 32),
    ("dsv32 chunk", 2048, 8, 256, 128),
    ("kexaone chunk", 2048, 8, 128, 256),
])
def test_tile_height_follows_the_rows_an_expert_gets(program, rows, k,
                                                     num_experts, want):
    """One rule for every model: a decode step's few rows an expert
    keep the smallest tile, a 1,024-row prompt over 512 experts its 32,
    and a chunk's 64-128 rows an expert get a tile that holds them, so
    the expert's weights are read once."""
    got = moe.expert_tile_rows(rows * k, num_experts)
    assert got == want
    # a multiple of the smallest bf16 tile that holds the rows an expert
    # can expect, up to the 256 at which a taller tile buys nothing
    assert got % 16 == 0 and got >= min(256, rows * k // num_experts)


def dense_held(x, experts, weights, gu, down, first):
    """Loop over tokens, choices and held experts in float64."""
    x, gu, down = (np.asarray(a, np.float64) for a in (x, gu, down))
    f = down.shape[1]
    y = np.zeros_like(x)
    for t, (row, ws) in enumerate(zip(np.asarray(experts),
                                      np.asarray(weights))):
        for e, w in zip(row, ws):
            if first <= e < first + gu.shape[0]:
                h = x[t] @ gu[e - first]
                act = h[:f] / (1 + np.exp(-h[:f])) * h[f:]
                y[t] += w * (act @ down[e - first])
    return y


@pytest.mark.parametrize("case", ["even", "one_expert_takes_all",
                                  "one_held_expert_idle", "none_local",
                                  "masked_tail"])
def test_held_experts_at_a_tall_tile_equal_the_dense_layer(case):
    """128 rows a tile: the layer is the dense computation whatever the
    counts (an expert past one tile, an expert with no row, no local
    row at all, pad rows given to no expert), no token is dropped, and
    ``weight_tiles`` counts the tiles that hold a token."""
    rng = np.random.default_rng(11)
    t, k, c, f, held, first, tile = 160, 2, 16, 8, 4, 4, 128
    x = jnp.asarray(rng.normal(size=(t, c)), jnp.float32)
    gu = jnp.asarray(rng.normal(size=(held, c, 2 * f)), jnp.float32)
    down = jnp.asarray(rng.normal(size=(held, f, c)), jnp.float32)
    weights = jnp.asarray(rng.uniform(0.1, 1, size=(t, k)), jnp.float32)
    # 12 experts, 4-7 held: two distinct choices a token
    experts = np.stack([rng.permutation(12)[:k] for _ in range(t)])
    if case == "one_expert_takes_all":
        experts = np.stack([np.full(t, 6), rng.integers(8, 12, t)], 1)
    if case == "one_held_expert_idle":
        experts = np.where(experts == 5, 0, experts)
    if case == "none_local":
        experts = np.where((experts >= 4) & (experts < 8), experts + 4,
                           experts)
    if case == "masked_tail":
        # what decoder_blocks.moe does with a chunk's pad rows
        experts = np.where(np.arange(t)[:, None] < 100, experts, -1)
    experts = jnp.asarray(experts, jnp.int32)
    y, stats = moe.held_experts_ffn(x, experts, weights, gu, down, first,
                                    num_experts=12, tile_rows=tile)
    want = dense_held(x, experts, weights, gu, down, first)
    assert np.abs(np.asarray(y) - want).max() < 1e-4
    flat = np.asarray(experts).ravel()
    counts = np.bincount(flat[(flat >= 4) & (flat < 8)] - 4,
                         minlength=held)
    assert float(stats["local_assignments"]) == counts.sum()
    assert float(stats["experts_hit"]) == (counts > 0).sum()
    assert float(stats["max_tokens_per_expert"]) == counts.max()
    assert float(stats["weight_tiles"]) == (-(-counts // tile)).sum()
    if case == "one_expert_takes_all":
        assert float(stats["weight_tiles"]) == 2      # 160 rows, 128 a tile
    if case == "none_local":
        assert float(stats["weight_tiles"]) == 0 and not np.asarray(y).any()


@pytest.mark.parametrize("tile_rows", [16, 32, 128])
def test_weight_tiles_is_the_sum_of_each_experts_tiles(tile_rows):
    """``weight_tiles = sum(ceil(counts / tile_rows))``: with the rule's
    own height (no override) and with others, the output does not
    depend on the height."""
    rng = np.random.default_rng(tile_rows)
    t, k, c, f, held = 64, 4, 16, 8, 4
    x = jnp.asarray(rng.normal(size=(t, c)), jnp.float32)
    gu = jnp.asarray(rng.normal(size=(held, c, 2 * f)), jnp.float32)
    down = jnp.asarray(rng.normal(size=(held, f, c)), jnp.float32)
    weights = jnp.asarray(rng.uniform(0.1, 1, size=(t, k)), jnp.float32)
    experts = jnp.asarray(np.stack([rng.permutation(8)[:k]
                                    for _ in range(t)]), jnp.int32)
    # 256 assignments over 8 experts: the rule gives 64 rows a tile
    assert moe.expert_tile_rows(t * k, 8) == 64
    y0, st0 = moe.held_experts_ffn(x, experts, weights, gu, down, 0, 8)
    y, st = moe.held_experts_ffn(x, experts, weights, gu, down, 0, 8,
                                 tile_rows=tile_rows)
    counts = np.bincount(np.asarray(experts).ravel(), minlength=8)[:held]
    assert float(st["weight_tiles"]) == (-(-counts // tile_rows)).sum()
    assert float(st0["weight_tiles"]) == (-(-counts // 64)).sum()
    assert np.abs(np.asarray(y) - np.asarray(y0)).max() < 1e-5
    for name in ("experts_hit", "local_assignments",
                 "max_tokens_per_expert"):
        assert float(st[name]) == float(st0[name])
