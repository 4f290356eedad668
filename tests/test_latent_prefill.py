"""The chunk kernel of absorbed latent attention over a slot's pages
(``ops/pallas/latent_attention.mla_paged_prefill``) on the interpreter,
against its XLA oracle ``mla_paged_prefill_reference``, and the oracle
against a dense softmax over the slot's gathered rows.

The blocks are shrunk (``PREFILL_ROWS``, ``PREFILL_KEYS`` and the
oracle's own) so that tiny widths walk several query and key blocks.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops.pallas import latent_attention as la

PAGE, MAX_PAGES, NUM_PAGES, W, V = 4, 16, 40, 128, 32


def case(t, h, first, dtype, shuffled, seed):
    """``(q, pool, block_table_row)``: the slot's pages hold every row up
    to the chunk's last; the table's unused entries are page 0."""
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.normal(size=(NUM_PAGES, PAGE, W)), dtype)
    used = -(-(first + t) // PAGE)
    table = np.zeros(MAX_PAGES, np.int32)
    table[:used] = rng.permutation(np.arange(1, NUM_PAGES))[:used] \
        if shuffled else np.arange(1, used + 1)
    q = jnp.asarray(rng.normal(size=(t, h, W)), jnp.float32)
    return q, pool, table


#: (t, h, first_pos, n_rows, dtype, shuffled table): with 8 keys a block
#: and 32 matrix rows a cell
CASES = {
    "first_pos_0": (16, 4, 0, 16, jnp.float32, False),
    "first_pos_inside_a_key_block": (16, 4, 5, 16, jnp.float32, False),
    "several_key_blocks_in": (16, 4, 27, 16, jnp.float32, False),
    "ragged_chunk": (32, 4, 3, 5, jnp.float32, False),
    "shuffled_table": (16, 4, 9, 16, jnp.float32, True),
    "heads_tiled": (16, 32, 9, 13, jnp.float32, True),
    "heads_tiled_bfloat16": (16, 32, 9, 13, jnp.bfloat16, True),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_mla_paged_prefill_on_the_interpreter(monkeypatch, name):
    monkeypatch.setattr(la, "PREFILL_ROWS", 32)
    monkeypatch.setattr(la, "PREFILL_KEYS", 8)
    t, h, first, n_rows, dtype, shuffled = CASES[name]
    q, pool, table = case(t, h, first, dtype, shuffled, seed=len(name))
    tq, hg, ppb = la.prefill_blocks(t, h, PAGE, MAX_PAGES, dtype)
    assert ppb * PAGE == 8
    if name.startswith("heads_tiled"):
        assert hg < h
    if name == "ragged_chunk":
        assert t - n_rows >= 2 * tq          # whole query blocks of pad
    # a power of two: the kernel's scaled query rounds as the oracle's
    scale = 0.125
    got = la.mla_paged_prefill(q, pool, table, jnp.int32(first),
                               jnp.int32(n_rows), V, scale)
    want = la.mla_paged_prefill_reference(q, pool, table, jnp.int32(first),
                                          jnp.int32(n_rows), V, scale)
    assert got.shape == (t, h, V) and got.dtype == jnp.float32
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    assert np.abs(np.asarray(got - want)).max() < tol
    assert not np.asarray(got[n_rows:]).any()


@pytest.mark.parametrize("first,n_rows", [(0, 12), (13, 9)])
def test_the_prefill_reference_is_a_dense_softmax(monkeypatch, first,
                                                  n_rows):
    monkeypatch.setattr(la, "_REF_QUERIES", 4)
    monkeypatch.setattr(la, "_REF_KEYS", 8)
    t, h, scale = 12, 4, 0.3
    q, pool, table = case(t, h, first, jnp.float32, True, seed=first)
    rows = np.asarray(pool)[table].reshape(-1, W).astype(np.float64)
    s = np.einsum("thw,kw->thk", np.asarray(q, np.float64), rows) * scale
    pos = first + np.arange(t)
    s = np.where(np.arange(rows.shape[0])[None, None, :]
                 <= pos[:, None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    dense = np.einsum("thk,kv->thv", p / p.sum(-1, keepdims=True),
                      rows[:, :V])
    dense[n_rows:] = 0.0
    with jax.default_matmul_precision("highest"):
        got = la.mla_paged_prefill_reference(q, pool, table, jnp.int32(first),
                                             jnp.int32(n_rows), V, scale)
    assert np.abs(np.asarray(got) - dense).max() < 1e-5
