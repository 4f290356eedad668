"""Serving runtime (ISSUE 9): paged KV allocator + scheduler invariants
in-process; the ragged paged-attention kernel and ServingEngine checks
run in a subprocess (tests/serving_driver.py)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import PagedKVAllocator
from mxnet_tpu.serving.kv_cache import SCRATCH_PAGE

pytestmark = pytest.mark.serving

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- paged allocator (pure host-side, no jax) ------------------------------

def test_allocator_basic_and_reuse():
    a = PagedKVAllocator(num_pages=8, page_size=4)
    assert a.free_pages == 7          # page 0 reserved (scratch)
    assert a.pages_for(1) == 1 and a.pages_for(4) == 1
    assert a.pages_for(5) == 2 and a.pages_for(0) == 1
    p1 = a.allocate(3)
    assert SCRATCH_PAGE not in p1 and len(set(p1)) == 3
    p2 = a.allocate(2)
    assert not set(p1) & set(p2)
    a.release(p1)
    assert a.free_pages == 5
    # LIFO free-list: the pages just released come back first
    p3 = a.allocate(3)
    assert set(p3) == set(p1)


def test_allocator_fragmentation_interleave():
    """Interleaved alloc/free churn never loses or duplicates a page."""
    a = PagedKVAllocator(num_pages=11, page_size=2)
    held = []
    rng = np.random.RandomState(3)
    for _ in range(50):
        if held and (rng.rand() < 0.5 or a.free_pages < 2):
            a.release(held.pop(rng.randint(len(held))))
        else:
            held.append(a.allocate(rng.randint(1, 3)))
        flat = [p for h in held for p in h]
        assert len(flat) == len(set(flat))          # no double alloc
        assert a.free_pages + len(flat) == 10       # conservation
        assert SCRATCH_PAGE not in flat
    for h in held:
        a.release(h)
    assert a.free_pages == 10


def test_allocator_oom_and_double_free():
    a = PagedKVAllocator(num_pages=4, page_size=4)
    assert a.can_reserve(3) and not a.can_reserve(4)
    pages = a.allocate(3)
    with pytest.raises(MXNetError, match="OOM"):
        a.allocate(1)
    a.release(pages)
    with pytest.raises(MXNetError, match="not allocated"):
        a.release(pages)        # double free
    with pytest.raises(MXNetError, match="not allocated"):
        a.release([SCRATCH_PAGE])


def test_allocator_refcounts_share_and_last_ref_frees():
    """ISSUE 15 refcount laws: retain adds a reference, release drops
    one, only the LAST release frees; conservation covers shared pages
    and over-release raises."""
    a = PagedKVAllocator(num_pages=6, page_size=4)
    pages = a.allocate(2)
    assert [a.refcount(p) for p in pages] == [1, 1]
    assert a.shared_pages == 0
    a.retain(pages)                       # a second sequence maps them
    assert [a.refcount(p) for p in pages] == [2, 2]
    assert a.shared_pages == 2
    a.assert_conservation()
    a.release(pages)                      # first reader leaves
    assert [a.refcount(p) for p in pages] == [1, 1]
    assert a.free_pages == 3 and a.used_pages == 2
    a.release(pages)                      # last ref -> freed
    assert a.free_pages == 5 and a.used_pages == 0
    with pytest.raises(MXNetError, match="not allocated"):
        a.release(pages)                  # over-release
    with pytest.raises(MXNetError, match="not allocated"):
        a.retain([pages[0]])              # retaining a free page
    a.assert_conservation()


def test_allocator_refcount_interleaved_conservation():
    """Random retain/release churn over shared pages never leaks,
    double-frees, or double-allocates (conservation with refcounts)."""
    a = PagedKVAllocator(num_pages=9, page_size=2)
    rng = np.random.RandomState(5)
    owners = []                           # list of page-lists (refs)
    for _ in range(120):
        r = rng.rand()
        if owners and r < 0.35:
            a.release(owners.pop(rng.randint(len(owners))))
        elif owners and r < 0.6:
            share = owners[rng.randint(len(owners))]
            a.retain(share)
            owners.append(list(share))
        elif a.free_pages >= 2:
            owners.append(a.allocate(rng.randint(1, 3)))
        a.assert_conservation()
    for o in owners:
        a.release(o)
    assert a.free_pages == 8 and a.used_pages == 0
    a.assert_conservation()


def test_allocator_speculative_marks():
    """ISSUE 16 host-side spec-page laws: marks are bookkeeping on
    ALLOCATED pages only; a release that beats the commit/rollback
    raises (a freed page whose stale draft K/V another slot would
    inherit); conservation audits stray marks on freed pages."""
    a = PagedKVAllocator(num_pages=6, page_size=4)
    pages = a.allocate(2)
    assert a.speculative_pages == 0
    a.mark_speculative(pages)
    assert a.speculative_pages == 2
    a.assert_conservation()            # marks on live pages are legal
    with pytest.raises(MXNetError, match="speculative"):
        a.release(pages)               # rollback leak caught at release
    assert a.clear_speculative(pages) == 2
    assert a.speculative_pages == 0
    a.release(pages)                   # cleared marks release fine
    with pytest.raises(MXNetError, match="not allocated"):
        a.mark_speculative(pages)      # marking free pages is corruption
    # clear_speculative(None) commits/rolls back EVERYTHING (the
    # failed-dispatch path) and reports how many marks it dropped
    p2 = a.allocate(3)
    a.mark_speculative(p2[:2])
    assert a.clear_speculative() == 2
    a.release(p2)
    a.assert_conservation()
    # a stray mark surviving past its page's free is the one corruption
    # only the audit can see (every legal path clears before release)
    p3 = a.allocate(1)
    a.mark_speculative(p3)
    a.clear_speculative(p3)
    a.release(p3)
    a._spec.add(p3[0])                 # simulate the bookkeeping bug
    with pytest.raises(MXNetError, match="speculative"):
        a.assert_conservation()
    a._spec.discard(p3[0])
    a.assert_conservation()


def test_prefix_cache_match_insert_evict_host_side():
    """PrefixCache trie laws without jax: page-aligned match, partial
    (COW) match, LRU leaf eviction, index consistency."""
    from mxnet_tpu.serving import PrefixCache
    a = PagedKVAllocator(num_pages=12, page_size=4)
    c = PrefixCache(a)
    prompt = np.arange(10, dtype=np.int32)          # 2 full pages + 2
    pages = a.allocate(3)
    c.insert(prompt, pages)                          # caches 2 pages
    assert c.cached_pages == 2
    c.assert_consistent()
    a.release(pages)                                 # request leaves
    assert a.used_pages == 2                         # cache pins them
    path, partial, overlap = c.match(prompt)
    assert [n.page for n in path] == pages[:2]
    assert partial is None and overlap == 0
    # diverging prompt: full match on page 0, partial on page 1
    div = np.array([0, 1, 2, 3, 4, 5, 99, 98], np.int32)
    path, partial, overlap = c.match(div)
    assert len(path) == 1 and partial is not None and overlap == 2
    # no match at all
    path, partial, overlap = c.match(np.full(8, 77, np.int32))
    assert path == [] and partial is None
    # eviction frees leaf-first and stops as soon as the reservation
    # fits (never over-evicts)
    assert not a.can_reserve(10)
    dropped = c.evict_for(10)
    assert dropped == 1 and a.can_reserve(10)
    assert c.cached_pages == 1 and a.used_pages == 1
    c.assert_consistent()
    # evict_all drops the rest (the serve.prefix.evict drill's move)
    assert c.evict_all() == 1
    assert c.cached_pages == 0 and a.used_pages == 0
    a.assert_conservation()


# -- kernel + engine (clean subprocess, pallas-capable) --------------------

def _run_driver(section):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    r = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tests", "serving_driver.py"), section],
        env=env, capture_output=True, timeout=420)
    out = r.stdout.decode() + r.stderr.decode()
    assert r.returncode == 0, out[-3000:]
    return out


def test_paged_attention_kernel():
    """Mixed-length equivalence vs the jnp oracle AND vs dense
    flash_attention; empty slots emit zeros.  Covers the ISSUE-16
    multi-query verify kernel too: per-position causal contexts vs the
    oracle, masked rows emit zeros, and G=1 is bit-identical to the
    single-query decode kernel."""
    assert "SERVING_KERNEL_OK" in _run_driver("kernel")


@pytest.mark.parametrize("case", ["mha_decode", "gqa_verify"])
@pytest.mark.parametrize("kv_dtype", ["fp32", "bf16"])
def test_paged_kernel_reproduces_the_page_at_a_time_kernel(kv_dtype, case):
    """The block kernel computes what the kernel it replaced computed:
    ``tests/data/paged_kernel_parent.npz`` holds inputs and outputs of
    commit be26b86's ``paged_attention_multi`` (one 16-token page a grid
    cell, a matmul pair a head, online softmax page by page) under the
    interpreter in this suite's configuration (x64 on, fp32 matmuls):
    one query position per slot at ``K_kv == H`` with an empty slot, and
    four with per-position contexts at ``K_kv == H / 2``.  Same
    mathematics, fp32 accumulation, another order of the sums: within
    2e-6 on fp32 pools and on bf16 ones (kept as their bits; the three
    bf16 pieces of the query and of the weights multiply them
    exactly).  One query position through ``paged_attention`` is the
    same launch, byte for byte."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.paged_attention import (
        paged_attention, paged_attention_multi)
    with np.load(os.path.join(REPO, "tests", "data",
                              "paged_kernel_parent.npz")) as z:
        prefix = "%s.%s." % (kv_dtype, case)
        rec = {k[len(prefix):]: z[k] for k in z.files
               if k.startswith(prefix)}

    kp, vp = (jnp.asarray(rec[k].view(jnp.bfloat16) if kv_dtype == "bf16"
                          else rec[k]) for k in ("k_pages", "v_pages"))
    assert kp.ndim == 3
    out = np.asarray(paged_attention_multi(
        rec["q"], kp, vp, rec["block_tables"], rec["context_lens"]))
    assert out.dtype == rec["out"].dtype
    assert np.abs(out - rec["out"]).max() < 2e-6
    if rec["q"].shape[1] == 1:
        one = np.asarray(paged_attention(
            rec["q"][:, 0], kp, vp, rec["block_tables"],
            rec["context_lens"][:, 0]))
        assert one.tobytes() == out[:, 0].tobytes()


def _block_edge_case(kv_dtype, n_q, kv_heads, heads, d, page, ppb):
    """Inputs that stand on every edge of a ``ppb``-page block, clean
    (for the oracle) and as the engine may leave them (for the kernel).

    Slots, in launch order: empty; a context that ends exactly on a
    block edge; empty; one token past the edge; inside the first page;
    a draft cut short (later positions empty); two and a half blocks,
    which with ``max_pages = 3 * ppb - 1`` ends in a block the table
    does not fill.  Dirty: every block-table entry past a slot's
    context is garbage (out of range, negative, or a page of NaN /
    NaN scales), and the rows of each last page past the context are
    NaN (fp32, bf16) or 127 (int8)."""
    import jax.numpy as jnp
    rng = np.random.RandomState(7 + n_q + kv_heads)
    t = ppb * page
    max_pages = 3 * ppb - 1
    last = [0, t, 0, t + 1, 3, 2 * page + 5, 2 * t + t // 2 + 3]
    s_n = len(last)
    ctx = np.zeros((s_n, n_q), np.int32)
    for s, c in enumerate(last):
        ctx[s] = np.maximum(c - (n_q - 1) + np.arange(n_q), 0)
    if n_q > 2:
        ctx[5, 2:] = 0                      # rows past the draft length
    width = kv_heads * d
    need = [-(-int(c.max()) // page) for c in ctx]
    n_pages = sum(need) + 3
    perm = rng.permutation(n_pages - 3) + 1
    poison = n_pages - 2                    # pages n_pages-2, n_pages-1
    q = rng.randn(s_n, n_q, heads, d).astype(np.float32)
    clean_bt = np.zeros((s_n, max_pages), np.int32)
    dirty_bt = np.empty((s_n, max_pages), np.int32)
    dirty_bt[:] = rng.choice([poison, poison + 1, 2 ** 30, -5, 10 ** 6],
                             (s_n, max_pages))
    at = 0
    for s in range(s_n):
        clean_bt[s, :need[s]] = perm[at:at + need[s]]
        dirty_bt[s, :need[s]] = perm[at:at + need[s]]
        at += need[s]
    scales = {}
    if kv_dtype == "int8":
        pools = [rng.randint(-127, 128, (n_pages, page, width))
                 .astype(np.int8) for _ in range(2)]
        scales = {n: (rng.rand(n_pages, kv_heads) / 64 + 1e-3)
                  .astype(np.float32) for n in ("k_scales", "v_scales")}
    else:
        pools = [rng.randn(n_pages, page, width).astype(np.float32)
                 for _ in range(2)]
    dirty_pools = [a.copy() for a in pools]
    dirty_scales = {n: a.copy() for n, a in scales.items()}
    bad = 127 if kv_dtype == "int8" else np.nan
    for a in dirty_pools:
        a[poison:] = bad
        for s in range(s_n):
            tail = int(ctx[s].max()) % page
            if tail:
                a[clean_bt[s, need[s] - 1], tail:] = bad
    for a in dirty_scales.values():
        a[poison:] = np.nan
    as_pool = (lambda a: jnp.asarray(a, jnp.bfloat16)) \
        if kv_dtype == "bf16" else jnp.asarray
    clean = (q, as_pool(pools[0]), as_pool(pools[1]), clean_bt, ctx)
    dirty = (q, as_pool(dirty_pools[0]), as_pool(dirty_pools[1]),
             dirty_bt, ctx)
    return clean, scales, dirty, dirty_scales


@pytest.mark.parametrize("block_tokens", [None, 32])
@pytest.mark.parametrize("kv_heads", [8, 2])
@pytest.mark.parametrize("n_q", [1, 5])
@pytest.mark.parametrize("kv_dtype", ["fp32", "bf16", "int8"])
def test_paged_kernel_on_every_edge_of_a_block(monkeypatch, kv_dtype, n_q,
                                               kv_heads, block_tokens):
    """The block kernel against the jnp oracle within 2e-6, in every
    page format, at one and five query positions, with ``K_kv = H`` and
    ``H / 4``: contexts that end exactly on a block edge, one token
    past it, inside the first page and at 0 (zeros out), a table whose
    ``max_pages`` is no multiple of the block, empty slots between live
    ones (each cell starts its successor's first copies), and garbage
    wherever the kernel must not look (``_block_edge_case``): a page
    past the context is never read, and what a read page holds past the
    context never reaches the output, NaN included.  Once at the block
    the kernel derives (256 tokens of 16-token pages) and once steered
    to 32 tokens of 8-token pages, where a block is no whole lane tile."""
    import importlib
    paged = importlib.import_module("mxnet_tpu.ops.pallas.paged_attention")
    heads, d, page = 8, 32, 16
    if block_tokens:
        monkeypatch.setattr(paged, "_BLOCK_TOKENS", block_tokens)
        d, page = 16, 8
    import jax.numpy as jnp
    ppb = paged.pages_per_block(
        page, kv_heads * d, {"fp32": jnp.float32, "bf16": jnp.bfloat16,
                             "int8": jnp.int8}[kv_dtype])
    assert ppb * page == (block_tokens or 256)
    clean, scales, dirty, dirty_scales = _block_edge_case(
        kv_dtype, n_q, kv_heads, heads, d, page, ppb)
    assert clean[3].shape[1] % ppb
    ref = np.asarray(paged.paged_attention_multi_reference(
        *clean, **scales))
    assert np.isfinite(ref).all()
    for args, sc in ((clean, scales), (dirty, dirty_scales)):
        out = np.asarray(paged.paged_attention_multi(*args, **sc))
        assert np.isfinite(out).all()
        assert np.abs(out - ref).max() < 2e-6
        assert (out[clean[4] == 0] == 0).all()
    if n_q == 1:
        one = np.asarray(paged.paged_attention(
            dirty[0][:, 0], *dirty[1:4], dirty[4][:, 0], **dirty_scales))
        assert one.tobytes() == out[:, 0].tobytes()


@pytest.mark.parametrize("page,width,kv_dtype,max_pages,want", [
    (16, 1024, "bf16", 64, 16),     # the benchmark's pools: 256 tokens
    (16, 1024, "fp32", 64, 16),
    (16, 1024, "int8", 64, 16),
    (16, 4096, "bf16", 64, 8),      # 32 heads of 128: the VMEM budget
    (16, 8192, "fp32", 64, 2),
    (64, 640, "bf16", 80, 4),       # longer pages, fewer of them
    (512, 1024, "bf16", 8, 1),      # a page is never split
    (16, 1024, "bf16", 3, 3),       # nor a sequence's table outrun
    (8, 128, "fp32", None, 32),
])
def test_pages_per_block_follows_the_shapes(page, width, kv_dtype,
                                            max_pages, want):
    """``P`` comes from the page size, the pool's width and dtype and
    the table's length, against a VMEM budget: no caller picks it."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.paged_attention import (
        _BLOCK_VMEM_BYTES, pages_per_block)
    dt = {"fp32": jnp.float32, "bf16": jnp.bfloat16,
          "int8": jnp.int8}[kv_dtype]
    got = pages_per_block(page, width, dt, max_pages)
    assert got == want
    assert got == 1 or 4 * got * page * width * jnp.dtype(dt).itemsize \
        <= _BLOCK_VMEM_BYTES


@pytest.mark.parametrize("n_kv,rows,d,pieces,want", [
    (16, 1, 64, 3, 16),     # decode: every head in one matmul (48 rows)
    (16, 5, 64, 3, 8),      # verify at spec_k 4: eight heads, 120 rows
    (16, 5, 64, 1, 16),     # the same on fp32 pools: one piece
    (4, 4, 64, 3, 4),       # grouped-query decode, H / 4
    (4, 20, 64, 3, 2),      # and verify: one 128-lane tile of heads
    (16, 17, 64, 3, 2),     # spec_k 16
    (8, 8, 128, 3, 4),      # D 128: any count of heads is whole tiles
    (3, 1, 16, 3, 3),       # widths the interpreter alone sees
    (2, 40, 16, 3, 2),
])
def test_heads_per_group_follows_the_shapes(n_kv, rows, d, pieces, want):
    """How many KV heads one block-diagonal matmul scores: all while
    their query rows stay under an MXU tile, else whole 128-lane tiles
    of heads; from static shapes, never a flag."""
    from mxnet_tpu.ops.pallas.paged_attention import _heads_per_group
    hb = _heads_per_group(n_kv, rows, d, pieces)
    assert hb == want and n_kv % hb == 0
    assert hb == n_kv or (hb * d) % 128 == 0


def test_paged_kernel_refuses_a_pool_that_is_not_flat():
    from mxnet_tpu.ops.pallas.paged_attention import (
        paged_attention, paged_attention_reference)
    q = np.zeros((2, 4, 16), np.float32)
    tables, ctx = np.ones((2, 2), np.int32), np.ones(2, np.int32)
    for pool in (np.zeros((4, 8, 4, 16), np.float32),    # 4-D
                 np.zeros((4, 8, 40), np.float32),       # not k * D
                 np.zeros((4, 8, 3 * 16), np.float32)):  # 4 % 3 heads
        for fn in (paged_attention, paged_attention_reference):
            with pytest.raises(ValueError):
                fn(q, pool, pool, tables, ctx)


def test_serving_engine_invariants():
    """Engine == dense generate at mixed lengths (greedy-vs-today
    bit-identity, prefix cache at its default ON); EOS early-leave;
    slot reuse leaks no stale KV; join/leave keeps resident logits
    bit-identical; OOM-aware admission queues and drains; exactly one
    dispatch per decode step with zero steady-state recompiles; serving
    telemetry populated.  Plus the fast ISSUE-15 siblings in the same
    subprocess (AOT-memo-shared — no extra compiles): prefix sharing +
    COW correctness vs the dense reference with refcount conservation,
    and the per-request sampling laws (seeded reproducibility,
    top_k=1 == greedy, per-slot isolation).  The fast ISSUE-16 spec
    laws ride the same subprocess: spec-on greedy streams bit-identical
    to the dense reference under staggered join/leave at mixed ragged
    lengths, drafting non-vacuous and strictly cheaper in decode steps,
    the serve.spec.poison drill (corrupted drafts between draft and
    verify -> all rejected, exact non-speculative stream), per-request
    spec_k=0 override, and zero speculative page marks at idle.
    The fast ISSUE-19 streaming laws ride here as well: poll-cursor
    idempotence + chunk reassembly against the unary stream, the typed
    `cancelled` verdict (mid-decode, queued, idempotent — survivors
    bit-identical, pages conserved), and the abandon sweep for a
    client that stops polling (typed `abandoned` verdict, unary
    requests never reclaimed).
    The fast ISSUE-20 quantized-KV laws complete the subprocess: int8
    pool/scale-pool shape + byte accounting with allocator conservation
    under churn, twin-engine int8 reproducibility, COW prefix reuse
    copying scales with payload bytes (grow-only scale law), spec
    rollback under the serve.spec.poison drill leaving no stale scale
    slots, sampled determinism quantized-to-ITSELF across churn +
    hot-swap + failover stand-in, and the serve.kv.scale_poison drill
    (poisoned page scale -> finite-guard repair re-prefills the victim;
    streams match the unfaulted reference)."""
    out = _run_driver("engine")
    assert "SERVING_ENGINE_OK" in out
    assert "SERVING_CAPACITY_FAST_OK" in out
    assert "SERVING_SPEC_FAST_OK" in out
    assert "SERVING_STREAM_OK" in out
    assert "SERVING_KVQ_FAST_OK" in out


@pytest.mark.slow
def test_serving_capacity_multipliers():
    """ISSUE 15 compile-heavy engine laws (slow; fast siblings ride the
    engine section): cache-off/cache-on greedy token identity, LRU
    eviction under admission pressure, GQA join/leave bit-exactness,
    and the >= 1.5x resident-capacity multiplier at K_kv = H/2 in the
    same pool bytes.  The ISSUE-20 kv_dtype sweep rides here (each
    dtype compiles its own engine programs): fp32/bf16/int8 twin-engine
    reproduction, fp32 == the dense reference, strict bytes-per-token
    ordering fp32 > bf16 > int8, GQA x int8 composition, and the
    MXTPU_SERVE_KV_DTYPE env override (bad names raise ValueError)."""
    assert "SERVING_CAPACITY_OK" in _run_driver("capacity")


@pytest.mark.slow
def test_serving_spec_k_sweep():
    """ISSUE 16 exhaustive spec_k sweep (slow: every k compiles its own
    spec-decode program; the fast single-config siblings ride the
    engine section): greedy bit-identity to the dense reference,
    sampled seeded reproducibility, and zero leaked speculative pages
    at k = 1, 2, 8 and 16 — the wpe boundary where
    max_seq_len + spec_k == the net's max_len."""
    assert "SERVING_SPEC_SWEEP_OK" in _run_driver("spec_sweep")


# -- predictor satellite (no pallas needed) --------------------------------

def _train_tiny(tmp_path, prefix="served"):
    np.random.seed(0)
    X = np.random.randn(64, 8).astype(np.float32)
    Y = (X.sum(1) > 0).astype(np.float32)
    data = mx.sym.Variable("data")
    s = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    s = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        s, num_hidden=2, name="fc2"), name="softmax")
    it = mx.io.NDArrayIter(X, Y, batch_size=32)
    mod = mx.mod.Module(s, context=mx.cpu())
    mod.fit(it, optimizer="sgd", num_epoch=2,
            initializer=mx.init.Xavier())
    p = str(tmp_path / prefix)
    mod.save_checkpoint(p, 2)
    return p, X


def test_predictor_refuses_torn_checkpoint(tmp_path):
    """from_checkpoint goes through CheckpointManager: a torn params
    file fails manifest validation and raises instead of binding
    garbage weights (the serving-replica-vs-live-trainer race)."""
    prefix, X = _train_tiny(tmp_path)
    params = "%s-0002.params" % prefix
    blob = open(params, "rb").read()
    with open(params, "wb") as f:
        f.write(blob[:len(blob) // 2])      # torn mid-write
    with pytest.raises(MXNetError, match="torn or corrupt"):
        mx.Predictor.from_checkpoint(prefix, 2, {"data": (4, 8)})


def test_predictor_epoch_none_follows_latest(tmp_path):
    prefix, X = _train_tiny(tmp_path)
    pred = mx.Predictor.from_checkpoint(prefix, None, {"data": (4, 8)})
    out = pred.predict(X[:4])
    assert out.shape == (4, 2)
    np.testing.assert_allclose(out.sum(1), 1.0, rtol=1e-5)
