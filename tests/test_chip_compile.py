"""Ask the chip's compiler, without the chip.

The TPU's compiler is installed wherever jax[tpu] is and compiles for a
chip that is DESCRIBED, not attached (on-chip-measurement guide, §2.3).
Every Pallas kernel of the main path is compiled here for a v5e at the
widths ``chip_smoke.py`` runs them at (GPT-2 medium: 16 heads of 64, 16-
token pages; T = 2048), under the suite's ``jax_enable_x64`` — the
interpreter, which every other test of these kernels runs under, accepts
block shapes, SMEM vector loads and f64 constants that Mosaic refuses.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, so the worker that is
handed this file loads it, and the others never try.  For the same
reason every compile runs in this process (no child could load the
library), and these tests stay in this one file.

Also here: the tier-1 run of ``chip_smoke.py --tiny``, the CPU rehearsal
of the script the driver runs on the chip.
"""
import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the package re-exports functions under the modules' names
flash = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")
paged = importlib.import_module("mxnet_tpu.ops.pallas.paged_attention")
layer_norm = importlib.import_module("mxnet_tpu.ops.pallas.layer_norm")

SLOTS, HEADS, HEAD_DIM, PAGE, PAGES, PAGES_PER_SEQ = 8, 16, 64, 16, 2048, 128
BATCH, SEQ = 4, 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # a compile for a described chip can be written to the persistent
    # cache but never read back without the chip (the next run warns)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture
def chip(topo, monkeypatch):
    """One described chip, and the kernels steered onto Mosaic: they ask
    ``jax.default_backend()``, which is still the CPU here."""
    monkeypatch.setattr(flash, "_use_interpret", lambda: False)
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)


def compile_for_chip(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "the compiled program holds no Mosaic kernel"
    return compiled


# every format multi-head; grouped-query in the format with the most to
# go wrong (the others differ from it only in the pool's dtype)
PAGED_CASES = [(f, n_q, 16) for f in ("fp32", "bf16", "int8")
               for n_q in (1, 5)] + [("int8", 1, 4), ("int8", 5, 4)]


@pytest.mark.parametrize("kv_dtype,n_q,kv_heads", PAGED_CASES)
def test_paged_kernel_compiles(chip, kv_dtype, n_q, kv_heads):
    """Decode (one query position) and speculative verify (five) in
    every page format.  Refused before PR 21: int8 (the (1, n_kv) scale
    block of a [num_pages, K_kv] array) and verify (context lengths
    read from SMEM as a vector)."""
    dt = {"fp32": jnp.float32, "bf16": jnp.bfloat16,
          "int8": jnp.int8}[kv_dtype]
    q = chip((SLOTS, n_q, HEADS, HEAD_DIM), jnp.float32)
    pool = chip((PAGES, PAGE, kv_heads * HEAD_DIM), dt)
    tables = chip((SLOTS, PAGES_PER_SEQ), jnp.int32)
    ctx = chip((SLOTS, n_q), jnp.int32)
    if kv_dtype == "int8":
        scales = chip((PAGES, kv_heads), jnp.float32)
        compile_for_chip(
            lambda q, k, v, b, c, ks, vs: paged.paged_attention_multi(
                q, k, v, b, c, k_scales=ks, v_scales=vs),
            q, pool, pool, tables, ctx, scales, scales)
    else:
        compile_for_chip(paged.paged_attention_multi, q, pool, pool,
                         tables, ctx)


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield from _pallas_calls(inner)


def _tiled_bytes(shape, dtype):
    """Bytes of a VMEM array: the two minor dimensions padded to the
    dtype's tile (8 x 128 words of 32 bits)."""
    item = np.dtype(dtype).itemsize
    shape = (1, 1) + tuple(shape)
    sub = 8 * 4 // item
    return int(np.prod(shape[:-2])) * -(-shape[-2] // sub) * sub \
        * -(-shape[-1] // 128) * 128 * item


def test_paged_kernel_at_the_serving_cell_stays_in_scoped_vmem(chip):
    """The kernel at the shape ``gpt2m-serve-backlog`` and ``-chat`` run
    it at: 128 slots, 64 pages a sequence, 5,711 bf16 pages of 16
    tokens, one query position.  It asks for no VMEM limit of its own,
    so Mosaic holds it to the default scoped 16 MiB; what the call
    keeps there (its scratch, and two buffers of every blocked operand)
    is counted from the call itself and has to leave three quarters of
    that free, so the pages a block (``pages_per_block``) cannot
    outgrow it unseen when a pool is wider or a page longer."""
    args = (chip((128, 1, HEADS, HEAD_DIM), jnp.float32),
            chip((5711, PAGE, HEADS * HEAD_DIM), jnp.bfloat16),
            chip((5711, PAGE, HEADS * HEAD_DIM), jnp.bfloat16),
            chip((128, 64), jnp.int32), chip((128, 1), jnp.int32))
    compile_for_chip(paged.paged_attention_multi, *args)
    (call,) = _pallas_calls(
        jax.make_jaxpr(paged.paged_attention_multi)(*args).jaxpr)
    assert call.params["compiler_params"]["mosaic_tpu"] \
        .vmem_limit_bytes is None
    mapping = call.params["grid_mapping"]
    assert mapping.grid == (128,)
    scratch = [v.aval for v in call.params["jaxpr"].invars[
        -mapping.num_scratch_operands:]]
    held = sum(_tiled_bytes(a.shape, a.dtype) for a in scratch
               if "vmem" in str(a))
    blocks = [m.transformed_block_aval for m in mapping.block_mappings]
    held += sum(2 * _tiled_bytes(a.shape, a.dtype) for a in blocks
                if "any" not in str(a))
    ppb = paged.pages_per_block(PAGE, HEADS * HEAD_DIM, jnp.bfloat16, 64)
    assert ppb * PAGE == 256
    # the four page buffers are nearly all of it
    assert 4 * ppb * PAGE * HEADS * HEAD_DIM * 2 <= held < 4 * 2 ** 20, held


# -- the engine's own programs: the pools keep one layout -----------------

_ENGINE_PROGRAMS = {}


def _engine_programs(kv_dtype, spec_k, monkeypatch, decode_ahead=0):
    """``{name: (fn, example shapes)}`` of a ``ServingEngine``'s decode
    and prefill programs at GPT-2-medium widths, as the engine hands
    them to its compiler: ``_compile`` is replaced by a recorder, so
    nothing is compiled for the CPU.  Two layers, a small vocabulary
    and a 128-token prefill keep a program's activations under one int8
    pool, so only a pool-sized temporary can break the bound below."""
    key = (kv_dtype, spec_k, decode_ahead)
    if key not in _ENGINE_PROGRAMS:
        from mxnet_tpu.gluon.model_zoo import gpt
        from mxnet_tpu.serving import ServingEngine
        got = {}
        monkeypatch.setattr(
            ServingEngine, "_compile",
            lambda self, name, fn, examples, extra:
            got.__setitem__(name, (fn, examples)))
        net = gpt.get_gpt(2, HEADS * HEAD_DIM, HEADS, vocab_size=512,
                          max_len=1024 + spec_k)
        net.initialize()
        eng = ServingEngine(net, num_slots=SLOTS, page_size=PAGE,
                            num_pages=PAGES, max_prefill_len=128,
                            max_seq_len=1024, spec_k=spec_k,
                            kv_dtype=kv_dtype, decode_ahead=decode_ahead)
        assert eng._kv[0][0].shape == (PAGES, PAGE, HEADS * HEAD_DIM)
        _ENGINE_PROGRAMS[key] = got
    return _ENGINE_PROGRAMS[key]


def _elements(shape_text):
    return int(np.prod([int(n) for n in shape_text.split(",") if n]))


@pytest.mark.parametrize("kv_dtype,program", [
    (kv_dtype, program) for kv_dtype in ("bf16", "fp32", "int8")
    for program in ("decode", "spec_decode", "prefill")] + [
    # what both backlog cells run: the programs of ``decode_ahead`` 2
    ("bf16", "ahead_decode"), ("bf16", "ahead_prefill")])
def test_engine_program_keeps_the_pools_in_one_layout(
        chip, monkeypatch, kv_dtype, program):
    """The layout the chip keeps a ``[num_pages, page, K_kv * D]`` pool
    in between programs is the one the scatter writes and the paged
    kernel reads: row-major, every pool updated in place, no pool-sized
    ``copy`` and no pool-sized temporary.  (A pool with a KV-head axis
    of its own before a D of 64 is kept pages-minor-most; each program
    then converted every pool to row-major and back, 85% of the serving
    cells' device time before PR 25.)"""
    import re
    fn, examples = _engine_programs(
        kv_dtype, 4 if program == "spec_decode" else 0, monkeypatch,
        decode_ahead=2 if program.startswith("ahead_") else 0)[
            "prefill" if program.endswith("prefill") else "decode"]
    pools = jax.tree_util.tree_leaves(examples[1])
    # matmuls as the chip runs them: the suite's fp32 precision is for
    # numeric checks on the CPU, and here it would hold every weight a
    # second time, split into bf16 parts
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            *jax.tree_util.tree_map(lambda a: chip(a.shape, a.dtype),
                                    examples)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text or program.endswith("prefill")
    pool_elements = PAGES * PAGE * HEADS * HEAD_DIM
    # a layout conversion is a `copy`, alone or as the root of a fusion
    # the compiler names after it.  `copy-start` / `copy-done` move an
    # array between memory spaces and keep its layout: the compiler
    # prefetches this test's 33 MB int8 pools into VMEM at spec_k 4,
    # which a deployment's pool (94 MB at 5,711 pages) is too large for
    copies = [line.strip()[:160] for line in text.splitlines()
              for m in [re.match(r"\s*(?:ROOT )?%(\S+) = \(?\w+"
                                 r"\[([\d,]*)\]\S* ([\w-]+)\(", line)]
              if m and m.group(3) not in ("copy-start", "copy-done")
              and (m.group(3) == "copy" or m.group(1).startswith("copy"))
              and _elements(m.group(2)) >= pool_elements]
    assert not copies, copies
    # wherever the program names an array of a pool's shape (arguments,
    # results, every instruction between), it is row-major
    layouts = re.findall(r"\w+\[%d,%d,%d\](\{[^}]*\})"
                         % (PAGES, PAGE, HEADS * HEAD_DIM), text)
    header = text.split("\n", 1)[0]
    assert header.count("[%d,%d,%d]{2,1,0" % (PAGES, PAGE,
                                              HEADS * HEAD_DIM)) \
        == 2 * len([a for a in pools if a.ndim == 3]), header[:400]
    assert layouts and all(l.startswith("{2,1,0") for l in layouts), \
        sorted(set(layouts))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes \
        < pool_elements * pools[0].dtype.itemsize, mem
    assert mem.alias_size_in_bytes == sum(
        int(np.prod(a.shape)) * a.dtype.itemsize for a in pools), mem
    if program.endswith("prefill") and kv_dtype != "int8":
        # a prefill writes each pool as the slot's consecutive PAGES: one
        # scatter a pool, T_pad / page + 1 updates of a whole page (a
        # mid-page prefix spills into one page more), not T_pad of a row
        t_pad = examples[2].shape[0]
        updates = _pool_scatter_updates(text)
        assert updates == [(t_pad // PAGE + 1, PAGE, HEADS * HEAD_DIM)] \
            * len(pools), updates


def _pool_scatter_updates(text):
    """The shape of the updates operand of every ``scatter`` into an
    array of a pool's shape, from a compiled program's text."""
    import re
    lines = text.splitlines()
    shapes = []
    for at, line in enumerate(lines):
        m = re.match(r"\s*(?:ROOT )?%%\S+ = \w+\[%d,%d,%d\]\S* scatter\("
                     r"%%\S+, %%\S+, %%(\S+)\)"
                     % (PAGES, PAGE, HEADS * HEAD_DIM), line)
        if not m:
            continue
        # the operand is defined above the scatter, in its computation
        defined = next(
            d for l in reversed(lines[:at])
            for d in [re.match(r"\s*%%%s = \w+\[([\d,]*)\]"
                               % re.escape(m.group(1)), l)] if d)
        shapes.append(tuple(int(n) for n in defined.group(1).split(",")))
    return shapes


# -- latent pages and per-slot state side by side ---------------------------

_HYBRID_PROGRAMS = {}


def _hybrid_engine_programs(monkeypatch):
    """``{name: (fn, example shapes)}`` of the engine's programs over
    the hybrid KDA / MLA / routed-expert decoder at its PUBLISHED widths
    and the benchmark cell's engine sizes (128 slots, 64-token pages,
    a 1024-token prefill, 5,120 pages), on three of its layers (KDA +
    dense, KDA + experts, MLA + experts; 128 of 512 experts held).
    Neither weights nor caches are made: the net hands the engine
    shapes, and ``_compile`` / ``_init_cache`` are recorders."""
    if not _HYBRID_PROGRAMS:
        from mxnet_tpu.gluon.model_zoo import ling3
        from mxnet_tpu.serving import ServingEngine
        net = ling3.ling3_flash_vl(layers=[1, 4, 5])
        programs = net.serving_programs()
        programs.decode_params = lambda net, kv_heads=None: \
            ling3.param_tree(net.cfg, lambda path, shape:
                             jax.ShapeDtypeStruct(shape, jnp.bfloat16))
        monkeypatch.setattr(net, "serving_programs", lambda: programs,
                            raising=False)
        monkeypatch.setattr(
            ServingEngine, "_compile",
            lambda self, name, fn, examples, extra:
            _HYBRID_PROGRAMS.__setitem__(name, (fn, examples)))
        make = ServingEngine._init_cache
        monkeypatch.setattr(
            ServingEngine, "_init_cache",
            lambda self, kind: jax.eval_shape(lambda: make(self, kind)))
        eng = ServingEngine(net, num_slots=128, page_size=64,
                            num_pages=5120, max_prefill_len=1024,
                            max_seq_len=5120, kv_dtype="bf16", spec_k=0,
                            decode_ahead=2)
        assert [tuple(a.shape) for a in eng._kv[2]] == [(5120, 64, 640)]
        assert eng._kv[0][0].shape == (129, 32, 128, 128)
    return _HYBRID_PROGRAMS


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_hybrid_engine_program_updates_every_cache_in_place(
        chip, monkeypatch, program):
    """Both kinds of cache are updated where they lie: the per-slot
    recurrent state (``kda_step`` aliases it; a prefill writes one
    slot's rows) and the paged latent pool (a 576-value row padded to
    640 lanes keeps the pool row-major).  No cache-sized ``copy``, no
    cache-sized temporary, every cache aliased; the decode program
    holds the three kernels by name."""
    import re
    fn, examples = _hybrid_engine_programs(monkeypatch)[program]
    caches = jax.tree_util.tree_leaves(examples[1])
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            *jax.tree_util.tree_map(lambda a: chip(a.shape, a.dtype),
                                    examples)).compile()
    text = compiled.as_text()
    if program == "decode":
        # 2 KDA layers, 1 MLA layer, 2 expert layers of two matmuls
        assert text.count("tpu_custom_call") >= 7
    state_elements = 129 * 32 * 128 * 128
    copies = [line.strip()[:160] for line in text.splitlines()
              for m in [re.match(r"\s*(?:ROOT )?%(\S+) = \(?\w+"
                                 r"\[([\d,]*)\]\S* ([\w-]+)\(", line)]
              if m and m.group(3) not in ("copy-start", "copy-done")
              and (m.group(3) == "copy" or m.group(1).startswith("copy"))
              and _elements(m.group(2)) >= state_elements // 2]
    assert not copies, copies
    layouts = re.findall(r"\w+\[5120,64,640\](\{[^}]*\})", text)
    assert layouts and all(l.startswith("{2,1,0") for l in layouts), \
        sorted(set(layouts))
    mem = compiled.memory_analysis()
    # a 1024-token prompt's own activations (the MLA layer's 32 x 1024
    # x 1024 scores alone are 134 MB) stay under ONE layer's state
    assert mem.temp_size_in_bytes < state_elements * 4 // (
        1 if program == "prefill" else 2), mem
    # (the chip pads the 3-row convolution history to its tile: a
    # little more is aliased than the arrays' own bytes)
    assert mem.alias_size_in_bytes >= sum(
        int(np.prod(a.shape)) * a.dtype.itemsize for a in caches), mem


_RING_PROGRAMS = {}


def _ring_engine_programs(monkeypatch):
    """``{name: (fn, example shapes)}`` of the engine's programs over
    K-EXAONE's share at its PUBLISHED widths and the benchmark cell's
    own engine sizes (``perfbench/workloads/kexaone-serve-mixedlen.json``:
    64 slots, 64-token pages, chunks of 2,048 rows), every kept layer.
    Neither weights nor caches are made (``_hybrid_engine_programs``)."""
    if not _RING_PROGRAMS:
        from mxnet_tpu.gluon.model_zoo import exaone_moe
        from mxnet_tpu.serving import ServingEngine
        with open(os.path.join(REPO, "perfbench", "workloads",
                               "kexaone-serve-mixedlen.json")) as f:
            sizes = json.load(f)["engine"]
        net = exaone_moe.k_exaone()
        programs = net.serving_programs()
        programs.decode_params = lambda net, kv_heads=None: \
            exaone_moe.param_tree(net.cfg, lambda path, shape:
                                  jax.ShapeDtypeStruct(shape, jnp.bfloat16))
        monkeypatch.setattr(net, "serving_programs", lambda: programs,
                            raising=False)
        monkeypatch.setattr(
            ServingEngine, "_compile",
            lambda self, name, fn, examples, extra:
            _RING_PROGRAMS.__setitem__(name, (fn, examples)))
        make = ServingEngine._init_cache
        monkeypatch.setattr(
            ServingEngine, "_init_cache",
            lambda self, kind: jax.eval_shape(lambda: make(self, kind)))
        _RING_PROGRAMS["engine"] = ServingEngine(net, **sizes)
    return _RING_PROGRAMS


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_ring_engine_programs_compile_at_the_published_widths(
        chip, monkeypatch, program):
    """Both programs of the model with window rings beside pages compile
    for the described v5e at the cell's sizes: the paged kernel at 64
    query heads over 8 K/V heads of 128 and the grouped matmul by name,
    every cache aliased (pools and rings are updated where they lie),
    and arguments, temporaries and fresh outputs inside the chip's 16
    GiB.  The engine's sizing units are the configuration file's."""
    got = _ring_engine_programs(monkeypatch)
    eng = got["engine"]
    with open(os.path.join(REPO, "perfbench", "configs",
                           "k-exaone-236b-a23b.json")) as f:
        cache = json.load(f)["cache"]
    assert eng.kv_bytes_per_token == cache["page_bytes_per_token"] == 4096
    assert eng.state_bytes_per_slot == cache["ring_bytes_per_slot"] \
        == 4 * 2 * 128 * 2048
    assert [tuple(a.shape) for a in eng._kv[4]] \
        == [(eng.alloc.num_pages, 64, 1024)] * 2
    assert [tuple(a.shape) for a in eng._kv[0]] == [(65, 128, 1024)] * 2
    fn, examples = got[program]
    caches = jax.tree_util.tree_leaves(examples[1])
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            *jax.tree_util.tree_map(lambda a: chip(a.shape, a.dtype),
                                    examples)).compile()
    text = compiled.as_text()
    assert "moe_gmm" in text
    if program == "decode":
        assert "paged_decode" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        int(np.prod(a.shape)) * a.dtype.itemsize for a in caches), mem
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + max(0, mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(program, "arguments %.2f GB, temporaries %.2f GB"
          % (mem.argument_size_in_bytes / 1e9, mem.temp_size_in_bytes / 1e9))
    assert held < 15.5 * 2 ** 30, mem


@pytest.mark.parametrize("kernel,groups,rows", [
    ("dsa_index", 16, 1), ("dsa_index", 64, 32),
    ("mla_sparse", 16, 1), ("mla_sparse", 64, 1),
    ("dsa_select", 16, 1), ("dsa_select", 2048, 1)])
def test_sparse_latent_attention_kernels_compile(chip, kernel, groups,
                                                 rows):
    """The indexer's scoring over paged keys, the selection and the
    latent attention over a list of rows, at DeepSeek-V3.2's published
    widths and the benchmark cell's sizes (64-token pages, 512 pages a
    sequence, 2,048 selected rows): one query row a slot (decode) and a
    block of a prefill chunk's rows (the selection: all 2,048)."""
    sla = importlib.import_module(
        "mxnet_tpu.ops.pallas.sparse_latent_attention")
    i32 = jnp.int32
    if kernel == "dsa_index":
        compiled = compile_for_chip(
            sla.dsa_index, chip((groups, rows, 64, 128), jnp.bfloat16),
            chip((groups, rows, 64), jnp.float32),
            chip((8192, 64, 128), jnp.bfloat16), chip((16, 512), i32),
            chip((groups,), i32), chip((groups,), i32),
            chip((groups,), i32))
    elif kernel == "dsa_select":
        compiled = compile_for_chip(
            lambda s, c: sla.dsa_select(s, c, 2048),
            chip((groups, 32768), jnp.float32), chip((groups,), i32))
    else:
        compiled = compile_for_chip(
            lambda q, r, n: sla.mla_sparse(q, r, n, 512, 0.1),
            chip((groups, 128, 640), jnp.float32),
            chip((groups, 2048, 640), jnp.bfloat16), chip((groups,), i32))
    assert kernel in compiled.as_text()


@pytest.mark.parametrize("head_dim,packed", [(64, False), (64, True),
                                             (128, False)])
def test_flash_fwd_bwd_compiles(chip, head_dim, packed):
    """Forward and split backward at T = 2048, with and without
    segment ids (refused before PR 21: the (1, block) slice of the
    [B, T] ids)."""
    q = chip((BATCH, HEADS, SEQ, head_dim), jnp.bfloat16)
    seg = chip((BATCH, SEQ), jnp.int32)

    def grads(q, k, v, seg=None):
        return jax.grad(
            lambda q, k, v: flash.flash_attention(
                q, k, v, causal=True, segment_ids=seg)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
    compiled = compile_for_chip(grads, *((q, q, q, seg) if packed
                                         else (q, q, q)))
    # forward, dq and dk/dv: three kernels
    assert compiled.as_text().count("tpu_custom_call") >= 3


def test_flash_compiles_on_a_mesh(topo, monkeypatch):
    """Mosaic kernels cannot be partitioned automatically: under a
    dp x tp mesh the op shard_maps itself over batch and heads.  Refused
    before PR 21 ("wrap the call in a shard_map")."""
    monkeypatch.setattr(flash, "_use_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "tp"))
    q = jax.ShapeDtypeStruct(
        (BATCH, HEADS, SEQ, HEAD_DIM), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("dp", "tp", None, None)))
    with jax.set_mesh(mesh):
        compiled = compile_for_chip(
            lambda q, k, v: flash.flash_attention(q, k, v, causal=True),
            q, q, q)
    assert "all-gather" not in compiled.as_text()


def test_fused_layer_norm_residual_compiles(chip):
    x = chip((BATCH * SEQ, 1024), jnp.bfloat16)
    g = chip((1024,), jnp.bfloat16)
    compile_for_chip(
        lambda x, r, g, b: jax.grad(
            lambda x, r, g, b: layer_norm.fused_layer_norm_residual(
                x, r, g, b).astype(jnp.float32).sum(),
            argnums=(0, 1, 2, 3))(x, r, g, b), x, x, g, g)


# -- chip_smoke.py --tiny: the CPU rehearsal of the chip run ----------------

def _smoke(*argv, **env_extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "MXTPU_FAULT")}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")] + list(argv),
        env=env, capture_output=True, text=True, timeout=600)
    lines = [l for l in r.stdout.splitlines() if l.strip()]
    return r, [json.loads(l) for l in lines if l.startswith("{")]


def test_chip_smoke_tiny_rehearsal(tmp_path):
    r, docs = _smoke("--tiny",
                     JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    # the last line is the verdict and nothing else, and it never
    # pretends: the rehearsal says cpu
    assert r.stdout.strip().splitlines()[-1] == json.dumps(docs[-1])
    assert docs[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": docs[-1]["device"]["kind"],
        "count": 1}}
    phases = {}
    for d in docs[:-1]:
        phases.setdefault(d["phase"], []).append(d)
    for name in ("train_resnet", "train_gpt"):
        (p,) = phases[name]
        assert p["losses"][-1] < p["losses"][0]
        assert p["recompiles"] == 0
    assert phases["train_resnet"][0]["dispatches_per_step"] == 1.0
    served = {(p["kv_dtype"], p["spec_k"]) for p in phases["serve"]}
    assert served == {(f, k) for f in ("fp32", "bf16", "int8")
                      for k in (0, 4)}
    assert phases["start"][0]["compile_cache_dir"] == \
        str(tmp_path / "cache")


def test_chip_smoke_fails_when_a_phase_fails_or_no_chip():
    # without --tiny the script is about a TPU and finds none
    r, docs = _smoke()
    assert r.returncode != 0
    assert docs[-1]["ok"] is False
    assert docs[-1]["device"]["platform"] == "cpu"
    # a failed admission in the serving phase (an existing fault site)
    # must fail the run, not be passed over
    r, docs = _smoke("--tiny", MXTPU_FAULT="serve.prefill.error:1")
    assert r.returncode != 0, r.stdout[-2000:]
    assert docs[-1]["ok"] is False and "error" in docs[-1]
    assert not any(d.get("ok") for d in docs)
