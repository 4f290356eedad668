"""GPT-2-class decoder language models — the transformer flagship.

TPU-native addition: the 2017 reference predates attention entirely (its
sequence story is bucketing, /root/reference/python/mxnet/module/
bucketing_module.py), but a TPU framework's MFU headline lives in
transformer matmuls, so the model zoo carries a decoder LM family built
on the Pallas flash-attention kernel (ops/pallas/flash_attention.py)
through the Gluon layer API (nn.FlashSelfAttention).

Design notes (all MXU-motivated):
- pre-LN residual blocks (stable in bf16 without warmup tricks);
- gelu(tanh) MLP at 4x width — two large [T, d]x[d, 4d] matmuls XLA
  tiles straight onto the systolic array;
- weight-tied embedding/head: logits ride one [B·T, d] x [d, V]
  FullyConnected against the embedding table, so the V-sized matmul
  appears exactly once per step;
- vocab padded to a multiple of 128 by the factory functions (lane
  dimension of the MXU; 50257 → 50304 exactly like megatron-era configs).

Weights save/load in the reference's V2 binary format like every other
zoo model (ndarray/serialization.py), so the fine-tune workflow
(example/language-model) round-trips through ``Module.load``.
"""
from __future__ import annotations

import functools

from .. import nn
from ..block import HybridBlock
from ...telemetry import device_scope
# the page-at-a-time write of one slot's rows, shared with the other
# served models
from .decoder_blocks import page_scatter as _page_scatter

__all__ = ["GPTBlock", "GPTLM", "get_gpt", "gpt2_tiny",
           "gpt2_tiny_moe", "gpt2_small", "gpt2_medium",
           "pack_sequences", "packed_positions", "generate",
           "decode_params", "paged_decode_step", "paged_prefill",
           "sample_tokens"]


class GPTBlock(HybridBlock):
    """One pre-LN transformer decoder block.

    ``moe_experts > 0`` swaps the dense gelu MLP for a GShard-style
    top-1-gated mixture of experts (parallel/moe.py): off-mesh the
    experts run locally (``moe_dense``); after
    :meth:`GPTLM.expert_parallel` they shard over the ``ep`` mesh axis
    with all_to_all dispatch — the flagship's fifth mesh axis.

    Scope note: routing is top-1 with a capacity bound and NO auxiliary
    load-balancing loss — adequate at the tested scales (the gate
    trains through the combine weights); large-scale MoE pretraining
    conventionally adds a Switch-style balance term, which needs the
    per-block gate logits plumbed to the loss (a possible extension)."""

    def __init__(self, units, num_heads, mlp_ratio=4, dropout=0.0,
                 moe_experts=0, moe_capacity=2.0, **kwargs):
        super().__init__(**kwargs)
        self._dropout = dropout
        self._moe = int(moe_experts)
        self._moe_capacity = moe_capacity
        self._moe_mesh = None
        with self.name_scope():
            self.ln1 = nn.LayerNorm(in_channels=units, prefix="ln1_")
            self.attn = nn.FlashSelfAttention(units, num_heads,
                                              causal=True,
                                              in_units=units,
                                              prefix="attn_")
            self.ln2 = nn.LayerNorm(in_channels=units, prefix="ln2_")
            if self._moe:
                e, f = self._moe, mlp_ratio * units
                self.moe_gate = self.params.get(
                    "moe_gate_weight", shape=(units, e))
                self.moe_w1 = self.params.get("moe_fc1_weight",
                                              shape=(e, units, f))
                self.moe_b1 = self.params.get("moe_fc1_bias",
                                              shape=(e, f))
                self.moe_w2 = self.params.get("moe_fc2_weight",
                                              shape=(e, f, units))
                self.moe_b2 = self.params.get("moe_fc2_bias",
                                              shape=(e, units))
            else:
                self.fc1 = nn.Dense(mlp_ratio * units, flatten=False,
                                    in_units=units, prefix="fc1_")
                self.fc2 = nn.Dense(units, flatten=False,
                                    in_units=mlp_ratio * units,
                                    prefix="fc2_")

    def expert_parallel(self, mesh, axis="ep", batch_axis=None):
        """Shard this block's experts over ``mesh``'s ``axis`` —
        tokens all_to_all to their expert's device (parallel.moe_apply).
        Traced path only; ``mesh=None`` restores local experts."""
        self._moe_mesh = (None if mesh is None
                          else (mesh, axis, batch_axis))
        self._cached_op = None

    def _moe_forward(self, F, h, moe_params):
        import jax
        from ... import parallel as _par
        from ... import autograd as _ag
        gate_w, w1, b1, w2, b2 = moe_params
        if hasattr(h, "_data"):
            if _ag.is_recording():
                raise RuntimeError(
                    "MoE blocks do not support the imperative autograd "
                    "tape; train through functionalize/jit")
            if self._moe_mesh is not None:
                raise RuntimeError(
                    "imperative inference with expert_parallel active: "
                    "call expert_parallel(None) first (the ep shard_map "
                    "needs the jit/functionalize path)")

        def _raw(a):
            return a._data if hasattr(a, "_data") else a
        hj = _raw(h)
        b, t, d = hj.shape
        flat = hj.reshape(b * t, d)
        args = tuple(_raw(a) for a in (gate_w, w1, b1, w2, b2))
        if self._moe_mesh is None:
            out = _par.moe.moe_dense(
                flat, *args, capacity_factor=self._moe_capacity,
                act=jax.nn.gelu)
        else:
            mesh, axis, batch_axis = self._moe_mesh
            out = _par.moe_apply(
                flat, *args, mesh=mesh, axis=axis,
                batch_axis=batch_axis,
                capacity_factor=self._moe_capacity, act=jax.nn.gelu)
        out = out.reshape(b, t, d)
        if hasattr(h, "_data"):
            # imperative (inference) caller: rewrap so the residual add
            # stays in the NDArray domain
            from ...ndarray import NDArray
            return NDArray(out)
        return out

    def hybrid_forward(self, F, x, segments=None, moe_gate=None,
                       moe_w1=None, moe_b1=None, moe_w2=None,
                       moe_b2=None):
        if segments is None:
            h = self.attn(self.ln1(x))
        else:
            h = self.attn(self.ln1(x), segments)
        if self._dropout:
            h = F.Dropout(h, p=self._dropout)
        x = x + h
        if self._moe:
            h = self._moe_forward(F, self.ln2(x),
                                  (moe_gate, moe_w1, moe_b1, moe_w2,
                                   moe_b2))
        else:
            h = self.fc2(F.Activation(self.fc1(self.ln2(x)),
                                      act_type="gelu"))
        if self._dropout:
            h = F.Dropout(h, p=self._dropout)
        return x + h


class GPTLM(HybridBlock):
    """Decoder-only LM: token + learned position embeddings, N blocks,
    final LayerNorm, tied output head.

    Input: int token ids [B, T] (T ≤ max_len); output: logits [B, T, V].
    """

    def __init__(self, vocab_size, num_layers, units, num_heads,
                 max_len=1024, dropout=0.0, remat=False, moe_experts=0,
                 moe_capacity=2.0, **kwargs):
        super().__init__(**kwargs)
        self._vocab = vocab_size
        self._units = units
        self._max_len = max_len
        self._dropout = dropout
        self._remat = remat
        with self.name_scope():
            self.wte = self.params.get("wte_weight",
                                       shape=(vocab_size, units))
            self.wpe = self.params.get("wpe_weight",
                                       shape=(max_len, units))
            self.blocks = nn.HybridSequential(prefix="h_")
            with self.blocks.name_scope():
                for _ in range(num_layers):
                    self.blocks.add(GPTBlock(units, num_heads,
                                             dropout=dropout,
                                             moe_experts=moe_experts,
                                             moe_capacity=moe_capacity))
            self.ln_f = nn.LayerNorm(in_channels=units, prefix="lnf_")

    def expert_parallel(self, mesh, axis="ep", batch_axis=None):
        """MoE switch: every block's experts shard over ``mesh``'s
        ``axis`` (tokens all_to_all to their expert's device —
        parallel/moe.py); ``mesh=None`` restores local experts.  Only
        meaningful when built with ``moe_experts > 0``."""
        for blk in self.blocks._children:
            blk.expert_parallel(mesh, axis=axis, batch_axis=batch_axis)
        self._cached_op = None

    def sequence_parallel(self, mesh, axis="sp", batch_axis=None,
                          impl=None):
        """Long-context switch: every block's attention becomes RING
        attention over ``mesh``'s ``axis`` (sequence dim sharded,
        nearest-neighbour ICI hops — parallel/ring_attention.py), so
        ``gpt2_small(max_len=32k)`` trains on an sp mesh through this
        one call; packing segment ids keep riding the forward and are
        threaded through the ring hops.  Shard the [B, T] token batch
        with T over ``axis`` (and B over dp/``batch_axis`` if
        composing); everything outside attention is position-local, so
        XLA GSPMD keeps it sharded.  ``mesh=None`` restores the
        single-device flash kernel."""
        for blk in self.blocks._children:
            blk.attn.sequence_parallel(mesh, axis=axis,
                                       batch_axis=batch_axis, impl=impl)
            blk._cached_op = None
        self._cached_op = None

    def serving_programs(self):
        """What ``ServingEngine`` needs of a model, in one object: this
        module's paged programs over K/V page pools in every layer."""
        from ...serving.programs import ServingPrograms
        return ServingPrograms(
            n_heads=self.blocks._children[0].attn._num_heads,
            max_len=self._max_len, decode_params=decode_params,
            decode_step=paged_decode_step,
            spec_decode_step=paged_spec_decode_step,
            prefill=paged_prefill)

    def hybrid_forward(self, F, tokens, segments=None, wte=None,
                       wpe=None):
        t = tokens.shape[1]
        if t > self._max_len:
            raise ValueError("sequence length %d exceeds max_len %d"
                             % (t, self._max_len))
        h = F.Embedding(tokens, wte, input_dim=self._vocab,
                        output_dim=self._units)
        if segments is None:
            h = h + F.slice_axis(wpe, axis=0, begin=0, end=t)
        else:
            # packed rows: positions restart at each segment boundary so
            # every document trains with the same wpe rows it would see
            # standalone (segments are contiguous per row)
            pos = packed_positions(segments)
            h = h + F.Embedding(pos, wpe, input_dim=self._max_len,
                                output_dim=self._units)
        if self._dropout:
            h = F.Dropout(h, p=self._dropout)
        if self._remat and not hasattr(h, "_data"):
            # per-block rematerialisation: the backward recomputes each
            # block's activations instead of keeping them in HBM —
            # memory O(L·T·d) -> O(T·d) + one extra forward of FLOPs,
            # the standard long-sequence trade.  Applies on the TRACED
            # path only (hybrid values are jnp arrays there, which
            # jax.checkpoint needs); the imperative NDArray path records
            # op-by-op on the autograd tape, where remat has no meaning.
            import jax
            for blk in self.blocks._children:
                if segments is None:
                    h = jax.checkpoint(lambda x, b=blk: b(x))(h)
                else:
                    h = jax.checkpoint(
                        lambda x, s, b=blk: b(x, s))(h, segments)
        elif segments is None:
            h = self.blocks(h)
        else:
            # packed rows: thread the segment ids into every block's
            # attention (HybridSequential can't forward extra inputs)
            for blk in self.blocks._children:
                h = blk(h, segments)
        h = self.ln_f(h)
        # tied head: one [B·T, d] x [d, V] matmul against the embedding
        return F.FullyConnected(h, wte, num_hidden=self._vocab,
                                no_bias=True, flatten=False)


def _pad_vocab(v, mult=128):
    return (v + mult - 1) // mult * mult


def packed_positions(segments):
    """Per-row positions that RESTART at each segment boundary — the
    wpe rows a packed document sees equal its standalone ones.  ONE
    copy of this math: GPTLM's forward and the pipeline stage cutter
    (parallel/gpt_pp.py) both call it.  segments [B, T] -> int32 [B, T]."""
    import jax.numpy as jnp
    seg = segments if not hasattr(segments, "_data") else segments._data
    t = seg.shape[1]
    idx = jnp.arange(t)[None, :]
    change = jnp.concatenate(
        [jnp.ones_like(seg[:, :1], dtype=bool),
         seg[:, 1:] != seg[:, :-1]], axis=1)
    from jax import lax as _lax
    start = _lax.cummax(jnp.where(change, idx, 0), axis=1)
    return (idx - start).astype(jnp.int32)


def pack_sequences(docs, seq_len, pad_id=0):
    """Pack variable-length token sequences into fixed [N, seq_len] rows
    with segment ids — the TPU-first replacement for the reference's
    bucketing (static shapes keep ONE compiled program; the flash
    kernel's ``segment_ids`` mask keeps documents independent).

    ``docs``: iterable of 1-d int token arrays.  Returns (tokens,
    segments): int32 [N, seq_len] each.  Segments are 1-based per row;
    0 marks padding (give the attention mask a pad id no real segment
    uses and pad positions attend nothing real).

    A document that would not fit the current row's remaining space
    starts a FRESH row rather than being split — a split continuation
    restarts at position 0 with no attention to its earlier tokens
    (mid-document context truncation).  Only documents longer than
    ``seq_len`` itself are ever split (round-4 ADVICE).
    """
    import numpy as np
    rows, segs = [], []
    cur = np.full(seq_len, pad_id, np.int32)
    cur_seg = np.zeros(seq_len, np.int32)
    pos, seg_id = 0, 1
    for doc in docs:
        doc = np.asarray(doc, np.int32)
        if 0 < seq_len - pos < doc.size <= seq_len:
            rows.append(cur); segs.append(cur_seg)
            cur = np.full(seq_len, pad_id, np.int32)
            cur_seg = np.zeros(seq_len, np.int32)
            pos, seg_id = 0, 1
        while doc.size:
            if pos == seq_len:
                rows.append(cur); segs.append(cur_seg)
                cur = np.full(seq_len, pad_id, np.int32)
                cur_seg = np.zeros(seq_len, np.int32)
                pos, seg_id = 0, 1
            take = min(doc.size, seq_len - pos)
            cur[pos:pos + take] = doc[:take]
            cur_seg[pos:pos + take] = seg_id
            pos += take
            doc = doc[take:]
        seg_id += 1
    if pos:
        rows.append(cur); segs.append(cur_seg)
    return np.stack(rows), np.stack(segs)


# ---------------------------------------------------------------------------
# KV-cache incremental decoding
# ---------------------------------------------------------------------------

def _decode_params(net):
    """Index the net's current parameter values by layer for the decode
    path, walking the LIVE child blocks (``net.blocks[i].attn.qkv
    .weight`` etc.) — no name templates, so custom prefixes, subclassed
    blocks that keep the attribute layout, and ``use_bias=False`` all
    work, and a renamed child cannot silently desync generate() from
    the training forward (round-4 VERDICT weak #5 / ADVICE)."""
    import jax.numpy as jnp

    def g(param):
        return param.data()._data.astype(jnp.float32)

    def bias(dense):
        if dense.bias is None:
            return jnp.zeros((dense._units,), jnp.float32)
        return g(dense.bias)

    layers = []
    for blk in net.blocks._children:
        lp = {
            "ln1_g": g(blk.ln1.gamma), "ln1_b": g(blk.ln1.beta),
            "qkv_w": g(blk.attn.qkv.weight), "qkv_b": bias(blk.attn.qkv),
            "out_w": g(blk.attn.out_proj.weight),
            "out_b": bias(blk.attn.out_proj),
            "ln2_g": g(blk.ln2.gamma), "ln2_b": g(blk.ln2.beta)}
        if getattr(blk, "_moe", 0):
            lp["moe"] = tuple(g(p) for p in (
                blk.moe_gate, blk.moe_w1, blk.moe_b1, blk.moe_w2,
                blk.moe_b2))
        else:
            lp.update({"fc1_w": g(blk.fc1.weight),
                       "fc1_b": bias(blk.fc1),
                       "fc2_w": g(blk.fc2.weight),
                       "fc2_b": bias(blk.fc2)})
        layers.append(lp)
    return {"wte": g(net.wte), "wpe": g(net.wpe),
            "lnf_g": g(net.ln_f.gamma), "lnf_b": g(net.ln_f.beta),
            "layers": layers}


def _ln(x, g, b, eps=1e-5):
    import jax.numpy as jnp
    from jax import lax
    with device_scope("norm"):
        mu = x.mean(-1, keepdims=True)
        var = jnp.square(x - mu).mean(-1, keepdims=True)
        return (x - mu) * lax.rsqrt(var + eps) * g + b


def _block_qkv(lp, x, n_heads):
    """Shared per-layer front half: LN1 + fused head-major qkv.
    x [B, T, C] -> q, k, v [B, H, T, D] (layout from basic_layers.py's
    FlashSelfAttention; the ONE copy _prefill and _decode_one share)."""
    b, t, c = x.shape
    d = c // n_heads
    with device_scope("attn.proj"):
        h = _ln(x, lp["ln1_g"], lp["ln1_b"])
        qkv = (h @ lp["qkv_w"].T + lp["qkv_b"]).reshape(
            b, t, n_heads, 3, d)
        qkv = qkv.transpose(0, 2, 1, 3, 4)       # [B, H, T, 3, D]
        return qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]


def _block_finish(lp, x, o):
    """Shared per-layer back half: attention output o [B, T, C] ->
    residual + LN2 + MLP (dense gelu or mixture of experts) +
    residual."""
    import jax
    with device_scope("attn.out"):
        x = x + o @ lp["out_w"].T + lp["out_b"]
    with device_scope("mlp"):
        h = _ln(x, lp["ln2_g"], lp["ln2_b"])
        if "moe" in lp:
            from ...parallel.moe import moe_dense
            b, t, c = h.shape
            gate_w, w1, b1, w2, b2 = lp["moe"]
            # DROPLESS at inference (capacity == token count): GShard's
            # capacity dropping is a training-throughput trade whose
            # queue positions couple tokens across the batch — decode
            # must stay position-local to match the cache-free forward
            out = moe_dense(h.reshape(b * t, c), gate_w, w1, b1, w2, b2,
                            capacity_factor=float(w1.shape[0]),
                            act=jax.nn.gelu)
            return x + out.reshape(b, t, c)
        h = jax.nn.gelu(h @ lp["fc1_w"].T + lp["fc1_b"],
                        approximate=True)
        return x + h @ lp["fc2_w"].T + lp["fc2_b"]


def _decode_one(p, tok, pos, caches, n_heads):
    """One decode step: tok [B] int32, pos scalar, caches list of
    (k_cache, v_cache) [B, H, T_max, D].  Returns (logits [B, V],
    new caches)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    x = p["wte"][tok][:, None] + lax.dynamic_index_in_dim(
        p["wpe"], pos, 0, keepdims=False)              # [B, 1, C]
    b, _, c = x.shape
    d = c // n_heads
    t_max = caches[0][0].shape[2]
    new_caches = []
    # keys at position > pos are zeros in the cache; mask them
    mask = (jnp.arange(t_max) <= pos)[None, None, :]
    for lp, (kc, vc) in zip(p["layers"], caches):
        q, k, v = _block_qkv(lp, x, n_heads)           # [B, H, 1, D]
        kc = lax.dynamic_update_index_in_dim(kc, k, pos, 2)
        vc = lax.dynamic_update_index_in_dim(vc, v, pos, 2)
        s = jnp.einsum("bhd,bhtd->bht", q[:, :, 0], kc) / jnp.sqrt(
            jnp.float32(d))
        s = jnp.where(mask, s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bht,bhtd->bhd", pr, vc).reshape(b, 1, c)
        x = _block_finish(lp, x, o)
        new_caches.append((kc, vc))
    x = _ln(x[:, 0], p["lnf_g"], p["lnf_b"])
    return x @ p["wte"].T, new_caches


def _prefill(p, toks, t_max, n_heads):
    """One batched causal pass over the prompt: fills every layer's KV
    cache for positions [0, T0) and returns the last position's logits
    — replacing T0 sequential decode steps with one forward (the
    standard prefill/decode split; same parameter dict and layer math
    as ``_decode_one``, pinned together by the generate-vs-recompute
    equality tests)."""
    import jax
    import jax.numpy as jnp
    b, t0 = toks.shape
    x = p["wte"][toks] + p["wpe"][:t0][None]           # [B, T0, C]
    c = x.shape[-1]
    d = c // n_heads
    causal = jnp.tril(jnp.ones((t0, t0), bool))[None, None]
    pad_t = t_max - t0
    caches = []
    for lp in p["layers"]:
        q, k, v = _block_qkv(lp, x, n_heads)           # [B, H, T0, D]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
        s = jnp.where(causal, s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", pr, v)
        o = o.transpose(0, 2, 1, 3).reshape(b, t0, c)
        x = _block_finish(lp, x, o)
        kc = jnp.pad(k, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
        caches.append((kc, vc))
    x = _ln(x[:, -1], p["lnf_g"], p["lnf_b"])          # [B, C]
    return x @ p["wte"].T, caches


def _filter_logits(logits, top_k, top_p):
    """Static top-k / nucleus filtering (jit-compatible: sort-based).
    Callers pass TEMPERATURE-SCALED logits — the nucleus must be the
    top_p mass of the actual sampling distribution."""
    import jax
    import jax.numpy as jnp
    if top_k:
        k = min(top_k, logits.shape[-1])
        kth = jax.lax.top_k(logits, k)[0][..., -1:]
        logits = jnp.where(logits < kth, -1e30, logits)
    if top_p:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest set whose mass >= top_p: keep entries whose cumsum
        # BEFORE them is < top_p
        keep_sorted = (cum - probs) < top_p
        cutoff = jnp.min(jnp.where(keep_sorted, sorted_logits, jnp.inf),
                         axis=-1, keepdims=True)
        logits = jnp.where(logits < cutoff, -1e30, logits)
    return logits


@functools.lru_cache(maxsize=32)
def _decode_runner(n_heads, greedy, n_new, t0, t_max,
                   top_k=0, top_p=0.0):
    """Build (once per static configuration) the jitted prefill+decode
    runner.  The prompt is consumed by ONE batched causal pass
    (``_prefill`` — fills all caches and yields the first new token's
    logits); only the n_new-1 truly sequential steps run in the
    ``lax.scan`` — long prompts cost one forward, not T0 scan
    iterations.  Params, prompt, key, and temperature are traced
    ARGUMENTS, so repeated generate() calls — and further training
    between them — hit jit's compile cache."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def pick(logits, key, temp):
        if greedy:
            return logits.argmax(-1).astype(jnp.int32), key
        key, sub = jax.random.split(key)
        scaled = _filter_logits(logits / temp, top_k, top_p)
        return (jax.random.categorical(sub, scaled, axis=-1)
                .astype(jnp.int32), key)

    def step(p, temp, carry, pos):
        caches, tok, key = carry
        logits, caches = _decode_one(p, tok, pos, caches, n_heads)
        nxt, key = pick(logits, key, temp)
        return (caches, nxt, key), nxt

    @jax.jit
    def run(p, prompt, key, temp):
        logits0, caches = _prefill(p, prompt, t_max, n_heads)
        first, key = pick(logits0, key, temp)
        if n_new == 1:
            return first[None]
        positions = jnp.arange(t0, t0 + n_new - 1)
        _, toks = lax.scan(functools.partial(step, p, temp),
                           (caches, first, key), positions)
        return jnp.concatenate([first[None], toks])  # [n_new, B]

    return run


def generate(net, prompt_ids, n_new, temperature=0.0, seed=0, top_k=0,
             top_p=0.0):
    """Autoregressive generation with a KV cache — ONE batched prefill
    pass over the prompt, then O(1) work per new token (vs the O(T²)
    full-context recompute).  The decode loop is one jitted
    ``lax.scan`` with static shapes (the cache is ``max_len`` long),
    TPU-friendly by construction; the compiled runner is cached per
    (shape, config), so repeated calls don't retrace.

    ``prompt_ids``: int array [B, T0]; returns int array
    [B, T0 + n_new].  temperature 0 = greedy; otherwise samples with
    ``jax.random`` (deterministic per ``seed``), optionally filtered to
    the ``top_k`` highest logits and/or the ``top_p`` nucleus.
    """
    import numpy as np
    import jax
    import jax.numpy as jnp

    prompt = jnp.asarray(np.asarray(prompt_ids), jnp.int32)
    bsz, t0 = prompt.shape
    t_max = net._max_len
    if n_new < 1:
        raise ValueError("n_new must be >= 1, got %d" % n_new)
    if t0 + n_new > t_max:
        raise ValueError("prompt %d + new %d exceeds max_len %d"
                         % (t0, n_new, t_max))
    n_heads = net.blocks._children[0].attn._num_heads
    p = _decode_params(net)

    greedy = temperature <= 0
    run = _decode_runner(n_heads, greedy, n_new, t0, t_max,
                         0 if greedy else int(top_k),
                         0.0 if greedy else float(top_p))
    toks = run(p, prompt, jax.random.PRNGKey(seed),
               jnp.float32(max(temperature, 1e-6)))
    return np.asarray(jnp.concatenate([prompt, toks.T], axis=1))


# ---------------------------------------------------------------------------
# paged / slot-addressable decoding (the serving runtime's model half)
# ---------------------------------------------------------------------------
#
# mxnet_tpu/serving/ keeps KV history in fixed-size pages with per-slot
# block tables (serving/kv_cache.py) so requests of any length share one
# decode program.  These two pure functions are the model's contract with
# that runtime: same parameter dict (_decode_params) and per-layer math
# (_block_qkv/_block_finish) as generate()'s dense-cache path — the
# equivalence tests in tests/test_serving.py pin the three paths (dense
# generate, paged decode, training forward) together.


def _apply_precision(p, policy):
    """Cast a decode-param tree per a PrecisionPolicy (None = as-is)."""
    if policy is None:
        return p
    out = {k: policy.cast_params(v, "embed" if k in ("wte", "wpe")
                                 else "final")
           for k, v in p.items() if k != "layers"}
    out["layers"] = [policy.cast_params(lp, "blocks.%d" % i)
                     for i, lp in enumerate(p["layers"])]
    return out


def decode_params(net, kv_heads=None, policy=None):
    """Public alias of the decode-path parameter indexer (fp32 values
    keyed by layer) — the tree ``paged_decode_step``/``paged_prefill``
    take as ``p``, and what :class:`mxnet_tpu.serving.ServingEngine`
    snapshots at construction.

    ``policy``: optional :class:`mxnet_tpu.precision.PrecisionPolicy`.
    Each transformer block's leaves are cast to the policy's resolved
    ``param`` dtype for ``blocks.<i>``; the embeddings and final LN
    resolve under ``embed`` / ``final`` — serving precision is one
    instance of the general per-layer policy, with the KV-page dtype
    (``policy.kv_dtype``) handled separately by the engine's pools.

    ``kv_heads``: serve with ``K_kv <= H`` KV heads (grouped-query /
    multi-query attention, ISSUE 15).  ``None`` or ``H`` keeps the
    trained multi-head layout bit-identical to before; a smaller value
    MEAN-POOLS each group's K/V projection rows (the standard
    MHA->GQA uptraining conversion, Ainslie et al.) so the serving KV
    pools shrink ``H / K_kv``-fold.  The converted layer dicts carry
    split ``q_w``/``k_w``/``v_w`` (+biases) instead of the fused
    ``qkv_w``."""
    p = _decode_params(net)
    if kv_heads is None:
        return _apply_precision(p, policy)
    n_heads = net.blocks._children[0].attn._num_heads
    kv_heads = int(kv_heads)
    if kv_heads == n_heads:
        return _apply_precision(p, policy)
    if kv_heads < 1 or n_heads % kv_heads:
        raise ValueError(
            "kv_heads must divide the model's %d query heads, got %d"
            % (n_heads, kv_heads))
    d = int(p["wte"].shape[1]) // n_heads
    g = n_heads // kv_heads
    for lp in p["layers"]:
        w = lp.pop("qkv_w").reshape(n_heads, 3, d, -1)
        b = lp.pop("qkv_b").reshape(n_heads, 3, d)
        lp["q_w"] = w[:, 0].reshape(n_heads * d, -1)
        lp["q_b"] = b[:, 0].reshape(n_heads * d)
        for name, idx in (("k", 1), ("v", 2)):
            lp[name + "_w"] = (w[:, idx].reshape(kv_heads, g, d, -1)
                               .mean(axis=1).reshape(kv_heads * d, -1))
            lp[name + "_b"] = (b[:, idx].reshape(kv_heads, g, d)
                               .mean(axis=1).reshape(kv_heads * d))
    return _apply_precision(p, policy)


def _block_qkv_kv(lp, x, n_heads):
    """Per-layer front half for the PAGED path: LN1 + projections with
    a possibly-reduced KV head count.  A fused-``qkv_w`` layer dict
    (``kv_heads == n_heads``) routes through :func:`_block_qkv`
    unchanged — bit-identical to the pre-GQA serving path; a split
    (GQA-converted) dict projects q at ``H`` heads and k/v at ``K_kv``.
    Returns ``q [B, H, T, D], k, v [B, K_kv, T, D]``."""
    if "qkv_w" in lp:
        return _block_qkv(lp, x, n_heads)
    b, t, c = x.shape
    d = c // n_heads
    kv_heads = lp["k_w"].shape[0] // d
    with device_scope("attn.proj"):
        h = _ln(x, lp["ln1_g"], lp["ln1_b"])
        q = (h @ lp["q_w"].T + lp["q_b"]).reshape(b, t, n_heads, d)
        k = (h @ lp["k_w"].T + lp["k_b"]).reshape(b, t, kv_heads, d)
        v = (h @ lp["v_w"].T + lp["v_b"]).reshape(b, t, kv_heads, d)
        return (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3))


def _bcast_kv(k, n_heads):
    """Broadcast ``K_kv`` KV heads over their query groups for a dense
    einsum ([B, K_kv, T, D] -> [B, H, T, D]); identity when the counts
    already agree (the fused multi-head path stays bit-identical)."""
    import jax.numpy as jnp
    kv_heads = k.shape[1]
    if kv_heads == n_heads:
        return k
    return jnp.repeat(k, n_heads // kv_heads, axis=1)


_KV_QMAX = 127.0


def _kv_quantized(kv_pages):
    """A per-layer entry is ``(k, v)`` for full-precision pools or
    ``(k, v, k_scales, v_scales)`` for int8 pools with fp32
    ``[num_pages, K_kv]`` absmax scales (ISSUE 20)."""
    return len(kv_pages[0]) == 4


def _quant_scatter(pool, scales, phys, offs, rows, mask):
    """Scatter one program's K or V rows into an INT8 page pool under
    per-page-per-KV-head absmax scales.

    ``pool``: int8 [num_pages, page_size, K_kv * D]; ``phys``/``offs``:
    int32 [R] physical page + in-page offset per row; ``rows``: fp32
    [R, K_kv, D]; ``mask``: bool [R] (False rows route to scratch page
    0, same as the full-precision scatter).  Only the GATHERED pages are
    ever viewed per KV head, never the pool.
    Scale discipline:

    - a page receiving a row at offset 0 is FRESH (just allocated —
      its payload and its scale slot are stale pool-reuse garbage):
      its scale resets to 0 first, so reuse can never leak a scale;
    - a page's scale GROWS monotonically while it is written:
      ``s_new = max(s_base, rowmax / 127)``, and the page's existing
      payload is re-expressed under the grown scale
      (``round(int8 * s_old / s_new)``) — an exact identity when the
      scale did not grow (ratio is exactly 1.0), one bounded rounding
      when it did.  The non-fresh writers are the decode/spec tail
      page and the copy-on-write page, both privately owned, so the
      whole-page rewrite can never race another reader;
    - new rows quantize under the page's FINAL scale, so scatter
      order within one call cannot matter.

    Returns ``(new_pool, new_scales)``.
    """
    import jax.numpy as jnp
    rows = rows * mask[:, None, None]
    tgt = jnp.where(mask, phys, 0)
    fresh_tgt = jnp.where(mask & (offs == 0), phys, 0)
    rowmax = jnp.abs(rows).max(-1)                     # [R, K_kv]
    s0 = scales.at[fresh_tgt].set(0.0)
    s_pre = s0[tgt]                                    # [R, K_kv]
    s1 = s0.at[tgt].max(rowmax / _KV_QMAX)
    s_post = s1[tgt]
    # duplicate rows landing in one page write IDENTICAL rescaled
    # payloads (same s_pre/s_post), so the duplicate-index scatter is
    # deterministic
    ratio = jnp.where(s_post > 0, s_pre / s_post, 0.0)
    r_n, n_kv, d = rows.shape
    old = pool[tgt].astype(jnp.float32).reshape(       # [R, page, KV, D]
        r_n, -1, n_kv, d)
    rescaled = jnp.clip(jnp.round(old * ratio[:, None, :, None]),
                        -_KV_QMAX, _KV_QMAX)
    p1 = pool.at[tgt].set(
        rescaled.reshape(r_n, -1, n_kv * d).astype(pool.dtype))
    q = jnp.clip(
        jnp.round(rows / jnp.maximum(s_post, 1e-30)[:, :, None]),
        -_KV_QMAX, _KV_QMAX)
    return p1.at[tgt, offs].set(
        q.reshape(r_n, n_kv * d).astype(pool.dtype)), s1


def _filter_logits_per_slot(logits, top_k, top_p):
    """Per-slot dynamic top-k / nucleus filtering (jit-compatible:
    sort-based, ``top_k``/``top_p`` are TRACED [S] arrays — per-request
    sampling params are ordinary program inputs, never a recompile).
    0 disables either filter for that slot.  Callers pass TEMPERATURE-
    SCALED logits, mirroring :func:`_filter_logits`."""
    import jax
    import jax.numpy as jnp
    v = logits.shape[-1]
    # top-k: threshold at the k-th largest value of each row
    sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
    idx = jnp.clip(top_k - 1, 0, v - 1).astype(jnp.int32)
    kth = jnp.take_along_axis(sorted_desc, idx[:, None], axis=-1)
    logits = jnp.where((top_k[:, None] > 0) & (logits < kth), -1e30,
                       logits)
    # nucleus: smallest set whose mass >= top_p, on the (k-filtered)
    # sampling distribution — same rule as the static filter
    sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = (cum - probs) < top_p[:, None]
    cutoff = jnp.min(jnp.where(keep_sorted, sorted_desc, jnp.inf),
                     axis=-1, keepdims=True)
    return jnp.where((top_p[:, None] > 0) & (logits < cutoff), -1e30,
                     logits)


def sample_tokens(logits, temps, top_ks, top_ps, keys):
    """Pick one token per slot from ``logits [S, V]`` under PER-SLOT
    sampling params (ISSUE 15): ``temps`` f32 [S] (<= 0 -> greedy
    argmax, bit-identical to the sampling-free path), ``top_ks`` int32
    [S], ``top_ps`` f32 [S] (0 disables), ``keys`` uint32 [S, 2] raw
    PRNG keys advanced FUNCTIONALLY — the returned ``new_keys`` is the
    only state, so the n-th token of a request depends on (seed, n)
    alone: same seed + params + prompt -> same tokens regardless of
    batch composition, join/leave, hot-swap, or failover re-decode.

    Returns ``(tokens int32 [S], new_keys uint32 [S, 2])``."""
    import jax
    import jax.numpy as jnp
    greedy = logits.argmax(-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    filtered = _filter_logits_per_slot(scaled, top_ks, top_ps)
    split = jax.vmap(jax.random.split)(keys)        # [S, 2, 2]
    new_keys, subs = split[:, 0], split[:, 1]
    sampled = jax.vmap(jax.random.categorical)(subs, filtered) \
        .astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy), new_keys


def paged_decode_step(p, tokens, positions, active, kv_pages,
                      block_tables, n_heads, sampling=None):
    """ONE decode step for every serving slot — the whole resident batch
    advances one token in one traced program.

    - ``tokens``: int32 [S] — each slot's current token (garbage where
      inactive);
    - ``positions``: int32 [S] — the position this token occupies (== the
      slot's context length before this step);
    - ``active``: bool [S] — slot occupancy mask.  Inactive slots write
      their K/V to physical page 0 (the allocator's scratch page) and
      attend over nothing, so occupancy changes can NEVER perturb a
      resident slot's math (bit-checked by tests);
    - ``kv_pages``: list of per-layer ``(k_pages, v_pages)``, each
      [num_pages, page_size, K_kv * D] (a token's KV heads side by side
      on the minor axis: the one shape the chip stores, the scatter
      writes and the paged kernel reads, see
      ``ops/pallas/paged_attention.py``) — donated by the caller's jit.
      ``K_kv < n_heads`` is grouped-query attention: the layer dicts
      must be the matching :func:`decode_params` conversion.  Pools
      may be any float dtype (bf16 halves bytes, values cast on
      scatter); an entry of ``(k, v, k_scales, v_scales)`` with int8
      pools selects QUANTIZED storage (ISSUE 20): absmax
      quantize-on-scatter here, dequant inside the paged kernel (see
      :func:`_quant_scatter`) — every paged program in this module
      accepts the same entry forms;
    - ``block_tables``: int32 [S, max_pages_per_seq];
    - ``sampling``: None for greedy argmax (the pre-ISSUE-15 contract,
      bit-identical), or ``(temps [S], top_ks [S], top_ps [S],
      keys [S, 2])`` per-slot params (see :func:`sample_tokens`).

    Returns ``(logits [S, V] fp32, next_tokens [S] int32, new_kv_pages)``
    without sampling, or ``(logits, next_tokens, new_keys,
    new_kv_pages)`` with it.
    """
    import jax.numpy as jnp

    s_n = tokens.shape[0]
    page_size = kv_pages[0][0].shape[1]
    from ...ops.pallas.paged_attention import paged_attention

    with device_scope("embed"):
        x = p["wte"][tokens][:, None] + p["wpe"][positions][:, None]
    c = x.shape[-1]
    # where each slot's new K/V lands: (physical page, in-page offset);
    # inactive slots are routed to scratch page 0
    with device_scope("kv_write"):
        logical = positions // page_size
        phys = jnp.where(active,
                         jnp.take_along_axis(block_tables, logical[:, None],
                                             axis=1)[:, 0], 0)
        offs = positions % page_size
    # the kernel masks keys at position >= ctx; this step's own token is
    # key position `positions`, so the inclusive context is positions+1
    with device_scope("attn"):
        ctx = jnp.where(active, positions + 1, 0).astype(jnp.int32)
    quantized = _kv_quantized(kv_pages)
    new_pages = []
    for lp, entry in zip(p["layers"], kv_pages):
        q, k, v = _block_qkv_kv(lp, x, n_heads)     # q [S, H, 1, D]
        if quantized:
            kc, vc, ks, vs = entry                  # k/v [S, K_kv, 1, D]
            with device_scope("kv_write"):
                kc, ks = _quant_scatter(kc, ks, phys, offs,
                                        k[:, :, 0, :], active)
                vc, vs = _quant_scatter(vc, vs, phys, offs,
                                        v[:, :, 0, :], active)
            with device_scope("attn"):
                o = paged_attention(q[:, :, 0, :], kc, vc, block_tables,
                                    ctx, k_scales=ks, v_scales=vs)
            new_pages.append((kc, vc, ks, vs))
        else:
            kc, vc = entry
            with device_scope("kv_write"):
                kc = kc.at[phys, offs].set(
                    k.reshape(s_n, -1).astype(kc.dtype))
                vc = vc.at[phys, offs].set(
                    v.reshape(s_n, -1).astype(vc.dtype))
            with device_scope("attn"):
                o = paged_attention(q[:, :, 0, :], kc, vc, block_tables,
                                    ctx)
            new_pages.append((kc, vc))
        x = _block_finish(lp, x, o.reshape(s_n, 1, c))
    with device_scope("lm_head"):
        h = _ln(x[:, 0], p["lnf_g"], p["lnf_b"])
        logits = h @ p["wte"].T
    if sampling is None:
        with device_scope("sample"):
            return logits, logits.argmax(-1).astype(jnp.int32), new_pages
    temps, top_ks, top_ps, keys = sampling
    # an all-greedy resident batch must not pay the sampling math
    # (vocab sorts + categorical per slot): cond executes ONE branch.
    # A sampled request is resident in every step that produces one of
    # its tokens, so its key still advances exactly once per token —
    # the per-request determinism law is composition-independent.
    from jax import lax
    with device_scope("sample"):
        nxt, new_keys = lax.cond(
            jnp.any(temps > 0),
            lambda: sample_tokens(logits, temps, top_ks, top_ps, keys),
            lambda: (logits.argmax(-1).astype(jnp.int32), keys))
    return logits, nxt, new_keys, new_pages


def _spec_accept_greedy(logits, tokens, draft_valid):
    """Greedy prefix acceptance for one speculative-verify pass:
    ``greedy_next[s, i]`` is the target's argmax continuation after
    query position ``i``; draft token ``tokens[s, i+1]`` is accepted
    iff every earlier draft matched AND it equals ``greedy_next[s, i]``.
    The emitted chain is ``greedy_next[s, :n_new]`` — position
    ``accepted_len`` is the free correction/bonus token, so ANY draft
    content (including poisoned garbage) still yields the exact greedy
    stream.  Returns ``(greedy_next [S, K], accepted_len [S])``."""
    import jax.numpy as jnp
    greedy_next = logits.argmax(-1).astype(jnp.int32)      # [S, K]
    match = (greedy_next[:, :-1] == tokens[:, 1:]) & draft_valid
    accepted = jnp.cumprod(match.astype(jnp.int32),
                           axis=1).sum(axis=1)
    return greedy_next, accepted


def _spec_sample(logits, tokens, draft_valid, temps, top_ks, top_ps,
                 keys):
    """Rejection-sampling verification of the draft tokens (sampled
    slots).  The drafter is DETERMINISTIC (it proposes draft ``d`` with
    probability 1), so the accept test is ``u < p_i[d]`` against the
    slot's filtered/temperature target distribution at position ``i``,
    and the residual distribution on rejection is ``p_i`` with ``d``
    masked out (renormalized inside ``categorical``).  The PRNG chain
    advances ONE split per emitted token — the i-th token of the step
    draws from the key after i splits, so the n-th token of a request
    still depends on (seed, n, context) alone and per-request streams
    reproduce across batch composition, churn, hot-swap, and failover
    re-decode (for a FIXED spec configuration; spec-on sampled streams
    need not match spec-off — only greedy is bit-pinned).

    Returns ``(emitted [S, K], accepted_len [S], keys_after [K, S, 2])``
    where ``keys_after[i]`` is the chain state after emitting ``i + 1``
    tokens."""
    import jax
    import jax.numpy as jnp
    s_n, k1, v = logits.shape
    rep = lambda a: jnp.repeat(a, k1, axis=0)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None, None]
    filtered = _filter_logits_per_slot(
        scaled.reshape(s_n * k1, v), rep(top_ks),
        rep(top_ps)).reshape(s_n, k1, v)
    vocab = jnp.arange(v)
    cur = keys
    emit, cont, keys_after = [], [], []
    for i in range(k1):
        sp = jax.vmap(jax.random.split)(cur)           # [S, 2, 2]
        cur, sub = sp[:, 0], sp[:, 1]
        keys_after.append(cur)
        sp2 = jax.vmap(jax.random.split)(sub)
        k_u, k_r = sp2[:, 0], sp2[:, 1]
        f_i = filtered[:, i]                           # [S, V]
        if i < k1 - 1:
            d_i = tokens[:, i + 1]
            probs = jax.nn.softmax(f_i, axis=-1)
            p_d = jnp.take_along_axis(probs, d_i[:, None],
                                      axis=-1)[:, 0]
            u = jax.vmap(lambda kk: jax.random.uniform(kk, ()))(k_u)
            accept = (u < p_d) & draft_valid[:, i]
            masked = jnp.where(vocab[None, :] == d_i[:, None], -1e30,
                               f_i)
            resample = jax.vmap(jax.random.categorical)(
                k_r, masked).astype(jnp.int32)
            direct = jax.vmap(jax.random.categorical)(
                k_r, f_i).astype(jnp.int32)
            emit.append(jnp.where(draft_valid[:, i],
                                  jnp.where(accept, d_i, resample),
                                  direct))
            cont.append(accept)
        else:
            # the bonus position: no draft beyond it, sample directly
            emit.append(jax.vmap(jax.random.categorical)(
                k_r, f_i).astype(jnp.int32))
            cont.append(jnp.zeros(s_n, bool))
    emit = jnp.stack(emit, axis=1)                     # [S, K]
    cont = jnp.stack(cont, axis=1)
    accepted = jnp.cumprod(cont.astype(jnp.int32), axis=1).sum(axis=1)
    return emit, accepted, jnp.stack(keys_after)


def paged_spec_decode_step(p, tokens, positions, active, draft_len,
                           kv_pages, block_tables, n_heads,
                           sampling=None):
    """ONE speculative decode step for every serving slot: the slot's
    last emitted token PLUS up to ``K - 1`` draft tokens run through
    the target model together, and the longest verified prefix (plus
    the free correction/bonus token) is emitted — up to ``K`` tokens
    per slot from ONE dispatch, same donated-program discipline as
    :func:`paged_decode_step` (occupancy and per-slot draft length are
    masks, never shapes).

    - ``tokens``: int32 [S, K] — ``tokens[s, 0]`` is the slot's current
      (last emitted) token, ``tokens[s, 1:]`` the drafted continuation
      (garbage past ``draft_len[s]``);
    - ``positions``: int32 [S, K] — consecutive positions starting at
      the slot's context length - 1 (host-clamped into the wpe table);
    - ``active``: bool [S]; ``draft_len``: int32 [S] in
      ``[0, K - 1]`` — how many draft tokens are real this step
      (``0`` degenerates to the plain single-token decode step);
    - ``sampling``: None for greedy, or the per-slot
      ``(temps, top_ks, top_ps, keys)`` arrays.

    Every query position's K/V is scattered into the slot's pages
    before attention (rows past ``draft_len`` go to scratch); query
    ``i`` attends through position ``positions[s, i]`` — the
    per-position causal mask of batched verification
    (``paged_attention_multi``).  Rejected draft positions need no
    physical rollback: their page offsets sit beyond the slot's
    committed context, so every later step masks them and the next
    tokens overwrite them in place.

    Returns ``(logits [S, K, V], out_tokens [S, K], n_new [S],
    new_kv_pages)`` — the emitted tokens are ``out_tokens[s, :n_new[s]]``
    — or, with ``sampling``, ``(logits, out_tokens, n_new, new_keys,
    new_kv_pages)``.
    """
    import jax.numpy as jnp

    s_n, k1 = tokens.shape
    page_size = kv_pages[0][0].shape[1]
    from ...ops.pallas.paged_attention import paged_attention_multi

    qpos = jnp.arange(k1)
    # query-row validity: the slot is live and the row is the current
    # token (i == 0) or a real draft (i <= draft_len)
    qmask = active[:, None] & (qpos[None, :] <= draft_len[:, None])
    with device_scope("embed"):
        x = p["wte"][tokens] + p["wpe"][positions]      # [S, K, C]
    c = x.shape[-1]
    with device_scope("kv_write"):
        logical = positions // page_size
        phys = jnp.where(qmask,
                         jnp.take_along_axis(block_tables, logical, axis=1),
                         0)
        offs = positions % page_size
    with device_scope("attn"):
        ctx = jnp.where(qmask, positions + 1, 0).astype(jnp.int32)
    quantized = _kv_quantized(kv_pages)
    flat = lambda a: a.reshape(s_n * k1)
    new_pages = []
    for lp, entry in zip(p["layers"], kv_pages):
        q, k, v = _block_qkv_kv(lp, x, n_heads)   # q [S, H, K, D]
        kr = k.transpose(0, 2, 1, 3)              # [S, K, K_kv, D]
        vr = v.transpose(0, 2, 1, 3)
        if quantized:
            kc, vc, ks, vs = entry
            with device_scope("kv_write"):
                kc, ks = _quant_scatter(
                    kc, ks, flat(phys), flat(offs),
                    kr.reshape((s_n * k1,) + kr.shape[2:]), flat(qmask))
                vc, vs = _quant_scatter(
                    vc, vs, flat(phys), flat(offs),
                    vr.reshape((s_n * k1,) + vr.shape[2:]), flat(qmask))
            with device_scope("attn"):
                o = paged_attention_multi(
                    q.transpose(0, 2, 1, 3), kc, vc, block_tables, ctx,
                    k_scales=ks, v_scales=vs)       # [S, K, H, D]
            new_pages.append((kc, vc, ks, vs))
        else:
            kc, vc = entry
            with device_scope("kv_write"):
                kc = kc.at[phys, offs].set(
                    kr.reshape(s_n, k1, -1).astype(kc.dtype))
                vc = vc.at[phys, offs].set(
                    vr.reshape(s_n, k1, -1).astype(vc.dtype))
            with device_scope("attn"):
                o = paged_attention_multi(q.transpose(0, 2, 1, 3), kc,
                                          vc, block_tables, ctx)
            new_pages.append((kc, vc))
        x = _block_finish(lp, x, o.reshape(s_n, k1, c))
    with device_scope("lm_head"):
        h = _ln(x, p["lnf_g"], p["lnf_b"])
        logits = h @ p["wte"].T                        # [S, K, V]
    draft_valid = qmask[:, 1:]          # draft at input column i+1
    with device_scope("sample"):
        greedy_next, acc_g = _spec_accept_greedy(logits, tokens,
                                                 draft_valid)
        n_new_g = jnp.where(active, acc_g + 1, 0).astype(jnp.int32)
    if sampling is None:
        return logits, greedy_next, n_new_g, new_pages
    temps, top_ks, top_ps, keys = sampling
    from jax import lax

    def _sampled():
        emit, acc_s, keys_after = _spec_sample(
            logits, tokens, draft_valid, temps, top_ks, top_ps, keys)
        n_new_s = jnp.where(active, acc_s + 1, 0).astype(jnp.int32)
        sampled_row = temps > 0
        out = jnp.where(sampled_row[:, None], emit, greedy_next)
        n_new = jnp.where(sampled_row, n_new_s, n_new_g)
        # key after the last emitted token; untouched for greedy or
        # inactive slots
        sel = jnp.take_along_axis(
            keys_after.transpose(1, 0, 2),
            jnp.clip(n_new - 1, 0, k1 - 1)[:, None, None]
            .astype(jnp.int32), axis=1)[:, 0]
        new_keys = jnp.where((sampled_row & active)[:, None], sel,
                             keys)
        return out, n_new, new_keys

    with device_scope("sample"):
        out_tokens, n_new, new_keys = lax.cond(
            jnp.any(temps > 0), _sampled,
            lambda: (greedy_next, n_new_g, keys))
    return logits, out_tokens, n_new, new_keys, new_pages


def _first_token(logits, sampling, new_pages):
    """Shared prefill tail: greedy 3-tuple, or per-request sampled
    4-tuple with the functionally-advanced key (scalar flavor of
    :func:`sample_tokens`; greedy requests skip the sampling math via
    cond)."""
    import jax.numpy as jnp
    from jax import lax
    if sampling is None:
        with device_scope("sample"):
            return logits, logits.argmax(-1).astype(jnp.int32), new_pages
    temp, top_k, top_p, key = sampling

    def _sampled():
        tok, new_key = sample_tokens(
            logits[None], jnp.reshape(temp, (1,)).astype(jnp.float32),
            jnp.reshape(top_k, (1,)).astype(jnp.int32),
            jnp.reshape(top_p, (1,)).astype(jnp.float32), key[None])
        return tok[0], new_key[0]

    with device_scope("sample"):
        tok, new_key = lax.cond(
            temp > 0, _sampled,
            lambda: (logits.argmax(-1).astype(jnp.int32), key))
    return logits, tok, new_key, new_pages


def _prefill_rows(p, tokens, prompt_len, prefix_len, prefix_kv, n_heads):
    """Compute half of an admission: one batched causal pass over the
    (padded) suffix tokens, touching no page pool.  With ``prefix_kv``
    (a cache HIT: per layer the slot's pages gathered through its block
    table, fp32 ``[mp, page, K_kv * D]``) the suffix queries attend over
    the cached prefix, masked at ``prefix_len``, PLUS the causal window
    of the suffix itself, in one joint softmax; with ``None`` (a MISS,
    ``prefix_len == 0``) over the causal window alone.  Returns
    ``(h [T_pad, C], [(k, v) per layer, each [T_pad, K_kv, D]])``."""
    import jax
    import jax.numpy as jnp

    t_pad = tokens.shape[0]
    positions = prefix_len + jnp.arange(t_pad)
    with device_scope("embed"):
        x = (p["wte"][tokens] + p["wpe"][positions])[None]  # [1, T_pad, C]
    c = x.shape[-1]
    d = c // n_heads
    with device_scope("attn"):
        valid = jnp.arange(t_pad) < prompt_len - prefix_len
        # suffix-vs-suffix: causal within the window, pads masked
        mask_suf = (jnp.tril(jnp.ones((t_pad, t_pad), bool))
                    & valid[None, :])[None, None]
        if prefix_kv is not None:
            # suffix-vs-cached-prefix: every suffix query sees every
            # cached key
            t_ctx = prefix_kv[0][0].shape[0] * prefix_kv[0][0].shape[1]
            pre_valid = jnp.arange(t_ctx) < prefix_len
            mask_pre = pre_valid[None, None, None, :]
        scale = jnp.sqrt(jnp.float32(d))
    rows = []
    for i, lp in enumerate(p["layers"]):
        q, k, v = _block_qkv_kv(lp, x, n_heads)   # [1, H|K_kv, T_pad, D]
        with device_scope("attn"):
            kd, vd = _bcast_kv(k, n_heads), _bcast_kv(v, n_heads)
            st = jnp.where(mask_suf,
                           jnp.einsum("bhqd,bhkd->bhqk", q, kd) / scale,
                           -1e30)
            if prefix_kv is not None:
                # cached prefix K/V: [mp, page, K_kv * D] -> [1, H, t_ctx, D]
                kp, vp = (_bcast_kv(a.reshape(t_ctx, -1, d)
                                    .transpose(1, 0, 2)[None], n_heads)
                          for a in prefix_kv[i])
                # positions past the cached prefix read scratch/unwritten
                # pages whose contents are GARBAGE — a NaN there (e.g. a
                # hot-swap canary's torn-weight writes to scratch) would
                # poison the output through 0 * NaN even though its softmax
                # weight is exactly zero.  Zero the V rows, not just the
                # scores.
                vp = jnp.where(pre_valid[None, None, :, None], vp, 0.0)
                st_pre = jnp.where(
                    mask_pre, jnp.einsum("bhqd,bhkd->bhqk", q, kp) / scale,
                    -1e30)
                st = jnp.concatenate([st_pre, st], axis=-1)
                vd = jnp.concatenate([vp, vd], axis=2)
            pr = jax.nn.softmax(st, axis=-1)
            o = jnp.einsum("bhqk,bhkd->bhqd", pr, vd)
            o = o.transpose(0, 2, 1, 3).reshape(1, t_pad, c)
        rows.append((k[0].transpose(1, 0, 2), v[0].transpose(1, 0, 2)))
        x = _block_finish(lp, x, o)
    return _ln(x[0], p["lnf_g"], p["lnf_b"]), rows


def paged_prefill(p, tokens, prompt_len, prefix_len, block_table_row,
                  cow_src, cow_dst, kv_pages, n_heads, sampling=None):
    """Admit one request: a single batched pass over its (padded)
    prompt that writes every position's K/V into the slot's pages (a
    whole page an update for full-precision pools, see
    :func:`_page_scatter`; a row an update for int8 pools, whose scales
    grow with what a page holds) and
    returns the last prompt position's logits — the first generated
    token costs one forward, not ``prompt_len`` decode steps.  Prefix-
    cache aware (ISSUE 15): only the un-cached SUFFIX of a prompt whose
    leading ``prefix_len`` tokens' K/V already sit in pages mapped by
    ``block_table_row`` (shared full pages + optionally one
    copy-on-write page) is run through the model.

    - ``tokens``: int32 [T_pad] — the suffix tokens
      (``prompt[prefix_len:]``; the whole prompt on a miss), padded to
      the engine's static prefill length; suffix position ``i`` is
      absolute position ``prefix_len + i``;
    - ``prompt_len`` / ``prefix_len``: int32 scalars, both TRACED — one
      compiled program serves every prompt and hit length.
      ``prefix_len == 0`` is a cache miss: ``lax.cond`` then runs the
      plain causal pass, which never attends over the pages;
    - ``block_table_row``: int32 [max_pages_per_seq] for this slot;
    - ``cow_src`` / ``cow_dst``: int32 physical page ids.  Page
      ``cow_src`` is copied into ``cow_dst`` per layer FIRST — the
      copy-on-write for a prefix that ends mid-page: the donor page
      stays immutable for its other readers while this request's
      suffix tokens overwrite the copy's tail.  Pass scratch (0) for
      both when no COW is needed (a scratch self-copy is a no-op);
    - ``sampling``: None for greedy, or scalar ``(temperature, top_k,
      top_p, key)`` for the request's first token.

    Pad positions (>= the suffix length) are masked out of attention
    and their K/V goes to scratch page 0 or, inside the prompt's last
    page, is written as zeros.  Only the attention
    math sits under the ``cond``, and the pools never enter it: the
    copy-on-write and the slot's page gather before it and the scatter
    after it touch the (donated) pools on the one path both branches
    share.  A conditional that takes or returns the pools makes the
    TPU compiler hold a second, layout-converted copy of every pool
    at once, which no deployment-sized pool survives.

    Returns ``(logits [V] fp32, first_token int32, new_kv_pages)``
    (plus the advanced key before ``new_kv_pages`` when sampling): the
    logits are the LAST PROMPT position's, so the first generated token
    is produced here (the suffix is always >= 1 token — a fully-cached
    prompt still runs its final position through the model).
    """
    import jax.numpy as jnp
    from jax import lax

    t_pad = tokens.shape[0]
    page_size = kv_pages[0][0].shape[1]
    quantized = _kv_quantized(kv_pages)
    # copy-on-write FIRST: the prefix gather must see the copy, and it
    # carries the donor page's SCALE row with its bytes — a COW page
    # dequantizes identically to its donor
    with device_scope("kv_write"):
        kv_pages = [tuple(a.at[cow_dst].set(a[cow_src]) for a in entry)
                    for entry in kv_pages]
    from ...ops.pallas.paged_attention import dequant_pages

    def gathered(pool, scales=None):
        # the slot's pages, fp32 [mp, page, K_kv * D]
        pages = pool[block_table_row]
        if scales is None:
            return pages.astype(jnp.float32)
        return dequant_pages(pages, scales[block_table_row])

    # an entry is (k, v) or (k, v, k_scales, v_scales)
    with device_scope("attn"), device_scope("attn.gather"):
        prefix_kv = [(gathered(*entry[0::2]), gathered(*entry[1::2]))
                     for entry in kv_pages]
    h, rows = lax.cond(
        prefix_len > 0,
        lambda: _prefill_rows(p, tokens, prompt_len, prefix_len,
                              prefix_kv, n_heads),
        lambda: _prefill_rows(p, tokens, prompt_len, 0, None, n_heads))
    suffix_len = prompt_len - prefix_len
    # where each row lands, for the pools that take a row an update (int8)
    with device_scope("kv_write"):
        positions = prefix_len + jnp.arange(t_pad)
        valid = jnp.arange(t_pad) < suffix_len
        phys = jnp.where(valid, block_table_row[positions // page_size], 0)
        offs = positions % page_size
    new_pages = []
    for entry, (k, v) in zip(kv_pages, rows):
        with device_scope("kv_write"):
            if quantized:
                # the COW page is the only written page with
                # pre-existing content; _quant_scatter's grow-only
                # rescale handles it (fresh pages start at an offs == 0
                # row and reset)
                kc, ks = _quant_scatter(entry[0], entry[2], phys, offs,
                                        k, valid)
                vc, vs = _quant_scatter(entry[1], entry[3], phys, offs,
                                        v, valid)
                new_pages.append((kc, vc, ks, vs))
            else:
                new_pages.append(tuple(
                    _page_scatter(pool, block_table_row, x, prefix_len,
                                  suffix_len)
                    for pool, x in zip(entry, (k, v))))
    with device_scope("lm_head"):
        last = lax.dynamic_index_in_dim(h, suffix_len - 1, 0,
                                        keepdims=False)
        logits = last @ p["wte"].T
    return _first_token(logits, sampling, new_pages)


def get_gpt(num_layers, units, num_heads, vocab_size=50257, max_len=1024,
            dropout=0.0, remat=False, moe_experts=0, **kwargs):
    """Build a GPTLM with the vocab padded to the MXU lane width."""
    return GPTLM(_pad_vocab(vocab_size), num_layers, units, num_heads,
                 max_len=max_len, dropout=dropout, remat=remat,
                 moe_experts=moe_experts, **kwargs)


def gpt2_tiny_moe(moe_experts=4, **kwargs):
    """2-layer test-scale MoE config (every block's MLP is a top-1
    mixture of ``moe_experts`` experts — the flagship's ep-axis form)."""
    kwargs.setdefault("vocab_size", 256)
    kwargs.setdefault("max_len", 128)
    return get_gpt(2, 128, 4, moe_experts=moe_experts, **kwargs)


def gpt2_tiny(**kwargs):
    """2-layer test-scale config (CI / CPU oracle checks)."""
    kwargs.setdefault("vocab_size", 256)
    kwargs.setdefault("max_len", 128)
    return get_gpt(2, 128, 4, **kwargs)


def gpt2_small(**kwargs):
    """124M-parameter class (12 x 768, 12 heads)."""
    kwargs.setdefault("max_len", 2048)
    return get_gpt(12, 768, 12, **kwargs)


def gpt2_medium(**kwargs):
    """350M-parameter class (24 x 1024, 16 heads)."""
    kwargs.setdefault("max_len", 2048)
    return get_gpt(24, 1024, 16, **kwargs)
