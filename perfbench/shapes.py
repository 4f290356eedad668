"""Operations and bytes that the work NEEDS, computed from shapes.

Model FLOPs count a multiply-add as 2 and the backward pass as twice the
forward; recomputation does not count.  Kernel bytes are what the
algorithm must move once, not what an implementation happens to move.
Every function takes the configuration file's own keys.
"""


def gpt2_params(cfg):
    """Parameter count of the configuration (tied head counted once)."""
    c, l, v, p = (cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"],
                  cfg["n_positions"])
    inner = cfg.get("n_inner") or 4 * c
    block = 4 * c * c + 4 * c + 2 * c * inner + inner + c + 4 * c
    return v * c + p * c + l * block + 2 * c


def gpt2_matmul_flops_per_token(cfg):
    """Forward multiply-add FLOPs of the dense matmuls for one token
    (qkv, out projection, the two MLP matrices, the tied head)."""
    c, l, v = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    inner = cfg.get("n_inner") or 4 * c
    return 2 * (l * (4 * c * c + 2 * c * inner) + c * v)


def attention_flops_fwd(batch, heads, t, head_dim, causal=True):
    """Forward FLOPs of softmax(QK^T)V for one call: two matmuls of
    2*T*T*D per head, halved under a causal mask."""
    full = 2 * 2 * batch * heads * t * t * head_dim
    return full // 2 if causal else full


def flash_fwd_bwd_flops(batch, heads, t, head_dim, causal=True):
    """Forward + backward of one attention layer as the algorithm needs
    it: the backward holds four matmuls (dV, dP, dQ, dK) of the
    forward's size two, so fwd + bwd = 3 x forward.  The recomputed
    QK^T inside a flash backward is NOT counted."""
    return 3 * attention_flops_fwd(batch, heads, t, head_dim, causal)


def gpt2_train_step_flops(cfg, batch, t):
    """Model FLOPs of one training step (forward + backward, no
    recompute) at ``batch`` sequences of ``t`` tokens."""
    dense = 3 * gpt2_matmul_flops_per_token(cfg) * batch * t
    attn = cfg["n_layer"] * flash_fwd_bwd_flops(
        batch, cfg["n_head"], t, cfg["n_embd"] // cfg["n_head"])
    return dense + attn


def paged_attention_bytes(context_lens, kv_heads, head_dim, kv_itemsize,
                          n_layers=1):
    """HBM bytes one decode step's attention must read: K and V of every
    live token once, per layer."""
    return (2 * int(sum(context_lens)) * kv_heads * head_dim
            * kv_itemsize * n_layers)


def gpt2_decode_weight_bytes(cfg, itemsize=4):
    """Weight bytes one decode step must read: every matrix once (the
    position table contributes one row per slot and is left out)."""
    return (gpt2_params(cfg) - cfg["n_positions"] * cfg["n_embd"]) \
        * itemsize


def roofline_seconds(flops, nbytes, peaks, flops_key="bf16_flops_per_s"):
    """Least time the chip could take, and which bound sets it."""
    tc = flops / peaks[flops_key]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
