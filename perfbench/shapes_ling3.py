"""Bytes that the work of the Ling-3.0-flash-VL share NEEDS, computed
from the configuration file's own keys (``configs/ling-3.0-flash-vl
.json``: ``num_experts`` is the count HELD here, ``published`` holds the
router's width) and from what a run counted.

Kernel bytes are what the algorithm must move once, not what an
implementation happens to move: a latent row counts at its 576 values,
not at the 640 lanes the pool pads it to; an expert that no token chose
counts nothing.  All three kernels are bound by memory at decode sizes,
so only bytes are counted.
"""

WEIGHT_BYTES = 2            # bfloat16, the published dtype
STATE_BYTES = 4             # the recurrent state is float32


def layer_kinds(cfg):
    """``(mix, ffn)`` of every kept layer (published indices in
    ``layers_kept``)."""
    return [("mla" if (l + 1) % cfg["layer_group_size"] == 0 else "kda",
             "dense" if l < cfg["first_k_dense_replace"] else "moe")
            for l in cfg["layers_kept"]]


def expert_params(cfg):
    """One routed expert: gate, up and down matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def attention_params(cfg, mix):
    c, h, d = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["head_dim"])
    if mix == "mla":
        dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
        rank = cfg["kv_lora_rank"]
        return (c * h * (dn + dr) + c * (rank + dr) + rank * h * (dn + dv)
                + c * h + h * dv * c)
    return (c * 3 * h * d + cfg["short_conv_kernel_size"] * 3 * h * d
            + c * h * d + h * d + h + 2 * c * h + h * d * c)


def params(cfg):
    """Parameter count of the share (norm gains left out)."""
    c = cfg["hidden_size"]
    total = 2 * cfg["vocab_size"] * c
    for mix, ffn in layer_kinds(cfg):
        total += attention_params(cfg, mix)
        if ffn == "dense":
            total += 3 * c * cfg["intermediate_size"]
        else:
            total += (cfg["num_experts"] * expert_params(cfg)
                      + 3 * c * cfg["moe_shared_expert_intermediate_size"]
                      + c * cfg["published"]["num_experts"])
    return total


def moe_gmm_bytes(cfg, experts_hit):
    """Weights of the held experts that got a token, each read once
    (``experts_hit`` summed over expert layers)."""
    return experts_hit * expert_params(cfg) * WEIGHT_BYTES


def kda_state_bytes(cfg, live_slots):
    """One decode step's recurrent state: every live slot's state read
    once and written once, in every KDA layer."""
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    n_kda = sum(1 for mix, _ in layer_kinds(cfg) if mix == "kda")
    return 2 * live_slots * n_kda * h * d * d * STATE_BYTES


def latent_bytes(cfg, context_tokens, itemsize=2):
    """One decode step's latent rows: every live token's row once, in
    every MLA layer."""
    n_mla = sum(1 for mix, _ in layer_kinds(cfg) if mix == "mla")
    return (context_tokens * n_mla
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize)


def decode_bytes(cfg, steps, experts_hit, live_slots, context_tokens):
    """Everything ``steps`` decode steps must move: every step reads
    each matrix outside the routed experts once (a token's embedding
    row, not the table) and, summed over the steps: the hit experts,
    the live slots' state read and written, their convolution history
    read and written, and the live latent rows."""
    c, h, d = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["head_dim"])
    dense = cfg["vocab_size"] * c                           # the head
    conv = 0
    for mix, ffn in layer_kinds(cfg):
        dense += attention_params(cfg, mix)
        if mix == "kda":
            conv += 2 * 3 * h * d * (cfg["short_conv_kernel_size"] - 1)
        if ffn == "dense":
            dense += 3 * c * cfg["intermediate_size"]
        else:
            dense += (3 * c * cfg["moe_shared_expert_intermediate_size"]
                      + c * cfg["published"]["num_experts"])
    return ((steps * dense + live_slots * (c + conv)) * WEIGHT_BYTES
            + moe_gmm_bytes(cfg, experts_hit)
            + kda_state_bytes(cfg, live_slots)
            + latent_bytes(cfg, context_tokens))
