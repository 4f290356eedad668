"""Operations and bytes that the work of the GigaChat3.5 share NEEDS,
computed from the configuration file's own keys
(``configs/gigachat3.5-432b-a28b.json``: ``n_routed_experts`` is the count
HELD here, ``published`` holds the router's width,
``full_attention_layers`` the published pattern read at ``layers_kept``)
and from what a run counted.

A count is what the mathematics needs once, whatever implements it: a
latent query scores each key it can see once (``2 x 192`` a head for the
score, ``2 x 128`` for the value, the keys and values expanded from the
latent) and a latent row is read once a query row's program at its 576
values, not the 640 lanes the pool pads it to; a delta-rule token costs
``6 Dk Dv`` a value head (``S^T k``, the rank-one update, ``S^T q``) and
its state is read and written once a decode step; a weight is read once
a run; an expert that no token chose counts nothing.  A multiply-add
counts 2.
"""

WEIGHT_BYTES = 2            # bfloat16, the published dtype
STATE_BYTES = 4             # the recurrent state and tail are float32


def layer_kinds(cfg):
    """``(mix, ffn)`` of every kept layer: "mla" or "gdn", "dense" or
    "moe"."""
    return [("mla" if l in cfg["full_attention_layers"] else "gdn",
             "dense" if l < cfg["first_k_dense_replace"] else "moe")
            for l in cfg["layers_kept"]]


def _count(cfg, mix):
    return sum(1 for m, _ in layer_kinds(cfg) if m == mix)


def gdn_params(cfg):
    """One delta-rule layer: ``W_qkv``, the convolution, ``W_z``, the
    decay's and beta's projections and their two vectors, ``W_o``."""
    c = cfg["hidden_size"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    width = 2 * hk * dk + hv * dv
    return (c * width + cfg["linear_conv_kernel_dim"] * width
            + c * hv * dv + 2 * c * hv + 2 * hv + hv * dv * c)


def mla_params(cfg):
    """One latent-attention layer with its output gate."""
    c, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rank, qr = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    return (c * qr + qr * h * (dn + dr) + c * (rank + dr)
            + rank * h * (dn + dv) + c * h * dv + h * dv * c)


def expert_params(cfg):
    """One routed expert (and the shared one): gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_params(cfg):
    """Every matrix OUTSIDE the routed experts, the embedding and the
    head: what any run of either program reads once."""
    c = cfg["hidden_size"]
    total = 0
    for mix, ffn in layer_kinds(cfg):
        total += mla_params(cfg) if mix == "mla" else gdn_params(cfg)
        if ffn == "dense":
            total += 3 * c * cfg["intermediate_size"]
        else:
            total += (cfg["n_shared_experts"] * expert_params(cfg)
                      + c * cfg["published"]["n_routed_experts"])
    return total


def params(cfg):
    """Parameter count of the share (norm gains and the router's bias
    left out)."""
    moe_layers = sum(1 for _, f in layer_kinds(cfg) if f == "moe")
    return (dense_params(cfg) + 2 * cfg["vocab_size"] * cfg["hidden_size"]
            + moe_layers * cfg["n_routed_experts"] * expert_params(cfg))


def state_bytes_per_slot(cfg):
    """A slot's recurrent states and convolution tails, all delta-rule
    layers."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    tail = (cfg["linear_conv_kernel_dim"] - 1) * (2 * hk * dk + hv * dv)
    return _count(cfg, "gdn") * (hv * dk * dv + tail) * STATE_BYTES


def latent_row_values(cfg):
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def state_bytes(cfg, live_slot_steps):
    """The delta-rule kernel's work: every live slot's state read once
    and written once a decode step, in every delta-rule layer."""
    hv = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return 2 * live_slot_steps * _count(cfg, "gdn") * hv * dk * dv \
        * STATE_BYTES


def latent_bytes(cfg, rows_read):
    """Latent rows read once a query row's program (``rows_read`` summed
    over the latent layers, the program's own count)."""
    return rows_read * latent_row_values(cfg) * WEIGHT_BYTES


def token_matmul_flops(cfg):
    """Forward FLOPs of one token through every matrix outside the
    routed experts and the head."""
    return 2 * dense_params(cfg)


def recurrence_flops(cfg):
    """One token's delta rule in every delta-rule layer: ``6 Dk Dv`` a
    value head."""
    hv = cfg["linear_num_value_heads"]
    return _count(cfg, "gdn") * 6 * hv * cfg["linear_key_head_dim"] \
        * cfg["linear_value_head_dim"]


def head_flops(cfg):
    """One row of logits over the vocabulary slice."""
    return 2 * cfg["vocab_size"] * cfg["hidden_size"]


def expert_flops(cfg, local_assignments):
    """The held experts' work: one expert's matrices a (token, expert)
    choice that fell on a held expert."""
    return 2 * local_assignments * expert_params(cfg)


def attention_flops(cfg, rows_read):
    """Scores and weighted values of latent attention: ``2 (dn + dr) + 2
    dv`` a (query, head, key) (``rows_read``: query-key pairs summed over
    the latent layers)."""
    h = cfg["num_attention_heads"]
    return 2 * h * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                    + cfg["v_head_dim"]) * rows_read


def moe_gmm_bytes(cfg, experts_hit):
    """Weights of the held experts that got a token, each read once
    (``experts_hit`` summed over expert layers and runs)."""
    return experts_hit * expert_params(cfg) * WEIGHT_BYTES


def decode_bytes(cfg, steps, experts_hit, live_slot_steps, rows_read):
    """Everything ``steps`` decode runs must move: every matrix outside
    the routed experts and the head once a run, the hit experts, each
    live slot's state read and written, the latent rows its query
    reads."""
    once = (dense_params(cfg) + cfg["vocab_size"] * cfg["hidden_size"]) \
        * WEIGHT_BYTES
    return (steps * once + moe_gmm_bytes(cfg, experts_hit)
            + state_bytes(cfg, live_slot_steps)
            + latent_bytes(cfg, rows_read))


def chunk_attention_rows(rows, offset):
    """Query-key pairs of one chunk of ``rows`` real rows at ``offset``
    on ONE latent layer: row ``i`` sees ``offset + i + 1`` keys."""
    return rows * offset + rows * (rows + 1) // 2


def step_flops(cfg, tokens, head_rows, local_assignments, rows_read):
    """Everything ``tokens`` rows (decoded or prefilled) need:
    ``head_rows`` of them a row of logits; the last two summed over
    layers."""
    return (tokens * (token_matmul_flops(cfg) + recurrence_flops(cfg))
            + head_rows * head_flops(cfg)
            + expert_flops(cfg, local_assignments)
            + attention_flops(cfg, rows_read))
