"""Operations and bytes that the work of the DeepSeek-V3.2 share NEEDS,
computed from the configuration file's own keys
(``configs/deepseek-v3.2.json``: ``n_routed_experts`` is the count HELD
here, ``published`` holds the router's width) and from what a run
counted.

A count is what the mathematics needs once, whatever implements it: a
query scores each indexer key in its context once and attends over its
selected rows once; a latent row counts at its 576 values, an indexer
key at its 128, not at the lanes a pool pads them to; an expert that no
token chose counts nothing.  A multiply-add counts 2.
"""

WEIGHT_BYTES = 2            # bfloat16, the published dtype


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["q_lora_rank"])


def attention_params(cfg):
    """Query compression and expansion, the cached row's projection,
    the latent's expansion and the output projection."""
    c, h, dn, dr, dv, rank, qr = _dims(cfg)
    return (c * qr + qr * h * (dn + dr) + c * (rank + dr)
            + rank * h * (dn + dv) + h * dv * c)


def indexer_params(cfg):
    c, qr = cfg["hidden_size"], cfg["q_lora_rank"]
    n, d = cfg["index_n_heads"], cfg["index_head_dim"]
    return qr * n * d + c * d + c * n


def expert_params(cfg):
    """One routed expert: gate, up and down matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def ffn_kinds(cfg):
    return ["dense" if l < cfg["first_k_dense_replace"] else "moe"
            for l in cfg["layers_kept"]]


def params(cfg):
    """Parameter count of the share (norm gains and biases left out)."""
    c = cfg["hidden_size"]
    total = 2 * cfg["vocab_size"] * c
    for ffn in ffn_kinds(cfg):
        total += attention_params(cfg) + indexer_params(cfg)
        if ffn == "dense":
            total += 3 * c * cfg["intermediate_size"]
        else:
            total += (cfg["n_routed_experts"] * expert_params(cfg)
                      + cfg["n_shared_experts"] * expert_params(cfg)
                      + c * cfg["published"]["n_routed_experts"])
    return total


def token_matmul_flops(cfg):
    """Forward FLOPs of one token through every matrix OUTSIDE the
    routed experts and the head (``W_UK`` and ``W_UV`` are counted with
    the attention, in their absorbed form)."""
    c, h, dn, dr, dv, rank, qr = _dims(cfg)
    total = 0
    for ffn in ffn_kinds(cfg):
        total += (c * qr + qr * h * (dn + dr) + c * (rank + dr)
                  + h * dv * c + indexer_params(cfg))
        if ffn == "dense":
            total += 3 * c * cfg["intermediate_size"]
        else:
            total += (cfg["n_shared_experts"] * expert_params(cfg)
                      + c * cfg["published"]["n_routed_experts"])
    return 2 * total


def head_flops(cfg):
    """One row of logits over the vocabulary slice."""
    return 2 * cfg["vocab_size"] * cfg["hidden_size"]


def expert_flops(cfg, local_assignments):
    """The held experts' work: one expert's matrices a (token, expert)
    choice that fell on a held expert."""
    return 2 * local_assignments * expert_params(cfg)


def index_flops(cfg, rows_in_context):
    """Index scores: a query scores a key with every indexer head
    (``rows_in_context``: query-key pairs, summed over layers)."""
    return 2 * rows_in_context * cfg["index_n_heads"] \
        * cfg["index_head_dim"]


def index_bytes(cfg, keys_read):
    """Indexer keys read once (``keys_read``: summed over layers)."""
    return keys_read * cfg["index_head_dim"] * WEIGHT_BYTES


def sparse_attention_flops(cfg, rows_attended, queries):
    """Absorbed latent attention over the selected rows: scores over
    ``rank + rope`` values and values over ``rank`` a (query, head,
    row), and a query's absorption of ``W_UK`` and ``W_UV`` a head
    (both summed over layers)."""
    c, h, dn, dr, dv, rank, qr = _dims(cfg)
    return 2 * h * (rows_attended * (rank + dr + rank)
                    + queries * rank * (dn + dv))


def sparse_attention_bytes(cfg, rows_attended):
    """The selected latent rows, each read once a query."""
    return rows_attended * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) \
        * WEIGHT_BYTES


def moe_gmm_bytes(cfg, experts_hit):
    """Weights of the held experts that got a token, each read once
    (``experts_hit`` summed over expert layers and runs)."""
    return experts_hit * expert_params(cfg) * WEIGHT_BYTES


def step_flops(cfg, tokens, head_rows, local_assignments, rows_in_context,
               rows_attended):
    """Everything ``tokens`` rows (decoded or prefilled) need:
    ``head_rows`` of them a row of logits; the last three summed over
    layers."""
    n_layers = len(cfg["layers_kept"])
    return (tokens * token_matmul_flops(cfg) + head_rows * head_flops(cfg)
            + expert_flops(cfg, local_assignments)
            + index_flops(cfg, rows_in_context)
            + sparse_attention_flops(cfg, rows_attended, tokens * n_layers))
