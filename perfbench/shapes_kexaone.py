"""Operations and bytes that the work of the K-EXAONE share NEEDS,
computed from the configuration file's own keys
(``configs/k-exaone-236b-a23b.json``: ``num_experts`` is the count HELD
here, ``published`` holds the router's width, ``layer_types`` the
published pattern read at ``layers_kept``) and from what a run counted.

A count is what the mathematics needs once, whatever implements it: a
query scores each key it can see once (its whole context on a full
layer, ``min(context, sliding_window)`` keys on a sliding one) and
weighs that key's value once; a row of K and of V is read once a query
row's program (a page and a ring row alike: 8 heads x 128 values each);
a weight is read once a run; an expert that no token chose counts
nothing.  A multiply-add counts 2.
"""

WEIGHT_BYTES = 2            # bfloat16, the published dtype
FULL = "full_attention"


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def layer_kinds(cfg):
    return [cfg["layer_types"][l] for l in cfg["layers_kept"]]


def ffn_kinds(cfg):
    return ["dense" if l < cfg["first_k_dense_replace"] else "moe"
            for l in cfg["layers_kept"]]


def attention_params(cfg):
    """The query, key, value and output projections of one layer."""
    c, h, kv, d = _dims(cfg)
    return c * (h + 2 * kv) * d + h * d * c


def expert_params(cfg):
    """One routed expert: gate, up and down matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_params(cfg):
    """Every matrix OUTSIDE the routed experts, the embedding and the
    head: what any run of either program reads once."""
    c = cfg["hidden_size"]
    total = 0
    for ffn in ffn_kinds(cfg):
        total += attention_params(cfg)
        if ffn == "dense":
            total += 3 * c * cfg["intermediate_size"]
        else:
            total += (cfg["num_shared_experts"] * expert_params(cfg)
                      + c * cfg["published"]["num_experts"])
    return total


def params(cfg):
    """Parameter count of the share (norm gains and biases left out)."""
    moe_layers = ffn_kinds(cfg).count("moe")
    return (dense_params(cfg) + 2 * cfg["vocab_size"] * cfg["hidden_size"]
            + moe_layers * cfg["num_experts"] * expert_params(cfg))


def kv_row_bytes(cfg):
    """One position's K and V in one layer, a page's row and a ring's
    alike."""
    _, _, kv, d = _dims(cfg)
    return 2 * kv * d * WEIGHT_BYTES


def page_bytes_per_token(cfg):
    """Paged bytes a token: the full layers' rows."""
    return layer_kinds(cfg).count(FULL) * kv_row_bytes(cfg)


def ring_bytes_per_slot(cfg):
    """Ring bytes a slot: ``sliding_window`` rows a sliding layer."""
    kinds = layer_kinds(cfg)
    return (len(kinds) - kinds.count(FULL)) * cfg["sliding_window"] \
        * kv_row_bytes(cfg)


def token_matmul_flops(cfg):
    """Forward FLOPs of one token through every matrix outside the
    routed experts and the head."""
    return 2 * dense_params(cfg)


def head_flops(cfg):
    """One row of logits over the vocabulary slice."""
    return 2 * cfg["vocab_size"] * cfg["hidden_size"]


def expert_flops(cfg, local_assignments):
    """The held experts' work: one expert's matrices a (token, expert)
    choice that fell on a held expert."""
    return 2 * local_assignments * expert_params(cfg)


def attention_flops(cfg, rows_read):
    """Scores and weighted values: ``2 D`` each a (query, head, key)
    (``rows_read``: query-key pairs, summed over layers)."""
    _, h, _, d = _dims(cfg)
    return 4 * h * d * rows_read


def kv_bytes(cfg, rows_read):
    """K and V rows read once a query row's pass over them."""
    return rows_read * kv_row_bytes(cfg)


def moe_gmm_bytes(cfg, experts_hit):
    """Weights of the held experts that got a token, each read once
    (``experts_hit`` summed over expert layers and runs)."""
    return experts_hit * expert_params(cfg) * WEIGHT_BYTES


def decode_bytes(cfg, steps, experts_hit, rows_read):
    """Everything ``steps`` decode runs must read: every matrix outside
    the routed experts and the head once a run, the hit experts, the K
    and V rows each slot's query sees (pages and rings)."""
    once = (dense_params(cfg) + cfg["vocab_size"] * cfg["hidden_size"]) \
        * WEIGHT_BYTES
    return (steps * once + moe_gmm_bytes(cfg, experts_hit)
            + kv_bytes(cfg, rows_read))


def chunk_full_attention(cfg, rows, offset):
    """Query-key pairs of one chunk of ``rows`` real rows at ``offset``
    on ONE full layer: row ``i`` sees ``offset + i + 1`` keys."""
    return rows * offset + rows * (rows + 1) // 2


def step_flops(cfg, tokens, head_rows, local_assignments, rows_read):
    """Everything ``tokens`` rows (decoded or prefilled) need:
    ``head_rows`` of them a row of logits; the last two summed over
    layers."""
    return (tokens * token_matmul_flops(cfg) + head_rows * head_flops(cfg)
            + expert_flops(cfg, local_assignments)
            + attention_flops(cfg, rows_read))
