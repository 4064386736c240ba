"""Operations and bytes of the ``mimo_v2_flash`` family's four attention
reads, from shapes — the yardstick's own count (Pallas custom calls report
nothing to XLA's cost analysis) — and the parameter count of a
configuration file of the family. Keys are ``head_dim`` wide and values
``v_head_dim``, so every count takes the two widths apart, and a layer
KIND's own KV heads (``num_key_value_heads`` in a global layer,
``swa_num_key_value_heads`` in a sliding one).
"""

from chipbench import flops_deepseek_v3


def layer_counts(cfg):
    """{"sliding": layers that read a window, "full": layers that read
    their whole context} of the layers served."""
    kinds = cfg["hybrid_layer_pattern"][:cfg["num_hidden_layers"]]
    return {"sliding": sum(kinds), "full": len(kinds) - sum(kinds)}


def kv_heads(cfg, kind):
    return cfg["swa_num_key_value_heads" if kind == "sliding"
               else "num_key_value_heads"]


def param_count(cfg):
    """Parameters of a configuration file of this family as it is RUN (the
    layers served, the experts held, the sliced vocabulary; embedding and
    head both counted: they are not tied; a sliding layer's sink logits
    among them)."""
    d, f, fe = (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["moe_intermediate_size"])
    H, Dk, Dv = (cfg["num_attention_heads"], cfg["head_dim"],
                 cfg["v_head_dim"])
    n = cfg["num_hidden_layers"]
    moe = d * cfg["router_width"] + cfg["router_width"] \
        + len(cfg["experts_held"]) * 3 * d * fe
    total = 2 * cfg["vocab_size"] * d + d
    for kind, routed in zip(cfg["hybrid_layer_pattern"][:n],
                            cfg["moe_layer_freq"][:n]):
        K = kv_heads(cfg, "sliding" if kind else "full")
        sink = cfg["add_swa_attention_sink_bias" if kind
                   else "add_full_attention_sink_bias"]
        total += d * ((H + K) * Dk + K * Dv) + H * Dv * d + (H if sink else 0)
        total += 2 * d + (moe if routed else 3 * d * f)
    return total


def decode_read_cost(rows, cfg, kind, itemsize=2):
    """(flops, bytes) of paged decode reads of a layer of ``kind`` over
    ``rows`` cache rows summed over the batch (and the layers): a row's k
    (``head_dim``) and v (``v_head_dim``) of the kind's KV heads ONCE for
    the whole group; q k is 2 x head_dim flops a query head a row, p v 2 x
    v_head_dim."""
    wide = cfg["head_dim"] + cfg["v_head_dim"]
    return (2.0 * rows * cfg["num_attention_heads"] * wide,
            float(rows) * kv_heads(cfg, kind) * wide * itemsize)


def flash_cost(pairs, positions, cfg, kind, itemsize=2):
    """(flops, bytes) of flash forwards of a layer of ``kind`` over
    ``pairs`` (query, key) pairs — a causal square's, or a band's — and
    ``positions`` positions (both summed over the layers): q k^T is 2 x
    head_dim flops a pair a query head, p v 2 x v_head_dim; q and o of the
    query heads, k and v of the kind's KV heads, once a position."""
    wide = cfg["head_dim"] + cfg["v_head_dim"]
    return (2.0 * pairs * cfg["num_attention_heads"] * wide,
            float(positions) * wide * itemsize
            * (cfg["num_attention_heads"] + kv_heads(cfg, kind)))


#: the held experts' three grouped products (chipbench/flops_deepseek_v3.py):
#: the same count for this family
expert_matmul_cost = flops_deepseek_v3.expert_matmul_cost
