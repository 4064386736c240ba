"""Operations and bytes of the ``afmoe`` family's kernels, from shapes — the
yardstick's own count (Pallas custom calls report nothing to XLA's cost
analysis) — and the parameter count of a configuration file of the family.
"""

from chipbench import flops_deepseek_v3, flops_lfm2


def layer_counts(cfg):
    """{"sliding": layers that read a window, "full": layers that read
    their whole context} of the layers served."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return {"sliding": kinds.count("sliding_attention"),
            "full": kinds.count("full_attention")}


def param_count(cfg):
    """Parameters of a configuration file of this family as it is RUN (all
    the layers, the experts held, the sliced vocabulary; embedding and head
    both counted: they are not tied)."""
    d, f, fe = (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["moe_intermediate_size"])
    H, K, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    attn = d * 2 * (H + K) * D + 2 * D + H * D * d     # q k v gate, norms, o
    moe = d * cfg["router_width"] + cfg["router_width"] \
        + (len(cfg["experts_held"]) + cfg["num_shared_experts"]) * 3 * d * fe
    n, dense = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    return 2 * cfg["vocab_size"] * d + d + n * (attn + 4 * d) \
        + dense * 3 * d * f + (n - dense) * moe


def window_decode_cost(window_rows, heads, kv_heads, d_head, itemsize):
    """(flops, bytes) of one windowed paged decode read over
    ``window_rows`` cache rows summed over the batch (``min(pos + 1,
    window)`` a slot): chipbench/flops_lfm2.gqa_decode_cost's count at the
    rows the window covers — K and V of the KV heads once for the whole
    group, 4 flops an element a query head."""
    return flops_lfm2.gqa_decode_cost(window_rows, heads, kv_heads, d_head,
                                      itemsize)


def band_keys(seq, window):
    """Keys the queries of a causal sequence of ``seq`` positions see
    through a window, summed: ``sum_i min(i + 1, window)``."""
    full = min(seq, window)
    return full * (full + 1) // 2 + (seq - full) * window


def window_flash_cost(batch, heads, kv_heads, seq, window, d_head, itemsize):
    """(flops, bytes) of one banded flash-attention forward: q k^T and p v
    over the BAND's (query, key) pairs for every query head; q and o of
    ``heads`` heads, k and v of ``kv_heads``, once each."""
    flops = 4.0 * batch * heads * band_keys(seq, window) * d_head
    one = batch * seq * d_head * itemsize
    return flops, 2.0 * one * heads + 2.0 * one * kv_heads


#: a full layer's causal square (chipbench/flops_lfm2.py) and the held
#: experts' three grouped products (chipbench/flops_deepseek_v3.py): the
#: same counts for this family
flash_prefill_cost = flops_lfm2.flash_prefill_cost
expert_matmul_cost = flops_deepseek_v3.expert_matmul_cost
