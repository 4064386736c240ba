"""Operations and bytes of the ``lfm2_moe`` family's kernels, from shapes —
the yardstick's own count (Pallas custom calls report nothing to XLA's cost
analysis) — and the parameter count of a configuration file of the family.
"""

from chipbench import flops, flops_deepseek_v3


def param_count(cfg):
    """Parameters of a configuration file of this family as it is RUN (the
    layers served, the experts held; the tied head counted once)."""
    d, f, fe = (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["moe_intermediate_size"])
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = d // H
    first = cfg.get("first_layer", 0)
    kinds = cfg["layer_types"][first:first + cfg["num_hidden_layers"]]
    conv = d * 3 * d + d * cfg["conv_L_cache"] + d * d
    attn = d * (H + 2 * K) * D + 2 * D + H * D * d
    dense = 3 * d * f
    moe = d * cfg["router_width"] + cfg["router_width"] \
        + len(cfg["experts_held"]) * 3 * d * fe
    total = cfg["vocab_size"] * d + d                  # embedding, last norm
    for i, kind in enumerate(kinds):
        total += (conv if kind == "conv" else attn) + 2 * d
        total += dense if i < cfg["num_dense_layers"] else moe
    return total


def gqa_decode_cost(live_rows, heads, kv_heads, d_head, itemsize):
    """(flops, bytes) of one grouped-query paged decode-attention call over
    ``live_rows`` cache rows summed over the batch: K and V of the
    ``kv_heads`` heads a row holds, read ONCE for the whole group; q k and
    p v are 2 flops per element for each of the ``heads`` query heads."""
    return (4.0 * live_rows * heads * d_head,
            2.0 * live_rows * kv_heads * d_head * itemsize)


def flash_prefill_cost(batch, heads, kv_heads, seq, d_head, itemsize):
    """(flops, bytes) of one causal flash-attention forward call of a
    prefill: chipbench/flops.flash_attention_cost's operations at the
    call's own length and ``heads`` query heads; q and o of ``heads``
    heads, k and v of ``kv_heads``, once each."""
    f, _ = flops.flash_attention_cost(batch, heads, seq, seq, d_head,
                                      itemsize, causal=True)
    one = batch * seq * d_head * itemsize
    return f, 2.0 * one * heads + 2.0 * one * kv_heads


#: the held experts' three grouped products: the same count for any
#: program, a decode segment or a prefill (chipbench/flops_deepseek_v3.py)
expert_matmul_cost = flops_deepseek_v3.expert_matmul_cost
