"""Operations and bytes of the ``keye_vl2`` family's kernels, counted from
the WORK — keys scored, rows selected, (query, key) pairs selected — never
from what an implementation happens to stream, so that a roofline share
reads the same work whatever implements it and cannot pass 100 %; and the
parameter count of a configuration file of the family.
"""

from chipbench import flops_deepseek_v3


def param_count(cfg):
    """Parameters of a configuration file of this family as it is RUN (the
    layers served, the experts held, the sliced vocabulary; embedding and
    head both counted: they are not tied)."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    H, K, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    sa = cfg["sa_config"]
    Hi, Di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    attn = d * (H + 2 * K) * D + 2 * D + H * D * d       # q k v, norms, o
    index = d * (Hi * Di + Di + Hi) + Di                 # qI kI w, key norm
    moe = d * cfg["router_width"] + len(cfg["experts_held"]) * 3 * d * fe
    return 2 * cfg["vocab_size"] * d + d \
        + cfg["num_hidden_layers"] * (attn + index + moe + 2 * d)


def index_score_cost(keys, index_heads, index_dim, itemsize):
    """(flops, bytes) of decode steps' indexer scores over ``keys`` cached
    keys (live slot x step x layer x context): a key's ``index_dim`` values
    read once (128 B), a product with each of the ``index_heads`` small
    queries."""
    return (2.0 * keys * index_heads * index_dim,
            float(keys) * index_dim * itemsize)


def sparse_decode_cost(rows, slot_steps, heads, kv_heads, d_head, itemsize):
    """(flops, bytes) of selected reads of ``rows`` cache rows (slot x step
    x layer x rows selected): a row of k and a row of v (2,048 B), q k and
    p v for each of the ``heads`` query heads; and the q it takes and the o
    it gives (float32) once a (slot, step, layer), ``slot_steps`` of
    them."""
    return (4.0 * rows * heads * d_head,
            2.0 * rows * kv_heads * d_head * itemsize
            + 2.0 * slot_steps * heads * d_head * 4)


def sparse_prefill_cost(pairs, heads, d_head):
    """(flops, bytes) of admissions' attention under the selection over
    ``pairs`` SELECTED (query, key) pairs (x layer): q k and p v for each
    of the ``heads`` query heads, 4 x 32 x 128 a pair. Compute-bound by
    construction: the bytes are left at 0."""
    return 4.0 * pairs * heads * d_head, 0.0


#: the held experts' three grouped products (chipbench/flops_deepseek_v3.py)
expert_matmul_cost = flops_deepseek_v3.expert_matmul_cost
