"""Operations and bytes from shapes — the yardstick's own count; nothing
here is read from the compiler (Pallas custom calls report zero FLOPs to
XLA's cost analysis, so attention would vanish from an MFU taken there).
"""


def param_count(cfg):
    """Parameters of a GPT-2 configuration file (tied head counted once)."""
    d, L, f = cfg["n_embd"], cfg["n_layer"], cfg["n_inner"]
    block = (d * 3 * d + 3 * d) + (d * d + d) + (d * f + f) + (f * d + d) \
        + 4 * d
    return cfg["vocab_size"] * d + cfg["n_positions"] * d + L * block + 2 * d


def matmul_params(cfg):
    """Parameters that sit in a matrix product per token: the blocks'
    matrices and the (tied) vocabulary head; embeddings are lookups."""
    d, L, f = cfg["n_embd"], cfg["n_layer"], cfg["n_inner"]
    return L * (3 * d * d + d * d + 2 * d * f) + cfg["vocab_size"] * d


def train_flops_per_token(cfg, seq_len):
    """Forward + backward of one token in a causal sequence of ``seq_len``:
    6 x matmul parameters, plus attention's two products (q k^T and p v),
    2 x 2 x d per attended pair, causal so seq_len / 2 pairs a token on
    average, three passes (forward, and twice over in the backward).
    Recomputed operations are not counted."""
    attn = 3 * 2 * 2 * cfg["n_embd"] * (seq_len / 2.0) * cfg["n_layer"]
    return 6.0 * matmul_params(cfg) + attn


def flash_attention_cost(batch, heads, seq_q, seq_k, d_head, itemsize,
                         causal=True, backward=False):
    """(flops, bytes) the algorithm needs for one flash-attention call.
    Forward: q k^T and p v. Backward: five products of that size (dq, dk,
    dv, and the recomputed s and dp). Bytes: q, k, v, o (and in the
    backward do, dq, dk, dv as well) once each."""
    pairs = seq_q * seq_k / (2.0 if causal else 1.0)
    prod = 2.0 * batch * heads * pairs * d_head
    flops = (5.0 if backward else 2.0) * prod
    one = batch * heads * d_head * itemsize
    tensors = (2 * seq_q + 2 * seq_k) * one
    return flops, (2.0 if backward else 1.0) * tensors


def paged_decode_cost(live_rows, heads, d_head, itemsize):
    """(flops, bytes) of one paged decode-attention call over ``live_rows``
    cache rows summed over the batch: each row's K and V read once; q k and
    p v are 2 flops per element each."""
    elems = live_rows * heads * d_head
    return 4.0 * elems, 2.0 * elems * itemsize


def roofline_share(flops, nbytes, seconds, peaks):
    """Share (in %) of the roofline a kernel reached: the least time the
    chip could take over the time it took; and which peak bounds it."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    least = max(t_flops, t_bytes)
    return 100.0 * least / seconds, ("compute" if t_flops >= t_bytes
                                     else "memory")
