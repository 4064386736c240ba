"""Operations and bytes of the ``deepseek_v3`` family's two kernels, from
shapes — the yardstick's own count (Pallas custom calls report nothing to
XLA's cost analysis).
"""


def mla_decode_cost(live_rows, heads, row, d_value, itemsize):
    """(flops, bytes) of absorbed latent-attention reads over ``live_rows``
    cache rows summed over the batch: every live row is read ONCE (keys and
    values are the same row, shared by all heads); q k is 2 x row flops a
    head a row, p v 2 x d_value."""
    return (live_rows * heads * (2.0 * row + 2.0 * d_value),
            float(live_rows) * row * itemsize)


def expert_matmul_cost(assignments, touched, d_model, d_expert, itemsize):
    """(flops, bytes) of the held experts' three grouped products:
    ``assignments`` (token, choice) pairs computed — 6 x d_model x d_expert
    flops each (gate, up, down) — and ``touched`` expert visits (an expert
    that had a token in a step), each reading that expert's three matrices
    once; activations in and out, d_model wide, in ``itemsize`` and f32."""
    flops = 6.0 * assignments * d_model * d_expert
    weights = 3.0 * touched * d_model * d_expert * itemsize
    acts = assignments * d_model * (itemsize + 4.0)
    return flops, weights + acts
