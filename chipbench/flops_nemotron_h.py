"""Operations and bytes of the ``nemotron_h`` family's kernels, from shapes —
the yardstick's own count (Pallas custom calls report nothing to XLA's cost
analysis) — and the parameter count of a configuration file of the family.
"""


def mamba_shape(cfg):
    """(heads, head_dim, groups, state) of a configuration's Mamba-2
    layers."""
    return (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
            cfg["ssm_state_size"])


def layer_counts(cfg):
    """{"M": state-space layers, "E": expert layers, "*": attention
    layers} of the configuration's pattern."""
    return {c: cfg["hybrid_override_pattern"].count(c) for c in "ME*"}


def param_count(cfg):
    """Parameters of a configuration file of this family as it is RUN (all
    the layers of the pattern, the experts held, the sliced vocabulary;
    embedding and head both counted: they are not tied)."""
    d = cfg["hidden_size"]
    H, P, G, N = mamba_shape(cfg)
    inner, conv = H * P, H * P + 2 * G * N
    mamba = d * (inner + conv + H) + conv * cfg["conv_kernel"] + conv \
        + 3 * H + inner + inner * d
    f, fs = (cfg["moe_intermediate_size"],
             cfg["moe_shared_expert_intermediate_size"])
    moe = d * cfg["router_width"] + cfg["router_width"] \
        + len(cfg["experts_held"]) * 2 * d * f + 2 * d * fs
    Hq, K, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    attn = d * (Hq + 2 * K) * D + Hq * D * d
    n = layer_counts(cfg)
    return 2 * cfg["vocab_size"] * d + d \
        + n["M"] * (mamba + d) + n["E"] * (moe + d) + n["*"] * (attn + d)


def ssm_update_cost(updates, heads, head_dim, groups, state, state_bytes=4):
    """(flops, bytes) of ``updates`` single-position state updates (one a
    live slot a Mamba layer a step): the state read once and written once
    (``state_bytes`` an element: float32 as the configuration states),
    and the step's own ``x, B, C, dt, y`` in float32; five operations a
    state element (decay, outer product, add, times C, the sum)."""
    elems = heads * head_dim * state
    step = (2 * heads * head_dim + 2 * groups * state + heads) * 4.0
    return 5.0 * updates * elems, updates * (2.0 * elems * state_bytes + step)


def ssd_scan_cost(tokens, heads, head_dim, groups, state, chunk, itemsize):
    """(flops, bytes) of the chunked scan over ``tokens`` REAL (prompt
    token, Mamba layer) pairs (padding is not work): a token's row of ``C
    B^T`` 2 x chunk x state a group; the masked product with ``X`` 2 x
    chunk x head_dim, the chunk's state 2 x state x head_dim and the state
    to the output 2 x state x head_dim a head. Bytes: ``x, B, C`` in the
    operands' ``itemsize``, ``dt`` and ``y`` in float32, once."""
    flops = 2.0 * chunk * state * groups \
        + heads * (2.0 * chunk * head_dim + 4.0 * state * head_dim)
    nbytes = (heads * head_dim + 2 * groups * state) * itemsize \
        + (heads + heads * head_dim) * 4.0
    return tokens * flops, tokens * nbytes


def relu2_expert_matmul_cost(assignments, touched, d_model, d_expert,
                             itemsize):
    """(flops, bytes) of the held experts' TWO grouped products (an expert
    is ``down(relu(up(y))^2)``, not gated): ``assignments`` (token, choice)
    pairs computed — 4 x d_model x d_expert flops each — and ``touched``
    expert visits, each reading that expert's two matrices once;
    activations in and out, d_model wide, in ``itemsize`` and f32."""
    flops = 4.0 * assignments * d_model * d_expert
    weights = 2.0 * touched * d_model * d_expert * itemsize
    acts = assignments * d_model * (itemsize + 4.0)
    return flops, weights + acts
