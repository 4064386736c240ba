"""The plain reference of the ``lfm2_moe`` family (LFM2-8B-A1B's block): the
forward pass of a whole sequence in straightforward ``jax.numpy``, float32,
under matmul precision ``highest``. No kernels, no cache, no batching, no
grouped products; it imports nothing of paddle_tpu and reads only the
parameter tree it is handed and the hyper-parameters of the configuration
file (:func:`hparams`). The (bfloat16-valued) weights stay as they are on
the device and are upcast one matrix at a time, inside the product that
uses them; the depth runs one layer's program at a time, so what is live
beside the weights is one layer's activations.

Written from the published ``lfm2_moe`` modelling code and the catalog row's
``config``. Layer ``i``, input ``h [T, d]``, every norm an RMSNorm (eps
``norm_eps``) in float32: ``h += operator_i(norm(h)); h += ffn_i(norm(h))``.

* ``conv`` operator (gated short convolution): ``[B | C | x] = u W_in``;
  ``z = B * x``; ``c_t = sum_{j=0..L-1} w[:, j] * z_{t-(L-1)+j}`` (depthwise,
  causal, zeros before the sequence, ``L = conv_L_cache`` = 3, no bias);
  ``y = (C * c) W_out``.
* ``full_attention`` operator: ``q, k, v = u W_q, u W_k, u W_v`` -> 32 / 8 / 8
  heads of 64; ``q`` and ``k`` RMS-normed per head (``q_layernorm``,
  ``k_layernorm``) BEFORE RoPE; RoPE over the whole head in the half-split
  (``rotate_half``) layout, ``theta`` 1e6; query head h reads KV head
  ``h // 4``; ``score = q . k / 8``, causal softmax, ``o = P v``,
  ``y = o W_O``. No biases.
* dense FFN (the first ``num_dense_layers`` layers): ``(silu(y W_g) * y W_u)
  W_d``.
* expert layer: ``s = sigmoid(y W_r)`` over all ``num_experts``; selection =
  the top ``num_experts_per_tok`` of ``s + expert_bias``; weights = ``s`` at
  the chosen, divided by (their sum + 1e-6) (``norm_topk_prob``), times
  ``routed_scaling_factor``. Output = sum over the chosen experts HELD HERE
  of ``w_e * SwiGLU_e(y)``; no shared expert.
* final RMSNorm (``embedding_norm``), head tied to the embedding.

Departures, shared with the system under test: weights are random from a
seed; the head is TIED (the catalog row does not say; the LFM2 family ties
it: the configuration file lists it under ``assumed``); ``w_qkv`` holds the
published q, k and v projections side by side, columns in that order. The
published code keeps the last ``L`` = 3 gated inputs of a sequence as its
convolution cache, of which a step uses two; the system keeps those two.
That is the system's state, not the reference's: here nothing is cached.

``operand`` rounds the operands of every matrix product: None is the
reference; "fp8" (operands scaled per tensor and rounded through
float8_e4m3fn before a bfloat16 product) is the CONTROL, the precision
below the configuration's bfloat16.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def layer_types(config):
    """The operators of the layers served: the published ``layer_types``
    from ``first_layer`` on, ``num_hidden_layers`` of them."""
    first = config.get("first_layer", 0)
    return tuple(config["layer_types"][first:first
                                       + config["num_hidden_layers"]])


def hparams(config):
    """The numbers the equations above name, from a configuration file."""
    return {
        "n_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "d_head": config["hidden_size"] // config["num_attention_heads"],
        "eps": config["norm_eps"],
        "theta": float(config["rope_theta"]),
        "taps": config["conv_L_cache"],
        "n_experts": config["router_width"],
        "experts_held": tuple(config["experts_held"]),
        "top_k": config["num_experts_per_tok"],
        "routed_scale": float(config["routed_scaling_factor"]),
        "norm_topk_prob": bool(config["norm_topk_prob"]),
    }


def _key(hp):
    return tuple(sorted(hp.items()))


def _round(x, operand):
    if operand == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))),
                            1e-30) / 448.0
        q = (x.astype(jnp.float32) / scale).astype(jnp.float8_e4m3fn)
        return (q.astype(jnp.float32) * scale).astype(jnp.bfloat16)
    return x.astype(jnp.float32)


def _mm(a, b, operand):
    if operand is None:
        return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=HIGHEST)
    return jnp.matmul(_round(a, operand), _round(b, operand),
                      preferred_element_type=jnp.float32)


def _ein(spec, a, b, operand):
    if operand is None:
        return jnp.einsum(spec, a.astype(jnp.float32),
                          b.astype(jnp.float32), precision=HIGHEST)
    return jnp.einsum(spec, _round(a, operand), _round(b, operand),
                      preferred_element_type=jnp.float32)


def _rms(x, gamma, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gamma.astype(jnp.float32)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


# -- the operators -----------------------------------------------------------

def short_conv(p, u, hp, operand=None):
    """u [T, d] (already normed) -> the operator's output [T, d]."""
    T, L = u.shape[0], hp["taps"]
    b, c, x = jnp.split(_mm(u, p["w_in"], operand), 3, axis=-1)
    z = jnp.concatenate([jnp.zeros((L - 1, b.shape[1]), jnp.float32),
                         b * x])
    w = p["w_conv"].astype(jnp.float32)                        # [d, L]
    conv = sum(w[:, j] * z[j:j + T] for j in range(L))
    return _mm(c * conv, p["w_out"], operand)


def _rope(x, theta):
    """x [T, H, D], half-split pairs (x_i, x_{i + D/2}), positions 0..T-1:
    ``x cos + rotate_half(x) sin`` with ``rotate_half(x) = [-x2 | x1]``."""
    T, _, D = x.shape
    inv = np.array([theta ** (-2.0 * i / D) for i in range(D // 2)],
                   np.float32)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(inv)[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x = x.astype(jnp.float32)
    rot = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * cos + rot * sin


def attention(p, u, hp, operand=None):
    """u [T, d] (already normed) -> the operator's output [T, d]. One KV
    head and its group of query heads at a time: the [T, T] scores of 4
    heads, not of 32, are live at once."""
    T = u.shape[0]
    H, K, D = hp["n_heads"], hp["kv_heads"], hp["d_head"]
    G = H // K
    qkv = _mm(u, p["w_qkv"], operand)
    q = qkv[:, :H * D].reshape(T, H, D)
    k = qkv[:, H * D:(H + K) * D].reshape(T, K, D)
    v = qkv[:, (H + K) * D:].reshape(T, K, D)
    q = _rope(_rms(q, p["q_norm"]["gamma"], hp["eps"]), hp["theta"])
    k = _rope(_rms(k, p["k_norm"]["gamma"], hp["eps"]), hp["theta"])
    causal = jnp.tril(jnp.ones((T, T), bool))
    outs = []
    for j in range(K):
        s = _ein("tgd,sd->gts", q[:, j * G:(j + 1) * G], k[:, j],
                 operand) * D ** -0.5
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        outs.append(_ein("gts,sd->gtd", w, v[:, j], operand))
    o = jnp.moveaxis(jnp.concatenate(outs, axis=0), 0, 1)      # [T, H, D]
    return _mm(o.reshape(T, H * D), p["w_o"], operand)


def swiglu(p, y, operand=None):
    return _mm(_silu(_mm(y, p["w_gate"], operand))
               * _mm(y, p["w_up"], operand), p["w_down"], operand)


def route(p, y, hp):
    """y [N, d] -> (chosen [N, k] expert ids, weights [N, k]); always
    float32 at full precision, whatever the control."""
    s = 1.0 / (1.0 + jnp.exp(-jnp.matmul(
        y.astype(jnp.float32), p["w_router"].astype(jnp.float32),
        precision=HIGHEST)))
    pick = s + p["e_bias"].astype(jnp.float32)
    chosen = jnp.argsort(-pick, axis=-1)[:, :hp["top_k"]]
    w = jnp.take_along_axis(s, chosen, axis=1)
    if hp["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
    return chosen, w * hp["routed_scale"]


def expert_layer(p, y, hp, operand=None):
    """y [N, d] (already normed) -> the layer's output [N, d]: the chosen
    experts that ``hp["experts_held"]`` names, one at a time over all the
    tokens. ``p["w_gate"][i]`` is the i-th HELD expert's matrix."""
    chosen, w = route(p, y, hp)

    def add_one(out, held):
        e, one = held
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)  # [N]
        return out + w_e[:, None] * swiglu(one, y, operand), None
    # a loop over the held experts in their order (one body to compile,
    # not one per expert)
    out, _ = jax.lax.scan(
        add_one, jnp.zeros(y.shape, jnp.float32),
        (jnp.asarray(hp["experts_held"], jnp.int32),
         {k: p[k] for k in ("w_gate", "w_up", "w_down")}))
    return out


def block(p, h, hp, operand=None):
    """One layer over one sequence: h [T, d] -> [T, d]. The operator is
    told by what the layer's parameters hold."""
    u = _rms(h, p["op_norm"]["gamma"], hp["eps"])
    h = h + (short_conv(p["conv"], u, hp, operand) if "conv" in p
             else attention(p["attn"], u, hp, operand))
    y = _rms(h, p["ffn_norm"]["gamma"], hp["eps"])
    if "moe" in p:
        return h + expert_layer(p["moe"], y, hp, operand)
    return h + swiglu(p["ffn"], y, operand)


def n_layers(params):
    return sum(1 for k in params if k.startswith("blocks_"))


# One compiled program per kind of layer (operator x FFN), the same for
# every layer of its kind, holding one layer's activations.

@partial(jax.jit, static_argnums=(2, 3))
def _block(p, h, hp_key, operand):
    return block(p, h, dict(hp_key), operand)


@partial(jax.jit, static_argnums=(3, 4))
def _head(norm_f, embed_w, h, eps, operand):
    return _mm(_rms(h, norm_f["gamma"], eps), embed_w.T, operand)


def forward(params, ids, hp, operand=None):
    """ids [T] -> logits [T, V] float32 (the head tied to the embedding)."""
    h = params["embed"]["w"][ids].astype(jnp.float32)
    for i in range(n_layers(params)):
        h = _block(params[f"blocks_{i}"], h, _key(hp), operand)
    return _head(params["norm_f"], params["embed"]["w"], h, hp["eps"],
                 operand)


@jax.jit
def _gaps(ref, low, nxt):
    best = jnp.max(ref, axis=-1)
    served = jnp.take_along_axis(ref, nxt[..., None], -1)[..., 0]
    pick = jnp.take_along_axis(ref, jnp.argmax(low, -1)[..., None],
                               -1)[..., 0]
    return best, served, pick


def token_gaps(params, ids, hp, operand=None):
    """For ids [T]: at every position t the reference logits of position t
    predict token t+1. Returns (best, at_served, control_pick): ``best[t]``
    the largest reference logit, ``at_served[t]`` the reference logit of
    ids[t+1], and, when ``operand`` names a lower precision,
    ``control_pick[t]`` the reference logit of the token that precision
    puts first (else None). All float32 [T-1]."""
    ref = forward(params, ids, hp)[:-1]
    low = ref if operand is None else forward(params, ids, hp, operand)[:-1]
    best, served, pick = _gaps(ref, low, ids[1:])
    return best, served, (None if operand is None else pick)
