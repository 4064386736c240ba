"""The plain reference of the ``deepseek_v3`` family (DeepSeek-V3's block,
which GigaChat3.1-702B-A36B publishes unchanged): the forward pass in
straightforward ``jax.numpy``, float32, under matmul precision ``highest``.
No kernels, no cache, no absorption of W_UK into the query, no grouped or
batched products; it imports nothing of paddle_tpu and reads only the
parameter tree it is handed and the hyper-parameters of the configuration
file (:func:`hparams`). The (bfloat16-valued) weights stay as they are on
the device and are upcast one matrix at a time, inside the product that
uses them.

Written from the DeepSeek-V2/V3 papers and the published ``deepseek_v3``
modelling code. Per layer, input ``h [T, d]``, every norm an RMSNorm (eps
from the configuration) computed in float32:

* latent attention: ``x = norm(h)``; ``c_q = norm(x W_DQ)``; ``q = c_q W_UQ``
  -> heads x (``q_nope`` | ``q_rope``); ``[c_kv | k_r] = x W_DKV``; ``c_kv =
  norm(c_kv)``; ``[k_nope | v] = c_kv W_UKV`` -> heads x (d_nope | d_v);
  ``k_rope = RoPE(k_r)``, one head shared by all; ``score = (q_nope . k_nope
  + RoPE(q_rope) . k_rope) * s``, causal softmax, ``o = P v``, ``h += o W_O``.
  ``s = (d_nope + d_rope)^-0.5 * m^2``, ``m = 0.1 * mscale_all_dim *
  ln(factor) + 1``. RoPE rotates interleaved pairs by ``t * f_i`` with YaRN's
  blended inverse frequencies (fixed, applied at every position); the
  cos/sin factor ``mscale / mscale_all_dim`` is 1 for this configuration
  and is applied as published.
* dense FFN (the first ``first_k_dense_replace`` layers): ``h += (silu(y W_g)
  * y W_u) W_d``, ``y = norm(h)``.
* expert layer: ``s = sigmoid(y W_r)`` over ALL routed experts; selection on
  ``s + e_score_correction_bias``: ``n_group`` groups, a group scores the
  sum of its top 2, the best ``topk_group`` groups stay (the others are set
  to -inf), the top ``num_experts_per_tok`` experts are chosen; weights are
  the original ``s`` there, divided by their sum, times
  ``routed_scaling_factor``. Output = sum over the chosen experts HELD HERE
  (``experts_held``) of ``w_e * SwiGLU_e(y)`` + the shared expert's SwiGLU.
  A choice that lands on an expert not held is left out (the chip's share
  of the layer, model-configs guide section 4).
* final norm, untied head.

Departures, shared with the system under test: the multi-token-prediction
module is left out (the main model's logits do not depend on it); weights
are random from a seed; the groups outside the kept ones are masked with
-inf as DeepSeek's own inference code does (the transformers port fills
0.0, which differs only when a kept expert's biased score is negative).

``operand`` rounds the operands of every matrix product: None is the
reference; "fp8" (operands scaled per tensor and rounded through
float8_e4m3fn before a bfloat16 product) is the CONTROL, the precision
below the configuration's bfloat16.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def hparams(config):
    """The numbers the equations above name, from a configuration file."""
    rs = config.get("rope_scaling") or {}
    return {
        "n_heads": config["num_attention_heads"],
        "d_nope": config["qk_nope_head_dim"],
        "d_rope": config["qk_rope_head_dim"],
        "d_v": config["v_head_dim"],
        "kv_rank": config["kv_lora_rank"],
        "eps": config["rms_norm_eps"],
        "theta": float(config["rope_theta"]),
        "factor": float(rs.get("factor", 1.0)),
        "beta_fast": rs.get("beta_fast", 32),
        "beta_slow": rs.get("beta_slow", 1),
        "mscale": rs.get("mscale", 1.0),
        "mscale_all_dim": rs.get("mscale_all_dim", 0.0),
        "original": rs.get("original_max_position_embeddings", 4096),
        "n_experts": config["router_width"],
        "experts_held": tuple(config["experts_held"]),
        "top_k": config["num_experts_per_tok"],
        "n_group": config["n_group"],
        "topk_group": config["topk_group"],
        "routed_scale": config["routed_scaling_factor"],
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


# -- rotary ------------------------------------------------------------------

def inv_freq(hp):
    """YaRN's blended inverse frequencies of the rotary slice."""
    d, theta, factor = hp["d_rope"], hp["theta"], hp["factor"]
    freq = np.array([theta ** (-2.0 * i / d) for i in range(d // 2)])
    if factor <= 1.0:
        return freq.astype(np.float32)

    def dim_of(turns):
        return d * math.log(hp["original"] / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(dim_of(hp["beta_fast"])), 0)
    high = min(math.ceil(dim_of(hp["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(d // 2):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(freq[i] / factor * ramp + freq[i] * (1.0 - ramp))
    return np.asarray(out, np.float32)


def _mscale(factor, m):
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def _rope(x, hp):
    """x [B, T, H, d_rope], interleaved pairs, positions 0..T-1."""
    B, T, H, d = x.shape
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq(hp))[None, :]                                 # [T, d/2]
    amp = _mscale(hp["factor"], hp["mscale"]) / (
        _mscale(hp["factor"], hp["mscale_all_dim"])
        if hp["mscale_all_dim"] else 1.0) if hp["factor"] > 1.0 else 1.0
    cos = (jnp.cos(ang) * amp)[None, :, None, :]
    sin = (jnp.sin(ang) * amp)[None, :, None, :]
    pairs = x.astype(jnp.float32).reshape(B, T, H, d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(B, T, H, d)


# -- the layers --------------------------------------------------------------

def attention(p, x, hp, operand=None):
    """x [B, T, d] (already normed) -> the attention output [B, T, d]."""
    B, T, _ = x.shape
    H, dn, dr, dv, r = (hp["n_heads"], hp["d_nope"], hp["d_rope"],
                        hp["d_v"], hp["kv_rank"])
    c_q = _rms(_mm(x, p["w_dq"], operand), p["q_norm"]["gamma"], hp["eps"])
    q = _mm(c_q, p["w_uq"], operand).reshape(B, T, H, dn + dr)
    ckr = _mm(x, p["w_dkv"], operand)
    c_kv = _rms(ckr[..., :r], p["kv_norm"]["gamma"], hp["eps"])
    kv = _mm(c_kv, p["w_ukv"], operand).reshape(B, T, H, dn + dv)
    q_rope = _rope(q[..., dn:], hp)
    k_rope = _rope(ckr[..., None, r:], hp)                     # one head
    m = _mscale(hp["factor"], hp["mscale_all_dim"]) \
        if hp["mscale_all_dim"] else 1.0
    scale = (dn + dr) ** -0.5 * m * m
    s = (_ein("bthd,bshd->bhts", q[..., :dn], kv[..., :dn], operand)
         + _ein("bthd,bsd->bhts", q_rope, k_rope[:, :, 0], operand)) * scale
    causal = jnp.tril(jnp.ones((T, T), bool))
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _ein("bhts,bshd->bthd", w, kv[..., dn:], operand)
    return _mm(o.reshape(B, T, H * dv), p["w_o"], operand)


def swiglu(p, y, operand=None):
    return _mm(_silu(_mm(y, p["w_gate"], operand))
               * _mm(y, p["w_up"], operand), p["w_down"], operand)


def route(p, y, hp):
    """y [N, d] -> (chosen [N, k] expert ids over ALL experts, weights
    [N, k]); always float32 at full precision, whatever the control."""
    E, G = hp["n_experts"], hp["n_group"]
    s = 1.0 / (1.0 + jnp.exp(-jnp.matmul(
        y.astype(jnp.float32), p["w_router"].astype(jnp.float32),
        precision=HIGHEST)))
    pick = s + p["e_bias"].astype(jnp.float32)
    per_group = pick.reshape(-1, G, E // G)
    group_score = jnp.sort(per_group, axis=-1)[..., -2:].sum(-1)
    worst_kept = jnp.sort(group_score, axis=-1)[:, -hp["topk_group"]]
    kept = group_score >= worst_kept[:, None]                  # [N, G]
    pick = jnp.where(jnp.repeat(kept, E // G, axis=1), pick, -jnp.inf)
    chosen = jnp.argsort(-pick, axis=-1)[:, :hp["top_k"]]
    w = jnp.take_along_axis(s, chosen, axis=1)
    return chosen, w / (w.sum(-1, keepdims=True) + 1e-20) * hp["routed_scale"]


def expert_layer(p, y, hp, operand=None, shared=True):
    """y [N, d] (already normed) -> this share's output [N, d]: the chosen
    experts that ``hp["experts_held"]`` names, one at a time over all the
    tokens, and the shared expert. ``p["w_gate"][i]`` is the i-th HELD
    expert's matrix."""
    chosen, w = route(p, y, hp)
    out = jnp.zeros(y.shape, jnp.float32)
    for i, e in enumerate(hp["experts_held"]):
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)  # [N]
        one = {k: p[k][i] for k in ("w_gate", "w_up", "w_down")}
        out = out + w_e[:, None] * swiglu(one, y, operand)
    if shared and "shared" in p:
        out = out + swiglu(p["shared"], y, operand)
    return out


def block(p, h, hp, operand=None):
    B, T, d = h.shape
    h = h + attention(p["attn"], _rms(h, p["attn_norm"]["gamma"], hp["eps"]),
                      hp, operand)
    y = _rms(h, p["ffn_norm"]["gamma"], hp["eps"])
    if "moe" in p:
        return h + expert_layer(p["moe"], y.reshape(B * T, d), hp,
                                operand).reshape(B, T, d)
    return h + swiglu(p["ffn"], y, operand)


def n_layers(params):
    return sum(1 for k in params if k.startswith("blocks_"))


# One compiled program per kind of layer (dense, expert), the same for every
# layer of its kind, holding one layer's activations.

@partial(jax.jit, static_argnums=(2, 3))
def _block(p, h, hp_key, operand):
    return block(p, h, dict(hp_key), operand)


@partial(jax.jit, static_argnums=(3, 4))
def _head(norm_f, head, h, eps, operand):
    return _mm(_rms(h, norm_f["gamma"], eps), head, operand)


def forward(params, ids, hp, operand=None):
    """ids [B, T] -> logits [B, T, V] float32."""
    h = params["embed"]["w"][ids].astype(jnp.float32)
    for i in range(n_layers(params)):
        h = _block(params[f"blocks_{i}"], h, _key(hp), operand)
    return _head(params["norm_f"], params["head"], h, hp["eps"], operand)


@jax.jit
def _gaps(ref, low, nxt):
    best = jnp.max(ref, axis=-1)
    served = jnp.take_along_axis(ref, nxt[..., None], -1)[..., 0]
    pick = jnp.take_along_axis(ref, jnp.argmax(low, -1)[..., None],
                               -1)[..., 0]
    return best, served, pick


def token_gaps(params, ids, hp, operand=None):
    """For ids [B, T]: at every position t the reference logits of position
    t predict token t+1. Returns (best, at_served, control_pick):
    ``best[b, t]`` the largest reference logit, ``at_served[b, t]`` the
    reference logit of ids[b, t+1], and, when ``operand`` names a lower
    precision, ``control_pick[b, t]`` the reference logit of the token that
    precision puts first (else None). All float32 [B, T-1]."""
    ref = forward(params, ids, hp)[:, :-1]
    low = ref if operand is None else forward(params, ids, hp,
                                              operand)[:, :-1]
    best, served, pick = _gaps(ref, low, ids[:, 1:])
    return best, served, (None if operand is None else pick)
