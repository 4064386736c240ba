"""The plain reference of the ``mimo_v2_flash`` family (Xiaomi's
MiMo-V2-Flash): the forward pass of a whole sequence in straightforward
``jax.numpy``, float32, under matmul precision ``highest``. No kernels, no
cache, no ring, no batching, no grouped products; it imports nothing of
paddle_tpu and reads only the parameter tree it is handed and the
hyper-parameters of the configuration file (:func:`hparams`). The
(bfloat16-valued) weights stay as they are on the device and are upcast one
matrix at a time, inside the product that uses them; the depth runs one
layer's program at a time, and inside a layer everything that is a
position's own (q, the scores of a block of queries, the feed-forward) is
taken a BLOCK of positions at a time, so that a 49k-token row fits beside
10.8 GB of weights.

Written from the catalog row's ``config`` (ISSUE 47, section 1). Residual
stream ``h [T, d]``. Layer ``i`` of kind ``hybrid_layer_pattern[i]`` (0 =
global, 1 = sliding) and ``moe_layer_freq[i]`` (0 = dense), every norm an
RMSNorm (eps ``layernorm_epsilon``) in float32, TWO a layer (pre-norm):

    x   = RMSNorm(h; w_in)
    q   = x Wq -> [H, Dk]     k = x Wk -> [Hkv, Dk]
    v   = attention_value_scale * (x Wv) -> [Hkv, Dv]               (no bias)
    q, k: the first R = int(Dk * partial_rotary_factor) values of every head
          rotated (half-split pairs (j, j + R/2), inv_freq_j = theta^(-2j/R),
          theta = rope_theta in a global layer, swa_rope_theta in a sliding
          one); the values R .. Dk-1 pass
    s_hj = q_h . k_g(h),j / sqrt(Dk)   over j <= p (global) or
                                       p - window < j <= p (sliding)
    P_hj = exp(s_hj) / (sum_j' exp(s_hj') + [sliding] exp(b_h))
    o_h  = sum_j P_hj v_g(h),j -> [H, Dv];   h = h + concat_h(o_h) Wo
    y   = RMSNorm(h; w_post)
    dense:   h = h + Wd(silu(Wg y) * (Wu y))
    experts: c = sigmoid(y Wr);  T = top-k of (c + e_bias);
             w_e = c_e / (sum_{e in T} c_e + 1e-20)
             h = h + sum_{e in T, e held here} w_e * Wd_e(silu(Wg_e y) * Wu_e y)

``Hkv`` is ``num_key_value_heads`` in a global layer and
``swa_num_key_value_heads`` in a sliding one; ``g(h) = h // (H / Hkv)``.
``b`` [H] is the sliding layers' learned sink logit
(``add_swa_attention_sink_bias``; global layers have none:
``add_full_attention_sink_bias`` false). No group limit (``n_group`` =
``topk_group`` = 1), no routed scale (null = 1), no shared expert. Final
RMSNorm, an untied head.

What the config's keys name and do not spell (the file's ``assumed``), one
line each below, marked ASSUMED: the rotation's layout, the window's edge,
where the value scale is applied, the sink as a logit in the denominator
only.

Departures, shared with the system under test: weights are random from a
seed; ``w_qkv`` holds the published q, k and v projections side by side,
columns in that order; the residual stream is float32.

``operand`` rounds the operands of every matrix product: None is the
reference; "fp8" (operands scaled per tensor and rounded through
float8_e4m3fn before a bfloat16 product) is the CONTROL, the precision
below the configuration's bfloat16. One more control alters the MECHANISM
and says by how much the comparison tells a program that forgot it:
``hp["sink"] = (False, False)``, the sink left out of every layer.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.lfm2 import (HIGHEST, _ein, _key, _mm, _rms,
                                      n_layers, swiglu)

#: positions whose feed-forward (and k, v) are live at once
P_BLOCK = 2048
#: float32 scores live at once, in elements (1 GB): a block of queries is
#: as long as that allows
SCORE_ELEMS = 1 << 28


def hparams(config):
    """The numbers the equations above name, from a configuration file."""
    n = config["num_hidden_layers"]
    return {
        "n_heads": config["num_attention_heads"],
        "kv_heads": (config["num_key_value_heads"],
                     config["swa_num_key_value_heads"]),
        "d_k": config["head_dim"],
        "d_v": config["v_head_dim"],
        "rotary": int(config["head_dim"] * config["partial_rotary_factor"]),
        "theta": (float(config["rope_theta"]),
                  float(config["swa_rope_theta"])),
        "window": config["sliding_window"],
        "value_scale": float(config["attention_value_scale"]),
        "eps": config["layernorm_epsilon"],
        "kinds": tuple(config["hybrid_layer_pattern"][:n]),
        "sink": (bool(config["add_full_attention_sink_bias"]),
                 bool(config["add_swa_attention_sink_bias"])),
        "n_experts": config["router_width"],
        "experts_held": tuple(config["experts_held"]),
        "top_k": config["num_experts_per_tok"],
    }


def _rope_part(x, pos, theta, R):
    """x [T, H, D] at positions ``pos`` [T]: the leading ``R`` values of
    every head rotated, half-split pairs (j, j + R/2) — ASSUMED: the
    family's published modelling code rotates the leading
    ``int(head_dim * partial_rotary_factor)`` values with ``rotate_half``
    — and the rest passed through."""
    inv = np.array([theta ** (-2.0 * j / R) for j in range(R // 2)],
                   np.float32)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv)[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x = x.astype(jnp.float32)
    r = x[..., :R]
    rot = jnp.concatenate([-r[..., R // 2:], r[..., :R // 2]], axis=-1)
    return jnp.concatenate([r * cos + rot * sin, x[..., R:]], axis=-1)


def _blocked(fn, T, block):
    """``fn(lo) -> [block, ...]`` over lo = 0, block, ... < T, joined."""
    out = jax.lax.map(fn, jnp.arange(0, T, block, dtype=jnp.int32))
    return out.reshape((T,) + out.shape[2:])


def _block_of(T, most):
    """The longest block <= ``most`` that divides T."""
    return next(b for b in range(min(most, T), 0, -1) if T % b == 0)


def attention(p, h, hp, kind, operand=None):
    """h [T, d] (NOT yet normed: a block norms its own rows) -> the
    operator's output [T, d], a block of query rows at a time."""
    T, d = h.shape
    H, K = hp["n_heads"], hp["kv_heads"][kind]
    Dk, Dv, R, W = hp["d_k"], hp["d_v"], hp["rotary"], hp["window"]
    G, theta = H // K, hp["theta"][kind]
    w_in = p["input_norm"]["gamma"]
    w = p["attn"]["w_qkv"]
    wq, wk, wv = (w[:, :H * Dk], w[:, H * Dk:(H + K) * Dk],
                  w[:, (H + K) * Dk:])

    def keys(lo):
        x = _rms(jax.lax.dynamic_slice(h, (lo, 0), (pb, d)), w_in, hp["eps"])
        pos = lo + jnp.arange(pb)
        k = _rope_part(_mm(x, wk, operand).reshape(pb, K, Dk), pos, theta, R)
        # ASSUMED: the scalar multiplies v before the product (on v or on o
        # it is the same o, sink or no sink)
        v = hp["value_scale"] * _mm(x, wv, operand).reshape(pb, K, Dv)
        return jnp.concatenate([k.reshape(pb, -1), v.reshape(pb, -1)], -1)
    pb = _block_of(T, P_BLOCK)
    kv = _blocked(keys, T, pb)
    k, v = kv[:, :K * Dk].reshape(T, K, Dk), kv[:, K * Dk:].reshape(T, K, Dv)
    sliding = kind == 1 and W is not None
    qb = _block_of(T, 1024 if sliding
                   else max(8, min(1024, SCORE_ELEMS // (H * T))))
    span = T
    if sliding:
        # a block's queries see the keys from ``window - 1`` before its
        # first row to its last: a slice of the row padded on the left
        span = qb + W
        k = jnp.pad(k, ((W, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((W, 0), (0, 0), (0, 0)))
    sink = p["attn"]["sink"].astype(jnp.float32).reshape(K, G, 1, 1) \
        if hp["sink"][kind] else None

    def rows(lo):
        x = _rms(jax.lax.dynamic_slice(h, (lo, 0), (qb, d)), w_in, hp["eps"])
        at = lo + jnp.arange(qb)
        q = _rope_part(_mm(x, wq, operand).reshape(qb, H, Dk), at, theta, R)
        if sliding:
            kb = jax.lax.dynamic_slice(k, (lo, 0, 0), (span, K, Dk))
            vb = jax.lax.dynamic_slice(v, (lo, 0, 0), (span, K, Dv))
            j = lo - W + jnp.arange(span)
        else:
            kb, vb, j = k, v, jnp.arange(T)
        seen = (j[None, :] <= at[:, None]) & (j[None, :] >= 0)
        if sliding:
            # ASSUMED: a query sees ``window`` keys, its own among them
            seen = seen & (at[:, None] - j[None, :] < W)
        s = _ein("qkgd,skd->kgqs", q.reshape(qb, K, G, Dk), kb,
                 operand) * Dk ** -0.5
        s = jnp.where(seen, s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        if sink is not None:
            m = jnp.maximum(m, sink)
        e = jnp.exp(s - m)
        z = jnp.sum(e, axis=-1, keepdims=True)
        if sink is not None:
            # ASSUMED: the sink is a logit in the denominator and no value
            z = z + jnp.exp(sink - m)
        o = _ein("kgqs,skd->qkgd", e / z, vb, operand)
        return _mm(o.reshape(qb, H * Dv), p["attn"]["w_o"], operand)
    return _blocked(rows, T, qb)


def route(p, y, hp):
    """y [N, d] -> (chosen [N, k] expert ids, weights [N, k]); always
    float32 at full precision, whatever the control."""
    c = 1.0 / (1.0 + jnp.exp(-jnp.matmul(
        y.astype(jnp.float32), p["w_router"].astype(jnp.float32),
        precision=HIGHEST)))
    pick = c + p["e_bias"].astype(jnp.float32)
    chosen = jnp.argsort(-pick, axis=-1)[:, :hp["top_k"]]
    w = jnp.take_along_axis(c, chosen, axis=1)
    return chosen, w / (w.sum(-1, keepdims=True) + 1e-20)


def expert_layer(p, y, hp, operand=None):
    """y [N, d] (already normed) -> the layer's output [N, d]: the chosen
    experts that ``hp["experts_held"]`` names, one at a time over all the
    tokens (``p["w_gate"][i]`` is the i-th HELD expert's matrix)."""
    chosen, w = route(p, y, hp)

    def add_one(out, held):
        e, one = held
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)  # [N]
        return out + w_e[:, None] * swiglu(one, y, operand), None
    out, _ = jax.lax.scan(
        add_one, jnp.zeros(y.shape, jnp.float32),
        (jnp.asarray(hp["experts_held"], jnp.int32),
         {k: p[k] for k in ("w_gate", "w_up", "w_down")}))
    return out


def feed_forward(p, h, hp, operand=None):
    """h [T, d] -> the second half's output [T, d], ``P_BLOCK`` positions
    at a time (the dense layer's 16,384-wide products of a whole 49k-token
    row would be 6 GB)."""
    T, d = h.shape
    pb = _block_of(T, P_BLOCK)

    def rows(lo):
        y = _rms(jax.lax.dynamic_slice(h, (lo, 0), (pb, d)),
                 p["ffn_norm"]["gamma"], hp["eps"])
        return expert_layer(p["moe"], y, hp, operand) if "moe" in p \
            else swiglu(p["ffn"], y, operand)
    return _blocked(rows, T, pb)


def block(p, h, hp, kind, operand=None):
    """One layer over one sequence: h [T, d] -> [T, d]."""
    h = h + attention(p, h, hp, kind, operand)
    return h + feed_forward(p, h, hp, operand)


# One compiled program per kind of layer (global or sliding x dense or
# experts), the same for every layer of its kind.

@partial(jax.jit, static_argnums=(2, 3, 4))
def _block(p, h, hp_key, kind, operand):
    return block(p, h, dict(hp_key), kind, operand)


@partial(jax.jit, static_argnums=(3, 4))
def _head(norm_f, head_w, h, eps, operand):
    return _mm(_rms(h, norm_f["gamma"], eps), head_w.T, operand)


def hidden(params, ids, hp, operand=None):
    """ids [T] -> the residual stream after the last layer [T, d]."""
    h = params["embed"]["w"][ids].astype(jnp.float32)
    for i in range(n_layers(params)):
        h = _block(params[f"blocks_{i}"], h, _key(hp), hp["kinds"][i],
                   operand)
    return h


def forward(params, ids, hp, operand=None, rows=None):
    """ids [T] -> logits [T, V] float32 (``rows`` = (lo, hi): of those
    positions alone, [hi - lo, V] — a 49k-token row's logits over 19k
    tokens would be 3.7 GB)."""
    h = hidden(params, ids, hp, operand)
    if rows is not None:
        h = h[rows[0]:rows[1]]
    return _head(params["norm_f"], params["head"]["w"], h, hp["eps"],
                 operand)
