"""The plain reference of the ``afmoe`` family (Arcee's Trinity-Mini): the
forward pass of a whole sequence in straightforward ``jax.numpy``, float32,
under matmul precision ``highest``. No kernels, no cache, no ring, no
batching, no grouped products; it imports nothing of paddle_tpu and reads
only the parameter tree it is handed and the hyper-parameters of the
configuration file (:func:`hparams`). The (bfloat16-valued) weights stay as
they are on the device and are upcast one matrix at a time, inside the
product that uses them; the depth runs one layer's program at a time.

Written from the published ``afmoe`` modelling code (``transformers``) and
the catalog row's ``config``. Residual stream ``h [T, d]``; with
``mup_enabled`` the embedding's output is multiplied by ``sqrt(d)``. Layer
``i``, every norm an RMSNorm (eps ``rms_norm_eps``) in float32, FOUR a
layer (sandwich norms):

    h += post_attn_norm(attention_i(input_norm(h)))
    h += post_mlp_norm(mlp_i(pre_mlp_norm(h)))

* attention: ``q, k, v = x W_q, x W_k, x W_v`` -> 32 / 4 / 4 heads of 128;
  ``g = x W_g`` (``gate_proj``, as wide as q); ``q`` and ``k`` RMS-normed
  per head. ``layer_types[i]`` decides two things. ``sliding_attention``:
  half-split (``rotate_half``) RoPE over the whole head, ``theta``
  ``rope_theta``, on q and k after the norms, and the query at position p
  sees the keys j with ``0 <= p - j < sliding_window``.
  ``full_attention``: NO positional term, and every key ``j <= p``. Query
  head h reads KV head ``h // 8``; ``score = q . k * 128^-0.5``, softmax,
  ``o = P v``, **``o = o * sigmoid(g)``**, ``y = o W_O``. No biases. The
  window is a MASK over the whole square, taken a block of query rows at a
  time (32 heads x 8,960^2 scores in float32 would be 10 GB at once).
* dense FFN (the first ``num_dense_layers`` layers): ``(silu(y W_g) * y
  W_u) W_d``.
* expert layer: ``s = sigmoid(y W_r)`` over all ``num_experts``
  (``score_func`` sigmoid, one group); selection = the top
  ``num_experts_per_tok`` of ``s + expert_bias``; weights = ``s`` at the
  chosen, divided by (their sum + 1e-20) (``route_norm``), times
  ``route_scale``. Output = sum over the chosen experts HELD HERE of ``w_e *
  SwiGLU_e(y)``, plus ONE shared expert of the same form for every token
  (``num_shared_experts`` 1).
* final RMSNorm, an untied head.

Departures, shared with the system under test: weights are random from a
seed; ``w_qkvg`` holds the published q, k, v and gate projections side by
side, columns in that order; the residual stream is float32.

``operand`` rounds the operands of every matrix product: None is the
reference; "fp8" (operands scaled per tensor and rounded through
float8_e4m3fn before a bfloat16 product) is the CONTROL, the precision
below the configuration's bfloat16. ``hp["window"] = None`` is the other
control: every layer reads its whole context (its RoPE kept) — by how much
the comparison tells a forgotten window.
"""

from functools import partial

import jax
import jax.numpy as jnp

from chipbench.reference.lfm2 import (HIGHEST, _ein, _gaps, _key, _mm, _rms,
                                      _rope, n_layers, swiglu)

#: query rows whose scores are live at once
Q_BLOCK = 1024


def hparams(config):
    """The numbers the equations above name, from a configuration file."""
    return {
        "n_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "d_head": config["head_dim"],
        "eps": config["rms_norm_eps"],
        "theta": float(config["rope_theta"]),
        "window": config["sliding_window"],
        "layer_types": tuple(config["layer_types"]
                             [:config["num_hidden_layers"]]),
        "embed_scale": float(config["hidden_size"]) ** 0.5
        if config["mup_enabled"] else 1.0,
        "n_experts": config["router_width"],
        "experts_held": tuple(config["experts_held"]),
        "top_k": config["num_experts_per_tok"],
        "route_scale": float(config["route_scale"]),
        "route_norm": bool(config["route_norm"]),
    }


def attention(p, u, hp, sliding, operand=None):
    """u [T, d] (already normed) -> the operator's output [T, d]. A block
    of query rows and one KV head's group at a time."""
    T = u.shape[0]
    H, K, D = hp["n_heads"], hp["kv_heads"], hp["d_head"]
    G = H // K
    y = _mm(u, p["w_qkvg"], operand)
    q = _rms(y[:, :H * D].reshape(T, H, D), p["q_norm"]["gamma"], hp["eps"])
    k = _rms(y[:, H * D:(H + K) * D].reshape(T, K, D), p["k_norm"]["gamma"],
             hp["eps"])
    v = y[:, (H + K) * D:(H + 2 * K) * D].reshape(T, K, D)
    gate = 1.0 / (1.0 + jnp.exp(-y[:, (H + 2 * K) * D:]))
    if sliding:
        q, k = _rope(q, hp["theta"]), _rope(k, hp["theta"])
    j = jnp.arange(T)[None, :]
    blocks = []
    for lo in range(0, T, Q_BLOCK):
        rows = jnp.arange(lo, min(lo + Q_BLOCK, T))[:, None]
        seen = j <= rows
        if sliding and hp["window"] is not None:
            seen = seen & (rows - j < hp["window"])
        outs = []
        for g in range(K):
            s = _ein("tgd,sd->gts", q[lo:lo + Q_BLOCK, g * G:(g + 1) * G],
                     k[:, g], operand) * D ** -0.5
            w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            outs.append(_ein("gts,sd->gtd", w, v[:, g], operand))
        blocks.append(jnp.moveaxis(jnp.concatenate(outs, axis=0), 0, 1))
    o = jnp.concatenate(blocks, axis=0).reshape(T, H * D)
    return _mm(o * gate, p["w_o"], operand)


def route(p, y, hp):
    """y [N, d] -> (chosen [N, k] expert ids, weights [N, k]); always
    float32 at full precision, whatever the control."""
    s = 1.0 / (1.0 + jnp.exp(-jnp.matmul(
        y.astype(jnp.float32), p["w_router"].astype(jnp.float32),
        precision=HIGHEST)))
    pick = s + p["e_bias"].astype(jnp.float32)
    chosen = jnp.argsort(-pick, axis=-1)[:, :hp["top_k"]]
    w = jnp.take_along_axis(s, chosen, axis=1)
    if hp["route_norm"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return chosen, w * hp["route_scale"]


def expert_layer(p, y, hp, operand=None, shared=True):
    """y [N, d] (already normed) -> the layer's output [N, d]: the chosen
    experts that ``hp["experts_held"]`` names, one at a time over all the
    tokens (``p["w_gate"][i]`` is the i-th HELD expert's matrix), plus the
    shared expert (``shared=False``: a share summed with another's)."""
    chosen, w = route(p, y, hp)

    def add_one(out, held):
        e, one = held
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)  # [N]
        return out + w_e[:, None] * swiglu(one, y, operand), None
    out, _ = jax.lax.scan(
        add_one, jnp.zeros(y.shape, jnp.float32),
        (jnp.asarray(hp["experts_held"], jnp.int32),
         {k: p[k] for k in ("w_gate", "w_up", "w_down")}))
    return out + swiglu(p["shared"], y, operand) if shared else out


def block(p, h, hp, sliding, operand=None):
    """One layer over one sequence: h [T, d] -> [T, d]."""
    eps = hp["eps"]
    a = attention(p["attn"], _rms(h, p["input_norm"]["gamma"], eps), hp,
                  sliding, operand)
    h = h + _rms(a, p["post_attn_norm"]["gamma"], eps)
    y = _rms(h, p["pre_mlp_norm"]["gamma"], eps)
    m = expert_layer(p["moe"], y, hp, operand) if "moe" in p \
        else swiglu(p["ffn"], y, operand)
    return h + _rms(m, p["post_mlp_norm"]["gamma"], eps)


# One compiled program per kind of layer (sliding or not x dense or
# experts), the same for every layer of its kind.

@partial(jax.jit, static_argnums=(2, 3, 4))
def _block(p, h, hp_key, sliding, operand):
    return block(p, h, dict(hp_key), sliding, operand)


@partial(jax.jit, static_argnums=(3, 4))
def _head(norm_f, head_w, h, eps, operand):
    return _mm(_rms(h, norm_f["gamma"], eps), head_w.T, operand)


def forward(params, ids, hp, operand=None):
    """ids [T] -> logits [T, V] float32."""
    h = params["embed"]["w"][ids].astype(jnp.float32) * hp["embed_scale"]
    for i in range(n_layers(params)):
        h = _block(params[f"blocks_{i}"], h, _key(hp),
                   hp["layer_types"][i] == "sliding_attention", operand)
    return _head(params["norm_f"], params["head"]["w"], h, hp["eps"],
                 operand)


def token_gaps(params, ids, hp, operand=None):
    """reference/lfm2.py's ``token_gaps`` over this family's forward: for
    ids [T], (best, at_served, control_pick), float32 [T-1]."""
    ref = forward(params, ids, hp)[:-1]
    low = ref if operand is None else forward(params, ids, hp, operand)[:-1]
    best, served, pick = _gaps(ref, low, ids[1:])
    return best, served, (None if operand is None else pick)
