"""The plain reference of the ``nemotron_h`` family (NVIDIA-Nemotron-3-Nano's
stack): the forward pass of a whole sequence in straightforward
``jax.numpy``, float32, under matmul precision ``highest``. No kernels, no
cache, no batching, no grouped products, and NOT the chunked form of the
state-space recurrence: the recurrence is a plain ``lax.scan`` over
positions, one state update a position, so it shares none of the program's
algebra. It imports nothing of paddle_tpu and reads only the parameter tree
it is handed and the hyper-parameters of the configuration file
(:func:`hparams`). The (bfloat16-valued) weights stay as they are on the
device and are upcast one matrix at a time, inside the product that uses
them; the depth runs one layer's program at a time, so what is live beside
the 5.26 B parameters is one layer's activations.

Written from the published ``nemotron_h`` modelling code and the catalog
row's ``config``. Layer ``i`` is ONE mixer, ``h += mixer_i(RMSNorm_i(h))``
(eps ``layer_norm_epsilon``), and ``hybrid_override_pattern`` says which; a
final RMSNorm, then the (untied) head.

* ``M``, Mamba-2 (H = ``mamba_num_heads`` heads of P = ``mamba_head_dim``, G
  = ``n_groups`` groups, N = ``ssm_state_size``): ``[z | xBC | dt] = u
  W_in`` (H P | H P + 2 G N | H); ``xBC = silu(conv1d(xBC))`` (depthwise,
  causal, ``conv_kernel`` taps, zeros before the sequence, WITH bias);
  ``[x | B | C] = xBC``; ``dt = softplus(dt + dt_bias)``; ``A =
  -exp(A_log)``. Head h of group ``g = h // (H / G)`` keeps ``S`` [P, N],
  zero before the sequence: ``S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x)
  B_t^g``; ``y_t = S_t C_t^g + D_h x_t``. Then ``y = RMSNorm_groups(y *
  silu(z))`` — the gate BEFORE the norm, G groups of H P / G, eps
  ``layer_norm_epsilon``, one gain of H P — and ``y W_out``.
* ``E``, routed experts: ``s = sigmoid(u W_r)`` over all ``router_width``
  experts; selection = the top ``num_experts_per_tok`` of ``s +
  e_score_correction_bias`` (``n_group`` 1: no groups); weights = ``s`` at
  the chosen over (their sum + 1e-20) (``norm_topk_prob``), times
  ``routed_scaling_factor``. An expert is NOT gated: ``relu(u W_up^T)^2
  W_down`` (``mlp_hidden_act`` relu2). Output = the sum over the chosen
  experts HELD HERE of ``w_e * expert_e(u)``, plus ONE shared expert of the
  same form (``moe_shared_expert_intermediate_size`` wide) for every token.
* ``*``, attention: ``q, k, v = u W_q, u W_k, u W_v`` -> 32 / 2 / 2 heads of
  ``head_dim`` 128 (32 x 128 = 4096, not ``hidden_size``); query head h
  reads KV head ``h // 16``; ``score = q . k / sqrt(128)``, causal softmax,
  ``o = P v``, ``y = o W_O``. No bias, NO rotary or other positional term
  (the published attention applies none), no norm on q or k.

Departures, shared with the system under test: weights are random from a
seed; ``w_qkv`` holds the published q, k and v projections side by side,
columns in that order; an expert's ``w_up[i]`` is the i-th HELD expert's
``up_proj.weight`` as published ([intermediate, hidden]) and ``w_down[i]``
its ``down_proj.weight`` transposed ([intermediate, hidden]); the shared
expert's are [hidden, width] and [width, hidden]; ``head.w`` is [vocab,
hidden]. The residual stream is float32 (``residual_in_fp32`` false as
published). The published cache keeps the last ``conv_kernel`` = 4 inputs
of the convolution, the system 3; that is the system's state: here nothing
is cached.

``operand`` rounds the operands of every MATRIX product (the projections,
the experts, attention's two products, the head): None is the reference;
"fp8" (operands scaled per tensor and rounded through float8_e4m3fn before
a bfloat16 product) is the CONTROL, the precision below the
configuration's bfloat16. The recurrence itself, the convolution's taps and
the router stay float32 under the control, as the program keeps them.
"""

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def hparams(config):
    """The numbers the equations above name, from a configuration file."""
    return {
        "n_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "d_head": config["head_dim"],
        "eps": config["layer_norm_epsilon"],
        "mamba_heads": config["mamba_num_heads"],
        "mamba_head_dim": config["mamba_head_dim"],
        "ssm_groups": config["n_groups"],
        "ssm_state": config["ssm_state_size"],
        "taps": config["conv_kernel"],
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


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


# -- the mixers --------------------------------------------------------------

def mamba(p, u, hp, operand=None):
    """u [T, d] (already normed) -> the mixer's output [T, d]."""
    T = u.shape[0]
    H, P = hp["mamba_heads"], hp["mamba_head_dim"]
    G, N, taps = hp["ssm_groups"], hp["ssm_state"], hp["taps"]
    inner, gn = H * P, G * N
    zxd = _mm(u, p["w_in"], operand)
    z, xbc = zxd[:, :inner], zxd[:, inner:2 * inner + 2 * gn]
    dt = _softplus(zxd[:, 2 * inner + 2 * gn:]
                   + p["dt_bias"].astype(jnp.float32))             # [T, H]
    zz = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), jnp.float32),
                          xbc])
    w = p["w_conv"].astype(jnp.float32)                            # [c, taps]
    xbc = _silu(p["b_conv"].astype(jnp.float32)
                + sum(w[:, j] * zz[j:j + T] for j in range(taps)))
    x = xbc[:, :inner].reshape(T, H, P)
    b = xbc[:, inner:inner + gn].reshape(T, G, N)
    c = xbc[:, inner + gn:].reshape(T, G, N)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))                   # [H]

    def position(s, at):
        x_t, dt_t, b_t, c_t = at
        bh = jnp.repeat(b_t, H // G, axis=0)                       # [H, N]
        ch = jnp.repeat(c_t, H // G, axis=0)
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * bh[:, None, :]
        return s, jnp.sum(s * ch[:, None, :], axis=-1)             # [H, P]
    _, y = jax.lax.scan(position, jnp.zeros((H, P, N), jnp.float32),
                        (x, dt, b, c))
    y = y + p["d"].astype(jnp.float32)[:, None] * x
    y = y.reshape(T, inner) * _silu(z)
    g = y.reshape(T, G, inner // G)
    g = g / jnp.sqrt(jnp.mean(g * g, -1, keepdims=True) + hp["eps"])
    y = g.reshape(T, inner) * p["norm_gamma"].astype(jnp.float32)
    return _mm(y, p["w_out"], operand)


def attention(p, u, hp, operand=None):
    """u [T, d] (already normed) -> the mixer's output [T, d]. At most 8
    query heads of one KV head at a time: their [T, T] scores, not 32
    heads', are live at once."""
    T = u.shape[0]
    H, K, D = hp["n_heads"], hp["kv_heads"], hp["d_head"]
    G = H // K
    qkv = _mm(u, p["w_qkv"], operand)
    q = qkv[:, :H * D].reshape(T, H, D)
    k = qkv[:, H * D:(H + K) * D].reshape(T, K, D)
    v = qkv[:, (H + K) * D:].reshape(T, K, D)
    causal = jnp.tril(jnp.ones((T, T), bool))
    step = min(G, 8)
    outs = []
    for h0 in range(0, H, step):
        j = h0 // G
        s = _ein("tgd,sd->gts", q[:, h0:h0 + step], k[:, j],
                 operand) * D ** -0.5
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        outs.append(_ein("gts,sd->gtd", w, v[:, j], operand))
    o = jnp.moveaxis(jnp.concatenate(outs, axis=0), 0, 1)          # [T, H, D]
    return _mm(o.reshape(T, H * D), p["w_o"], operand)


def relu2_expert(w_up_t, w_down, y, operand=None):
    """``relu(y W_up^T)^2 W_down`` with ``w_up_t`` [f, d] (as published)
    and ``w_down`` [f, d]."""
    u = _mm(y, w_up_t.T, operand)
    return _mm(jnp.square(jnp.maximum(u, 0.0)), w_down, operand)


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
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return chosen, w * hp["routed_scale"]


def expert_layer(p, y, hp, operand=None, shared=True):
    """y [N, d] (already normed) -> the layer's output [N, d]: the chosen
    experts that ``hp["experts_held"]`` names, one at a time over all the
    tokens, plus the shared expert (``shared=False`` leaves it out: a
    share that is summed with another's)."""
    chosen, w = route(p, y, hp)

    def add_one(out, held):
        e, w_up_t, w_down = held
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)     # [N]
        return out + w_e[:, None] * relu2_expert(w_up_t, w_down, y,
                                                 operand), None
    out, _ = jax.lax.scan(
        add_one, jnp.zeros(y.shape, jnp.float32),
        (jnp.asarray(hp["experts_held"], jnp.int32), p["w_up"],
         p["w_down"]))
    if shared and "shared" in p:
        sh = p["shared"]
        out = out + relu2_expert(sh["w_up"].T, sh["w_down"], y, operand)
    return out


def block(p, h, hp, operand=None):
    """One layer over one sequence: h [T, d] -> [T, d]. The mixer is told
    by what the layer's parameters hold."""
    u = _rms(h, p["norm"]["gamma"], hp["eps"])
    if "mixer" in p:
        return h + mamba(p["mixer"], u, hp, operand)
    if "attn" in p:
        return h + attention(p["attn"], u, hp, operand)
    return h + expert_layer(p["moe"], u, hp, operand)


def n_layers(params):
    return sum(1 for k in params if k.startswith("blocks_"))


# One compiled program per kind of layer, the same for every layer of its
# kind, holding one layer's activations.

@partial(jax.jit, static_argnums=(2, 3))
def _block(p, h, hp_key, operand):
    return block(p, h, dict(hp_key), operand)


@partial(jax.jit, static_argnums=(3, 4))
def _head(norm_f, head_w, h, eps, operand):
    return _mm(_rms(h, norm_f["gamma"], eps), head_w.T, operand)


def forward(params, ids, hp, operand=None):
    """ids [T] -> logits [T, V] float32 (the head untied: ``head.w``)."""
    h = params["embed"]["w"][ids].astype(jnp.float32)
    for i in range(n_layers(params)):
        h = _block(params[f"blocks_{i}"], h, _key(hp), operand)
    return _head(params["norm_f"], params["head"]["w"], h, hp["eps"],
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
