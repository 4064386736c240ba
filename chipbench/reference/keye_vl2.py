"""The plain reference of the ``keye_vl2`` family (Keye-VL-2.0's language
model): the forward pass of a whole sequence in straightforward
``jax.numpy``, float32, under matmul precision ``highest``. No kernels, no
cache, no pages, no batching, no grouped products; it imports nothing of
paddle_tpu and reads only the parameter tree it is handed and the
hyper-parameters of the configuration file (:func:`hparams`). The
(bfloat16-valued) weights stay as they are on the device and are upcast one
matrix at a time, inside the product that uses them; the depth runs one
layer's program at a time.

Written from the catalog row's ``config`` and, for the indexer, from the
published equations of DeepSeek Sparse Attention's lightning indexer
(DeepSeek-V3.2-Exp report), which the row's ``described_as`` names.
Residual stream ``h [T, d]`` in float32; per layer, with ``x = RMSNorm(h)``
(eps ``rms_norm_eps``):

1. ``q = x W_q`` (32 x 128), ``k = x W_k``, ``v = x W_v`` (4 x 128 each), no
   biases; q and k RMS-normed per head; half-split RoPE over the whole head
   of 128, ``rope_theta`` 1e7. ``rope_scaling.mrope_section`` [16, 24, 24]
   gives frequency pairs 0-15 the temporal position id, 16-39 the height id
   and 40-63 the width id (:func:`mrope`); a text token's three ids are
   equal, so for text it IS 1-D RoPE, which is what :func:`forward` runs.
2. The indexer, from the same ``x``: ``qI = x W_qI`` (16 heads x 64), ``kI =
   x W_kI`` (ONE head of 64), ``w = x W_w`` (16). ``kI`` LayerNormed (unit
   gain, no bias), ``qI`` and ``kI`` rotated by the layer's RoPE over their
   own 64 dims. ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])``.
3. ``S_t`` = the ``topk`` keys ``s <= t`` of largest ``I[t, s]`` (every key
   while ``t + 1 <= topk``), ties to the lower index (``jax.lax.top_k``'s
   order). One set a query token, shared by all 32 heads.
4. ``o = softmax over s in S_t of (q_t . k_s / sqrt(128)) v_s``, a KV head
   serving its 8 query heads; ``h += o W_o``.
5. ``y = RMSNorm(h)``; ``p = softmax(y W_r)`` over all 128 logits; the 8
   largest; weights ``p`` at the chosen over their sum (``norm_topk_prob``);
   an expert is ``down(silu(gate(y)) * up(y))``; no shared expert; ``h +=``
   the weighted sum over the chosen experts THAT ARE HELD HERE.
6. Final RMSNorm, an untied head.

``q_chunk_size`` / ``kv_chunk_size`` (512) are taken as the tiles in which
the published code computes ``I``; they change no number and are ignored.
The scores and the selection run a block of ``Q_BLOCK`` queries at a time,
so that 33,792 positions fit.

Departures, shared with the system under test: weights are random from a
seed; ``w_qkv`` holds the published q, k, v projections side by side and
``w_idx`` the indexer's three (qI, kI, w), columns in that order; the
residual stream is float32; the vision tower is left out (text only).

``operand`` rounds the operands of every matrix product: None is the
reference; "fp8" (operands scaled per tensor and rounded through
float8_e4m3fn before a bfloat16 product) is the CONTROL, the precision
below the configuration's bfloat16. ``hp["select"]`` names two more
controls: "all" takes step 3 away (every key ``s <= t`` attended: a
FORGOTTEN selection), "recent" keeps the ``topk`` MOST RECENT keys (a
window passed off as a selection); "topk" is the model.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.lfm2 import (HIGHEST, _ein, _gaps, _key, _mm, _rms,
                                      _rope, n_layers, swiglu)

#: query rows whose scores are live at once
Q_BLOCK = 1024


def hparams(config):
    """The numbers the equations above name, from a configuration file."""
    sa = config["sa_config"]
    return {
        "n_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "d_head": config["head_dim"],
        "eps": config["rms_norm_eps"],
        "theta": float(config["rope_theta"]),
        "index_heads": sa["indexer_num_heads"],
        "index_dim": sa["indexer_head_dim"],
        "topk": sa["topk"],
        "select": "topk",
        "n_experts": config["router_width"],
        "experts_held": tuple(config["experts_held"]),
        "top_k": config["num_experts_per_tok"],
        "norm_topk_prob": bool(config["norm_topk_prob"]),
    }


def mrope(x, ids3, theta, sections):
    """The published rotary term: x [T, H, D], ids3 [3, T] (temporal,
    height, width position ids), ``sections`` the frequency pairs each id
    takes (sum = D / 2), half-split pairs. With three equal ids it is
    :func:`_rope` at those positions."""
    T, _, D = x.shape
    inv = np.array([theta ** (-2.0 * i / D) for i in range(D // 2)],
                   np.float32)
    which = np.repeat(np.arange(len(sections)), sections)   # [D / 2]
    pos = jnp.asarray(ids3, jnp.float32)[which, :].T        # [T, D / 2]
    ang = pos * jnp.asarray(inv)[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x = x.astype(jnp.float32)
    rot = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * cos + rot * sin


def index_scores(p, u, hp, operand=None):
    """u [T, d] (already normed) -> (qI [T, Hi, Di], kI [T, Di], w [T, Hi])
    as the scores take them."""
    T = u.shape[0]
    Hi, Di = hp["index_heads"], hp["index_dim"]
    y = _mm(u, p["w_idx"], operand)
    qi = y[:, :Hi * Di].reshape(T, Hi, Di)
    ki = y[:, Hi * Di:Hi * Di + Di]
    mu = jnp.mean(ki, -1, keepdims=True)
    ki = (ki - mu) / jnp.sqrt(jnp.mean((ki - mu) ** 2, -1, keepdims=True)
                              + hp["eps"]) \
        * p["k_norm"]["gamma"].astype(jnp.float32)
    qi = _rope(qi, hp["theta"])
    ki = _rope(ki[:, None, :], hp["theta"])[:, 0]
    return qi, ki, y[:, Hi * Di + Di:]


def selection(scores, rows, hp):
    """scores [R, T] of the queries at positions ``rows`` [R, 1] -> the keys
    each attends, bool [R, T]."""
    T = scores.shape[1]
    j = jnp.arange(T)[None, :]
    seen = j <= rows
    if hp["select"] == "all":
        return seen
    if hp["select"] == "recent":
        return seen & (rows - j < hp["topk"])
    masked = jnp.where(seen, scores, -jnp.inf)
    vals, idx = jax.lax.top_k(masked, min(hp["topk"], T))
    hit = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], idx].set(vals > -jnp.inf)
    return hit & seen


def attention(p, pi, u, hp, operand=None):
    """u [T, d] (already normed) -> (the operator's output [T, d], the
    selected (query, key) pairs). A block of ``Q_BLOCK`` query rows and one
    KV head's group at a time."""
    T = u.shape[0]
    H, K, D = hp["n_heads"], hp["kv_heads"], hp["d_head"]
    G = H // K
    y = _mm(u, p["w_qkv"], operand)
    q = _rms(y[:, :H * D].reshape(T, H, D), p["q_norm"]["gamma"], hp["eps"])
    k = _rms(y[:, H * D:(H + K) * D].reshape(T, K, D), p["k_norm"]["gamma"],
             hp["eps"])
    v = y[:, (H + K) * D:].reshape(T, K, D)
    q, k = _rope(q, hp["theta"]), _rope(k, hp["theta"])
    qi, ki, w = index_scores(pi, u, hp, operand)
    B = min(Q_BLOCK, T)
    n_blocks = -(-T // B)

    def rows_of(x, lo):
        """Rows ``lo .. lo + B`` of x (rows past T are zeros)."""
        x = jnp.pad(x, ((0, n_blocks * B - T),) + ((0, 0),) * (x.ndim - 1))
        return jax.lax.dynamic_slice_in_dim(x, lo, B)

    def one_block(lo):
        rows = lo + jnp.arange(B)[:, None]
        qb, qib, wb = rows_of(q, lo), rows_of(qi, lo), rows_of(w, lo)
        scores = sum(                                       # [B, T]
            wb[:, j, None] * jnp.maximum(
                _ein("td,sd->ts", qib[:, j], ki, operand), 0.0)
            for j in range(hp["index_heads"]))
        keep = selection(scores, rows, hp)
        outs = []
        for g in range(K):
            s = _ein("tgd,sd->gts", qb[:, g * G:(g + 1) * G], k[:, g],
                     operand) * D ** -0.5
            a = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
            outs.append(_ein("gts,sd->gtd", a, v[:, g], operand))
        return (jnp.moveaxis(jnp.concatenate(outs, axis=0), 0, 1),
                jnp.sum(keep & (rows < T)))
    # one block's program, run a block after another (not unrolled: a
    # 33,792-token row is 33 blocks)
    o, pairs = jax.lax.map(one_block, jnp.arange(n_blocks) * B)
    o = o.reshape(n_blocks * B, H * D)[:T]
    return _mm(o, p["w_o"], operand), jnp.sum(pairs)


def route(p, y, hp):
    """y [N, d] -> (chosen [N, k] expert ids, weights [N, k]); always
    float32 at full precision, whatever the control."""
    s = jax.nn.softmax(jnp.matmul(
        y.astype(jnp.float32), p["w_router"].astype(jnp.float32),
        precision=HIGHEST), axis=-1)
    chosen = jnp.argsort(-s, axis=-1)[:, :hp["top_k"]]
    w = jnp.take_along_axis(s, chosen, axis=1)
    if hp["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    return chosen, w


def expert_layer(p, y, hp, operand=None):
    """y [N, d] (already normed) -> the layer's output [N, d]: the chosen
    experts that ``hp["experts_held"]`` names, one at a time over all the
    tokens (``p["w_gate"][i]`` is the i-th HELD expert's matrix). No shared
    expert."""
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


def block(p, h, hp, operand=None):
    """One layer over one sequence: h [T, d] -> ([T, d], pairs selected)."""
    eps = hp["eps"]
    a, pairs = attention(p["attn"], p["idx"],
                         _rms(h, p["input_norm"]["gamma"], eps), hp, operand)
    h = h + a
    return h + expert_layer(p["moe"], _rms(h, p["ffn_norm"]["gamma"], eps),
                            hp, operand), pairs


# One compiled program for every layer (they are all of one kind).

@partial(jax.jit, static_argnums=(2, 3))
def _block(p, h, hp_key, operand):
    return block(p, h, dict(hp_key), operand)


@partial(jax.jit, static_argnums=(3, 4))
def _head(norm_f, head_w, h, eps, operand):
    return _mm(_rms(h, norm_f["gamma"], eps), head_w.T, operand)


def forward(params, ids, hp, operand=None, pairs=False):
    """ids [T] -> logits [T, V] float32 (``pairs``: and the (query, key)
    pairs each layer selected, [layers])."""
    h = params["embed"]["w"][ids].astype(jnp.float32)
    sel = []
    for i in range(n_layers(params)):
        h, n = _block(params[f"blocks_{i}"], h, _key(hp), operand)
        sel.append(n)
    out = _head(params["norm_f"], params["head"]["w"], h, hp["eps"], operand)
    return (out, jnp.stack(sel)) if pairs else out


def token_gaps(params, ids, hp, operand=None):
    """reference/lfm2.py's ``token_gaps`` over this family's forward: for
    ids [T], (best, at_served, control_pick), float32 [T-1]. The selection
    controls are the same call under ``dict(hp, select="all" | "recent")``:
    the served tokens held to a reference that forgot the selection."""
    ref = forward(params, ids, hp)[:-1]
    low = ref if operand is None else forward(params, ids, hp, operand)[:-1]
    best, served, pick = _gaps(ref, low, ids[1:])
    return best, served, (None if operand is None else pick)
