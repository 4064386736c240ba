"""The plain reference: GPT-2's forward pass, LM loss, gradients and Adam in
straightforward ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``. No kernels, no cache, no
batching tricks; it imports nothing of paddle_tpu and reads only the
parameter tree it is handed (``embed/w``, ``pos_embed``, ``blocks_<i>/{ln1,
qkv,proj,ln2,mlp_in,mlp_out}``, ``ln_f``; head tied to the embedding).

Written from Radford et al. 2019 and the published ``modeling_gpt2``
description: learned positions, pre-LayerNorm (eps 1e-5), fused qkv split
q|k|v, heads of d_model/n_head, causal softmax(q k^T / sqrt(d_head)),
``gelu_new`` (the tanh form), residuals, final LayerNorm, tied head.
Departures from the published model, shared with the system under test: no
dropout, no attention/residual-projection init scaling (weights are random
from a seed anyway).

``operand`` rounds the operands of every matrix product: None is the
reference, and the lower precisions are the CONTROLS of PERF.md
("bf16": all weights and activations in bfloat16; "fp8": operands scaled
per tensor and rounded through float8_e4m3fn before a bfloat16 product).
"""

import math
from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _round(x, operand):
    if operand == "fp8":
        # per-tensor scaling to e4m3's range (448), as fp8 training does;
        # without it the backward pass's small values all round to zero
        scale = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))),
                            1e-30) / 448.0
        q = (x.astype(jnp.float32) / scale).astype(jnp.float8_e4m3fn)
        return (q.astype(jnp.float32) * scale).astype(jnp.bfloat16)
    return x


def _mm(a, b, operand):
    if operand is None:
        return jnp.matmul(a, b, precision=HIGHEST)
    return jnp.matmul(_round(a, operand), _round(b, operand))


def _layer_norm(x, p, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    y = (x32 - mu) / jnp.sqrt(var + eps)
    return (y * p["gamma"] + p["beta"]).astype(x.dtype)


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(p, x, n_head, operand=None):
    B, T, D = x.shape
    dh = D // n_head
    h = _layer_norm(x, p["ln1"])
    qkv = _mm(h, p["qkv"]["w"], operand) + p["qkv"]["b"].astype(x.dtype)
    q, k, v = (a.reshape(B, T, n_head, dh) for a in jnp.split(qkv, 3, -1))
    if operand is None:
        s = jnp.einsum("bthd,bshd->bhts", q, k, precision=HIGHEST)
    else:
        s = jnp.einsum("bthd,bshd->bhts", _round(q, operand),
                       _round(k, operand))
    s = s.astype(jnp.float32) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    if operand is None:
        o = jnp.einsum("bhts,bshd->bthd", w, v, precision=HIGHEST)
    else:
        o = jnp.einsum("bhts,bshd->bthd", _round(w.astype(x.dtype), operand),
                       _round(v, operand))
    o = o.reshape(B, T, D).astype(x.dtype)
    x = x + _mm(o, p["proj"]["w"], operand) + p["proj"]["b"].astype(x.dtype)
    h = _layer_norm(x, p["ln2"])
    h = _gelu_new(_mm(h, p["mlp_in"]["w"], operand)
                  + p["mlp_in"]["b"].astype(x.dtype))
    return (x + _mm(h, p["mlp_out"]["w"], operand)
            + p["mlp_out"]["b"].astype(x.dtype))


def n_layers(params):
    return sum(1 for k in params if k.startswith("blocks_"))


# The model is run LAYER BY LAYER: one small compiled program per kind of
# piece (embedding, block, head), the same for every layer, so the reference
# compiles in seconds whatever the depth and holds one layer's activations.

def _low(tree, operand):
    """The control computes in bfloat16; the reference stays float32."""
    if operand is None:
        return tree
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), tree)


@jax.jit
def _embed(emb, pos, ids):
    return emb[ids] + pos[:ids.shape[1]]


@partial(jax.jit, static_argnums=(2, 3))
def _block_fwd(p, x, n_head, operand):
    return block(_low(p, operand), x, n_head, operand)


@partial(jax.jit, static_argnums=(3, 4))
def _block_bwd(p, x, g, n_head, operand):
    _, vjp = jax.vjp(lambda p, x: block(_low(p, operand), x, n_head,
                                        operand), p, x)
    return vjp(g)


def _logits(emb, ln_f, x, operand):
    emb, ln_f = _low(emb, operand), _low(ln_f, operand)
    return _mm(_layer_norm(x, ln_f), emb.T, operand).astype(jnp.float32)


@partial(jax.jit, static_argnums=(3,))
def _head(emb, ln_f, x, operand):
    return _logits(emb, ln_f, x, operand)


@partial(jax.jit, static_argnums=(4,))
def _head_loss_bwd(emb, ln_f, x, targets, operand):
    """Summed next-token cross-entropy of one row and its gradients."""
    def f(emb, ln_f, x):
        logp = jax.nn.log_softmax(_logits(emb, ln_f, x, operand), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[..., None],
                                            -1)[..., 0])
    return jax.value_and_grad(f, argnums=(0, 1, 2))(emb, ln_f, x)


@jax.jit
def _embed_bwd(emb, pos, ids, g):
    g = g.astype(jnp.float32)
    return (jnp.zeros_like(emb).at[ids].add(g),
            jnp.zeros_like(pos).at[:ids.shape[1]].add(jnp.sum(g, 0)))


def _hidden(params, ids, n_head, operand, keep=False):
    """The blocks' output for ids [B, T]; with ``keep`` also every block's
    input (what the backward pass needs)."""
    dt = jnp.float32 if operand is None else jnp.bfloat16
    x = _embed(params["embed"]["w"], params["pos_embed"], ids).astype(dt)
    inputs = []
    for i in range(n_layers(params)):
        if keep:
            inputs.append(x)
        x = _block_fwd(params[f"blocks_{i}"], x, n_head, operand)
    return (x, inputs) if keep else x


def forward(params, ids, n_head, operand=None):
    """ids [B, T] -> logits [B, T, V] (float32)."""
    x = _hidden(params, ids, n_head, operand)
    return _head(params["embed"]["w"], params["ln_f"], x, operand)


def lm_loss(params, ids, n_head, operand=None):
    """Mean next-token cross-entropy over ids [B, T] (T-1 predictions a
    row), as a training job computes it."""
    logp = jax.nn.log_softmax(forward(params, ids[:, :-1], n_head, operand),
                              axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None],
                                         -1)[..., 0])


@jax.jit
def _tree_add(a, b):
    return jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) + y.astype(jnp.float32), a, b)


def loss_and_grad(params, ids, n_head, operand=None):
    """Loss and gradient (float32, the tree of ``params``) of a [B, T]
    batch by hand-rolled backpropagation over the per-layer programs; the
    vocabulary head one row at a time, so its logits stay a row's."""
    ids = jnp.asarray(ids)
    inp, tgt = ids[:, :-1], ids[:, 1:]
    B, n = inp.shape[0], float(tgt.size)
    x, inputs = _hidden(params, inp, n_head, operand, keep=True)
    emb, ln_f = params["embed"]["w"], params["ln_f"]
    loss, d_emb, d_ln, rows = 0.0, None, None, []
    for r in range(B):
        l, (de, dl, dx) = _head_loss_bwd(emb, ln_f, x[r:r + 1], tgt[r:r + 1],
                                         operand)
        loss = loss + l / n
        d_emb = de if d_emb is None else _tree_add(d_emb, de)
        d_ln = dl if d_ln is None else _tree_add(d_ln, dl)
        rows.append(dx)
    g = jnp.concatenate(rows, 0) / n
    grads = {"ln_f": jax.tree_util.tree_map(lambda a: a / n, d_ln)}
    for i in reversed(range(n_layers(params))):
        dp, g = _block_bwd(params[f"blocks_{i}"], inputs.pop(), g, n_head,
                           operand)
        grads[f"blocks_{i}"] = dp
    de, dpos = _embed_bwd(emb, params["pos_embed"], inp, g)
    grads["embed"] = {"w": _tree_add(de, d_emb / n)}
    grads["pos_embed"] = dpos
    return loss, jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                        grads)


@partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam(p, m, v, g, step, lr, b1=0.9, b2=0.999, eps=1e-8):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mhat = m / (1 - b1 ** step)
    vhat = v / (1 - b2 ** step)
    return p - lr * mhat / (jnp.sqrt(vhat) + eps), m, v


def adam_step(params, m, v, grads, step, lr):
    """Kingma & Ba 2015, bias-corrected, no weight decay; leaf by leaf, the
    old leaf's memory given to the new."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    out = [_adam(p_, m_, v_, g_, step, lr) for p_, m_, v_, g_ in zip(
        leaves, jax.tree_util.tree_leaves(m), jax.tree_util.tree_leaves(v),
        jax.tree_util.tree_leaves(grads))]
    return tuple(jax.tree_util.tree_unflatten(treedef, [o[i] for o in out])
                 for i in range(3))


@jax.jit
def _norm(a):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))


def leaf_norms(tree):
    return [_norm(a) for a in jax.tree_util.tree_leaves(tree)]


@jax.jit
def _diff_norm(a, b):
    return _norm(a.astype(jnp.float32) - b.astype(jnp.float32))


def leaf_diff_norms(a, b):
    return [_diff_norm(x, y) for x, y in zip(jax.tree_util.tree_leaves(a),
                                             jax.tree_util.tree_leaves(b))]


def train_reference(params, batches, n_head, lr, operand=None):
    """Follow the first ``len(batches)`` Adam steps from ``params`` (which
    are left as they were). Returns plain lists: each step's loss, the
    per-leaf norms of the FIRST gradient, and the per-leaf norms of the
    parameters' change after the last step."""
    p = jax.tree_util.tree_map(jnp.copy, params)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, gnorm = [], None
    for t, ids in enumerate(batches, start=1):
        loss, g = loss_and_grad(p, ids, n_head, operand)
        losses.append(float(loss))
        if gnorm is None:
            gnorm = [float(x) for x in leaf_norms(g)]
        p, m, v = adam_step(p, m, v, g, float(t), lr)
        del g
    dnorm = [float(x) for x in leaf_diff_norms(p, params)]
    return losses, gnorm, dnorm


@jax.jit
def _gaps(ref_logits, low_logits, nxt):
    best = jnp.max(ref_logits, -1)
    served = jnp.take_along_axis(ref_logits, nxt[..., None], -1)[..., 0]
    pick = jnp.take_along_axis(
        ref_logits, jnp.argmax(low_logits, -1)[..., None], -1)[..., 0]
    return best, served, pick


def token_gaps(params, ids, n_head, operand=None):
    """For ids [B, T]: at every position t the reference logits of position
    t predict token t+1. Returns (best, at_served, control_pick):
    ``best[b, t]`` the largest reference logit, ``at_served[b, t]`` the
    reference logit of ids[b, t+1], and, when ``operand`` names a lower
    precision, ``control_pick[b, t]`` the reference logit of the token that
    precision puts first (else None). All float32 [B, T-1]."""
    ref = forward(params, ids, n_head)[:, :-1]
    low = ref if operand is None else forward(params, ids, n_head,
                                              operand)[:, :-1]
    best, served, pick = _gaps(ref, low, ids[:, 1:])
    return best, served, (None if operand is None else pick)
