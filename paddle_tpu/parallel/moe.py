"""Expert parallelism: token-choice top-k mixture-of-experts FFN sharded
over the ``expert`` mesh axis.

The 2017 reference's closest machinery is sparse/embedding sharding
(SparseRowMatrix + remote sparse updates, SURVEY §2.5); expert parallelism
is the modern extension of the same idea — parameters too big for one chip,
touched sparsely per token — built TPU-first (GShard/Mesh-TF shape):

* tokens AND experts shard over one mesh axis (``expert``): each device
  holds ``T/n`` tokens and ``E/n`` experts' weights;
* each shard routes its tokens with top-k gating into a fixed-capacity
  dispatch tensor ``[E, C, D]`` (static shapes — XLA-friendly; over-capacity
  tokens drop, the GShard contract);
* one ``all_to_all`` turns shard-major dispatch into expert-major compute
  ``[E_local, n*C, D]``, the expert FFN runs as big batched einsums on the
  MXU, and the reverse ``all_to_all`` brings results home where the combine
  weights (gate probs) produce the output;
* the auxiliary load-balance loss (mean gate fraction x mean assignment
  fraction x E) is returned next to the output.

Capacity semantics are per (source shard, expert): ``capacity`` tokens per
expert from EACH shard. With capacity >= T_local no token ever drops and
the sharded output equals the dense single-device reference exactly
(tests/test_moe.py).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def init_moe_params(key, d_model: int, d_ff: int, n_experts: int,
                    dtype=jnp.float32) -> dict:
    kg, k1, k2 = jax.random.split(key, 3)
    s_in = 1.0 / np.sqrt(d_model)
    s_ff = 1.0 / np.sqrt(d_ff)
    return {
        "gate_w": (jax.random.normal(kg, (d_model, n_experts), dtype) * s_in),
        "w1": jax.random.normal(k1, (n_experts, d_model, d_ff), dtype) * s_in,
        "w2": jax.random.normal(k2, (n_experts, d_ff, d_model), dtype) * s_ff,
    }


def _route(x, gate_w, n_experts: int, k: int, capacity: int):
    """Top-k routing for one shard's tokens.

    Returns (dispatch [T, E, C] 0/1, combine [T, E, C] prob-weighted,
    aux_loss scalar). GShard discipline: choices assign greedily per k
    (the 2nd choice only sees capacity left by the 1st), positions come
    from a cumsum over tokens, over-capacity tokens drop.
    """
    if k > n_experts:
        raise ValueError(f"top-{k} routing needs k <= n_experts "
                         f"({n_experts}): an exhausted gate row would "
                         "re-dispatch to expert 0")
    T = x.shape[0]
    logits = x @ gate_w                               # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)

    # aux load-balance loss (GShard eq.(4)): E * mean_e(gate frac * assign frac)
    top1 = jnp.argmax(probs, axis=-1)
    assign_frac = jnp.mean(jax.nn.one_hot(top1, n_experts), axis=0)
    gate_frac = jnp.mean(probs, axis=0)
    aux = n_experts * jnp.sum(assign_frac * gate_frac)

    remaining = probs
    used = jnp.zeros((n_experts,), jnp.int32)         # slots taken per expert
    dispatch = jnp.zeros((T, n_experts, capacity), x.dtype)
    combine = jnp.zeros((T, n_experts, capacity), x.dtype)
    for _ in range(k):
        choice = jnp.argmax(remaining, axis=-1)       # [T]
        prob = jnp.take_along_axis(probs, choice[:, None], 1)[:, 0]
        onehot = jax.nn.one_hot(choice, n_experts, dtype=jnp.int32)
        # slot index within the chosen expert: earlier tokens first, offset
        # by slots previous choices already consumed
        pos = jnp.cumsum(onehot, axis=0) - onehot + used[None, :]   # [T, E]
        slot = jnp.sum(pos * onehot, axis=-1)                        # [T]
        keep = slot < capacity
        oh_slot = jax.nn.one_hot(slot, capacity, dtype=x.dtype)
        d_k = (onehot.astype(x.dtype)[:, :, None] * oh_slot[:, None, :]
               * keep[:, None, None].astype(x.dtype))
        dispatch = dispatch + d_k
        combine = combine + d_k * prob[:, None, None]
        used = used + jnp.sum(onehot * keep[:, None].astype(jnp.int32),
                              axis=0)
        remaining = remaining * (1.0 - onehot.astype(probs.dtype))
    return dispatch, combine, aux


def _expert_ffn(tokens, w1, w2):
    """tokens [E, N, D] through each expert's 2-layer relu FFN."""
    h = jax.nn.relu(jnp.einsum("end,edf->enf", tokens, w1))
    return jnp.einsum("enf,efd->end", h, w2)


def moe_ffn_dense(params, x, *, k: int = 1,
                  capacity: Optional[int] = None) -> Tuple[jax.Array, jax.Array]:
    """Single-device reference: x [T, D] -> (y [T, D], aux loss)."""
    E = params["gate_w"].shape[-1]
    T = x.shape[0]
    capacity = capacity if capacity is not None else T
    dispatch, combine, aux = _route(x, params["gate_w"], E, k, capacity)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)     # [E, C, D]
    expert_out = _expert_ffn(expert_in, params["w1"], params["w2"])
    y = jnp.einsum("tec,ecd->td", combine, expert_out)
    return y, aux


class ExpertParallelMoE:
    """Expert-sharded MoE FFN over mesh axis ``expert``.

    ``shard_params`` places w1/w2 expert-sharded and the gate replicated;
    ``__call__`` jits one shard_map step: tokens x [T, D] sharded over the
    expert axis rows, output identically sharded.
    """

    def __init__(self, mesh: Mesh, *, k: int = 1,
                 capacity: Optional[int] = None, axis: str = "expert"):
        self.mesh = mesh
        self.axis = axis
        self.n = mesh.shape[axis]
        self.k = k
        self.capacity = capacity
        self._compiled = {}       # (E, T_local, capacity) -> jitted shard_map

    def shard_params(self, params: dict) -> dict:
        es = NamedSharding(self.mesh, P(self.axis))
        rep = NamedSharding(self.mesh, P())
        return {"gate_w": jax.device_put(params["gate_w"], rep),
                "w1": jax.device_put(params["w1"], es),
                "w2": jax.device_put(params["w2"], es)}

    def shard_tokens(self, x) -> jax.Array:
        return jax.device_put(
            x, NamedSharding(self.mesh, P(self.axis, None)))

    def __call__(self, params, x) -> Tuple[jax.Array, jax.Array]:
        E = params["gate_w"].shape[-1]
        T_local = x.shape[0] // self.n
        capacity = self.capacity if self.capacity is not None else T_local
        key = (E, T_local, capacity)
        if key not in self._compiled:
            self._compiled[key] = self._build(E, capacity)
        return self._compiled[key](params["gate_w"], params["w1"],
                                   params["w2"], x)

    def _build(self, E: int, capacity: int):
        n, axis, k = self.n, self.axis, self.k

        def local(gate_w, w1, w2, xs):
            dispatch, combine, aux = _route(xs, gate_w, E, k, capacity)
            ein = jnp.einsum("tec,td->ecd", dispatch, xs)   # [E, C, D]
            # shard-major -> expert-major: [n, E_l, C, D] a2a over the ring
            el = E // n
            ein = ein.reshape(n, el, capacity, -1)
            recv = jax.lax.all_to_all(ein, axis, split_axis=0, concat_axis=0)
            # recv [n, E_l, C, D]: dim0 = source shard; fold into the token dim
            tokens = jnp.swapaxes(recv, 0, 1).reshape(el, n * capacity, -1)
            out = _expert_ffn(tokens, w1, w2)               # [E_l, n*C, D]
            back = jnp.swapaxes(out.reshape(el, n, capacity, -1), 0, 1)
            back = jax.lax.all_to_all(back, axis, split_axis=0, concat_axis=0)
            # back [n, E_l, C, D] with dim0 = expert-home shard == expert id
            # major order: reshape to [E, C, D] for the combine
            back = back.reshape(E, capacity, -1)
            y = jnp.einsum("tec,ecd->td", combine, back)
            # aux is a per-shard mean over its tokens; average across shards
            return y, jax.lax.pmean(aux, axis)

        fn = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(P(), P(self.axis), P(self.axis), P(self.axis, None)),
            out_specs=(P(self.axis, None), P()))
        return jax.jit(fn)
