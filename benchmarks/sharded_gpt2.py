"""Sharded GPT-2 training throughput — the GSPMD sharding plane's row.

GPT-2-small (same shape as benchmarks/transformer_lm.py) trained over a
named ``data x fsdp x tp`` mesh: parameters and Adam moments are placed
per :class:`paddle_tpu.parallel.SpecLayout` (embeddings vocab-sharded over
fsdp x tp, 2-D weights over (fsdp, tp)), the batch shards over ``data``,
and the step compiles through ``jax.jit(..., in_shardings=...,
donate_argnums=...)`` — the same jit+in_shardings path the mesh-aware
fluid Executor lowers annotations through (docs/design/spmd.md), measured
with the shared chained-loop methodology.

The JSON note carries the mesh shape, the resolved per-axis layout
utilization (the fraction of parameter bytes each axis actually divides —
the ``mesh.axis_utilization`` gauge's definition), per-device parameter MB
vs replicated, and MFU against the FULL mesh peak (chip peak x device
count), decomposed per axis as ``mfu_vs_axis`` = achieved FLOP/s over the
peak of that axis's device count alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.transformer_lm import (BATCH, D_MODEL, N_HEADS, N_LAYERS,
                                       NBUF, SEQ, VOCAB)


def build_mesh():
    from paddle_tpu import parallel as pp
    n = len(jax.devices())
    tp = 2 if n % 2 == 0 else 1
    fsdp = 2 if (n // tp) % 2 == 0 else 1
    data = n // (tp * fsdp)
    return pp.make_mesh(data=data, fsdp=fsdp, tp=tp)


def build(batch: int = BATCH, seq: int = SEQ):
    from paddle_tpu import parallel as pp
    from paddle_tpu.models import TransformerLM
    from paddle_tpu.optimizer import Adam

    mesh = build_mesh()
    from jax.sharding import PartitionSpec as _P
    # the positional table is tiny and added to tp-sharded activations
    # every block — sharding it buys nothing and costs an SPMD
    # rematerialization per add, so pin it replicated ahead of the roles
    layout = pp.SpecLayout(rules=[(r"pos_embed$", _P())])
    model = TransformerLM(VOCAB, d_model=D_MODEL, n_heads=N_HEADS,
                          n_layers=N_LAYERS, max_len=seq)
    params = layout.apply(mesh, model.init(jax.random.PRNGKey(0)))
    opt = Adam(3e-4)
    state = layout.apply(mesh, opt.init(params))

    def loss_fn(params, ids):
        p16 = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16)
            if a.dtype == jnp.float32 else a, params)
        return model.loss(p16, ids)

    def step_fn(params, state, ids):
        loss, grads = jax.value_and_grad(loss_fn)(params, ids)
        grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32), grads)
        params, state = opt.update(grads, state, params)
        return params, state, loss

    p_sh = layout.shardings(mesh, params)
    s_sh = layout.shardings(mesh, state)
    from jax.sharding import NamedSharding, PartitionSpec as P
    ids_sh = NamedSharding(mesh, pp.SpecLayout.fit(
        mesh, P("data", None, None), (NBUF, batch, seq)))

    @jax.jit
    def run_n(params, state, idss, n):
        def body(i, carry):
            params, state, _ = carry
            ids = jax.lax.dynamic_index_in_dim(idss, i % NBUF, 0,
                                               keepdims=False)
            return step_fn(params, state, ids)
        return jax.lax.fori_loop(0, n, body, (params, state, jnp.float32(0)))

    rs = np.random.RandomState(0)
    idss = jax.device_put(
        jnp.asarray(rs.randint(0, VOCAB, (NBUF, batch, seq)), jnp.int32),
        ids_sh)
    return mesh, layout, run_n, step_fn, params, state, idss


def _layout_note(mesh, params):
    """Per-axis utilization + per-device footprint of the placed tree."""
    leaves = jax.tree_util.tree_leaves(params)
    total = sum(l.nbytes for l in leaves)
    by_axis = {a: 0 for a in mesh.shape}
    per_device = 0
    for l in leaves:
        ways = 1
        for entry in l.sharding.spec:
            axes = (entry,) if isinstance(entry, str) else tuple(entry or ())
            for a in axes:
                by_axis[a] += l.nbytes
                ways *= mesh.shape[a]
        per_device += l.nbytes // ways
    return {"mesh": dict(mesh.shape),
            "axis_utilization": {a: round(b / total, 3)
                                 for a, b in by_axis.items()},
            "param_mb_per_device": round(per_device / 2**20, 1),
            "param_mb_replicated": round(total / 2**20, 1)}


def run(iters: int = 12, repeats: int = 2, batch: int = BATCH,
        seq: int = SEQ):
    from benchmarks.mfu import peak_flops_per_sec, step_flops
    from benchmarks.timing import chained_ms_per_step

    mesh, layout, run_n, step_fn, params, state, idss = build(batch, seq)
    note = _layout_note(mesh, params)
    with mesh:
        ms = chained_ms_per_step(run_n, (params, state, idss), iters,
                                 repeats)
        flops = step_flops(step_fn, params, state, idss[0])
    tokens = batch * (seq - 1)
    n_dev = int(np.prod(list(mesh.shape.values())))
    row = {"metric": f"sharded_gpt2s_train_tokens_per_sec_bs{batch}"
                     f"_seq{seq}_mesh{n_dev}",
           "value": round(tokens / (ms / 1e3), 1), "unit": "tokens/sec",
           "vs_baseline": None,
           "mfu": None,           # overwritten below when peak is known
           "methodology": "measured",   # XLA-analyzed FLOPs, real timing
           "note": note}
    peak = peak_flops_per_sec()
    if flops and peak:
        row["gflops_per_step"] = round(flops / 1e9, 2)
        achieved = flops / (ms / 1e3)
        mfu = achieved / (peak * n_dev)
        row["mfu"] = None if mfu > 1.0 else round(mfu, 4)
        note["mfu_vs_axis"] = {
            a: round(min(achieved / (peak * size), 99.0), 4)
            for a, size in mesh.shape.items()}
        row["peak_tflops"] = round(peak * n_dev / 1e12, 1)
    return row


if __name__ == "__main__":
    import json
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    print(json.dumps(run()))
