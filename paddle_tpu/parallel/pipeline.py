"""Pipeline parallelism over a ``pipe`` mesh axis (GPipe-style SPMD).

The reference pipelines by placing whole layers on devices and streaming
batches through per-device threads (ParallelNeuralNetwork.h:23-34, TaskType
fwd/bwd queues). TPU-native: all stages run the SAME jitted SPMD program; stage
parameters are stacked on a leading axis sharded over ``pipe``, microbatch
activations hop stage->stage via ``ppermute`` over ICI, and the schedule is a
``lax.fori_loop`` of (n_microbatches + n_stages - 1) ticks. Autodiff flows
through ppermute, so the same program trains (XLA overlaps the transfers —
recovering the reference's thread-pipelined overlap, SURVEY §2.5 row
'Pipeline-ish overlap').

Constraint inherited from SPMD: every stage must share one activation shape
(equal-width trunk), the usual homogeneous-transformer-stack case.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..nn.module import Module


class PipelineStage(Module):
    """Repeats one stage Module across pipeline stages with stacked params.

    ``init`` produces params with a leading [n_stages] axis on every leaf;
    shard that axis over ``pipe`` and run via :func:`pipeline_spmd`.
    """

    def __init__(self, make_stage: Callable[[], Module], n_stages: int):
        super().__init__()
        self.n_stages = n_stages
        self.stage = make_stage()

    def init(self, rng):
        keys = jax.random.split(rng, self.n_stages)
        per_stage = [self.stage.init(k) for k in keys]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_stage)

    def __call__(self, params, x, **kw):
        """Reference (non-pipelined) execution: fold over stages sequentially."""
        def body(x, stage_params):
            return self.stage(stage_params, x, **kw), None
        out, _ = lax.scan(body, x, params)
        return out


def pipeline_spmd(stage_fn: Callable, mesh: Mesh, n_microbatches: int,
                  axis: str = "pipe"):
    """Build fn(stacked_params, x) running stage_fn through the pipe ring.

    stage_fn(stage_params, mb) -> mb', same shape. ``x`` is [B, ...]; it is
    split into ``n_microbatches`` along dim 0 (B % n_microbatches == 0).
    Returns the full output batch, replicated over the pipe axis.
    """
    n_stages = mesh.shape[axis]

    def local(params, x):
        # params leaves arrive [1, ...] (this stage's slice); drop the axis.
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        stage_id = lax.axis_index(axis)
        mb = x.reshape(n_microbatches, x.shape[0] // n_microbatches, *x.shape[1:])
        # activations become device-varying over 'pipe' after the first stage_fn;
        # cast the loop carry up front so the fori_loop carry type is stable
        state = lax.pcast(jnp.zeros_like(mb[0]), axis, to="varying")
        out_buf = lax.pcast(jnp.zeros_like(mb), axis, to="varying")
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        total = n_microbatches + n_stages - 1

        def tick(t, carry):
            state, out_buf = carry
            # stage 0 injects microbatch t (garbage-in after the last one;
            # results of those ticks are never collected)
            inj = mb[jnp.minimum(t, n_microbatches - 1)]
            inp = jnp.where(stage_id == 0, inj, state)
            out = stage_fn(params, inp)
            # last stage owns microbatch t-(n_stages-1) at tick t
            done_idx = t - (n_stages - 1)
            is_done = jnp.logical_and(stage_id == n_stages - 1, done_idx >= 0)
            write_at = jnp.clip(done_idx, 0, n_microbatches - 1)
            upd = jnp.where(is_done, out, out_buf[write_at])
            out_buf = lax.dynamic_update_index_in_dim(out_buf, upd, write_at, 0)
            state = lax.ppermute(out, axis, fwd)
            return state, out_buf

        state, out_buf = lax.fori_loop(0, total, tick, (state, out_buf))
        # replicate the collected outputs (held by the last stage) to all stages
        mask = (stage_id == n_stages - 1).astype(out_buf.dtype)
        out_buf = lax.psum(out_buf * mask, axis)
        return out_buf.reshape(x.shape[0], *out_buf.shape[2:])

    pspec = P(axis)   # prefix spec: applies to every leaf of the params pytree
    xspec = P()
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(pspec, xspec),
                                 out_specs=xspec))


def pipeline_1f1b(stage_fn: Callable, loss_fn: Callable, mesh: Mesh,
                  n_microbatches: int, axis: str = "pipe"):
    """1F1B (PipeDream-flush) training schedule over the ``pipe`` axis.

    Builds ``step(stacked_params, x, y) -> (loss, stacked_grads)``.

    GPipe (``jax.grad`` through :func:`pipeline_spmd`) runs all M forwards
    then all M backwards, so every stage stashes M microbatch activations.
    1F1B interleaves: stage s's timetable is forwards at ticks ``s + 2m`` and
    backwards at ``2S - s - 1 + 2m`` (parities never collide), so at most
    ``S - s`` microbatches are in flight per stage and the input stash is a
    circular buffer of S slots — the memory bound is min(S, M) activations
    instead of M. The bubble fraction is the same (S-1)/(M+S-1) for both
    schedules (each does M+S-1 forward slots and M+S-1 backward slots);
    1F1B's win is memory, which is what lets M grow to amortize the bubble.
    Backward recomputes the stage forward from the stashed INPUT (standard
    rematerialization), so the stash holds inputs, not full residuals.

    Reference analog: ParallelNeuralNetwork.h:23-34 streams batches through
    per-device fwd/bwd task queues — 1F1B is that interleave, made explicit
    as a static SPMD timetable instead of threads.

    stage_fn(stage_params, mb) -> mb' (same shape); loss_fn(out_mb, y_mb) ->
    scalar mean loss for the microbatch. Returned loss/grads are averaged
    over microbatches; grads keep the stacked [n_stages, ...] leading axis.
    """
    n_stages = mesh.shape[axis]

    def local(params, x, y):
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        S, M = n_stages, n_microbatches
        s = lax.axis_index(axis)
        mbx = x.reshape(M, x.shape[0] // M, *x.shape[1:])
        mby = y.reshape(M, y.shape[0] // M, *y.shape[1:])
        mb_shape = mbx[0]
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]
        bwd_perm = [((i + 1) % S, i) for i in range(S)]

        def bwd_of(saved_inp, cot, y_mb, is_last):
            """Recompute-vjp one stage. The last stage seeds from the loss."""
            def last_branch(p, inp):
                lv, vjp = jax.vjp(
                    lambda pp, xx: loss_fn(stage_fn(pp, xx), y_mb), p, inp)
                dp, dx = vjp(jnp.ones_like(lv))
                return lv.astype(jnp.float32), dp, dx

            def mid_branch(p, inp):
                _, vjp = jax.vjp(stage_fn, p, inp)
                dp, dx = vjp(cot)
                return jnp.float32(0), dp, dx

            return lax.cond(is_last, last_branch, mid_branch,
                            params, saved_inp)

        def tick(t, carry):
            fwd_msg, bwd_msg, stash, dparams, loss_acc = carry
            # static timetable, evaluated per device from its axis index
            tf = t - s
            do_fwd = (tf >= 0) & (tf % 2 == 0) & (tf // 2 < M)
            m_f = jnp.clip(tf // 2, 0, M - 1)
            tb = t - (2 * S - s - 1)
            do_bwd = (tb >= 0) & (tb % 2 == 0) & (tb // 2 < M)
            m_b = jnp.clip(tb // 2, 0, M - 1)

            inp = jnp.where(s == 0, mbx[m_f], fwd_msg)
            saved = lax.dynamic_index_in_dim(stash, m_b % S, 0,
                                             keepdims=False)

            def do_backward(_):
                lv, dp, dx = bwd_of(saved, bwd_msg, mby[m_b], s == S - 1)
                return jnp.zeros_like(mb_shape), dx, dp, lv

            def do_forward(_):
                out = stage_fn(params, inp)
                zp = jax.tree_util.tree_map(jnp.zeros_like, params)
                return out, jnp.zeros_like(mb_shape), zp, jnp.float32(0)

            send_f, send_b, dp, lv = lax.cond(do_bwd, do_backward,
                                              do_forward, None)
            # mask edges: idle ticks run the forward branch on garbage input
            send_f = jnp.where(do_fwd, send_f, 0).astype(mb_shape.dtype)
            stash = lax.cond(
                do_fwd,
                lambda st: lax.dynamic_update_index_in_dim(
                    st, inp, m_f % S, 0),
                lambda st: st, stash)
            dparams = jax.tree_util.tree_map(jnp.add, dparams, dp)
            loss_acc = loss_acc + lv
            fwd_msg = lax.ppermute(send_f, axis, fwd_perm)
            bwd_msg = lax.ppermute(send_b, axis, bwd_perm)
            return fwd_msg, bwd_msg, stash, dparams, loss_acc

        zero_mb = lax.pcast(jnp.zeros_like(mb_shape), axis, to="varying")
        stash0 = lax.pcast(
            jnp.zeros((S,) + mb_shape.shape, mb_shape.dtype), axis,
            to="varying")
        dp0 = lax.pcast(jax.tree_util.tree_map(jnp.zeros_like, params),
                        axis, to="varying")
        carry = (zero_mb, zero_mb, stash0, dp0, jnp.float32(0))
        total = 2 * (M + S - 1)
        _, _, _, dparams, loss_acc = lax.fori_loop(0, total, tick, carry)
        loss = lax.psum(loss_acc, axis) / M
        dparams = jax.tree_util.tree_map(lambda g: (g / M)[None], dparams)
        return loss, dparams

    pspec = P(axis)
    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(pspec, P(), P()),
        out_specs=(P(), pspec), check_vma=False))
