"""Data-parallel training — the MultiGradientMachine replacement.

Reference semantics being preserved (gserver/gradientmachines/MultiGradientMachine.h):
* batch split across devices (``TrainerThread`` per GPU, .h:44-60)
* gradient ring allreduce + broadcast of updated params (.h:61-83)
* final parameters identical to single-device training on the whole batch
  (tested by the test_CompareSparse.cpp-style equivalence test).

TPU-native: ONE jitted SPMD train step. The batch carries a ``data``-axis sharding,
loss is a mean over the global batch, and XLA inserts the grad ``psum`` over ICI
automatically from the sharding propagation — no explicit communication code.
Optionally optimizer state is sharded over ``data`` (ZeRO-1) via reduce_scatter
semantics, recovering what the pserver did (each server owns a param shard's
optimizer state, ParameterServer2.h:383 doOperation).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import make_mesh, use_mesh
from .sharding import ShardingRules, replicate, shard_batch, shard_params


class DataParallel:
    """Wrap (loss_fn, optimizer) into a sharded, jitted train step.

    loss_fn(params, *batch) -> scalar loss (mean over ITS batch rows).
    """

    def __init__(self, loss_fn: Callable, optimizer, mesh: Optional[Mesh] = None,
                 axis: str = "data", param_rules: Optional[ShardingRules] = None,
                 donate: bool = True, aux_fn: Optional[Callable] = None):
        self.loss_fn = loss_fn
        self.opt = optimizer
        self.mesh = mesh if mesh is not None else make_mesh(data=-1)
        self.axis = axis
        self.rules = param_rules
        self.aux_fn = aux_fn

        def _step(params, opt_state, *batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
            # aux (eval outputs) computed INSIDE the same jitted step so XLA
            # shares the forward pass — no second per-batch dispatch
            aux = aux_fn(params, *batch) if aux_fn is not None else None
            new_params, new_state = self.opt.update(grads, opt_state, params)
            if aux_fn is not None:
                return new_params, new_state, loss, aux
            return new_params, new_state, loss

        self._raw_step = _step
        self._donate = (0, 1) if donate else ()
        self._step = None           # jitted at the first step()

    def _build_step(self, params, opt_state):
        """The jitted step, with the new params and optimizer state PINNED
        to the shardings the old ones arrive under. Left to XLA, outputs can
        come back under another spec (1-D leaves sharded over ``tp``) and
        the next step sees new input shardings: a silent recompile under
        jit, a refusal from an AOT executable — which is what the obs cost
        ledger runs. Pinned, donation also aliases leaf for leaf."""
        def keep(tree):
            return jax.tree_util.tree_map(
                lambda a: getattr(a, "sharding", None), tree)
        out = (keep(params), keep(opt_state), None)
        if self.aux_fn is not None:
            out += (None,)
        # cost-instrumented jit (as Trainer._step): an obs session sees the
        # SPMD step's FLOPs/bytes in the roofline ledger per dispatch
        from ..obs import roofline
        return roofline.instrument(
            jax.jit(self._raw_step, donate_argnums=self._donate,
                    out_shardings=out), "data_parallel.step")

    # -- placement ---------------------------------------------------------
    def init(self, params, opt_state=None):
        """Place params (+ optimizer state) on the mesh. Called again on a
        checkpoint restore, this is what re-places host arrays onto the
        CURRENT mesh — the rules are a pure function of path+shape, so a
        job resumed on a different mesh shape just re-resolves."""
        params = shard_params(params, self.mesh, self.rules)
        if opt_state is None:
            opt_state = self.opt.init(params)
        if hasattr(self.rules, "resolve"):
            # SpecLayout: slot paths embed their parameter's path, so the
            # same resolution shards optimizer moments like their params
            opt_state = self.rules.apply(self.mesh, opt_state)
        else:
            opt_state = jax.device_put(opt_state, replicate(self.mesh))
        return params, opt_state

    def shard_batch(self, batch):
        return shard_batch(batch, self.mesh, self.axis)

    # -- the hot loop ------------------------------------------------------
    def step(self, params, opt_state, *batch) -> Tuple[Any, Any, jax.Array]:
        """One global-batch SGD step; batch leaves should already be sharded
        (use :meth:`shard_batch`) or will be sharded by XLA on first use."""
        if self._step is None:
            self._step = self._build_step(params, opt_state)
        with use_mesh(self.mesh):       # ambient: kernels wrap themselves
            return self._step(params, opt_state, *batch)


class Zero1State(NamedTuple):
    """ZeRO-1 training state: the f32 master copy of all trainable parameters
    lives as ONE flat vector sharded over the data axis; optimizer slots share
    that sharding; non-trainable ``stats`` leaves stay replicated."""
    flat: jax.Array          # [N_padded] f32, sharded P(axis)
    opt_state: Any           # {"step": scalar, "slots": {"flat": ...}} P(axis)
    stats: Tuple[Any, ...]   # replicated non-trainable leaves, original order


class Zero1DataParallel:
    """TRUE ZeRO-1 data parallelism (partitioned optimizer states).

    Semantics recovered from the reference's parameter server, where each
    pserver owns a shard of every parameter block and runs the optimizer on
    its shard only (ParameterServer2.h:383 doOperation; ParameterClient2
    splits parameters into blocks hashed across pservers):

    * each device owns 1/n of one flat f32 master parameter vector and the
      optimizer slots FOR THAT SHARD ONLY (n× slot-memory saving),
    * per step inside one jitted shard_map: all_gather(param shards) →
      local fwd/bwd → **reduce_scatter**(grads) → shard-local optimizer
      update → next step's all_gather broadcasts the new params,
    * final parameters match plain DP / single-device training exactly
      (equivalence-tested like test_CompareSparse.cpp).

    loss_fn(params, *batch) -> scalar loss (mean over ITS batch rows).
    """

    def __init__(self, loss_fn: Callable, optimizer, mesh: Optional[Mesh] = None,
                 axis: str = "data"):
        if getattr(optimizer, "grad_clip", None) is not None and \
                optimizer.grad_clip[0] in ("norm", "global_norm"):
            raise ValueError(
                "norm-based grad clip inside the shard-local optimizer would "
                "clip by the LOCAL shard's norm (not per-leaf / global); "
                "clip in loss_fn or use grad_clip=('value', ...)")
        self.loss_fn = loss_fn
        self.opt = optimizer
        self.mesh = mesh if mesh is not None else make_mesh(data=-1)
        self.axis = axis
        self.n = self.mesh.shape[axis]
        self._stepfns = {}        # batch treedef -> compiled shard_map step

    # -- flat <-> pytree ----------------------------------------------------
    def _build_template(self, params):
        from ..optimizer.optimizers import _is_stat_path
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        self._treedef = treedef
        self._is_stat = [_is_stat_path(path) for path, _ in flat]
        train = [leaf for (path, leaf), st in zip(flat, self._is_stat) if not st]
        self._shapes = [l.shape for l in train]
        self._dtypes = [l.dtype for l in train]
        self._sizes = [int(np.prod(l.shape)) if l.shape else 1 for l in train]
        total = sum(self._sizes)
        self._padded = -(-total // self.n) * self.n
        self._offsets = np.cumsum([0] + self._sizes).tolist()

    def _flatten(self, leaves):
        """Trainable leaves -> [N_padded] f32."""
        parts = [jnp.ravel(l).astype(jnp.float32) for l in leaves]
        flat = jnp.concatenate(parts) if parts else jnp.zeros((0,), jnp.float32)
        pad = self._padded - flat.shape[0]
        return jnp.pad(flat, (0, pad)) if pad else flat

    def _unflatten(self, flat, stats):
        """[N_padded] f32 + replicated stat leaves -> params pytree."""
        train = [flat[o:o + s].reshape(shape).astype(dt)
                 for o, s, shape, dt in zip(self._offsets, self._sizes,
                                            self._shapes, self._dtypes)]
        it_t, it_s = iter(train), iter(stats)
        leaves = [next(it_s) if st else next(it_t) for st in self._is_stat]
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    def _train_leaves(self, tree):
        flat = jax.tree_util.tree_leaves(tree)
        return [l for l, st in zip(flat, self._is_stat) if not st]

    def _stat_leaves(self, tree):
        flat = jax.tree_util.tree_leaves(tree)
        return tuple(l for l, st in zip(flat, self._is_stat) if st)

    # -- placement ----------------------------------------------------------
    def init(self, params) -> Zero1State:
        self._build_template(params)
        flat = self._flatten(self._train_leaves(params))
        flat = jax.device_put(flat, NamedSharding(self.mesh, P(self.axis)))
        opt_state = self.opt.init({"flat": flat})   # slots inherit the sharding
        opt_state = jax.tree_util.tree_map(
            lambda x: x if getattr(x, "ndim", 0) >= 1 else
            jax.device_put(x, replicate(self.mesh)), opt_state)
        stats = jax.device_put(self._stat_leaves(params), replicate(self.mesh))
        return Zero1State(flat, opt_state, stats)

    def params(self, state: Zero1State):
        """Materialise the full parameter pytree (for eval / checkpointing)."""
        return self._unflatten(jax.device_get(state.flat), state.stats)

    def shard_batch(self, batch):
        return shard_batch(batch, self.mesh, self.axis)

    # -- the hot loop --------------------------------------------------------
    def _make_step(self, state: Zero1State, batch):
        axis, n = self.axis, self.n
        flat_spec = P(axis)
        state_spec = jax.tree_util.tree_map(
            lambda x: P(axis) if getattr(x, "ndim", 0) >= 1 else P(),
            state.opt_state)
        stats_spec = jax.tree_util.tree_map(lambda x: P(), state.stats)
        batch_specs = tuple(
            jax.tree_util.tree_map(
                lambda l: P(axis, *([None] * (jnp.ndim(l) - 1)))
                if jnp.ndim(l) >= 1 else P(), b)
            for b in batch)

        def local_step(flat_shard, opt_state, stats, *batch):
            from . import collectives as cc
            full = cc.all_gather(flat_shard, axis)
            params = self._unflatten(full, stats)
            loss, grads = jax.value_and_grad(self.loss_fn)(params, *batch)
            gflat = self._flatten(self._train_leaves(grads))
            # mean over the data axis, scattered so each device only keeps
            # (and updates) its own 1/n shard
            g_shard = cc.reduce_scatter(gflat, axis) / n
            new_p, new_state = self.opt.update({"flat": g_shard}, opt_state,
                                               {"flat": flat_shard})
            return new_p["flat"], new_state, jax.lax.pmean(loss, axis)

        fn = jax.shard_map(
            local_step, mesh=self.mesh,
            in_specs=(flat_spec, state_spec, stats_spec) + batch_specs,
            out_specs=(flat_spec, state_spec, P()),
            check_vma=False)
        return jax.jit(fn, donate_argnums=(0, 1))

    def step(self, state: Zero1State, *batch):
        """One global-batch ZeRO-1 step -> (new_state, loss)."""
        # key on leaf ranks too: in_specs bake each leaf's rank, so same-tree
        # batches with different ranks must not share a compiled step
        key = (str(jax.tree_util.tree_structure(batch)),
               tuple(jnp.ndim(l) for l in jax.tree_util.tree_leaves(batch)))
        if key not in self._stepfns:
            self._stepfns[key] = self._make_step(state, batch)
        with self.mesh:
            flat, opt_state, loss = self._stepfns[key](
                state.flat, state.opt_state, state.stats, *batch)
        return Zero1State(flat, opt_state, state.stats), loss
