"""Executor: run a Program's blocks as ONE compiled XLA computation.

Reference: framework/executor.cc:87 ``Executor::Run`` creates vars then
interprets ops sequentially (:120-124). TPU-native redesign (SURVEY.md §7): the
op list is *traced* through the registry's jax computes into a single function,
jitted and cached keyed on (program fingerprint, feed shapes/dtypes) — the
shape-keyed executable cache that makes repeated `run` calls free of Python op
dispatch. Feed/fetch (feed_op.cc/fetch_op.cc) become function inputs/outputs.

Control flow (while_op.cc, conditional_block_op.cc, recurrent_op.cc): sub-block
ops are traced into ``lax.while_loop`` / ``lax.cond`` / ``lax.scan`` bodies.
The loop-carried state is derived from the IR: any outer variable a sub-block
writes is carried (the reference threads these through the enclosing Scope;
here they thread through the XLA loop carry, which is what the hardware wants).

Autodiff: a block may contain one ``autodiff_grad`` op (appended by
backward.append_backward). During tracing it replays the forward prefix as a
closure over the parameter leaves and calls jax.grad — XLA CSE merges the
replayed forward with the primal one, recovering the classic single
forward+backward graph (replacing backward.cc:414 AppendBackward's explicit
grad-op emission).
"""

from __future__ import annotations

import warnings
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..data.feeder import BucketSpec
from .framework import Block, Program, Variable
from .registry import OpRegistry


class _OpTraceError(RuntimeError):
    """An op failed during Program tracing; the message names the op and
    the chain leading to it (CustomStackTrace.h:51 crash-stack analog)."""


import re as _re

_SCOPE_SAFE = _re.compile(r"[^A-Za-z0-9_]")


def _scope_tag(op, idx: int) -> str:
    """The jax.named_scope stamp for one op site — the machine-parseable
    twin of analysis.diagnostics.op_site ('block B, op #I (type)'):
    obs/xplane.py's `site_of` inverts it when attributing profiled HLO
    ops back to Program sites."""
    bidx = getattr(op.block, "idx", None)
    b = bidx if bidx is not None else 0
    return f"b{b}_op{idx}_{_SCOPE_SAFE.sub('_', op.type)}"


class Scope:
    """Runtime variable store (scope.h analog); persistables live here across
    run() calls. Child scopes see parent vars."""

    def __init__(self, parent: Optional["Scope"] = None):
        self.parent = parent
        self.vars: Dict[str, Any] = {}

    def set(self, name: str, value):
        self.vars[name] = value

    def get(self, name: str):
        s: Optional[Scope] = self
        while s is not None:
            if name in s.vars:
                return s.vars[name]
            s = s.parent
        raise KeyError(name)

    def has(self, name: str) -> bool:
        try:
            self.get(name)
            return True
        except KeyError:
            return False

    def new_child(self) -> "Scope":
        return Scope(self)


_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


class TraceContext:
    """Per-trace state threaded through op lowering: the owning program (for
    sub-block lookup) and the block-entry environment (for autodiff replay).
    Replaces the former in-place ``op.attrs['_init_env']`` stash, which was
    non-reentrant and leaked traced arrays into the desc layer."""

    def __init__(self, program: Program, entry_env: Dict[str, Any]):
        self.program = program
        self.entry_env = entry_env


def _trace_ops(ops, env: Dict[str, Any], ctx: TraceContext):
    """Symbolically run an op list over env (name -> traced array).

    A failing op re-raises with the op's position, type, and io names plus
    the chain of ops leading up to it — the fluid-level analog of the
    reference's crash-time layer-name stack (utils/CustomStackTrace.h:51),
    without which a shape error deep in a traced Program is anonymous.
    """
    for idx, op in enumerate(ops):
        try:
            if op.type == "autodiff_grad":
                _trace_autodiff(op, ops, env, ctx)
                continue
            if op.type == "while":
                _trace_while(op, env, ctx)
                continue
            if op.type == "conditional_block":
                _trace_cond(op, env, ctx)
                continue
            if op.type == "static_rnn":
                _trace_static_rnn(op, env, ctx)
                continue
            if op.type == "beam_search_gen":
                _trace_beam_search_gen(op, env, ctx)
                continue
            compute = OpRegistry.get(op.type)
            ins = {k: [env[n] for n in vs] for k, vs in op.inputs.items()}
            # per-op-site name scope: HLO ops lowered from this compute
            # carry "b{B}_op{I}_{type}" in their metadata, so a device
            # profile (obs/xplane.py, `paddle_tpu profile`) attributes
            # hot ops back to the analysis plane's `block B, op #I
            # (type)` site — the same site runtime trace errors cite
            with jax.named_scope(_scope_tag(op, idx)):
                outs = compute(ins, op.attrs)
            for k, names in op.outputs.items():
                vals = outs[k]
                for n, v in zip(names, vals):
                    env[n] = v
        except Exception as e:
            if getattr(e, "_op_ctx", False):
                raise          # innermost op already carries its context
            chain = " -> ".join(o.type for o in ops[max(0, idx - 4):idx + 1])
            # one source of truth for the location format so a runtime
            # failure and the static diagnostic for an op cite the same site
            from ..analysis.diagnostics import block_paths, op_site
            blk = getattr(op, "block", None)
            bidx = getattr(blk, "idx", None)
            path = None
            prog = getattr(blk, "program", None)
            if prog is not None and bidx is not None:
                # nested sub-block failures cite the full parent chain
                # ("block 0.2, op #5") — same format as lint diagnostics
                path = block_paths(prog).get(bidx)
            site = op_site(bidx, idx, op.type, block_path=path)
            msg = (f"{site} failed while tracing the Program "
                   f"(inputs={op.inputs}, outputs={op.outputs})\n"
                   f"  op chain: ...{chain}")
            if hasattr(e, "add_note"):
                # annotate the ORIGINAL exception: re-constructing via
                # type(e)(msg) would drop structured args (OSError.errno,
                # KeyError's key) that callers match on
                e.add_note(msg)
                e._op_ctx = True
                raise
            try:               # pre-3.11 fallback: keep the type so callers'
                new = type(e)(f"{msg}: {type(e).__name__}: {e}")
            except Exception:
                new = _OpTraceError(f"{msg}: {type(e).__name__}: {e}")
            new._op_ctx = True
            raise new from e
    return env


def _trace_autodiff(op, ops, env, ctx: TraceContext):
    loss_name = op.attrs["loss"]
    param_names = list(op.attrs["params"])
    # forward = every op BEFORE this one in the CURRENT list (backward/
    # optimizer ops are appended after it). The op's own position — not the
    # recorded num_fwd_ops attr — stays correct after Program.prune drops
    # dangling forward ops and shifts indices (a stale count would make the
    # replay include this op itself and recurse forever).
    n_fwd = ops.index(op)
    init_env = ctx.entry_env

    def replay(param_vals):
        env2 = dict(init_env)
        for name, val in zip(param_names, param_vals):
            env2[name] = val
        _trace_ops(ops[:n_fwd], env2, ctx)
        return env2[loss_name]

    grads = jax.grad(replay)([env[n] for n in param_names])
    for name, g in zip(param_names, grads):
        env[name + "@GRAD"] = g


def _sub_block_written(sub: Block, env) -> List[str]:
    """Outer vars a sub-block (transitively) writes — the loop-carried state.

    The reference threads these through the parent Scope
    (while_op.cc's step scopes); under XLA they become the loop carry."""
    written: List[str] = []
    prog = sub.program

    def collect(block: Block):
        for o in block.ops:
            for n in o.output_vars():
                written.append(n)
            for key in ("sub_block_idx", "true_block_idx", "false_block_idx"):
                if key in o.attrs and o.attrs[key] is not None:
                    collect(prog.blocks[o.attrs[key]])

    collect(sub)
    return list(dict.fromkeys(n for n in written if n in env))


def _trace_while(op, env, ctx: TraceContext):
    """Lower a while op to lax.while_loop (while_op.cc semantics: re-run the
    sub-block until the condition var — updated inside the block — is false)."""
    sub = ctx.program.blocks[op.attrs["sub_block_idx"]]
    cond_name = op.inputs["Condition"][0]
    carried = _sub_block_written(sub, env)
    if cond_name not in carried:
        raise ValueError(
            f"while condition '{cond_name}' is never updated in the loop body "
            "(would loop forever); write it with less_than(..., cond=cond)")
    ci = carried.index(cond_name)

    def cond_fn(state):
        return jnp.reshape(state[ci], ()).astype(bool)

    def body_fn(state):
        env2 = dict(env)
        env2.update(zip(carried, state))
        _trace_ops(sub.ops, env2, ctx)
        return tuple(env2[n] for n in carried)

    init = tuple(env[n] for n in carried)
    final = jax.lax.while_loop(cond_fn, body_fn, init)
    env.update(zip(carried, final))


def _trace_cond(op, env, ctx: TraceContext):
    """Lower conditional_block(+optional else block) to lax.cond. Vars written
    by either branch must pre-exist outside so the untaken branch has a value
    to pass through (conditional_block_op.cc runs the block or skips it,
    leaving scope vars untouched)."""
    true_b = ctx.program.blocks[op.attrs["true_block_idx"]]
    false_idx = op.attrs.get("false_block_idx")
    false_b = ctx.program.blocks[false_idx] if false_idx is not None else None
    cond_name = op.inputs["Condition"][0]
    carried = _sub_block_written(true_b, env)
    if false_b is not None:
        for n in _sub_block_written(false_b, env):
            if n not in carried:
                carried.append(n)

    def make_branch(blk: Optional[Block]):
        def branch(state):
            env2 = dict(env)
            env2.update(zip(carried, state))
            if blk is not None:
                _trace_ops(blk.ops, env2, ctx)
            return tuple(env2[n] for n in carried)
        return branch

    init = tuple(env[n] for n in carried)
    pred = jnp.reshape(env[cond_name], ()).astype(bool)
    final = jax.lax.cond(pred, make_branch(true_b), make_branch(false_b), init)
    env.update(zip(carried, final))


def _trace_static_rnn(op, env, ctx: TraceContext):
    """Lower a static_rnn op (recurrent_op.cc / fluid StaticRNN) to ONE
    lax.scan over the time axis — the TPU-native form of the reference's
    per-step frame cloning (RecurrentGradientMachine.h:304)."""
    a = op.attrs
    sub = ctx.program.blocks[a["sub_block_idx"]]
    # step inputs: outer [B, T, ...] -> scan over [T, B, ...]
    xs = tuple(jnp.moveaxis(env[n], 1, 0) for n in a["outer_inputs"])
    init = tuple(env[n] for n in a["boot_mems"])

    def body(carry, xt):
        env2 = dict(env)
        env2.update(zip(a["mem_names"], carry))
        env2.update(zip(a["step_in_names"], xt))
        _trace_ops(sub.ops, env2, ctx)
        new_carry = tuple(env2[n] for n in a["mem_update_names"])
        outs = tuple(env2[n] for n in a["step_out_names"])
        return new_carry, outs

    carry, ys = jax.lax.scan(body, init, xs)
    for name, y in zip(a["outer_outputs"], ys):
        env[name] = jnp.moveaxis(y, 0, 1)            # [T, B, ...] -> [B, T, ...]
    for name, c in zip(a["last_mem_outputs"], carry):
        if name is not None:
            env[name] = c


def _trace_beam_search_gen(op, env, ctx: TraceContext):
    """Lower a beam_search_gen op: the user's step sub-block becomes the
    step_fn of the on-device masked-top-k beam decode (ops/beam_search.py).

    The reference runs beam search on CPU with per-step frame cloning and
    Python callbacks (RecurrentGradientMachine::beamSearch:1020); here the
    whole decode is one lax.scan — memories and static (encoder) inputs ride
    the beam 'cell' so they tile across beams together.
    """
    from ..ops.beam_search import beam_search
    a = op.attrs
    sub = ctx.program.blocks[a["sub_block_idx"]]
    embed_w = env[a["embed_param"]]
    boots = tuple(env[n] for n in a["boot_mems"])
    statics = tuple(env[n] for n in a["static_outer"])
    B = (boots[0].shape[0] if boots else statics[0].shape[0])
    K = a["beam_size"]
    V = embed_w.shape[0]
    # statics are invariant across beams AND steps: tile to [B*K, ...] ONCE
    # and close over them — carrying them in the scan cell would reshape and
    # beam-gather the whole encoder tensor every decode step for no effect
    tiled = tuple(jnp.broadcast_to(s[:, None], (B, K) + s.shape[1:])
                  .reshape((B * K,) + s.shape[1:]) for s in statics)

    def step_fn(mems, tokens):
        env2 = dict(env)
        env2.update(zip(a["mem_names"], mems))
        env2.update(zip(a["static_in_names"], tiled))
        env2[a["token_embed_name"]] = jnp.take(embed_w, tokens, axis=0)
        _trace_ops(sub.ops, env2, ctx)
        probs = env2[a["prob_name"]]
        logp = jnp.log(jnp.maximum(probs, 1e-9))
        new_mems = tuple(env2[n] for n in a["mem_update_names"])
        return logp, new_mems

    constraint_fn = None
    if a.get("constraint"):
        from ..ops.beam_search import CONSTRAINTS
        try:
            constraint_fn = CONSTRAINTS[a["constraint"]]
        except KeyError:
            raise KeyError(
                f"beam-search constraint {a['constraint']!r} is not "
                "registered; call paddle_tpu.ops.beam_search."
                "register_constraint(name, fn) before running the program")

    toks, scores = beam_search(
        boots, step_fn, batch_size=B,
        beam_size=K, max_len=a["max_length"], vocab_size=V,
        bos_id=a["bos_id"], eos_id=a["eos_id"],
        length_penalty=a.get("length_penalty", 0.0),
        constraint_fn=constraint_fn)
    env[op.outputs["Tokens"][0]] = toks
    env[op.outputs["Scores"][0]] = scores


class _CompiledEntry:
    """One compiled-fn cache entry: the jitted callable plus the cost
    record the roofline ledger reads (docs/design/observability.md
    "Device timelines & roofline").

    The first call under an installed obs session lowers + compiles AOT
    (``jitted.lower(...).compile()``
    — the same compile jit would pay, just held where
    ``cost_analysis()`` / ``memory_analysis()`` are reachable) and
    records the executable's :class:`~paddle_tpu.obs.roofline.Cost`.
    Installing obs AFTER an entry warmed up on the plain jit path makes
    that first session call re-pay one compile for the signature (jit's
    internal executable is not reachable for cost analysis); the
    persistent XLA compile cache turns it into a deserialize when
    enabled.
    The executor's cache key pins the argument signature, so one
    executable serves the entry for its lifetime. Any AOT
    lowering/compile failure — or the stricter AOT argument check
    rejecting a call the polymorphic jit would have accepted — falls
    back to the plain jitted callable (counted as a cost-analysis
    failure; cost stays an honest None)."""

    __slots__ = ("_jitted", "_call", "cost", "kernel_bytes")

    def __init__(self, jitted):
        self._jitted = jitted
        self._call = None
        self.cost = None
        #: {kernel: modeled bytes per dispatch} collected at trace time
        #: from note_kernel_bytes launch sites (Pallas routes) inside the
        #: program — re-emitted per run by the executor
        self.kernel_bytes = None

    def __call__(self, feed, kept_vals, donated_vals):
        call = self._call
        if call is None:
            if not obs.is_active():
                # plane off: stay on the plain jit path — no AOT compile,
                # no cost-analysis warnings in processes that never
                # installed obs (CostInstrumentedJit's discipline; an
                # entry first hit under a session records its cost)
                return self._jitted(feed, kept_vals, donated_vals)
            roofline = obs.roofline
            try:
                with roofline.collect_kernel_bytes() as col:
                    lowered = self._jitted.lower(feed, kept_vals,
                                                 donated_vals)
                if col.per_kernel:
                    self.kernel_bytes = col.per_kernel
                compiled = lowered.compile()
                self.cost = roofline.compiled_cost(compiled,
                                                   "fluid.Executor")
                call = compiled
            except Exception as e:
                roofline.cost_failure("fluid.Executor lower/compile", e)
                call = self._jitted
            self._call = call
        try:
            return call(feed, kept_vals, donated_vals)
        except TypeError as e:
            if call is self._jitted:
                raise
            # AOT argument strictness (weak types, committed devices) the
            # shape-keyed cache cannot see; the check fires BEFORE
            # dispatch, so donated buffers are intact and the jit retry
            # is safe
            obs.roofline.cost_failure("fluid.Executor (aot call)", e)
            self._call = self._jitted
            return self._jitted(feed, kept_vals, donated_vals)


#: consecutive compiled-fn cache misses before the executor warns that the
#: workload is shape-churning with no bucket spec (L006, analysis/lints.py)
_CHURN_STREAK = 4

#: default compiled-fn LRU capacity — generous (a cache entry is a traced
#: closure + XLA executable handle, not the HBM working set), but bounded so
#: unbucketed shape churn is a warning, not a slow leak
DEFAULT_CACHE_CAPACITY = 512


class Executor:
    """exe.run(program, feed=..., fetch_list=...) (fluid/executor.py:7-20).

    Hot-path contract (docs/design/executor_perf.md):

    * ``donate=True`` (default) hands persistables that the run overwrites
      (optimizer updates, BN stats) to XLA as donated buffers — the update
      happens in place, no second HBM copy per step.  A persistable that is
      also fetched (or fed) in the same run is automatically kept; pass
      ``donate=False`` (constructor or per-run) to opt out entirely.  After
      a donating run, previously-held references to the old parameter
      arrays are dead (``x.is_deleted()``) — re-read them from the scope.
    * Persistables live in the scope as **device arrays** between runs;
      ``run(..., return_numpy=False)`` returns jax arrays without blocking
      the host, so a training loop only syncs where it reads values.
    * ``buckets=...`` (a :class:`~paddle_tpu.data.feeder.BucketSpec` or its
      dict form) pads designated feed axes up to a bounded set of shapes so
      the compiled-fn cache is keyed on bucket shapes; the true length is
      fed alongside as ``<name>@LEN``.
    * The compiled-fn cache is a bounded LRU (``cache_capacity``).

    Sharding contract (docs/design/spmd.md): ``mesh=`` (a
    ``jax.sharding.Mesh``, defaulting to the ambient
    :func:`paddle_tpu.parallel.use_mesh`) makes the executor compile every
    program through ``jax.jit(..., in_shardings=..., out_shardings=...)``.
    Each persistable's ``PartitionSpec`` resolves through ``layout`` (a
    :class:`paddle_tpu.parallel.SpecLayout`; annotation > layout rule >
    replicated), parameters are *placed* sharded the first time the mesh
    executor touches them (init, load, checkpoint restore) and stay
    sharded in the device-resident scope across runs; feeds shard their
    batch dim over the ``data`` axis unless annotated otherwise. Sharding
    specs join the compiled-fn cache key, and donation keeps aliasing the
    sharded buffers in place.
    """

    def __init__(self, place=None, scope: Optional[Scope] = None, *,
                 donate: bool = True,
                 buckets: Optional[Any] = None,
                 mesh: Optional[Any] = None,
                 layout: Optional[Any] = None,
                 cache_capacity: int = DEFAULT_CACHE_CAPACITY):
        self.place = place
        self.scope = scope if scope is not None else global_scope()
        self.donate = donate
        if mesh is None:
            from ..parallel.mesh import current_mesh
            mesh = current_mesh()
        if layout is not None and mesh is None:
            raise ValueError(
                "Executor(layout=...) needs a mesh: pass mesh=... or "
                "construct inside parallel.use_mesh(...)")
        if mesh is not None and layout is None:
            from ..parallel.sharding import SpecLayout
            layout = SpecLayout()
        self.mesh = mesh
        self.layout = layout
        # device identity joins the cache key: a compiled executable is
        # pinned to its device assignment
        self._mesh_sig = (tuple(mesh.shape.items()),
                          tuple(int(d.id) for d in mesh.devices.flat)) \
            if mesh is not None else None
        self._mesh_stats_emitted = False
        # resolved-sharding memo (specs are a pure function of program +
        # mesh + layout + arg shapes): a steady-state training loop must
        # not re-walk the layout's rule table per persistable per step
        self._shard_memo: Dict[Tuple, Tuple] = {}
        if buckets is not None and not isinstance(buckets, BucketSpec):
            buckets = BucketSpec(buckets)
        self.buckets: Optional[BucketSpec] = buckets
        if cache_capacity < 1:
            raise ValueError("cache_capacity must be >= 1")
        self.cache_capacity = cache_capacity
        self._cache: "OrderedDict[Tuple, Any]" = OrderedDict()
        self._verified: set = set()   # analysis pre-flights already passed
        self._step = 0   # feeds the implicit '__step__' var (stochastic ops)
        # L006 shape-churn heuristic: consecutive never-seen-key misses per
        # (program, block, fetch) signature — keyed so first-runs of
        # DIFFERENT programs (startup + train + eval) never sum to a
        # streak, with a seen-key set so LRU-eviction thrash over a
        # BOUNDED shape family (which bucketing can't improve) doesn't
        # count as churn either
        self._miss_streaks: Dict[Tuple, int] = {}
        self._seen_keys: set = set()
        self._churn_warned = False
        # L011 donation-safety: statically-proven-hazardous persistables
        # per (program serial, version) — their donation is downgraded to
        # keep (see _run), warned once per program
        self._hazard_memo: Dict[Tuple, frozenset] = {}
        self._hazard_warned: set = set()

    # ------------------------------------------------------------------
    def run(self, program: Optional[Program] = None,
            feed: Optional[Dict[str, Any]] = None,
            fetch_list: Optional[Sequence] = None,
            use_cache: bool = True, verify: bool = False,
            return_numpy: bool = True,
            donate: Optional[bool] = None) -> List[Any]:
        with obs.span("fluid.run", metric="fluid.run_seconds"):
            return self._run(program, feed, fetch_list, use_cache, verify,
                             return_numpy, donate)

    # ------------------------------------------------------------------
    def _default_bucket_axis(self, block: Block, name: str,
                             ndim: int) -> Optional[int]:
        """Axis to bucket when the spec doesn't pin one: the feed Variable's
        declared ``bucket_axis``, else its first dynamic (-1) non-batch dim
        (layers.data marks the batch dim -1 at axis 0; a second -1 is the
        variable-length axis). A declared feed with NO dynamic non-batch
        dim is an error — silently guessing an axis would pad a static
        feature dim and surface as a distant shape mismatch inside the
        traced program."""
        v = block.vars.get(name)
        if v is not None:
            if getattr(v, "bucket_axis", None) is not None:
                return v.bucket_axis
            dyn = [i for i, s in enumerate(v.shape) if i > 0 and s == -1]
            if dyn and dyn[0] < ndim:
                return dyn[0]
            if ndim >= 2:
                raise ValueError(
                    f"cannot infer a bucket axis for feed '{name}': its "
                    f"declared shape {v.shape} has no dynamic (-1) non-batch "
                    "dim; pin one in the spec "
                    f"(buckets={{'{name}': {{'axis': A, 'buckets': (...)}}}}) "
                    "or declare layers.data(..., bucket_axis=A)")
        return None

    def _apply_buckets(self, feed: Dict[str, Any], block: Block) -> bool:
        """Pad spec'd feeds in place; True when any feed was bucketed."""
        applied = False
        for name in self.buckets.names():
            if name not in feed:
                continue
            arr = feed[name]
            if not hasattr(arr, "shape"):
                arr = np.asarray(arr)
            default_axis = None
            if self.buckets.pinned_axis(name) is None:
                default_axis = self._default_bucket_axis(block, name,
                                                         arr.ndim)
            padded, true_len = self.buckets.pad(name, arr, default_axis)
            feed[name] = padded
            # the true extent rides along so masked ops can ignore the pad
            # tail; scalar shape — it never perturbs the cache key
            feed[name + "@LEN"] = np.int32(true_len)
            applied = True
        return applied

    def _maybe_warn_churn(self, streak: int):
        """L006 shape-churn: a streak of never-seen-before cache keys for
        ONE (program, fetch) signature means every distinct feed shape is
        paying a fresh trace + XLA compile (warns once per executor; lint
        id in analysis/lints.py). Fires with a partial BucketSpec too —
        a spec that misses the churning feed doesn't bound anything — but
        the threshold then grows by the spec's own shape-family size, so a
        covering spec legitimately warming one compile per bucket never
        trips it."""
        threshold = _CHURN_STREAK
        if self.buckets is not None:
            threshold += sum(len(b) + 1            # +1: pow-2 overflow shape
                             for _, b in self.buckets.spec.values())
        if self._churn_warned or streak < threshold:
            return
        self._churn_warned = True
        fix = ("pass Executor(buckets={'<feed>': (32, 64, ...)})"
               if self.buckets is None else
               "extend the BucketSpec to cover the still-varying feed(s)")
        warnings.warn(
            f"L006 shape-churn: {streak} consecutive compiled-fn cache "
            "misses for the same program — each distinct feed shape pays a "
            f"fresh trace and XLA compile. If feeds vary in length, {fix} "
            "to pad onto a bounded shape family "
            "(docs/design/executor_perf.md).",
            RuntimeWarning, stacklevel=4)

    # -------------------------------------------------- sharding plane ----
    def _annotation(self, block: Block, name: str):
        """The variable's ``sharding`` annotation; optimizer accumulators
        (``param@moment1``) inherit their base parameter's annotation —
        slot layouts must follow the parameter or the update op pays a
        reshard every step."""
        v = block.vars.get(name)
        ann = getattr(v, "sharding", None) if v is not None else None
        if ann is None and "@" in name:
            base = block.vars.get(name.split("@", 1)[0])
            ann = getattr(base, "sharding", None) if base is not None else None
        return ann

    def _persist_sharding(self, block: Block, name: str, value):
        return self.layout.resolve(self.mesh, name, np.shape(value),
                                   self._annotation(block, name))

    def _feed_sharding(self, block: Block, name: str, value):
        """Feeds: annotation wins; a fed persistable resolves like a
        parameter; plain data shards its batch dim over ``data``."""
        shape = np.shape(value)
        ann = self._annotation(block, name)
        v = block.vars.get(name)
        if ann is None and v is not None and v.persistable:
            return self._persist_sharding(block, name, value)
        if ann is not None:
            return self.layout.resolve(self.mesh, name, shape, ann)
        from jax.sharding import NamedSharding
        spec = type(self.layout).fit(self.mesh,
                                     self.layout.batch_spec(len(shape)),
                                     shape)
        return NamedSharding(self.mesh, spec)

    def _place_persistables(self, persist_in, spec_of) -> None:
        """Move scope values whose live sharding differs from the resolved
        layout (host arrays from a startup program / checkpoint restore,
        or arrays placed for a previous mesh) onto the mesh — the
        init/load-time sharded placement of the GSPMD plane."""
        placed = 0
        for n in persist_in:
            cur = self.scope.get(n)
            target = spec_of[n]
            if getattr(cur, "sharding", None) == target:
                continue
            new = jax.device_put(cur, target)
            self.scope.set(n, new)
            placed += int(getattr(new, "nbytes", 0))
        if placed:
            obs.count("fluid.placed_bytes_total", placed)
            self._mesh_stats_emitted = False
        if not self._mesh_stats_emitted and obs.is_active():
            self._emit_mesh_stats(persist_in, spec_of)
            self._mesh_stats_emitted = True

    def _emit_mesh_stats(self, persist_in, spec_of) -> None:
        """Per-axis utilization through the obs plane: how much of the
        persistable footprint each mesh axis actually divides, and the
        per-device parameter bytes the layout achieves."""
        total = per_device = 0
        by_axis: Dict[str, int] = {a: 0 for a in self.mesh.shape}
        for n in persist_in:
            v = self.scope.get(n)
            nbytes = int(getattr(v, "nbytes", 0))
            total += nbytes
            ways = 1
            for entry in spec_of[n].spec:
                axes = ((entry,) if isinstance(entry, str)
                        else tuple(entry or ()))
                for a in axes:
                    by_axis[a] += nbytes
                    ways *= self.mesh.shape[a]
            per_device += nbytes // ways
        for a, size in self.mesh.shape.items():
            obs.gauge_set("mesh.axis_size", size, axis=a)
            obs.gauge_set("mesh.axis_utilization",
                          (by_axis[a] / total) if total else 0.0, axis=a)
        obs.gauge_set("fluid.param_bytes_per_device", per_device)
        obs.gauge_set("fluid.param_bytes_global", total)

    def _run(self, program, feed, fetch_list, use_cache, verify,
             return_numpy=True, donate=None):
        from .framework import default_main_program
        program = program or default_main_program()
        block = program.global_block()
        feed = dict(feed or {})
        bucketed = self.buckets is not None and self._apply_buckets(feed,
                                                                    block)
        # weak_type rides the cache key (below) instead of being stripped
        # from the value: a python-scalar feed keeps jit's exact promotion
        # semantics (weak f32 * bf16 -> bf16), and the AOT-compiled entries
        # (cost ledger) never see a weak/strong aval mismatch because the
        # weak and strong variants compile separate entries — the same
        # retrace jit itself would do
        feed = {k: jnp.asarray(v) for k, v in feed.items()}
        # anything with a .name (Variable, v2 LayerOutput) or a plain string
        fetch_names = [v if isinstance(v, str) else v.name
                       for v in (fetch_list or [])]
        if "__step__" in block.vars and "__step__" not in feed:
            feed["__step__"] = jnp.asarray(self._step, jnp.int32)
            self._step += 1
        donate = self.donate if donate is None else donate
        if verify:
            # static pre-flight: reject malformed programs with precise
            # Diagnostics BEFORE burning a trace/compile (analysis subpackage).
            # Memoized like the compiled-fn cache so a training loop pays the
            # analysis once per (program version, feed signature), not per step.
            # The donation switch rides along: with donate on, a provable
            # read-after-donate hazard (L011) is an ERROR this pre-flight
            # refuses instead of letting the run consume a donated buffer.
            from .. import analysis
            vkey = (program._serial, program.version, tuple(fetch_names),
                    bool(donate),
                    tuple((k, v.shape, str(v.dtype))
                          for k, v in sorted(feed.items())))
            if vkey not in self._verified:
                with obs.span("fluid.verify", metric="fluid.verify_seconds"):
                    analysis.check_or_raise(program, feed=feed,
                                            fetch=fetch_names,
                                            donate=bool(donate))
                self._verified.add(vkey)

        # vars the block reads from the scope (persistables created earlier)
        # — minus any the caller feeds this run: the fed value must WIN
        # (and the scope copy would otherwise ride to the device as a dead
        # argument only to be shadowed, or worse, shadow the feed)
        persist_in = [name for name, v in block.vars.items()
                      if v.persistable and name not in feed
                      and self.scope.has(name)]
        # persistable vars written by ops (optimizer updates, BN stats) synced
        # back after the run — including writes inside control-flow sub-blocks
        # (those values flow to env via the loop carry; they must also be
        # listed here or the scope silently keeps the stale value)
        top_written = {n for op in block.ops for n in op.output_vars()}
        written = list(dict.fromkeys(
            n for n in self._written_vars(program, block)
            if n in block.vars and block.vars[n].persistable))
        # a persistable written ONLY in a sub-block must already have a value
        # (scope or feed): the loop carry is derived from pre-existing env
        # entries, so an uninitialized one would be silently dropped
        for n in written:
            if n not in top_written and n not in feed and not self.scope.has(n):
                raise ValueError(
                    f"persistable '{n}' is written inside a control-flow "
                    "sub-block but has no initial value; initialize it in the "
                    "scope (or a startup program) first")

        # donation split, decided from desc-level facts so it is a pure
        # function of the cache key: a persistable the run overwrites is
        # donated to XLA (updated in place) UNLESS the same run also
        # fetches it — that needs the old buffer readable (fed persistables
        # never reach persist_in at all; the fed value wins)
        written_set, fetch_set = set(written), set(fetch_names)
        donated_in = [n for n in persist_in
                      if donate and n in written_set
                      and n not in fetch_set]
        # L011 donation-safety: a persistable whose pre-update value may
        # still be read after its overwrite (proved by the dataflow plane)
        # is downgraded to keep instead of donated — correctness beats the
        # buffer reuse.  verify=True already refused such programs above;
        # this protects verify=False runs.  Memoized per program version.
        if donated_in:
            hkey = (program._serial, program.version)
            hz = self._hazard_memo.get(hkey)
            if hz is None:
                from ..analysis.dataflow import donation_hazards
                hz = frozenset(h.name for h in donation_hazards(
                    program, feed=feed, fetch=fetch_names))
                self._hazard_memo[hkey] = hz
            hazardous = [n for n in donated_in if n in hz]
            if hazardous:
                donated_in = [n for n in donated_in if n not in hz]
                if hkey not in self._hazard_warned:
                    self._hazard_warned.add(hkey)
                    warnings.warn(
                        "L011 donation-hazard: persistable(s) "
                        f"{sorted(hazardous)} may be read after their "
                        "in-place update; donation downgraded to keep for "
                        "them (run with verify=True for the full def-use "
                        "chain)", RuntimeWarning, stacklevel=3)
        donated_set = set(donated_in)
        kept_in = [n for n in persist_in if n not in donated_set]

        # mesh path: resolve every argument's sharding, place scope
        # persistables, and extend the cache key with the resolved specs.
        # Resolution is memoized per (program version, args signature) —
        # it is a pure function of program + mesh + layout, and the rule-
        # table regex walk must not run per persistable per hot-loop step
        shardings = None
        if self.mesh is not None:
            skey = (program._serial, program.version, block.idx,
                    tuple(persist_in), tuple(written),
                    tuple((k, v.shape, str(v.dtype))
                          for k, v in sorted(feed.items())))
            memo = self._shard_memo.get(skey)
            if memo is None:
                feed_sh = {k: self._feed_sharding(block, k, v)
                           for k, v in feed.items()}
                spec_of = {n: self._persist_sharding(block, n,
                                                     self.scope.get(n))
                           for n in persist_in}
                from jax.sharding import NamedSharding, PartitionSpec
                replicated = NamedSharding(self.mesh, PartitionSpec())
                out_sh = [spec_of.get(n) or feed_sh.get(n) or replicated
                          for n in written]
                mesh_key = (self._mesh_sig,
                            tuple(sorted((k, str(s.spec))
                                         for k, s in feed_sh.items())),
                            tuple((n, str(spec_of[n].spec))
                                  for n in persist_in))
                if len(self._shard_memo) > 1024:   # unbounded-churn cap
                    self._shard_memo.clear()
                memo = (feed_sh, spec_of, out_sh, replicated, mesh_key)
                self._shard_memo[skey] = memo
            feed_sh, spec_of, out_sh, replicated, mesh_key = memo
            self._place_persistables(persist_in, spec_of)
            shardings = (feed_sh, spec_of, out_sh, replicated)
        else:
            mesh_key = None

        bflag = "true" if bucketed else "false"
        key = (program._serial, program.version, block.idx, tuple(fetch_names),
               tuple(persist_in), bool(donate), mesh_key,
               tuple((k, v.shape, str(v.dtype),
                      bool(getattr(v, "weak_type", False)))
                     for k, v in sorted(feed.items())))
        fn = self._cache.get(key) if use_cache else None
        obs.count("fluid.runs_total")
        churn_key = (program._serial, block.idx, tuple(fetch_names))
        if fn is None:
            # a miss pays the trace (+ XLA compile on first call)
            obs.count("fluid.cache_misses_total", bucketed=bflag)
            # deliberate use_cache=False runs and re-compiles of a key the
            # LRU evicted (a bounded shape family thrashing a small cache)
            # are not shape churn
            if use_cache and key not in self._seen_keys:
                if len(self._seen_keys) > 4096:     # unbounded-churn cap
                    self._seen_keys.clear()
                self._seen_keys.add(key)
                if len(self._miss_streaks) > 64:    # stale program signatures
                    self._miss_streaks.clear()
                streak = self._miss_streaks.get(churn_key, 0) + 1
                self._miss_streaks[churn_key] = streak
                self._maybe_warn_churn(streak)
            fn = self._build(program, block, list(feed), kept_in, donated_in,
                             fetch_names, written, shardings)
            if use_cache:
                self._cache[key] = fn
                while len(self._cache) > self.cache_capacity:
                    self._cache.popitem(last=False)   # evict the LRU entry
                    obs.count("fluid.cache_evictions_total")
        else:
            obs.count("fluid.cache_hits_total", bucketed=bflag)
            self._miss_streaks[churn_key] = 0
            self._cache.move_to_end(key)
        if use_cache:
            obs.gauge_set("fluid.cache_size", len(self._cache))
        kept_vals = [self.scope.get(n) for n in kept_in]
        donated_vals = [self.scope.get(n) for n in donated_in]
        if donated_in and obs.is_active():
            obs.count("fluid.donated_bytes_total",
                      sum(getattr(v, "nbytes", 0) for v in donated_vals))
        try:
            fetches, new_persist = fn(feed, kept_vals, donated_vals)
        except Exception:
            # a failure AFTER dispatch (e.g. jax_debug_nans) has already
            # invalidated the donated inputs but never produced outputs to
            # sync back — the scope now maps those names to dead buffers.
            # Say so here, where the cause is known; the next run would
            # otherwise fail with an anonymous 'Array has been deleted'.
            dead = [n for n, v in zip(donated_in, donated_vals)
                    if getattr(v, "is_deleted", lambda: False)()]
            if dead:
                warnings.warn(
                    f"Executor.run failed after donating {len(dead)} "
                    f"persistable buffer(s) ({dead[:4]}...): their scope "
                    "values are invalidated — reload them (startup program "
                    "/ load_persistables / checkpoint) before the next run, "
                    "or use donate=False while debugging.",
                    RuntimeWarning, stacklevel=3)
            raise
        # device cost ledger — AFTER the dispatch try/except: telemetry
        # must never discard a successful run's fetches or dress its own
        # failure up as the donated-buffer post-dispatch warning. No-op
        # when the plane is off or the analysis resolved to None.
        cost = getattr(fn, "cost", None)
        kb = getattr(fn, "kernel_bytes", None)
        if (cost is not None or kb) and obs.is_active():
            # Pallas launches inside the program are zero to XLA's
            # analysis: re-emit the trace-collected models once per run —
            # the same per-dispatch semantics as the decode sites
            obs.roofline.account(
                cost, extra_bytes=obs.roofline.emit_kernel_bytes(kb))
        for n, v in zip(written, new_persist):
            self.scope.set(n, v)
        if return_numpy:
            return [np.asarray(v) for v in fetches]
        return list(fetches)

    # ------------------------------------------------------------------
    @staticmethod
    def _written_vars(program: Program, block: Block) -> List[str]:
        out: List[str] = []
        for op in block.ops:
            out.extend(op.output_vars())
            for key in ("sub_block_idx", "true_block_idx", "false_block_idx"):
                idx = op.attrs.get(key)
                if idx is not None:
                    out.extend(Executor._written_vars(program,
                                                      program.blocks[idx]))
        return out

    # ------------------------------------------------------------------
    def _build(self, program: Program, block: Block, feed_names, kept_in,
               donated_in, fetch_names, written, shardings=None):
        has_host_ops = any(op.type == "fill_init" for op in block.ops)

        def raw(feed: Dict[str, Any], kept_vals: List[Any],
                donated_vals: List[Any]):
            env: Dict[str, Any] = {}
            env.update(feed)
            env.update(dict(zip(kept_in, kept_vals)))
            env.update(dict(zip(donated_in, donated_vals)))
            ctx = TraceContext(program, dict(env))
            _trace_ops(block.ops, env, ctx)
            fetches = [env[n] for n in fetch_names]
            new_persist = [env.get(n) for n in written]
            return fetches, new_persist

        if has_host_ops:
            return raw  # startup programs run eagerly (host-side initializers)
        # every donated name is also written (enforced by the _run split), so
        # XLA aliases each donated input buffer with its updated output —
        # params/BN stats update in place instead of allocating a second copy
        donate_args = (2,) if donated_in else ()
        if shardings is None:
            return _CompiledEntry(jax.jit(raw, donate_argnums=donate_args))
        # GSPMD lowering: argument/result shardings pin the layout the
        # resolver chose; XLA's SPMD partitioner inserts the collectives.
        # Donated sharded buffers keep the same out-sharding, so the alias
        # holds and updates stay in place per shard. EVERY output sharding
        # is specified — fetches gather to replicated (the host reads them
        # anyway): donation pairs inputs to outputs by aval, and a
        # mesh-run with unspecified out_shardings mispairs a donated
        # shard with a fetch on this jax version (alias size mismatch).
        feed_sh, spec_of, out_sh, replicated = shardings
        in_shardings = (feed_sh,
                        [spec_of[n] for n in kept_in],
                        [spec_of[n] for n in donated_in])
        out_shardings = ([replicated] * len(fetch_names), out_sh)
        return _CompiledEntry(jax.jit(raw, in_shardings=in_shardings,
                                      out_shardings=out_shardings,
                                      donate_argnums=donate_args))
