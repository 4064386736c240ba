"""SGD training driver.

Re-provides the reference's two drivers as one:
* C++ Trainer: pass/batch loops, evaluator wiring, testing, gradient check,
  per-pass checkpoints (trainer/Trainer.cpp:265, TrainerInternal.cpp:66-172,
  Tester.cpp, ParamUtil.cpp:50-67, --job=train/test/checkgrad/time
  TrainerMain.cpp:54).
* Python v2 SGD: events to user callbacks, reader-driven batches
  (v2/trainer.py:124-202).

TPU-native: the batch step is ONE jitted function (forward+backward+update fused
by XLA; the reference's per-parameter update callback pipelining,
TrainerInternal.cpp:70-73, is recovered by XLA's latency-hiding scheduler); data
parallelism is the SPMD mesh (parallel/data_parallel.py), not trainer threads;
host-side prep overlaps via DoubleBuffer.
"""

from __future__ import annotations

import itertools
import signal
import threading
import time
from collections.abc import Mapping
from typing import Any, Callable, Dict, Iterable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import faults, obs
from ..obs.goodput import maybe_bucket
from ..data.prefetch import DoubleBuffer
from ..parallel.data_parallel import DataParallel
from ..utils.logging import get_logger
from ..utils.stats import StatSet
from . import event as EV
from .checkpoint import load_checkpoint, save_checkpoint
from .evaluator import EvaluatorGroup

log = get_logger(__name__)

_NONFINITE_POLICIES = ("raise", "skip", "halt", "off")


def _timed_input(batches, gp):
    """Yield from ``batches`` timing each pull into the goodput ledger's
    ``host_input`` bucket and a ``trainer.input`` span — the reader/feeder
    wait as the driver loop experiences it (prefetch overlap shows up as
    near-zero pulls)."""
    it = iter(batches)
    while True:
        with obs.span("trainer.input"), gp.bucket("host_input"):
            try:
                batch = next(it)
            except StopIteration:
                return
        yield batch


class _TrainStatsView(Mapping):
    """Read-only compatibility view of the legacy ``train_stats`` dict.

    The robustness counters moved to typed obs counters on the trainer's
    own registry (ISSUE 3); existing callers and tests keep reading the
    old keys through this Mapping. It is intentionally not writable —
    the counters are the single source of truth."""

    _KEYS = {"nonfinite_batches": "trainer.nonfinite_total",
             "skipped_batches": "trainer.skipped_total",
             "preemptions": "trainer.preemptions_total"}

    def __init__(self, registry: obs.MetricsRegistry):
        self._registry = registry

    def __getitem__(self, key: str) -> int:
        return int(self._registry.counter(self._KEYS[key]).get())

    def __iter__(self):
        return iter(self._KEYS)

    def __len__(self) -> int:
        return len(self._KEYS)

    def __repr__(self):
        return repr(dict(self))


class Trainer:
    """Drive (loss_fn, optimizer) over reader batches with events/evaluators.

    Args:
      loss_fn: (params, *batch) -> scalar loss.
      optimizer: paddle_tpu optimizer.
      mesh: optional jax Mesh -> SPMD data-parallel step over its 'data' axis.
      layout: optional :class:`paddle_tpu.parallel.SpecLayout` (or
        ShardingRules) resolving parameter paths to PartitionSpecs —
        params and optimizer slots shard across the mesh (fsdp/tp) instead
        of replicating, and checkpoint restore re-places them onto the
        current mesh via the same rules.
      outputs_fn: optional (params, *batch) -> dict of device metrics handed to
        evaluators (e.g. {'logits':..., 'labels':...}). Evaluated INSIDE the
        fused train step on the PRE-update parameters — the reference's
        semantics (TrainerInternal.cpp:144-148 evaluates the training
        forward's outputs, which precede the update) and one forward cheaper
        than a separate post-update pass.
      evaluators: EvaluatorGroup or list of Evaluators.
      output_dir: if set, save pass-%05d checkpoints (ParamUtil semantics).
      nan_guard: legacy on/off switch for the non-finite-loss check.
      on_nonfinite: what a non-finite loss does — "raise" (fail fast, the
        feenableexcept analog), "skip" (drop the batch's update, count it,
        warn), "halt" (drop the update, checkpoint the last finite state,
        then raise), or "off". Defaults to "raise" when nan_guard else
        "off".
      prefetch_timeout: watchdog on the prefetch DoubleBuffer — if no batch
        arrives within this many seconds, raise TimeoutError instead of
        hanging the pod (a stalled data source on a TPU slice otherwise
        wedges every chip behind the collective).
      metrics: injectable :class:`paddle_tpu.obs.MetricsRegistry` backing
        the robustness counters (``trainer.nonfinite_total`` etc.) and the
        ``train_stats`` compatibility view; a fresh per-trainer registry
        by default so parallel trainers don't share counts. Hot-path step
        metrics additionally flow to the installed obs session (zero-cost
        when none is).
    """

    def __init__(self, loss_fn: Callable, optimizer, *, mesh=None,
                 layout=None,
                 outputs_fn: Optional[Callable] = None,
                 evaluators=None, output_dir: Optional[str] = None,
                 prefetch: int = 2, log_period: int = 0,
                 param_stats_period: int = 0,
                 nan_guard: bool = True,
                 on_nonfinite: Optional[str] = None,
                 prefetch_timeout: Optional[float] = None,
                 metrics: Optional[obs.MetricsRegistry] = None):
        self.loss_fn = loss_fn
        self.opt = optimizer
        self.outputs_fn = jax.jit(outputs_fn) if outputs_fn is not None else None
        if evaluators is None:
            self.evaluators = EvaluatorGroup()
        elif isinstance(evaluators, EvaluatorGroup):
            self.evaluators = evaluators
        else:
            self.evaluators = EvaluatorGroup(*evaluators)
        self.output_dir = output_dir
        self.prefetch = prefetch
        self.log_period = log_period
        # --show_parameter_stats_period analog (TrainerInternal.cpp:80-87):
        # 0 = off; falls back to the global flag when unset
        if param_stats_period == 0:
            from ..utils.flags import FLAGS
            param_stats_period = FLAGS.show_parameter_stats_period
        self.param_stats_period = param_stats_period
        if on_nonfinite is None:
            on_nonfinite = "raise" if nan_guard else "off"
        if on_nonfinite not in _NONFINITE_POLICIES:
            raise ValueError(f"on_nonfinite must be one of "
                             f"{_NONFINITE_POLICIES}, got {on_nonfinite!r}")
        self.on_nonfinite = on_nonfinite
        self.nan_guard = on_nonfinite != "off"
        self.prefetch_timeout = prefetch_timeout
        self.stats = StatSet()
        #: typed robustness counters (trainer.* catalogue names)
        self.metrics = metrics if metrics is not None else \
            obs.MetricsRegistry()
        #: legacy read-only view over the counters (ISSUE 3 compat)
        self.train_stats: Mapping = _TrainStatsView(self.metrics)
        # hot-path counters bound once: the per-batch cost is one locked
        # float add on the trainer's own registry (the obs session mirror
        # stays gated on is_active)
        self._c_steps = self.metrics.counter("trainer.steps_total")
        self._c_examples = self.metrics.counter("trainer.examples_total")
        self._preempt = threading.Event()
        self.preempted = False
        # skip AND halt both need the update dropped on a non-finite loss:
        # skip to continue from the last finite state, halt to checkpoint it
        # (checkpointing the NaN-poisoned trees would make resume start from
        # garbage — worse than no checkpoint at all)
        guard_mode = on_nonfinite in ("skip", "halt")
        if layout is not None and mesh is None:
            from ..parallel.mesh import current_mesh
            mesh = current_mesh()
            if mesh is None:
                raise ValueError("Trainer(layout=...) needs mesh=... or an "
                                 "enclosing parallel.use_mesh(...)")
        self.mesh = mesh
        self.layout = layout
        if mesh is not None:
            # the revert needs the pre-update trees alive after the step,
            # so buffer donation is off on that path
            self._dp = DataParallel(loss_fn, optimizer, mesh=mesh,
                                    param_rules=layout,
                                    aux_fn=outputs_fn, donate=not guard_mode)
            self._step = None
        else:
            self._dp = None

            def _step(params, opt_state, *batch):
                loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
                # eval outputs computed inside the SAME jitted step (XLA
                # shares the forward) — no second per-batch forward dispatch
                outs = outputs_fn(params, *batch) if outputs_fn else None
                new_params, new_opt = optimizer.update(grads, opt_state,
                                                       params)
                if guard_mode:
                    # drop-the-batch INSIDE the jitted step: select the
                    # pre-update trees when the loss is non-finite — donation
                    # stays legal because the select reads both operands
                    ok = jnp.isfinite(loss)
                    new_params = jax.tree_util.tree_map(
                        lambda n, o: jnp.where(ok, n, o), new_params, params)
                    new_opt = jax.tree_util.tree_map(
                        lambda n, o: jnp.where(ok, n, o), new_opt, opt_state)
                if outputs_fn is not None:
                    return new_params, new_opt, loss, outs
                return new_params, new_opt, loss

            # cost-instrumented jit: first call per batch signature AOT-
            # compiles and records FLOPs/bytes in the roofline ledger, so
            # a training run under an obs session accumulates
            # fluid.device_flops_total and the derived roofline.mfu gauge
            # as a byproduct of just running
            self._step = obs.roofline.instrument(
                jax.jit(_step, donate_argnums=(0, 1)), "trainer.step")
        self._loss_jit = jax.jit(loss_fn)

    # ------------------------------------------------------------------ train
    def _log_param_stats(self, params):
        """Per-parameter magnitude dump — the --show_parameter_stats_period
        observability of TrainerInternal.cpp:80-87,156 (value stats; grads
        are not retained past the fused update step)."""
        from ..nn.module import Module
        for name, value in Module.named_parameters(jax.device_get(params)):
            a = np.abs(np.asarray(value, np.float32))
            log.info("param %-40s shape=%-16s absmax=%.4e absmean=%.4e",
                     name, str(tuple(a.shape)), float(a.max(initial=0.0)),
                     float(a.mean()) if a.size else 0.0)

    # -- preemption --------------------------------------------------------
    def request_preemption(self):
        """Ask the train loop to checkpoint and exit after the current batch
        — what the SIGTERM/SIGINT handlers call; safe from any thread."""
        self._preempt.set()

    def _install_preemption_handlers(self):
        """SIGTERM/SIGINT -> checkpoint-then-exit. On a TPU pod preemption
        is the COMMON case (maintenance events deliver SIGTERM), not the
        exception. A SECOND SIGINT raises KeyboardInterrupt — a batch hung
        inside a wedged step/collective never reaches the between-batch
        preemption check, and Ctrl-C must still offer an escape. Returns
        the previous handlers for restoration; no-op off the main thread
        (signal.signal would raise)."""

        def handler(signum, frame):
            if signum == signal.SIGINT and self._preempt.is_set():
                raise KeyboardInterrupt
            self.request_preemption()

        prev = {}
        try:
            for sig in (signal.SIGTERM, signal.SIGINT):
                prev[sig] = signal.signal(sig, handler)
        except ValueError:
            pass
        return prev

    def _mirror(self, name: str, n: float = 1) -> None:
        """Mirror a count into the installed obs session — unless the
        session shares this trainer's registry (Trainer(metrics=
        obs.REGISTRY) under a default session), where mirroring would
        double-count."""
        s = obs.session()
        if s is not None and s.registry is not self.metrics:
            s.registry.counter(name).inc(n)

    def _count(self, name: str, n: float = 1) -> None:
        """Robustness counter: the trainer's own registry is the always-on
        source of truth (train_stats view); the session gets a mirror so
        exports include it."""
        self.metrics.counter(name).inc(n)
        self._mirror(name, n)

    def _checkpoint_preempted(self, pass_id, batch_id, params, opt_state):
        # the flight ring first (no-op unless armed): if the checkpoint
        # write itself dies, the post-mortem still shows the final batches
        obs.flight_dump("preemption")
        if self.output_dir:
            with obs.span("trainer.checkpoint", pass_id=pass_id,
                          reason="preemption"):
                save_checkpoint(self.output_dir, pass_id, params, opt_state,
                                extra={"pass_complete": False,
                                       "batch_id": batch_id})
            log.warning("preempted at pass %d batch %d: checkpoint saved; "
                        "resume re-runs this pass", pass_id, batch_id)
        else:
            log.warning("preempted at pass %d batch %d with no output_dir: "
                        "nothing durable to save", pass_id, batch_id)
        self._count("trainer.preemptions_total")
        self.preempted = True

    def _handle_nonfinite(self, cost_f, pass_id, batch_id, params, opt_state):
        self._count("trainer.nonfinite_total")
        if self.on_nonfinite == "skip":
            # the jitted step (or the host-side revert on the mesh path)
            # already dropped the update; account for it and move on
            self._count("trainer.skipped_total")
            log.warning("non-finite loss %s at pass %d batch %d: batch "
                        "skipped (%d skipped so far)", cost_f, pass_id,
                        batch_id, self.train_stats["skipped_batches"])
            return
        if self.on_nonfinite == "halt" and self.output_dir:
            # durable state first, then fail: params/opt_state were reverted
            # to the pre-update (last finite) trees, so the operator restarts
            # from the last finite step instead of losing the pass
            with obs.span("trainer.checkpoint", pass_id=pass_id,
                          reason="halt"):
                save_checkpoint(self.output_dir, pass_id, params, opt_state,
                                extra={"pass_complete": False,
                                       "batch_id": batch_id, "halted": True})
            log.error("non-finite loss at pass %d batch %d: state "
                      "checkpointed before halting", pass_id, batch_id)
        # the feenableexcept(FE_INVALID|DIVBYZERO|OVERFLOW) analog
        # (TrainerMain.cpp:49): fail fast, don't train on garbage
        raise FloatingPointError(
            f"non-finite loss {cost_f} at pass {pass_id} batch "
            f"{batch_id}; re-run with "
            f"jax.config.update('jax_debug_nans', True) to locate "
            f"the producing op")

    def train(self, reader: Callable[[], Iterable], params, *,
              num_passes: int = 1, event_handler: Optional[Callable] = None,
              feeder: Optional[Callable] = None,
              test_reader: Optional[Callable] = None,
              resume: bool = False, checkpoint_every: int = 1,
              handle_signals: bool = True):
        """Run the pass/batch loop; returns (params, opt_state).

        reader yields raw row-batches; ``feeder`` converts one row-batch to the
        loss_fn's *batch arrays (identity if None).

        ``resume=True`` restarts from the newest verifiable checkpoint. A
        pass checkpointed as incomplete (preemption/halt) resumes at its
        next batch: the checkpoint holds post-batch state, so the first
        ``batch_id + 1`` reader batches are skipped rather than re-applied —
        with a deterministic reader the continuation is byte-identical to an
        uninterrupted run. ``checkpoint_every=N`` saves every Nth pass (the
        final pass always saves); preemption checkpoints ignore the cadence.
        ``handle_signals`` installs SIGTERM/SIGINT checkpoint-then-exit
        handlers for the duration of the call (main thread only).
        """
        handler = event_handler or (lambda e: None)

        def event_handler(e):
            # the caller's code, on this thread, between two steps
            with obs.span("trainer.handler", event=type(e).__name__):
                handler(e)
        if checkpoint_every < 1:
            raise ValueError("checkpoint_every must be >= 1")
        start_pass = 0
        skip_batches = 0
        opt_state = None
        self.preempted = False
        self._preempt.clear()
        if resume and self.output_dir:
            # one load_checkpoint call does discovery + verification + read
            # in a single pass over the members; a dir with no verifiable
            # checkpoint falls through to fresh init
            try:
                params, opt_state, st = load_checkpoint(self.output_dir)
            except FileNotFoundError:
                st = None
                log.info("resume requested but no verifiable checkpoint "
                         "under %s; starting fresh", self.output_dir)
            if st is not None and st.get("pass_complete", True):
                start_pass = st["pass_id"] + 1
                log.info("resumed from completed pass %d", st["pass_id"])
            elif st is not None:
                # the preemption checkpoint holds state AFTER batch_id, so
                # the interrupted pass continues at batch_id + 1
                start_pass = st["pass_id"]
                skip_batches = st.get("batch_id", -1) + 1
                log.info("resumed preempted pass %d at batch %d",
                         st["pass_id"], skip_batches)
        if opt_state is None:
            if self._dp is not None:
                params, opt_state = self._dp.init(params)
            else:
                opt_state = self.opt.init(params)
        elif self._dp is not None:
            params, opt_state = self._dp.init(params, opt_state)

        prev_handlers = (self._install_preemption_handlers()
                         if handle_signals else {})
        # goodput ledger (None when the obs plane is off): splits this
        # call's wall time into compile / host_input / device / host_sync
        # / idle — goodput.*_seconds_total + the goodput.ratio gauge
        gp = obs.goodput.open_ledger("trainer")
        try:
            last_pass = start_pass + num_passes - 1
            for pass_id in range(start_pass, start_pass + num_passes):
              # pass-scoped trace span: reader RPC pulls, checkpoint saves
              # and every step nest under it on this thread (the Perfetto
              # trainer -> ckpt/rpc containment of docs/design/observability)
              with obs.span("trainer.pass", pass_id=pass_id):
                event_handler(EV.BeginPass(pass_id))
                self.evaluators.start()
                first_batch = skip_batches if pass_id == start_pass else 0
                batches = self._batches(reader, feeder, skip=first_batch)
                if gp is not None:
                    batches = _timed_input(batches, gp)
                for batch_id, batch in enumerate(batches, start=first_batch):
                    event_handler(EV.BeginIteration(pass_id, batch_id))
                    if (self.on_nonfinite in ("skip", "halt")
                            and self._dp is not None):
                        # mesh path: revert host-side (donation disabled)
                        prev_params, prev_opt = params, opt_state
                    with obs.span("trainer.step",
                                  metric="trainer.step_seconds"):
                        with self.stats.timer("TrainBatch"), \
                                obs.span("trainer.device_step"), \
                                maybe_bucket(gp, "device"):
                            with obs.span("trainer.dispatch"):
                                if self._dp is not None:
                                    batch = self._dp.shard_batch(batch)
                                    res = self._dp.step(params, opt_state,
                                                        *batch)
                                else:
                                    res = self._step(params, opt_state,
                                                     *batch)
                            if gp is not None:
                                # under async dispatch (TPU) the step's wall
                                # time surfaces at the FIRST host block — the
                                # bucket contract puts that block here, so
                                # block now rather than at float(cost) below
                                # (which would book device time as host_sync;
                                # nothing runs between dispatch and that sync,
                                # so this costs no overlap)
                                with obs.span("trainer.device_wait"):
                                    jax.block_until_ready(res)
                        # rebinding drops the last references to the
                        # previous step's (donated) arrays: hundreds of
                        # releases, on this thread, before the next dispatch
                        with obs.span("trainer.release"):
                            if self.outputs_fn is not None:
                                params, opt_state, cost, outs = res
                            else:
                                params, opt_state, cost = res
                                outs = None
                        with obs.span("trainer.host_sync",
                                      metric="trainer.sync_seconds"), \
                                maybe_bucket(gp, "host_sync"):
                            cost_f = faults.filter_value("step.grad",
                                                         float(cost))
                    self._c_steps.inc()
                    self._mirror("trainer.steps_total")
                    lead = (getattr(batch[0], "shape", None)
                            if isinstance(batch, (tuple, list)) and batch
                            else None)
                    if lead:
                        self._c_examples.inc(lead[0])
                        self._mirror("trainer.examples_total", lead[0])
                    if self.nan_guard and not np.isfinite(cost_f):
                        if (self.on_nonfinite in ("skip", "halt")
                                and self._dp is not None):
                            params, opt_state = prev_params, prev_opt
                        self._handle_nonfinite(cost_f, pass_id, batch_id,
                                               params, opt_state)
                        event_handler(EV.EndIteration(pass_id, batch_id,
                                                      cost_f, None))
                        if self._preempt.is_set():
                            self._checkpoint_preempted(pass_id, batch_id,
                                                       params, opt_state)
                            return params, opt_state
                        continue
                    ev_result = None
                    if outs is not None:
                        with self.stats.timer("Eval"):
                            self.evaluators.update(cost=cost_f, **outs)
                            ev_result = self.evaluators.result()
                    if self.log_period and (batch_id + 1) % self.log_period == 0:
                        log.info("pass %d batch %d cost %.6f", pass_id,
                                 batch_id, cost_f)
                    if (self.param_stats_period and
                            (batch_id + 1) % self.param_stats_period == 0):
                        self._log_param_stats(params)
                    event_handler(EV.EndIteration(pass_id, batch_id, cost_f,
                                                  ev_result))
                    if self._preempt.is_set():
                        self._checkpoint_preempted(pass_id, batch_id,
                                                   params, opt_state)
                        return params, opt_state
                pass_result = (self.evaluators.result()
                               if self.outputs_fn is not None else None)
                if test_reader is not None:
                    tr = self.test(test_reader, params, feeder=feeder)
                    event_handler(EV.TestResult(pass_id, tr["cost"],
                                                tr.get("evaluator_result")))
                if self.output_dir and (
                        (pass_id - start_pass + 1) % checkpoint_every == 0
                        or pass_id == last_pass):
                    with obs.span("trainer.checkpoint", pass_id=pass_id,
                                  reason="pass_end"):
                        save_checkpoint(self.output_dir, pass_id, params,
                                        opt_state)
                event_handler(EV.EndPass(pass_id, pass_result))
        finally:
            if gp is not None:
                gp.close()
            for sig, handler in prev_handlers.items():
                try:
                    signal.signal(sig, handler)
                except (ValueError, TypeError):
                    pass
        return params, opt_state

    def _batches(self, reader, feeder, skip: int = 0):
        if skip:
            # resume: slice the RAW reader, before the feeder transform —
            # re-running host-side conversion on thousands of about-to-be-
            # discarded batches would delay the restart by their full cost
            raw, reader = reader, (lambda: itertools.islice(raw(), skip,
                                                            None))
        if feeder is None and self.prefetch_timeout is None:
            return iter(reader())
        # a feeder wants the prefetch thread for overlap; a prefetch_timeout
        # needs it too — the watchdog only works with a producer thread to
        # watch, so the timeout must not be silently ignored without one
        return iter(DoubleBuffer(reader, depth=self.prefetch, transform=feeder,
                                 timeout=self.prefetch_timeout))

    # ---------------------------------------------------------------- summary
    def summary(self) -> str:
        """Operator-facing report: the trainer's typed counters plus
        immutable :class:`~paddle_tpu.utils.stats.StatSnapshot` rows —
        ``obs.summary`` subsumes the old ``StatSet.report()`` table."""
        return obs.summary({"metrics": self.metrics.collect()},
                           stats=self.stats.items().values())

    # ------------------------------------------------------------------- test
    def test(self, reader, params, *, feeder=None) -> Dict[str, Any]:
        """Average cost (+ evaluator results) over a test reader (Tester.cpp)."""
        total, n = 0.0, 0
        self.evaluators.start()
        for batch in self._batches(reader, feeder):
            cost = self._loss_jit(params, *batch)
            total += float(cost)
            n += 1
            if self.outputs_fn is not None:
                outs = self.outputs_fn(params, *batch)
                self.evaluators.update(cost=float(cost), **outs)
        out: Dict[str, Any] = {"cost": total / max(n, 1)}
        if self.outputs_fn is not None:
            out["evaluator_result"] = self.evaluators.result()
        return out

    # -------------------------------------------------------------- checkgrad
    def check_gradient(self, params, batch: Tuple, *, eps: float = 1e-3,
                       rtol: float = 5e-2, max_checks_per_param: int = 5,
                       seed: int = 0) -> bool:
        """Central-difference gradient check (--job=checkgrad,
        Trainer.h:84; LayerGradUtil perturbation semantics, SURVEY §4.1).
        Runs in float64 (enable_x64) — float32 losses don't resolve the
        perturbation; returns True when analytic and numeric agree."""
        import contextlib

        @contextlib.contextmanager
        def enable_x64():
            prev = jax.config.jax_enable_x64
            jax.config.update("jax_enable_x64", True)
            try:
                yield
            finally:
                jax.config.update("jax_enable_x64", prev)

        def to64(x):
            # one host transfer: device_get already yields ndarray (the old
            # np.asarray(jax.device_get(x)) chain materialized the leaf
            # twice). astype keeps its default copy — device_get can return
            # a READ-ONLY view, and the check loop below writes into these
            # leaves through p_host, so they must be owned writable copies
            x = jax.device_get(x)
            if not hasattr(x, "dtype"):
                x = np.asarray(x)
            return (x.astype(np.float64)
                    if np.issubdtype(x.dtype, np.floating) else x)

        with enable_x64():
            params64 = jax.tree_util.tree_map(to64, params)
            batch64 = jax.tree_util.tree_map(to64, batch)
            loss64 = jax.jit(self.loss_fn)
            grads = jax.jit(jax.grad(self.loss_fn))(params64, *batch64)
            leaves, treedef = jax.tree_util.tree_flatten(params64)
            gleaves = jax.tree_util.tree_leaves(grads)
            rs = np.random.RandomState(seed)
            ok = True
            for li, (p, g) in enumerate(zip(leaves, gleaves)):
                # host copies hoisted OUT of the perturbation loop: the old
                # code re-transferred the whole gradient leaf from device
                # once per checked index (np.asarray(device_get(g)) inside
                # the loop) — n_checks transfers where one suffices
                p_host = np.asarray(jax.device_get(p), np.float64)
                g_flat = np.asarray(jax.device_get(g),
                                    np.float64).reshape(-1)
                flat = p_host.reshape(-1)
                n_checks = min(max_checks_per_param, flat.size)
                for idx in rs.choice(flat.size, size=n_checks, replace=False):
                    orig = flat[idx]
                    vals = {}
                    for sign in (+1, -1):
                        flat[idx] = orig + sign * eps
                        leaves2 = list(leaves)
                        leaves2[li] = jnp.asarray(p_host)
                        vals[sign] = float(loss64(
                            jax.tree_util.tree_unflatten(treedef, leaves2),
                            *batch64))
                    flat[idx] = orig
                    numeric = (vals[+1] - vals[-1]) / (2 * eps)
                    analytic = float(g_flat[idx])
                    denom = max(abs(numeric), abs(analytic), 1e-6)
                    if abs(numeric - analytic) / denom > rtol:
                        log.warning("checkgrad mismatch leaf %d idx %d: "
                                    "numeric %.6g analytic %.6g", li, idx,
                                    numeric, analytic)
                        ok = False
        return ok

    # ------------------------------------------------------------------- time
    def benchmark(self, reader, params, *, feeder=None, warmup: int = 3,
                  iters: int = 20,
                  profile_dir: Optional[str] = None) -> Dict[str, float]:
        """--job=time analog (TrainerBenchmark.cpp): steady-state ms/batch.
        ``profile_dir`` wraps the timed loop in an XLA trace
        (utils/profiler — the hl_profiler_start/WITH_PROFILER analog)."""
        opt_state = self.opt.init(params) if self._dp is None else None
        if self._dp is not None:
            params, opt_state = self._dp.init(params)
        batches = list(self._batches(reader, feeder))
        if not batches:
            raise ValueError("empty reader")
        step = (self._step if self._dp is None
                else lambda p, s, *b: self._dp.step(p, s, *b))
        i = 0
        for _ in range(warmup):
            res = step(params, opt_state, *batches[i % len(batches)])
            params, opt_state, loss = res[0], res[1], res[2]
            i += 1
        jax.block_until_ready(loss)
        from ..utils import profiler as _prof
        import contextlib
        prof_cm = (_prof.profile(profile_dir) if profile_dir
                   else contextlib.nullcontext())
        with prof_cm:
            t0 = time.perf_counter()
            for _ in range(iters):
                with self.stats.timer("BenchBatch"):
                    res = step(params, opt_state, *batches[i % len(batches)])
                    params, opt_state, loss = res[0], res[1], res[2]
                i += 1
            jax.block_until_ready(loss)
            # timed INSIDE the profiler context: stop_trace() serialization
            # must not inflate the reported steady-state number
            ms = (time.perf_counter() - t0) / iters * 1e3
        return {"ms_per_batch": ms}
