"""mode ``train``: one process on the chip drives ``paddle_tpu.Trainer.train``
over a seeded reader — set-up steps, then the measured window, in ONE call on
ONE compiled step with its state.

The first ``check_steps`` steps are the output check: the plain reference
(chipbench/reference/gpt2.py) follows them from the same weights and batches
before the Trainer exists, and the Trainer's own losses, its first gradient
(read back from Adam's first moment after step 1) and its parameters' change
(after the last check step) are held to the reference's, worst leaf each.
"""

import json
import os
import statistics
import time

import numpy as np

from chipbench import device as dev
from chipbench import harness, trace_reduce, weights
from chipbench.reference import gpt2 as ref


def worst_leaf_gap(got, want):
    """Largest |got - want| over the leaves, each against the reference's
    norm of that leaf or of the median leaf, whichever is larger (some
    gradients are all but zero)."""
    floor = statistics.median(want)
    return max(abs(g - w) / max(w, floor) for g, w in zip(got, want))


def compare(readings, reference, limits):
    """The numbers compared, each beside its limit:
    [(name, value, limit, ok)]."""
    loss = max(abs(a - b) / abs(b) for a, b in
               zip(readings["losses"], reference["losses"]))
    rows = [("loss_rel_gap", loss, limits["loss_rel_gap"]),
            ("grad_norm_gap", worst_leaf_gap(readings["grad_norms"],
                                             reference["grad_norms"]),
             limits["grad_norm_gap"]),
            ("update_norm_gap", worst_leaf_gap(readings["update_norms"],
                                               reference["update_norms"]),
             limits["update_norm_gap"])]
    return [(n, v, lim, bool(np.isfinite(v) and v <= lim))
            for n, v, lim in rows]


class _Watch:
    """Stands in ``trainer._step``'s place for the check steps only: calls
    the compiled step unchanged and reads norms off what it returns."""

    def __init__(self, trainer, n_check, seed, shapes, b1, broken=None):
        self.trainer, self.inner = trainer, trainer._step
        self.n_check, self.seed, self.shapes, self.b1 = (n_check, seed,
                                                         shapes, b1)
        self.broken = broken
        self.calls = 0
        self.grad_norms = self.update_norms = None
        trainer._step = self

    def __getattr__(self, name):          # .ledger, .cost_of of the wrapped
        return getattr(self.inner, name)

    def __call__(self, params, opt_state, *batch):
        import jax
        res = self.inner(params, opt_state, *batch)
        if self.broken is not None:
            res = self.broken(res, self.calls)
        self.calls += 1
        if self.calls == 1:
            m = jax.tree_util.tree_map(
                lambda s: s["m"], res[1]["slots"],
                is_leaf=lambda t: isinstance(t, dict) and "m" in t)
            self.grad_norms = [float(x) / (1.0 - self.b1)
                               for x in ref.leaf_norms(m)]
        if self.calls == self.n_check:
            # the parameters the run started from, made again from the seed
            # (the step donated the first copy); held between two steps only
            start = weights.make(self.shapes, self.seed)
            self.update_norms = [float(x) for x in
                                 ref.leaf_diff_norms(res[0], start)]
            del start
            self.trainer._step = self.inner       # the window runs bare
        return res


def run(loaded, args, log=print, broken=None):
    """Returns the result dict (harness.result keys) of one run."""
    t_start = args.t_start
    cell, config, traffic = loaded["cell"], loaded["config"], loaded["traffic"]
    seed = harness.program_seed(args.seed)
    gen = harness.generator_for(loaded).batches(traffic, seed,
                                                config["vocab_size"])
    n_check, n_warm = cell["check_steps"], cell["warm_steps"]
    check_batches = [next(gen) for _ in range(n_check)]
    lr = cell["optimizer"]["learning_rate"]
    phases = [("start", t_start), ("check batches drawn", time.time())]

    # -- the reference first, in a process of its own, while this one has
    # not touched the chip ------------------------------------------------
    t_ref = time.time()
    answer = harness.run_reference(
        {"kind": "train", "config": config, "seed": seed, "lr": lr,
         "batches": [b.tolist() for b in check_batches],
         "control": (cell["control_operand"]
                     if os.environ.get("CHIPBENCH_CONTROL") else None)},
        args.work_dir, loaded["root"], args.rehearsal,
        cell["reference_timeout_s"])
    reference, control = answer["reference"], answer.get("control")
    ref_seconds = time.time() - t_ref
    log(f"reference: {n_check} steps in {answer['seconds']:.1f}s, "
        f"{ref_seconds:.1f}s with its process (not in setup_s); its own "
        f"device peak {answer['memory_peak_bytes']} bytes")

    phases.append(("reference, excluded", time.time()))
    import jax
    device, runtime_up_s = dev.start_runtime(args.rehearsal, cell["chips"])
    phases.append(("accelerator runtime up, excluded", time.time()))
    if answer["device"] != device:
        raise harness.BenchError(
            f"reference ran on {answer['device']}, the Trainer on {device}")
    import paddle_tpu
    from paddle_tpu import Trainer, obs
    from paddle_tpu.optimizer import Adam
    from paddle_tpu.trainer import event
    cache_dir = paddle_tpu.enable_compile_cache()
    phases.append(("import paddle_tpu", time.time()))
    compiles = dev.CompileLog().install()
    session = obs.ObsSession().install()
    log(f"device {device}; compile cache {cache_dir} "
        f"({len(os.listdir(cache_dir))} entries at start)")
    model, shapes = weights.model_and_shapes(config)
    params = weights.make(shapes, seed)
    phases.append(("weights dispatched", time.time()))

    # -- the program: one Trainer, one train() call ------------------------
    import jax.numpy as jnp

    def loss_fn(p, ids):
        p16 = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a,
            p)
        return model.loss(p16, ids)

    trainer = Trainer(loss_fn, Adam(lr))
    watch = _Watch(trainer, n_check, seed, shapes, trainer.opt.b1, broken)
    tokens_per_step = traffic["rows"] * traffic["seq_len"]
    st = {"losses": [], "stamps": [], "t_window": None, "t_end": None,
          "trace": None, "trace_dir": None, "trace_span": None}
    n_setup = n_check + n_warm
    trace_at = n_setup + cell["trace_skip_steps"]

    def reader():
        for b in check_batches:
            yield (b,)
        while True:
            now = time.time()
            if st["t_window"] is not None and \
                    now - st["t_window"] >= args.seconds:
                return
            yield (next(gen),)

    def handler(e):
        if not isinstance(e, event.EndIteration):
            return
        now = time.time()
        st["losses"].append(float(e.cost))
        st["stamps"].append(now)
        n = len(st["losses"])
        if n == n_setup:
            st["t_window"] = now
        if args.trace and n == trace_at:
            st["trace_dir"] = os.path.join(args.work_dir, "trace")
            st["trace_span"] = [time.time(), None]
            jax.profiler.start_trace(st["trace_dir"],
                                     profiler_options=dev.trace_options())
        if args.trace and n == trace_at + cell["trace_steps"]:
            jax.block_until_ready(e.cost)
            st["trace_span"][1] = time.time()
            jax.profiler.stop_trace()

    params, opt_state = trainer.train(reader, params, event_handler=handler,
                                      handle_signals=False)
    jax.block_until_ready(params)
    t_end = time.time()
    if st["trace_span"] and st["trace_span"][1] is None:
        st["trace_span"][1] = time.time()
        jax.profiler.stop_trace()
    peak = dev.memory_peak_bytes()

    losses = st["losses"]
    if st["t_window"] is None or len(losses) <= n_setup:
        raise harness.BenchError("the window saw no step")
    t_w = st["t_window"]
    n_steps = len(losses) - n_setup
    phases += [("step 1 (trace, cache load)", st["stamps"][0]),
               (f"steps 2-{n_setup}", t_w)]
    log("set-up phases: " + "; ".join(
        f"{name} {b - a:.2f}s" for (_, a), (name, b)
        in zip(phases, phases[1:])))
    log(f"accelerator runtime came up in {runtime_up_s:.2f}s (its own "
        "start: not in setup_s)")
    window_s = st["stamps"][-1] - t_w
    in_window = compiles.between(t_w, t_end)
    log(f"window: {n_steps} steps of {tokens_per_step} tokens in "
        f"{window_s:.3f}s; compilations inside the window: {len(in_window)}; "
        f"backend compile seconds in set-up: {compiles.seconds():.1f}; "
        + compiles.cache_line())
    log(f"device peak after the window {peak} bytes")

    readings = {"losses": losses[:n_check], "grad_norms": watch.grad_norms,
                "update_norms": watch.update_norms}
    rows = compare(readings, reference, cell["limits"])
    finite = bool(np.all(np.isfinite(losses)))
    rows.append(("nonfinite_losses", float(len(losses) - int(np.sum(
        np.isfinite(losses)))), 0.0, finite))
    rows.append(("compilations_in_window", float(len(in_window)), 0.0,
                 not in_window))
    for name, value, limit, ok in rows:
        log(f"compared {name} = {value:.6g}  limit {limit:.6g}  "
            f"{'ok' if ok else 'FAIL'}")
    if control is not None:
        for name, value, limit, ok in compare(control, reference,
                                              cell["limits"]):
            log(f"control[{cell['control_operand']}] {name} = {value:.6g}  "
                f"limit {limit:.6g}  {'passes' if ok else 'fails'}")
    log(f"losses of the check steps {losses[:n_check]} reference "
        f"{reference['losses']}")

    rate = n_steps * tokens_per_step / window_s
    if args.trace and trace_at > n_setup:
        # starting, stopping and writing the trace stalls the loop: a traced
        # run's rate (what train_mfu reads) is that of the steps BEFORE it
        rate = ((trace_at - n_setup) * tokens_per_step
                / (st["stamps"][trace_at - 1] - t_w))
        log(f"rate of the {trace_at - n_setup} steps before the trace: "
            f"{rate:.1f} tokens/s (whole traced window: "
            f"{n_steps * tokens_per_step / window_s:.1f})")
    values = {"train_tokens_per_s": rate,
              "setup_s": t_w - t_start - ref_seconds - runtime_up_s}
    ctx = {"mode": "train", "cell": cell, "config": config,
           "traffic": traffic, "device": device, "base": loaded["base"],
           "obs": session.dump(), "values": values,
           "tokens_per_step": tokens_per_step,
           "window": (t_w, st["stamps"][-1]), "trace": None}
    result_device = dict(device, memory_peak_bytes=peak)
    breakdown = None
    if args.trace and st["trace_dir"]:
        tr = trace_reduce.reduce_dir(st["trace_dir"], session.dump(),
                                     st["trace_span"][0])
        ctx["trace"] = tr
        if tr is not None:
            result_device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
            breakdown = tr["breakdown"]
            log(f"trace: {tr['summary']}")
    with open(os.path.join(args.work_dir, "train_obs.json"), "w") as f:
        json.dump(ctx["obs"], f)
    session.uninstall()
    return {"checks": rows, "attempted": n_steps, "failed": 0,
            "values": values, "ctx": ctx, "device": result_device,
            "breakdown": breakdown}
