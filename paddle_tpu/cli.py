"""Command-line driver — the `paddle` CLI analog.

Reference surface (paddle/scripts/submit_local.sh.in:3-16 + TrainerMain.cpp
job types): train / test / time / version / dump_config / merge_model — plus
``lint`` (static Program verification, paddle_tpu.analysis).

The config file is a Python script (the reference's config style,
config_parser.py executing user configs) that builds a model through the v2
or fluid front end and exposes module-level names:

    cost       — v2 LayerOutput or fluid Variable to minimize
    optimizer  — paddle_tpu.v2.optimizer.* (or fluid optimizer)
    train_reader() / test_reader() — batched reader creators
    feeding    — list of v2 data layers in row order (v2 configs)
    outputs    — optional list of layers to export for inference

Usage: python -m paddle_tpu train --config cfg.py --num_passes 2 --save_dir out
"""

from __future__ import annotations

import argparse
import json
import runpy
import sys
import time
from typing import Any, Dict


def _load_config(path: str) -> Dict[str, Any]:
    from . import fluid
    fluid.reset_default_programs()
    return runpy.run_path(path)


def _make_trainer(cfg):
    from . import v2
    cost = cfg["cost"]
    opt = cfg.get("optimizer") or v2.optimizer.SGD(0.01)
    if not hasattr(opt, "fluid_opt"):
        opt = type("O", (), {"fluid_opt": opt})()
    return v2.SGD(cost, opt)


def _parse_hostport(addr, default_host="127.0.0.1", default_port=0):
    """host:port with the `obs serve --master` validation discipline:
    bracket-stripped IPv6 literals, and None on anything malformed so the
    caller answers with a clear exit-2 instead of a ValueError traceback.
    Returns (host, port) or None."""
    if not addr:
        return default_host, default_port
    host, _, port = addr.rpartition(":")
    try:
        return (host.strip("[]") or default_host), int(port)
    except ValueError:
        return None


def _cmd_train_elastic(args):
    """``train --elastic master|worker`` — the elastic data-parallel mode
    (docs/design/elastic.md). The config script defines
    ``elastic_workload()`` returning ``{"loss_fn", "params", "optimizer",
    "batches"}`` (params/batches as host arrays; workers only need
    loss_fn)."""
    import runpy
    import signal
    import threading

    from .trainer.elastic import ElasticMaster, ElasticWorker
    cfg = runpy.run_path(args.config)
    wl_fn = cfg.get("elastic_workload")
    if not callable(wl_fn):
        print(f"error: --elastic needs the config to define "
              f"elastic_workload(); {args.config} does not", file=sys.stderr)
        return 2
    wl = wl_fn()
    parsed = _parse_hostport(args.master_addr)
    if parsed is None:
        print(f"error: --master_addr must be host:port, got "
              f"{args.master_addr!r}", file=sys.stderr)
        return 2
    host, port = parsed
    if args.elastic == "worker":
        if not args.master_addr or not port:
            print("error: --elastic worker needs --master_addr HOST:PORT",
                  file=sys.stderr)
            return 2
        worker = ElasticWorker(wl["loss_fn"], (host, port),
                               worker=args.worker_id)
        # drain-at-barrier (ISSUE 18): the fleet actor's subprocess
        # backend drains with SIGTERM — finish the in-flight shard, push
        # its gradient, leave membership, then exit, so a drained worker
        # never costs the step a discarded shard
        stop = threading.Event()
        try:
            signal.signal(signal.SIGTERM, lambda *_: stop.set())
            signal.signal(signal.SIGINT, lambda *_: stop.set())
        except ValueError:
            pass     # not the main thread (embedded runs): no handler
        summary = worker.run(stop=stop)
        print(f"elastic worker {summary['worker']} served "
              f"{summary['shards']} shard(s); job done: {summary['done']}")
        return 0 if summary["done"] else 2
    em = ElasticMaster(wl["loss_fn"], wl["optimizer"], host=host, port=port,
                       shards_per_step=args.shards_per_step,
                       min_workers=args.min_workers, ttl=args.heartbeat_ttl,
                       snapshot_dir=args.save_dir or None)
    em.start()
    completed = False
    try:
        print(f"ELASTIC MASTER {em.address[0]} {em.address[1]}", flush=True)
        params, _, loss = em.fit(wl["batches"], wl.get("params"),
                                 num_passes=args.num_passes)
        completed = True
        print(f"elastic training done: {args.num_passes} pass(es), "
              f"final loss {loss:.6f}, membership epoch "
              f"{em.membership.epoch}")
        if args.save_dir:
            print(f"state checkpoints under {args.save_dir}")
    finally:
        # drain only after a COMPLETED run: workers leave once they
        # observe the done signal, which a failed fit never sets — the
        # error path must surface the traceback now, not after 10s of
        # waiting for departures that cannot happen
        em.stop(drain_s=10.0 if completed else 0.0)
    return 0


def cmd_train(args):
    from .trainer import event
    if getattr(args, "elastic", None):
        return _cmd_train_elastic(args)
    # persistent compile cache BEFORE the config builds/compiles anything:
    # a preemption-resume of this same command re-loads its executables
    # from disk instead of re-paying the compiles
    from . import enable_compile_cache
    enable_compile_cache()
    cfg = _load_config(args.config)
    trainer = _make_trainer(cfg)
    costs = []

    def handler(e):
        if isinstance(e, event.EndIteration):
            costs.append(e.cost)
            if args.log_period and (e.batch_id + 1) % args.log_period == 0:
                print(f"pass {e.pass_id} batch {e.batch_id} cost {e.cost:.6f}")
        elif isinstance(e, event.EndPass):
            print(f"pass {e.pass_id} done; last cost "
                  f"{costs[-1] if costs else float('nan'):.6f}")
            if args.save_dir:
                import io
                import json as _json

                from .trainer.checkpoint import (FORMAT_VERSION,
                                                 publish_members)
                # the same tmp-dir + CRC manifest + atomic-rename protocol
                # as save_checkpoint: a crash mid-dump leaves no dir that
                # latest_pass would mistake for a checkpoint. state.json
                # rides along so load_checkpoint can read the dir, not
                # just verify it
                buf = io.BytesIO()
                trainer.parameters.to_tar(buf)
                state = _json.dumps({"pass_id": e.pass_id,
                                     "version": FORMAT_VERSION,
                                     "pass_complete": True}).encode()
                publish_members(args.save_dir, e.pass_id,
                                [("params.tar", buf.getvalue()),
                                 ("state.json", state)])

    train_reader = cfg["train_reader"]
    srv = None
    obs_session = None
    flight = None
    pusher = None
    if getattr(args, "obs_out", None):
        from . import obs as _obs
        obs_session = _obs.ObsSession().install()
        # crash flight recorder: until the clean save below runs, any
        # death mode (SIGTERM, injected fault, uncaught exception) leaves
        # the span ring + counter deltas at --obs_out for post-mortem
        flight = _obs.FlightRecorder(obs_session, args.obs_out).arm()
    if getattr(args, "local_master", False):
        # One-binary bring-up (TrainerMain.cpp:32-49 --start_pserver analog):
        # self-host the ENTIRE data-dispatch cluster in this process — the
        # native task master + its TCP service on a background thread, the
        # trainer as its first consumer. Same code paths as the real
        # multi-host deployment (chunk dump, get_task RPC, timeout
        # re-dispatch), zero extra processes: the local dev mode.
        import os
        import tempfile

        from .data.chunks import cloud_reader, dump_to_chunks
        from .runtime.master_service import MasterClient, MasterServer

        chunk_dir = (os.path.join(args.save_dir, "chunks") if args.save_dir
                     else tempfile.mkdtemp(prefix="paddle_tpu_chunks_"))
        os.makedirs(chunk_dir, exist_ok=True)
        paths = dump_to_chunks(train_reader, chunk_dir,
                               samples_per_chunk=args.samples_per_chunk)
        srv = MasterServer().start()
        client = MasterClient(*srv.address)
        client.set_dataset(paths)
        print(f"local master: {len(paths)} chunks on "
              f"{srv.address[0]}:{srv.address[1]}")
        train_reader = cloud_reader(client, new_pass_at_end=True)
        if obs_session is not None:
            # exercise the real cluster-telemetry path even in the one-
            # binary mode: this consumer obs_pushes its snapshots to the
            # in-process master exactly as a remote worker would. Own
            # fail-fast client: _call holds a per-client lock across its
            # retry budget, so sharing the data-plane client would let a
            # slow push stall the trainer's get_task behind it
            from .obs.aggregate import ObsPusher, telemetry_client
            pusher = ObsPusher(telemetry_client(*srv.address),
                               worker=f"local-{os.getpid()}",
                               interval=2.0).start()
    try:
        trainer.train(train_reader, num_passes=args.num_passes,
                      event_handler=handler, feeding=cfg.get("feeding"))
    finally:
        # dump FIRST: a failed run is exactly the one whose telemetry the
        # user asked for, and a server-teardown error must not discard it
        if pusher is not None:
            pusher.stop()
            pusher.client.close()
        if obs_session is not None:
            if flight is not None:
                # clean(ish) exit: the full session dump below supersedes
                # the ring; disarm so atexit can't overwrite it later
                flight.disarm()
            obs_session.uninstall()
            try:
                obs_session.save(args.obs_out)
            except Exception as e:
                # telemetry loss must not mask the training outcome
                print(f"warning: could not write obs dump {args.obs_out}: "
                      f"{type(e).__name__}: {e}", file=sys.stderr)
            else:
                print(f"observability dump written to {args.obs_out} "
                      f"(inspect: paddle_tpu obs summary --input "
                      f"{args.obs_out})")
        if srv is not None:
            srv.stop()
    if args.save_dir and "outputs" in cfg:
        from . import fluid
        fluid.io.export_inference_model(
            args.save_dir + "/inference",
            [dl.var.name for dl in cfg.get("feeding", [])],
            [o.var for o in cfg["outputs"]], trainer.exe)
    return 0


def cmd_test(args):
    cfg = _load_config(args.config)
    trainer = _make_trainer(cfg)
    if args.init_model_path:
        with open(args.init_model_path, "rb") as f:
            trainer.parameters.from_tar(f)
    res = trainer.test(cfg.get("test_reader", cfg["train_reader"]),
                       feeding=cfg.get("feeding"))
    print(json.dumps({"cost": res.cost}))
    return 0


def _config_workload(config_path, n_batches):
    """The shared --config training-step setup ``time`` and ``profile``
    drive: load the config, build its trainer, materialize up to
    ``n_batches`` reader batches, and close over feeder + fetch list.
    Returns ``(one, batches)`` where ``one(i)`` runs step *i* (batches
    recycle)."""
    cfg = _load_config(config_path)
    trainer = _make_trainer(cfg)
    batches = list(cfg["train_reader"]())[: max(n_batches, 1)]
    from .v2.trainer import _V2Feeder
    feeder = _V2Feeder(cfg["feeding"]) if cfg.get("feeding") else None
    fetch = [cfg["cost"].var]

    def one(i):
        rows = batches[i % len(batches)]
        trainer.exe.run(feed=feeder(rows) if feeder else rows,
                        fetch_list=fetch)
    return one, batches


def cmd_time(args):
    """--job=time analog (TrainerBenchmark.cpp): steady-state ms/batch."""
    one, _ = _config_workload(args.config, args.iters + args.warmup)
    i = 0
    for _ in range(args.warmup):
        one(i)
        i += 1
    t0 = time.perf_counter()
    for _ in range(args.iters):
        one(i)
        i += 1
    ms = (time.perf_counter() - t0) / args.iters * 1e3
    print(json.dumps({"ms_per_batch": round(ms, 3)}))
    return 0


def cmd_profile(args):
    """``paddle_tpu profile`` — run N profiled steps of a workload under
    ``jax.profiler.trace`` and print the top-k per-op device-time report,
    HLO ops attributed back to the analysis plane's ``block B, op #I
    (type)`` sites (the fluid Executor's named-scope stamps, inverted by
    obs/xplane.py).

    Workloads: ``--config cfg.py`` profiles the config's training step
    (the ``time`` command's loop, traced); ``--decode B,PROMPT,NEW``
    profiles a fused-decode serve workload on a randomly-initialized
    TransformerLM built from the model flags + ``--seed``.

    Warmup steps run before the trace so compiles stay out of the
    profile. The raw ``.xplane.pb`` path prints at the end — feed it to
    ``paddle_tpu obs export --xplane`` to merge the device lanes into a
    host-span Perfetto timeline.
    """
    import glob
    import os
    import tempfile

    import jax

    if not args.config and not args.decode:
        print("profile: pass --config cfg.py or --decode B,PROMPT,NEW",
              file=sys.stderr)
        return 2
    if args.config:
        one, batches = _config_workload(args.config,
                                        args.steps + args.warmup)
        if not batches:
            print(f"profile: {args.config!r} train_reader yielded no "
                  "batches — nothing to profile", file=sys.stderr)
            return 2
    else:
        try:
            b, prompt_len, new = (int(x) for x in args.decode.split(","))
        except ValueError:
            print(f"profile: --decode must be B,PROMPT,NEW integers, got "
                  f"{args.decode!r}", file=sys.stderr)
            return 2
        from .models import TransformerLM
        model = TransformerLM(args.vocab, d_model=args.d_model,
                              n_heads=args.n_heads, n_layers=args.n_layers,
                              max_len=args.max_len)
        params = model.init(jax.random.PRNGKey(args.seed))
        prompt = jax.random.randint(jax.random.PRNGKey(args.seed + 1),
                                    (b, prompt_len), 0, args.vocab)

        def one(i):
            model.generate_fused(params, prompt, new,
                                 kv_dtype=args.kv_dtype)

    for i in range(args.warmup):          # compiles stay out of the trace
        one(i)
    out_dir = args.trace_dir or tempfile.mkdtemp(prefix="paddle_tpu_profile_")
    with jax.profiler.trace(out_dir):
        for j in range(args.steps):
            one(args.warmup + j)
    pbs = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                           recursive=True), key=os.path.getmtime)
    if not pbs:
        print(f"profile: profiler wrote no .xplane.pb under {out_dir}",
              file=sys.stderr)
        return 2
    from .obs import xplane as _xp
    space = _xp.read_xspace(pbs[-1])
    print(_xp.top_ops_report(space, topk=args.topk, steps=args.steps))
    print(f"\ntrace: {pbs[-1]}")
    print("merge: paddle_tpu obs export --format=chrome "
          f"--xplane {pbs[-1]} [--input obs.jsonl] --output trace.json")
    return 0


def cmd_dump_config(args):
    """Print the built Program IR as JSON (dump_config / make_diagram data)."""
    cfg = _load_config(args.config)
    from . import fluid
    print(json.dumps(fluid.default_main_program().to_dict(), indent=2,
                     default=str))
    return 0


def cmd_lint(args):
    """Static verification + lint of a config's Program IR — rejects
    malformed programs (undefined vars, unregistered ops, duplicate writes,
    broken sub-block scoping, shape mismatches) with precise diagnostics
    BEFORE any trace/compile, and reports the advisory lint catalogue
    (dead ops, unused vars, trace-safety, sharding consistency).

    Exit-code contract (stable, scripts may rely on it):
      0 — clean: no finding at or above the --fail-on threshold
      1 — findings at or above the threshold (or invalid bench rows)
      2 — usage error: missing/broken config or unreadable inputs

    ``--format=json`` emits the stable machine schema on a pure-JSON
    stdout: ``{"version": 1, "findings": [{code, severity, message,
    hint, explain, site: {program, block, block_path, op, op_type,
    var}}], "summary": {errors, warnings, info, total}}`` (human
    summary goes to stderr).  The legacy ``--json`` flat list of
    Diagnostic dicts is kept for old pipelines.  ``--explain``
    annotates each finding's variable with its def-use chain from the
    dataflow plane (where it is defined, redefined, and last read).

    ``--bench-rows FILE...`` additionally (or, without --config, ONLY)
    validates saved bench rows — JSON or JSONL of bench.py output lines —
    against the bench-row schema (analysis/bench_schema.py: required keys
    per row, roofline columns per metric family), so a benchmark that
    drops a column fails in CI instead of silently thinning the trend
    data."""
    from . import analysis, fluid
    if args.bench_rows and args.config is None:
        return _lint_bench_rows(args.bench_rows,
                                as_json=args.json or
                                getattr(args, "format", "text") == "json")
    if args.config is None:
        print("lint: --config is required (or pass --bench-rows alone)",
              file=sys.stderr)
        return 2
    try:
        cfg = _load_config(args.config)
    except Exception as e:
        print(f"lint: cannot load config {args.config!r}: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        return 2
    # liveness roots: the config's cost + declared outputs are what a
    # trainer/exporter would fetch
    fetch = []
    for key in ("cost",):
        if key in cfg:
            v = cfg[key]
            fetch.append(v.var.name if hasattr(v, "var") else v.name)
    for o in cfg.get("outputs") or []:
        fetch.append(o.var.name if hasattr(o, "var") else o.name)
    threshold = {"error": analysis.Severity.ERROR,
                 "warning": analysis.Severity.WARNING,
                 "info": analysis.Severity.INFO}[args.fail_on]
    mesh_axes = args.mesh_axes.split(",") if args.mesh_axes else None
    all_diags = []
    for label, prog in (("main", fluid.default_main_program()),
                        ("startup", fluid.default_startup_program())):
        prog_fetch = fetch if label == "main" else []
        diags = analysis.analyze_program(prog, fetch=prog_fetch,
                                         mesh_axes=mesh_axes)
        for d in diags:
            d.program = label
        if getattr(args, "explain", False) and any(d.var for d in diags):
            # --explain: cite each flagged var's def-use chain so the
            # reader sees WHY (where defined/redefined/last read), not
            # just WHERE.  Dataflow may legitimately fail on programs
            # with structural errors — the findings still stand alone.
            try:
                df = analysis.analyze_dataflow(prog, fetch=prog_fetch)
                for d in diags:
                    if d.var:
                        d.explain = analysis.explain_var(df, d.var)
            except Exception:
                pass
        all_diags.extend(diags)
    # L005: the obs metric catalogue is part of the lint surface — a PR
    # adding an off-contract metric name fails here, not on a dashboard
    from . import obs as _obs
    for d in analysis.lint_metric_names(_obs.CATALOGUE):
        d.program = "obs"
        all_diags.append(d)
    # L007: catalogue drift — emit sites and catalogue.py must agree in
    # both directions (an undeclared emit or an orphaned entry fails CI)
    for d in analysis.lint_catalogue_drift():
        d.program = "obs"
        all_diags.append(d)
    # L009: the shipped alert rules must reference catalogued metrics —
    # a rule naming a typo'd metric silently never fires
    for d in analysis.lint_alert_rules():
        d.program = "obs"
        all_diags.append(d)
    n_err = len(analysis.errors(all_diags))
    n_warn = sum(1 for d in all_diags
                 if d.severity == analysis.Severity.WARNING)
    summary = (f"lint: {n_err} error(s), {n_warn} warning(s), "
               f"{len(all_diags) - n_err - n_warn} info over "
               f"{sum(len(b.ops) for b in fluid.default_main_program().blocks)} "
               "main-program op(s)")
    as_json = args.json or getattr(args, "format", "text") == "json"
    if getattr(args, "format", "text") == "json":
        # the STABLE machine schema (version-gated; see docstring) —
        # stdout stays pure JSON so `lint --format=json | jq` works
        print(json.dumps(_lint_json_payload(all_diags, n_err, n_warn),
                         indent=1, sort_keys=True))
        print(summary, file=sys.stderr)
    elif args.json:
        # legacy flat list of Diagnostic dicts, kept verbatim for old
        # pipelines; new tooling should use --format=json
        print(json.dumps([d.to_dict() for d in all_diags], indent=1))
        print(summary, file=sys.stderr)
    else:
        if all_diags:
            print(analysis.format_diagnostics(all_diags))
        print(summary)
    failed = any(d.severity >= threshold for d in all_diags)
    if args.bench_rows:
        # under either json mode, bench-row findings go to STDERR so
        # stdout stays the pure diagnostics JSON (`| jq` contract)
        rc = _lint_bench_rows(args.bench_rows,
                              stream=sys.stderr if as_json
                              else sys.stdout)
        failed = failed or rc != 0
    return 1 if failed else 0


def _lint_json_payload(diags, n_err: int, n_warn: int) -> dict:
    """The ``lint --format=json`` schema.  STABLE: additions only, and a
    shape change bumps ``version``.  Every finding has every key (null
    when absent) so consumers can index without guards."""
    return {
        "version": 1,
        "findings": [{
            "code": d.code,
            "severity": str(d.severity),
            "message": d.message,
            "hint": d.hint,
            "explain": d.explain,
            "site": {
                "program": d.program,
                "block": d.block_idx,
                "block_path": d.block_path,
                "op": d.op_idx,
                "op_type": d.op_type,
                "var": d.var,
            },
        } for d in diags],
        "summary": {"errors": n_err, "warnings": n_warn,
                    "info": len(diags) - n_err - n_warn,
                    "total": len(diags)},
    }


def _lint_bench_rows(paths, as_json: bool = False, stream=None) -> int:
    """Validate bench-row files (JSON array/object or JSONL) against the
    bench-row schema; 0 clean, 1 findings, 2 unreadable input.
    ``as_json`` (the bench-rows-only ``--json`` path) emits the findings
    as a JSON array on stdout instead of text lines."""
    from .analysis.bench_schema import validate_row
    stream = stream if stream is not None else sys.stdout
    findings = []

    def emit(path, ln, name, problem):
        findings.append({"code": "B001", "path": path, "line": ln,
                         "metric": name, "message": problem})
        if not as_json:
            print(f"{path}:{ln}: B001 bench-row-schema: {name}: {problem}",
                  file=stream)

    n_rows = n_bad = 0
    for path in paths:
        try:
            with open(path) as f:
                text = f.read()
        except OSError as e:
            print(f"lint: cannot read bench rows {path!r}: {e}",
                  file=sys.stderr)
            return 2
        rows = []
        try:
            data = json.loads(text)
            if isinstance(data, dict) and "metric" not in data \
                    and isinstance(data.get("tail"), str):
                # a driver record (a JSON object whose "tail" field holds
                # the run's stdout): the rows live there as JSONL
                text = data["tail"]
                raise ValueError("driver record: parse tail as JSONL")
            rows = data if isinstance(data, list) else [data]
        except ValueError:
            for ln, line in enumerate(text.splitlines(), 1):
                line = line.strip()
                if not line.startswith("{"):
                    continue      # log noise / truncated tail heads
                try:
                    rows.append((ln, json.loads(line)))
                except ValueError as e:
                    emit(path, ln, "<no metric>", f"not valid JSON: {e}")
                    n_bad += 1
        rows = [r if isinstance(r, tuple) else (i + 1, r)
                for i, r in enumerate(rows)]
        for ln, row in rows:
            n_rows += 1
            for problem in validate_row(row):
                name = (row.get("metric", "<no metric>")
                        if isinstance(row, dict) else "<not a dict>")
                emit(path, ln, name, problem)
                n_bad += 1
    if as_json:
        print(json.dumps(findings, indent=1))
        print(f"lint: bench rows — {n_bad} problem(s) over {n_rows} "
              "row(s)", file=sys.stderr)
    else:
        print(f"lint: bench rows — {n_bad} problem(s) over {n_rows} "
              "row(s)", file=stream)
    return 1 if n_bad else 0


def cmd_merge_model(args):
    """Merge a params tar + config into one inference bundle
    (trainer/MergeModel.cpp:29 analog)."""
    cfg = _load_config(args.config)
    trainer = _make_trainer(cfg)
    with open(args.model_path, "rb") as f:
        trainer.parameters.from_tar(f)
    from . import fluid
    outs = cfg.get("outputs") or [cfg["cost"]]
    fluid.io.export_inference_model(
        args.output_dir, [dl.var.name for dl in cfg.get("feeding", [])],
        [o.var for o in outs], trainer.exe)
    print(f"merged model written to {args.output_dir}")
    return 0


def cmd_checkgrad(args):
    """--job=checkgrad (TrainerMain.cpp:54 / Trainer::checkGradient): compare
    the program's autodiff gradients against central differences on sampled
    parameter entries, through the executor (LayerGradUtil semantics)."""
    import numpy as np

    from . import fluid
    cfg = _load_config(args.config)
    trainer = _make_trainer(cfg)
    feeder = None
    if cfg.get("feeding"):
        from .v2.trainer import _V2Feeder
        feeder = _V2Feeder(cfg["feeding"])
    rows = next(iter(cfg["train_reader"]()))
    feed = feeder(rows) if feeder else rows
    exe = trainer.exe
    prog = fluid.default_main_program()
    cost_name = cfg["cost"].var.name
    params = [v.name for v in prog.global_block().all_parameters()]
    # pruned programs: running the full program would fire the optimizer
    # update ops and mutate params between evaluations. Stochastic ops key
    # off the implicit __step__ feed — pin it so every evaluation sees the
    # SAME dropout masks / negative samples.
    feed = dict(feed)
    feed["__step__"] = 0
    cost_prog = prog.prune([cost_name])
    grad_names = [p + "@GRAD" for p in params]
    grad_prog = prog.prune(grad_names)
    all_grads = exe.run(grad_prog, feed=feed, fetch_list=grad_names)
    rs = np.random.RandomState(0)
    eps = args.eps
    worst = 0.0
    ok = True
    for pname, grad in zip(params, all_grads):
        grad = np.asarray(grad)
        base = np.asarray(exe.scope.get(pname)).copy()
        flat = base.reshape(-1)
        for idx in rs.choice(flat.size,
                             size=min(args.checks_per_param, flat.size),
                             replace=False):
            orig = flat[idx]
            vals = {}
            for sign in (+1, -1):
                flat[idx] = orig + sign * eps
                exe.scope.set(pname, base.reshape(base.shape))
                vals[sign], = exe.run(cost_prog, feed=feed,
                                      fetch_list=[cost_name])
            flat[idx] = orig
            exe.scope.set(pname, base.reshape(base.shape))
            numeric = (float(vals[+1]) - float(vals[-1])) / (2 * eps)
            analytic = float(grad.reshape(-1)[idx])
            denom = max(abs(numeric), abs(analytic), 1e-6)
            rel = abs(numeric - analytic) / denom
            worst = max(worst, rel)
            if rel > args.rtol:
                print(f"MISMATCH {pname}[{idx}]: numeric {numeric:.6g} "
                      f"analytic {analytic:.6g} rel {rel:.3g}")
                ok = False
    print(f"checkgrad {'PASS' if ok else 'FAIL'} "
          f"({len(params)} params, worst rel err {worst:.3g})")
    return 0 if ok else 1


def _poll_job(procs, timeout: float, grace: float) -> int:
    """Shared failure-detection loop: the moment ANY worker fails (or the
    deadline passes), SIGTERM survivors with a teardown grace, then SIGKILL
    stragglers. Returns the job rc."""
    import time as _time
    rc = 0
    deadline = _time.time() + timeout
    try:
        # poll-all: the moment ANY worker fails, tear the job down (the
        # docstring's failure-detection contract); one shared deadline
        pending = list(procs)
        while pending:
            for p in list(pending):
                code = p.poll()
                if code is not None:
                    pending.remove(p)
                    if code and not rc:
                        rc = code
                        print(f"cluster_train: worker {procs.index(p)} "
                              f"exited rc={code}; tearing the job down "
                              f"(survivors get SIGTERM, {grace:.0f}s "
                              f"grace).", file=sys.stderr)
            if not rc and _time.time() > deadline:
                rc = 124
                print(f"cluster_train: --timeout {timeout:.0f}s "
                      f"exceeded; tearing the job down.", file=sys.stderr)
            if rc:     # peer failure or timeout -> graceful teardown
                for p in pending:
                    if p.poll() is None:
                        p.terminate()   # survivors run their teardown hook
                grace_end = _time.time() + grace
                while (any(p.poll() is None for p in pending)
                       and _time.time() < grace_end):
                    _time.sleep(0.1)
                break
            _time.sleep(0.2)
    finally:
        for p in procs:           # a dead/hung peer must not strand the rest
            if p.poll() is None:
                p.kill()
    return rc


def _cluster_attempt(args, attempt: int) -> int:
    """One full local-job launch: spawn all workers on a fresh coordinator
    port, then run the shared failure-detection loop."""
    import os
    import socket
    import subprocess

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    procs = []
    for i in range(args.num_workers):
        env = dict(os.environ)
        env["PADDLE_TPU_COORDINATOR"] = f"127.0.0.1:{port}"
        env["PADDLE_TPU_NUM_PROCESSES"] = str(args.num_workers)
        env["PADDLE_TPU_PROCESS_ID"] = str(i)
        env["PADDLE_TPU_RESTART_COUNT"] = str(attempt)
        if args.devices_per_worker:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                f" --xla_force_host_platform_device_count="
                                f"{args.devices_per_worker}").strip()
            env["JAX_PLATFORMS"] = "cpu"
        procs.append(subprocess.Popen(
            [sys.executable, args.script] + (args.script_args or []),
            env=env))
    return _poll_job(procs, args.timeout, args.grace)


def _cluster_hosts(args):
    """Host list from --hosts (comma-separated) or --hostfile (one host per
    line, '#' comments) — the conf.py HOSTS list of the reference launcher
    (scripts/cluster_train/conf.py)."""
    hosts = []
    if getattr(args, "hosts", None):
        hosts += [h.strip() for h in args.hosts.split(",") if h.strip()]
    if getattr(args, "hostfile", None):
        with open(args.hostfile) as f:
            for line in f:
                line = line.split("#", 1)[0].strip()
                if line:
                    hosts.append(line)
    return hosts


def _render_host_commands(args, hosts, attempt: int = 0,
                          job_id: str = "dryrun"):
    """Render the per-host launch command lines for a real multi-host
    jax.distributed job — the capability of the reference's fabric/ssh
    launcher (scripts/cluster_train/paddle.py job_prepare+job_start;
    cluster_train_v2/fabric), re-targeted at jax.distributed membership:
    node 0's host serves the coordinator, every node gets its process id
    and the world size via PADDLE_TPU_* env (consumed by
    parallel/multihost.py initialize()).

    ``--ssh-template`` wraps each per-node command; placeholders ``{host}``
    and ``{cmd}`` (shell-quoted). Default: ssh <host> '<cmd>'.

    Each node runs inside a tiny bash supervisor whose command line carries
    ``PADDLE_TPU_JOB_ID=<id>`` and which forwards SIGTERM to the python
    child — that is what makes the job remotely reapable
    (``pkill -f PADDLE_TPU_JOB_ID=<id>``, see :func:`_reap_remote_job`),
    since signalling an ssh client does not signal the remote process
    (the reference's kill_process grep-marker trick, paddle.py:51-60).

    The coordinator address strips an ssh ``user@`` login prefix from
    node 0's host, and its port is offset by the attempt number so an
    elastic restart never collides with a stale coordinator socket from
    the previous generation.
    """
    import shlex

    coord_host = hosts[0].rsplit("@", 1)[-1]   # user@host is ssh login only
    coordinator = f"{coord_host}:{args.coordinator_port + attempt}"
    template = args.ssh_template or "ssh {host} {cmd}"
    lines = []
    for i, host in enumerate(hosts):
        inner = " ".join(
            [f"PADDLE_TPU_JOB_ID={job_id}",
             f"PADDLE_TPU_COORDINATOR={coordinator}",
             f"PADDLE_TPU_NUM_PROCESSES={len(hosts)}",
             f"PADDLE_TPU_PROCESS_ID={i}",
             f"PADDLE_TPU_RESTART_COUNT={attempt}",
             args.remote_python, shlex.quote(args.script)]
            + [shlex.quote(a) for a in (args.script_args or [])])
        # supervisor: its /proc cmdline contains the job id (the exec'd
        # python's does not); TERM/INT forward to the child
        wrapped = ("bash -c " + shlex.quote(
            'trap "kill -TERM $c 2>/dev/null" TERM INT; '
            + inner + " & c=$!; wait $c"))
        lines.append(template.format(host=shlex.quote(host),
                                     cmd=shlex.quote(wrapped)))
    return lines


def _reap_remote_job(args, hosts, job_id: str):
    """Best-effort remote teardown: ssh a targeted pkill to every host so a
    crashed job's survivors do not keep the accelerators (the reference's
    paddle.py kill_process). TERM first (teardown hooks run), then KILL."""
    import shlex
    import subprocess

    import shlex as _shlex

    template = args.ssh_template or "ssh {host} {cmd}"
    # bracket the first id char: the regex still matches the literal job id
    # in the supervisors' cmdlines, but the REAPING shell's own cmdline
    # (which contains the pattern text "…=[x]yz") does not match it — so
    # pkill never TERMs the shell running the sleep+KILL escalation (the
    # reference's grep -v marker trick, paddle.py kill_process)
    pat = f"PADDLE_TPU_JOB_ID=[{job_id[0]}]{job_id[1:]}"
    kill = (f"pkill -TERM -f {_shlex.quote(pat)}; sleep 2; "
            f"pkill -KILL -f {_shlex.quote(pat)}; true")
    for host in hosts:
        cmd = template.format(host=shlex.quote(host), cmd=shlex.quote(kill))
        try:
            subprocess.run(cmd, shell=True, timeout=30,
                           stdout=subprocess.DEVNULL,
                           stderr=subprocess.DEVNULL)
        except Exception:
            pass                       # a dead host cannot be reaped anyway


def _multihost_attempt(args, hosts, attempt: int) -> int:
    """One multi-host launch: run every rendered per-host command (ssh by
    default) and apply the same any-failure-tears-all-down contract the
    local path uses — the analog of paddle.py's job_all + kill-on-failure,
    including reaping the REMOTE worker processes, not just the local ssh
    clients."""
    import os
    import subprocess

    # dot-free id: it doubles as a pkill -f regex literal in the reaper
    job_id = f"{os.getpid():x}x{attempt}"
    cmds = _render_host_commands(args, hosts, attempt, job_id)
    procs = [subprocess.Popen(c, shell=True) for c in cmds]
    rc = _poll_job(procs, args.timeout, args.grace)
    if rc:
        _reap_remote_job(args, hosts, job_id)
    return rc


def cmd_cluster_train(args):
    """Local cluster launcher — the scripts/cluster_train/paddle.py (ssh) and
    cluster_train_v2 fabric/openmpi analog, process-model edition.

    Spawns ``--num_workers`` worker processes that join one jax.distributed
    job (coordinator on localhost; PADDLE_TPU_* env carries the membership
    that etcd/MPI carried for the reference) and each execute the training
    SCRIPT. The script calls ``paddle_tpu.parallel.multihost.initialize()``
    to join, then trains over the global mesh. A failing worker tears the
    job down (failure detection; rc propagated).

    ``--restart-on-failure N``: elastic recovery (the reference's
    trainers-are-stateless-consumers design, go/master/service.go:311-321 +
    doc/design/cluster_train/README.md). A synchronous SPMD job cannot
    continue minus one collective participant, so recovery is job-grained:
    tear down, then relaunch ALL workers on a fresh coordinator, up to N
    times. Scripts resume from their latest pass checkpoint (the trainer's
    pass-%05d discipline); a ``--local_master`` data plane requeues the dead
    consumer's pending task chunks by lease timeout automatically
    (native/task_master.cc), so no sample is lost or double-trained across
    the restart. ``PADDLE_TPU_RESTART_COUNT`` tells the script which
    attempt it is on. Timeouts are per-attempt.

    With ``--hosts``/``--hostfile`` the same job shape targets REAL
    machines: per-host launch commands are rendered (``--ssh-template``)
    around jax.distributed membership env — node 0's host carries the
    coordinator at ``--coordinator-port`` — and executed (ssh by default),
    or just printed with ``--dry-run`` for inspection/external schedulers.
    The reference capability: scripts/cluster_train/paddle.py (fabric/ssh)
    and cluster_train_v2/{fabric,openmpi}."""
    hosts = _cluster_hosts(args)
    if hosts:
        # world size is the host list in this mode; flag the conflict
        # instead of silently dropping an explicitly-passed local option
        if args.num_workers is not None:
            print(f"cluster_train: --hosts mode runs one node per host "
                  f"({len(hosts)}); ignoring --num_workers "
                  f"{args.num_workers}.", file=sys.stderr)
        if args.devices_per_worker:
            print("cluster_train: --devices_per_worker is a local-mode "
                  "testing option; ignored with --hosts (set XLA_FLAGS on "
                  "the remote hosts instead).", file=sys.stderr)
    if getattr(args, "dry_run", False):
        if not hosts:
            print("cluster_train: --dry-run needs --hosts/--hostfile",
                  file=sys.stderr)
            return 2
        for line in _render_host_commands(args, hosts):
            print(line)
        return 0
    if args.num_workers is None:
        args.num_workers = 2             # local-mode default world size
    restarts = max(0, getattr(args, "restart_on_failure", 0) or 0)
    for attempt in range(restarts + 1):
        rc = (_multihost_attempt(args, hosts, attempt) if hosts
              else _cluster_attempt(args, attempt))
        if rc == 0:
            return 0
        if attempt < restarts:
            print(f"cluster_train: attempt {attempt} failed rc={rc}; "
                  f"relaunching from the latest checkpoint "
                  f"({restarts - attempt} restart(s) left).", file=sys.stderr)
        else:
            print("cluster_train: restart budget exhausted."
                  if restarts else
                  "cluster_train: failed (pass --restart-on-failure N for "
                  "elastic recovery).", file=sys.stderr)
    return rc


def cmd_make_diagram(args):
    """Model visualization (scripts/submit_local.sh.in:13 make_diagram):
    emit a graphviz .dot of the config's Program — ops as boxes, data flow
    as edges, parameters dashed."""
    from . import fluid
    _load_config(args.config)
    prog = fluid.default_main_program()
    lines = ["digraph G {", "  rankdir=TB;",
             '  node [fontsize=10, fontname="Helvetica"];']
    params = {v.name for v in prog.global_block().all_parameters()}
    var_nodes = set()
    for bi, block in enumerate(prog.blocks):
        for oi, op in enumerate(block.ops):
            op_id = f"op_{bi}_{oi}"
            lines.append(f'  {op_id} [shape=box, style=filled, '
                         f'fillcolor="#DDEEFF", label="{op.type}"];')
            for names in op.inputs.values():
                for n in names:
                    var_nodes.add(n)
                    lines.append(f'  "{n}" -> {op_id};')
            for names in op.outputs.values():
                for n in names:
                    var_nodes.add(n)
                    lines.append(f'  {op_id} -> "{n}";')
    for n in sorted(var_nodes):          # one declaration per variable
        style = ", style=dashed" if n in params else ""
        lines.append(f'  "{n}" [shape=ellipse{style}];')
    lines.append("}")
    import os
    out = args.output or (os.path.splitext(args.config)[0] + ".dot")
    with open(out, "w") as f:
        f.write("\n".join(lines))
    n_ops = sum(len(b.ops) for b in prog.blocks)
    print(f"wrote {out} ({n_ops} ops, {len(params)} parameters)")
    return 0


def _read_obs_inputs(inputs):
    """Load one or more JSONL dumps as a list (the caller merges —
    cmd_obs appends xplane-derived dumps first). Errors name the
    failing file."""
    from . import obs
    dumps = []
    for p in inputs:
        try:
            dumps.append(obs.read_jsonl(p))
        except (OSError, ValueError) as e:
            raise OSError(f"{p}: {e}") from e
    return dumps


def cmd_obs(args):
    """``paddle_tpu obs`` — inspect/convert observability dumps (the JSONL
    written by ``ObsSession.save`` / ``train --obs_out`` / the flight
    recorder). ``--input`` may repeat: several dumps merge into one
    cluster view (distributed-trace stitching).

    * ``summary``: the human table (counters, gauges, histograms with
      p50/p99, span totals) — the ``StatSet.report()`` successor.
    * ``export --format=chrome``: Chrome ``trace_event`` JSON; load the
      file in Perfetto (ui.perfetto.dev) or chrome://tracing to see the
      nested trainer -> checkpoint/rpc span timeline — with several
      inputs, one lane per process plus client->server flow arrows.
    * ``export --format=prom``: Prometheus text exposition — serve it or
      drop it where a textfile collector scrapes.
    * ``export --format=jsonl``: normalized event stream (re-emits the
      dump; useful to strip a corrupt tail or persist a merge).
    """
    from . import obs
    inputs = list(args.input or ())
    xplanes = list(getattr(args, "xplane", None) or ())
    if not inputs and not xplanes:
        print("obs: pass --input dump.jsonl (repeatable) and/or "
              "--xplane trace.xplane.pb", file=sys.stderr)
        return 2
    try:
        dumps = _read_obs_inputs(inputs)
        if xplanes:
            # device timelines: each .xplane.pb becomes one dump whose
            # lanes merge beside the host spans. Anchored at the earliest
            # host dump's clock origin — XLine clocks are backend-
            # dependent, so the alignment is coarse but the lanes always
            # render (obs/xplane.py states the contract)
            from .obs import xplane as _xp
            origins = [(d.get("meta") or {}).get("clock_origin_unix")
                       for d in dumps]
            origins = [o for o in origins if o is not None]
            anchor = min(origins) if origins else None
            for path in xplanes:
                try:
                    space = _xp.read_xspace(path)
                except (OSError, ValueError) as e:
                    raise OSError(f"{path}: {e}") from e
                dumps.append(_xp.xplane_dump(space, anchor_unix=anchor))
        dump = dumps[0] if len(dumps) == 1 else obs.merge_dumps(dumps)
    except (OSError, ValueError) as e:
        print(f"obs: cannot read dump: {e}", file=sys.stderr)
        return 2
    if args.obs_cmd == "summary":
        print(obs.summary(dump))
        return 0
    if args.format == "chrome":
        out = json.dumps(obs.chrome_trace(dump), indent=1)
    elif args.format == "prom":
        out = obs.prometheus_text(dump)
    else:                                  # jsonl: normalized re-emit
        if args.output:
            obs.write_jsonl(args.output, dump)
            print(f"wrote {args.output}")
            return 0
        from .obs.export import jsonl_lines
        out = "\n".join(jsonl_lines(dump)) + "\n"
    if args.output:
        with open(args.output, "w") as f:
            f.write(out)
        print(f"wrote {args.output}")
    else:
        print(out, end="" if out.endswith("\n") else "\n")
    return 0


def cmd_obs_serve(args):
    """``paddle_tpu obs serve`` — read-only HTTP view over dumps and/or a
    live master's merged fleet metrics:

    * ``/metrics`` — Prometheus text exposition (point a scraper here)
    * ``/trace``   — Chrome trace_event JSON (load in Perfetto)
    * ``/summary`` (and ``/``) — the human table

    Sources re-read per request, so a dump being appended to (or a live
    master) always serves its current state. ``--master host:port`` polls
    ``MasterClient.obs_stats()`` — the worker-tagged merged registry the
    ``obs_push`` RPC accumulates.
    """
    from . import obs
    from .obs.aggregate import ObsHttpServer
    inputs = list(args.input or ())
    master = getattr(args, "master", None)
    if not inputs and not master:
        print("obs serve: pass --input dump.jsonl (repeatable) and/or "
              "--master host:port", file=sys.stderr)
        return 2
    master_addr = None
    if master:
        # validate ONCE at startup: a malformed flag must be a clear exit-2
        # here, not a ValueError 500ing every later scrape inside provider
        master_addr = _parse_hostport(master)
        if master_addr is None:
            print(f"obs serve: --master must be host:port, got {master!r}",
                  file=sys.stderr)
            return 2

    def provider():
        dumps = [obs.read_jsonl(p) for p in inputs]
        if master_addr is not None:
            # fail-fast telemetry client — a down master must not wedge
            # every scrape for the data plane's full retry budget
            from .obs.aggregate import telemetry_client
            client = telemetry_client(*master_addr)
            try:
                workers, samples = client.obs_stats()
                try:
                    h = client.obs_health()
                except (OSError, ConnectionError):
                    # a master predating obs_health still serves metrics
                    h = {"health": {}, "active": [], "events": [],
                         "actions": []}
                dumps.append({"meta": {"process": "master",
                                       "obs_workers": workers},
                              "metrics": samples,
                              "events": h["events"],
                              "alerts": h["active"],
                              "health": h["health"],
                              "actions": h.get("actions", []),
                              "requests": h.get("requests", []),
                              "exemplars": h.get("exemplars", [])})
            except (OSError, ConnectionError) as e:
                # keep serving whatever dumps we do have; a master-only
                # serve surfaces the outage as a 500 with the cause
                if not dumps:
                    raise
                print(f"obs serve: master {master} unreachable: {e}",
                      file=sys.stderr)
            finally:
                client.close()
        if len(dumps) == 1:
            return dumps[0]
        merged = obs.merge_dumps(dumps)
        # merge_dumps knows meta/metrics/events; the health-plane extras
        # (live alerts, derived health) carry through for /alerts and the
        # /summary fleet table
        for d in dumps:
            if d.get("alerts"):
                merged.setdefault("alerts", []).extend(d["alerts"])
            if d.get("health"):
                merged.setdefault("health", {}).update(d["health"])
            if d.get("actions"):
                merged.setdefault("actions", []).extend(d["actions"])
            if d.get("exemplars"):
                merged.setdefault("exemplars", []).extend(d["exemplars"])
        return merged

    srv = ObsHttpServer(provider, host=args.host, port=args.port).start()
    # machine-parseable address line first (port 0 binds an ephemeral one)
    print(f"SERVING {srv.address[0]} {srv.address[1]}", flush=True)
    print(f"  http://{srv.address[0]}:{srv.address[1]}/metrics  (prometheus)")
    print(f"  http://{srv.address[0]}:{srv.address[1]}/trace    (chrome json)")
    print(f"  http://{srv.address[0]}:{srv.address[1]}/requests (request "
          f"timelines)")
    print(f"  http://{srv.address[0]}:{srv.address[1]}/summary")
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        srv.stop()
    return 0


def cmd_obs_top(args):
    """``paddle_tpu obs top`` — the live fleet terminal view: one row per
    worker (goodput ratio, mfu, queue depth, straggler score, heartbeat
    jitter, active alerts) over a live master's health plane
    (``--master`` → ``obs_stats`` + ``obs_health``) and/or dump files
    (``--input``, re-read per refresh). ``--once`` prints a single table
    and exits (tests, scripts); otherwise the view refreshes every
    ``--interval`` seconds until Ctrl-C.
    """
    from . import obs
    from .obs.health import health_table
    inputs = list(args.input or ())
    master = getattr(args, "master", None)
    if not inputs and not master:
        print("obs top: pass --input dump.jsonl (repeatable) and/or "
              "--master host:port", file=sys.stderr)
        return 2
    master_addr = None
    if master:
        master_addr = _parse_hostport(master)
        if master_addr is None:
            print(f"obs top: --master must be host:port, got {master!r}",
                  file=sys.stderr)
            return 2

    def fetch():
        samples, alerts, health, actions = [], [], {}, []
        if inputs:
            dumps = _read_obs_inputs(inputs)
            # always merge (even one dump): the merge stamps the worker
            # label every per-worker cell keys on
            merged = obs.merge_dumps(dumps)
            samples.extend(merged.get("metrics", ()))
            alerts.extend(e for e in merged.get("events", ())
                          if e.get("name") == "alert")
        if master_addr is not None:
            from .obs.aggregate import telemetry_client
            client = telemetry_client(*master_addr)
            try:
                _, live = client.obs_stats()
                samples.extend(live)
                try:
                    h = client.obs_health()
                except (OSError, ConnectionError):
                    # a master predating obs_health still serves metrics
                    h = {"health": {}, "active": [], "events": [],
                         "actions": []}
                health = h["health"]
                actions = h.get("actions", [])
                # transitions first (chronological fold), live state last
                alerts.extend(h["events"])
                alerts.extend(h["active"])
            finally:
                client.close()
        return samples, alerts, health, actions

    def render():
        try:
            samples, alerts, health, actions = fetch()
        except (OSError, ConnectionError) as e:
            return None, f"obs top: source unavailable: {e}"
        from .obs.health import fold_alert_stream
        table = health_table(samples, alerts=alerts, health=health,
                             actions=actions)
        firing = fold_alert_stream(alerts)
        head = (f"fleet: {len(health) if health else '-'} worker(s) in "
                f"health view, {len(firing)} alert(s) firing")
        return table, head

    once = bool(getattr(args, "once", False))
    try:
        while True:
            table, head = render()
            if table is None:
                print(head, file=sys.stderr)
                if once:
                    return 2
            else:
                if not once:
                    print("\x1b[2J\x1b[H", end="")   # clear + home
                print(head)
                print(table if table else "(no per-worker series yet)")
            if once:
                return 0
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


def cmd_obs_trace(args):
    """``paddle_tpu obs trace <submit_key>`` — print one request's
    stitched cross-worker timeline: every phase record the fabric wrote
    for that submit_key (admitted → queued/prefill/ship/adopt →
    first_token → decode segments → done), legs from a mid-stream
    re-route (``<key>#r<n>``) merged onto one clock, the phase breakdown
    that reconciles with TTFT, and the dominant phase.

    Sources: ``--input`` JSONL dumps (``--obs_out`` files, flight rings)
    and/or ``--master host:port`` (the live aggregator's request store
    via ``obs_health``). Passing a leg key resolves to its base request.
    """
    from . import obs
    from .obs.requests import base_key, format_timeline, group_legs, stitch
    inputs = list(args.input or ())
    master = getattr(args, "master", None)
    if not inputs and not master:
        print("obs trace: pass --input dump.jsonl (repeatable) and/or "
              "--master host:port", file=sys.stderr)
        return 2
    timelines = []
    try:
        for d in _read_obs_inputs(inputs):
            timelines.extend(d.get("requests") or ())
    except (OSError, ValueError) as e:
        print(f"obs trace: cannot read dump: {e}", file=sys.stderr)
        return 2
    if master:
        try:
            addr = _parse_hostport(master)
        except ValueError:
            print(f"obs trace: --master must be host:port, got {master!r}",
                  file=sys.stderr)
            return 2
        from .obs.aggregate import telemetry_client
        client = telemetry_client(*addr)
        try:
            h = client.obs_health()
            timelines.extend(h.get("requests") or ())
        except (OSError, ConnectionError) as e:
            print(f"obs trace: master {master} unreachable: {e}",
                  file=sys.stderr)
            if not timelines:
                return 2
        finally:
            client.close()
    groups = group_legs(timelines)
    want = base_key(args.key)
    legs = groups.get(want)
    if not legs:
        print(f"obs trace: no timeline for {args.key!r} "
              f"({len(groups)} request(s) in the sources)", file=sys.stderr)
        for k in sorted(groups)[:16]:
            print(f"  known: {k}", file=sys.stderr)
        return 1
    print(format_timeline(stitch(legs)))
    return 0


def cmd_serve(args):
    """``paddle_tpu serve`` — the production serving daemon: a paged
    KV-cache continuous-batching engine behind the native RPC plane
    (srv_submit / srv_poll / srv_cancel / srv_stats; see
    docs/design/serving.md and :class:`paddle_tpu.serving.ServingClient`).

    The model comes from ``--config`` (a Python script exposing module-
    level ``model`` and ``params``) or, without one, a randomly-initialized
    TransformerLM built from the
    ``--vocab/--d_model/...`` flags and ``--seed`` (the bring-up and e2e
    test mode: the same flags + seed reproduce the exact weights).

    A ``--config`` model is a ``paddle_tpu.models.paged_lm.PagedLM`` (as
    ``TransformerLM``, ``DeepseekV3LM``, ``Lfm2MoeLM``, ``NemotronHLM``,
    ``AfmoeLM``, ``KeyeSparseLM`` and ``MimoV2LM`` are): that class IS the contract the
    page pool reads — ``max_len``, ``cache_rows`` (``CacheRow`` for what
    lives in pages, ``SlotRow`` for what lives per slot), ONE ``prefill``
    signature, ``decode_step_paged``, the decode read's cost model, what a
    program returns beside its tokens — each with its default, and a new
    model writes its blocks, ``cache_rows``, ``_sequence`` and
    ``_decode_layer`` (docs/design/serving.md, "What a served model
    states"). A model without ``prefill_paged`` needs ``--no_prefix_cache``
    (with the prefix cache on, ``serve`` refuses at start-up), as does one
    whose rows state a window. The dtype of weights and cache follows the
    arrays the script hands over: there is no dtype flag.

    ``--prompt_buckets 512,1024,2048,4096`` names the prompt lengths
    admissions are padded to, one compiled admit program each; the default
    32..512 is what the GPT-2 and GigaChat cells run. A prompt past the last bucket compiles
    its own length when it arrives.

    The address line ``SERVING <host> <port>`` prints first and flushed
    (machine-parseable, same contract as ``obs serve``); the process then
    serves until SIGTERM/SIGINT, drains, and (with ``--obs_out``) saves
    the metric/span dump — TTFT/TPOT histograms included.
    """
    import signal

    from . import enable_compile_cache, obs as _obs
    from .serving import ServingDaemon, ServingEngine
    enable_compile_cache()              # before the first compile
    if args.config:
        cfg = _load_config(args.config)
        if "model" not in cfg or "params" not in cfg:
            print("serve: --config must expose module-level `model` and "
                  "`params`", file=sys.stderr)
            return 2
        model, params = cfg["model"], cfg["params"]
    else:
        import jax

        from .models import TransformerLM
        model = TransformerLM(args.vocab, d_model=args.d_model,
                              n_heads=args.n_heads, n_layers=args.n_layers,
                              max_len=args.max_len)
        params = model.init(jax.random.PRNGKey(args.seed))
    session = _obs.ObsSession().install()
    flight = None
    if args.obs_out:
        flight = _obs.FlightRecorder(session, args.obs_out).arm()
    if args.role == "prefill":
        # a prefill-only worker (disaggregated serving): pool + ship, no
        # decode scheduler — it MUST join a router to be useful
        if not args.router:
            if flight is not None:
                flight.disarm()
            session.uninstall()
            print("serve: --role prefill requires --router HOST:PORT "
                  "(a prefill worker only receives work via the router)",
                  file=sys.stderr)
            return 2
        return _serve_prefill(args, model, params, session, flight)
    try:
        engine = ServingEngine(
            model, params, slots=args.slots, segment=args.segment,
            page_block=args.page_block, pages=args.pages,
            cache_bucket=args.cache_bucket, kv_dtype=args.kv_dtype,
            prompt_buckets=args.prompt_buckets, queue_cap=args.queue_cap,
            default_timeout_s=args.request_timeout,
            prefix_cache=not args.no_prefix_cache,
            class_weights={"interactive": args.interactive_weight,
                           "batch": args.batch_weight},
            max_tenants=args.max_tenants)
    except ValueError as e:
        # bad flag combinations (page_block not dividing max_len, a
        # cache_bucket off the page grid, ...) get the same structured
        # refusal as a bad --config, not a construction traceback
        if flight is not None:
            flight.disarm()
        session.uninstall()
        print(f"serve: {e}", file=sys.stderr)
        return 2
    try:
        daemon = ServingDaemon(engine, args.host, args.port).start()
    except OSError as e:
        # bind failures (port in use, bad host) get the structured refusal
        # too — and nothing half-started may outlive it: the engine's
        # scheduler thread stops, the armed recorder must not write a
        # spurious death dump
        engine.stop()
        if flight is not None:
            flight.disarm()
        session.uninstall()
        print(f"serve: cannot bind {args.host}:{args.port}: {e}",
              file=sys.stderr)
        return 2
    host, port = daemon.address
    _role_name_session(session, "decode", args.worker or f"serve-{port}")
    print(f"SERVING {host} {port}", flush=True)
    if args.router:
        try:
            epoch = daemon.join_router(
                _parse_hostport(args.router),
                args.worker or f"serve-{port}", role="decode")
        except Exception as e:
            daemon.stop()
            if flight is not None:
                flight.disarm()
            session.uninstall()
            print(f"serve: cannot join router {args.router}: {e}",
                  file=sys.stderr)
            return 2
        print(f"JOINED {args.router} epoch {epoch}", flush=True)
    print(f"  slots={args.slots} segment={args.segment} "
          f"page_block={engine.pool.bs} "
          f"pages={engine.pool.pages} queue_cap={args.queue_cap} "
          f"prefix_cache={'off' if args.no_prefix_cache else 'on'} "
          f"weights=interactive:{args.interactive_weight:g}/"
          f"batch:{args.batch_weight:g}"
          + (f" kv_dtype={args.kv_dtype}" if args.kv_dtype else ""),
          flush=True)
    import threading
    stop = threading.Event()

    def _on_term(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)
    try:
        while not stop.wait(0.5):
            pass
    finally:
        daemon.stop(drain_s=args.drain)
        if flight is not None:
            flight.disarm()
        if args.obs_out:
            # save BEFORE uninstall: the dump captures the request ledger
            # (per-request timelines) only while the plane is installed
            try:
                session.save(args.obs_out)
                print(f"observability dump written to {args.obs_out}",
                      flush=True)
            except Exception as e:
                print(f"warning: could not write obs dump: {e}",
                      file=sys.stderr)
        session.uninstall()
    return 0


def _int_list(s: str):
    """``"512,1024"`` -> (512, 1024): ascending positive ints."""
    out = tuple(int(x) for x in str(s).split(",") if x.strip())
    if not out or any(b <= 0 for b in out) or list(out) != sorted(set(out)):
        raise argparse.ArgumentTypeError(
            f"expected ascending positive integers N,N,..., got {s!r}")
    return out


def _parse_hostport(s: str):
    host, _, port = str(s).rpartition(":")
    if not host or not port.isdigit():
        raise ValueError(f"expected HOST:PORT, got {s!r}")
    return host, int(port)


def _role_name_session(session, role, worker=None):
    """Rename an installed ObsSession after its serving role (``router``,
    ``prefill:<worker>``, ``decode:<worker>``) — the lane name the Chrome
    exporter ranks router-above-prefill-above-decode and the worker id
    merged request timelines stitch under. An explicit
    PADDLE_TPU_OBS_PROCESS wins (operator override)."""
    import os
    if os.environ.get("PADDLE_TPU_OBS_PROCESS"):
        return
    session.process = f"{role}:{worker}" if worker else role


def _serve_prefill(args, model, params, session, flight):
    """The ``--role prefill`` half of cmd_serve: a pool-only worker that
    admits+exports KV pages and ships them to the router-chosen decode
    worker (serving/daemon.py PrefillDaemon)."""
    import signal
    import threading

    from .serving import PagePool, PrefillDaemon

    def _teardown():
        if flight is not None:
            flight.disarm()
        session.uninstall()

    try:
        pool = PagePool(model, params, slots=args.slots,
                        segment=args.segment, page_block=args.page_block,
                        pages=args.pages, cache_bucket=args.cache_bucket,
                        kv_dtype=args.kv_dtype,
                        prompt_buckets=args.prompt_buckets,
                        prefix_cache=not args.no_prefix_cache)
    except ValueError as e:
        _teardown()
        print(f"serve: {e}", file=sys.stderr)
        return 2
    try:
        daemon = PrefillDaemon(pool, args.host, args.port).start()
    except OSError as e:
        _teardown()
        print(f"serve: cannot bind {args.host}:{args.port}: {e}",
              file=sys.stderr)
        return 2
    host, port = daemon.address
    _role_name_session(session, "prefill", args.worker or f"prefill-{port}")
    print(f"SERVING {host} {port}", flush=True)
    try:
        epoch = daemon.join_router(_parse_hostport(args.router),
                                   args.worker or f"prefill-{port}",
                                   role="prefill")
    except Exception as e:
        daemon.stop()
        _teardown()
        print(f"serve: cannot join router {args.router}: {e}",
              file=sys.stderr)
        return 2
    print(f"JOINED {args.router} epoch {epoch}", flush=True)
    print(f"  role=prefill slots={args.slots} page_block={pool.bs} "
          f"pages={pool.pages} "
          f"prefix_cache={'off' if args.no_prefix_cache else 'on'}"
          + (f" kv_dtype={args.kv_dtype}" if args.kv_dtype else ""),
          flush=True)
    stop = threading.Event()

    def _on_term(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)
    try:
        while not stop.wait(0.5):
            pass
    finally:
        daemon.stop()
        if args.obs_out:
            # before _teardown: the dump captures the request ledger only
            # while the plane is installed
            try:
                session.save(args.obs_out)
                print(f"observability dump written to {args.obs_out}",
                      flush=True)
            except Exception as e:
                print(f"warning: could not write obs dump: {e}",
                      file=sys.stderr)
        _teardown()
    return 0


def cmd_route(args):
    """``paddle_tpu route`` — the serving router daemon: model-free
    placement over a membership table of prefill/decode serving workers
    (docs/design/serving.md "Disaggregation & routing"). Workers join
    with ``paddle_tpu serve --router HOST:PORT --role decode|prefill``;
    clients point :class:`paddle_tpu.serving.RouterClient` here.

    The address line ``ROUTER <host> <port>`` prints first and flushed
    (machine-parseable, the ``SERVING``/``MASTER`` contract)."""
    import signal
    import threading

    from . import obs as _obs
    from .serving import ServingRouter

    session = _obs.ObsSession().install()
    _role_name_session(session, "router")
    try:
        router = ServingRouter(args.host, args.port, ttl=args.ttl,
                               scrape_interval_s=args.scrape_interval
                               ).start()
    except OSError as e:
        session.uninstall()
        print(f"route: cannot bind {args.host}:{args.port}: {e}",
              file=sys.stderr)
        return 2
    host, port = router.address
    print(f"ROUTER {host} {port}", flush=True)
    print(f"  ttl={args.ttl:g} scrape_interval={args.scrape_interval:g}",
          flush=True)
    stop = threading.Event()

    def _on_term(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)
    try:
        while not stop.wait(0.5):
            pass
    finally:
        router.stop()
        if args.obs_out:
            # before uninstall: the dump captures the request ledger only
            # while the plane is installed
            try:
                session.save(args.obs_out)
                print(f"observability dump written to {args.obs_out}",
                      flush=True)
            except Exception as e:
                print(f"warning: could not write obs dump: {e}",
                      file=sys.stderr)
        session.uninstall()
    return 0


def cmd_cluster_autoscale(args):
    """``paddle_tpu cluster autoscale`` — the fleet actor (ISSUE 18,
    docs/design/fleet.md): watch the membership + health planes and
    DRIVE the fleet to them — spawn workers on a sustained join
    recommendation or an SLO burn, drain them gracefully on leave /
    scale-in, yield training capacity to serving under a shared
    ``--total-workers`` budget.

    Populations come from the flags: ``--train-master HOST:PORT`` +
    ``--train-cmd`` (a launch template with ``{worker}`` — and
    optionally ``{python}`` — placeholders) drives an elastic-DP
    training pool; ``--router HOST:PORT`` + ``--decode-cmd`` drives a
    decode serving pool toward ``--decode-target``. At least one
    population is required. Spawned processes must join the matching
    membership plane under the worker name the actor passed — that
    (never the subprocess's exit status alone) is the success oracle."""
    import signal
    import threading

    from . import obs as _obs
    from .cluster import (ActorReporter, FleetActor, MasterProbe,
                          Population, RouterProbe, SubprocessSpawnBackend)

    populations, closers = [], []
    for flag, cmd_flag, name, probe_cls, target in (
            ("train_master", "train_cmd", "train", MasterProbe, None),
            ("router", "decode_cmd", "serve", RouterProbe,
             args.decode_target)):
        addr = getattr(args, flag, None)
        if not addr:
            continue
        try:
            parsed = _parse_hostport(addr)
        except ValueError:
            parsed = None
        if parsed is None or not parsed[1]:
            print(f"cluster autoscale: --{flag.replace('_', '-')} must be "
                  f"host:port, got {addr!r}", file=sys.stderr)
            return 2
        template = getattr(args, cmd_flag, None)
        if not template or "{worker}" not in template:
            print(f"cluster autoscale: --{cmd_flag.replace('_', '-')} must "
                  f"be a launch template containing {{worker}}",
                  file=sys.stderr)
            return 2
        host, port = parsed
        probe = probe_cls(host, port)
        reporter = ActorReporter(host, port, args.actor)
        closers.extend((probe, reporter))
        populations.append(Population(
            name=name, backend=SubprocessSpawnBackend(template),
            probe=probe, reporter=reporter,
            min_workers=getattr(args, f"{name}_min"),
            max_workers=getattr(args, f"{name}_max"),
            target=target))
    if not populations:
        print("cluster autoscale: pass --train-master/--train-cmd and/or "
              "--router/--decode-cmd", file=sys.stderr)
        return 2

    session = _obs.ObsSession().install()
    actor = FleetActor(populations, total_workers=args.total_workers,
                       interval_s=args.interval, cooldown_s=args.cooldown,
                       max_churn=args.max_churn,
                       spawn_grace_s=args.spawn_grace,
                       drain_grace_s=args.drain_grace, name=args.actor)
    pops = ", ".join(f"{q.name}[{q.min_workers}..{q.max_workers}"
                     + (f"->{q.target}]" if q.target is not None else "]")
                     for q in populations)
    print(f"AUTOSCALE ACTOR {args.actor}", flush=True)
    print(f"  populations: {pops}  interval={args.interval:g} "
          f"cooldown={args.cooldown:g} max_churn={args.max_churn}"
          + (f" total={args.total_workers}" if args.total_workers else ""),
          flush=True)
    stop = threading.Event()

    def _on_term(signum, frame):
        stop.set()

    try:
        signal.signal(signal.SIGTERM, _on_term)
        signal.signal(signal.SIGINT, _on_term)
    except ValueError:
        pass
    try:
        if args.once:
            for entry in actor.step():
                print(f"  {entry['action']} {entry['population']}/"
                      f"{entry['worker']}: {entry['reason']}", flush=True)
        else:
            actor.run(stop=stop)
            if actor.deposed:
                print("cluster autoscale: deposed by a newer actor "
                      "registration; exiting", file=sys.stderr)
                return 2
    finally:
        for c in closers:
            try:
                c.close()
            except Exception:
                pass
        session.uninstall()
    return 0


def cmd_version(args):
    from . import __version__
    import jax
    print(f"paddle_tpu {__version__} (jax {jax.__version__}, "
          f"backend {jax.default_backend()})")
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="paddle_tpu")
    sub = p.add_subparsers(dest="job", required=True)

    def common(sp):
        sp.add_argument("--config", required=True)

    t = sub.add_parser("train")
    common(t)
    t.add_argument("--num_passes", type=int, default=1)
    t.add_argument("--save_dir", default=None)
    t.add_argument("--log_period", type=int, default=0)
    t.add_argument("--local_master", action="store_true",
                   help="self-host the task-master data plane in-process "
                        "(TrainerMain --start_pserver analog): dump the "
                        "reader to chunks, serve them over the real RPC "
                        "plane, train as its first consumer")
    t.add_argument("--samples_per_chunk", type=int, default=64,
                   help="reader items per dispatched chunk (--local_master)")
    t.add_argument("--obs_out", default=None,
                   help="install an observability session for the run and "
                        "write its JSONL dump here (inspect with "
                        "'paddle_tpu obs summary/export')")
    t.add_argument("--elastic", choices=["master", "worker"], default=None,
                   help="elastic data-parallel mode (docs/design/elastic.md): "
                        "'master' serves membership + shard dispatch and "
                        "applies the updates; 'worker' joins a master under "
                        "a heartbeat lease and computes shard gradients. "
                        "The config must define elastic_workload() -> "
                        "{loss_fn, params, optimizer, batches}")
    t.add_argument("--master_addr", default=None,
                   help="--elastic worker: HOST:PORT of the elastic master "
                        "to join; --elastic master: bind address "
                        "(default 127.0.0.1:0 — the chosen port is printed "
                        "as 'ELASTIC MASTER host port')")
    t.add_argument("--min_workers", type=int, default=1,
                   help="--elastic master: members required before the "
                        "first step dispatches")
    t.add_argument("--shards_per_step", type=int, default=4,
                   help="--elastic master: fixed shard tasks per global "
                        "batch (the elasticity quantum; membership-"
                        "independent so the reduce stays byte-stable)")
    t.add_argument("--heartbeat_ttl", type=float, default=5.0,
                   help="--elastic master: seconds without a heartbeat "
                        "before a worker is evicted and its in-flight "
                        "shards re-bucket")
    t.add_argument("--worker_id", default=None,
                   help="--elastic worker: stable membership name (a "
                        "re-join under the same name fences the old "
                        "incarnation)")
    t.set_defaults(fn=cmd_train)

    te = sub.add_parser("test")
    common(te)
    te.add_argument("--init_model_path", default=None)
    te.set_defaults(fn=cmd_test)

    tm = sub.add_parser("time")
    common(tm)
    tm.add_argument("--warmup", type=int, default=2)
    tm.add_argument("--iters", type=int, default=10)
    tm.set_defaults(fn=cmd_time)

    dc = sub.add_parser("dump_config")
    common(dc)
    dc.set_defaults(fn=cmd_dump_config)

    lt = sub.add_parser("lint", help="statically verify + lint the config's "
                                     "Program IR (no trace, no compile)")
    lt.add_argument("--config", required=False, default=None,
                    help="config to verify (optional when --bench-rows "
                         "is given alone)")
    lt.add_argument("--bench-rows", nargs="+", default=None,
                    dest="bench_rows", metavar="FILE",
                    help="also validate saved bench rows (BENCH_*.json / "
                         "bench.py JSONL) against the bench-row schema")
    lt.add_argument("--fail-on", choices=["error", "warning", "info"],
                    default="error", dest="fail_on",
                    help="lowest severity that makes the exit code nonzero")
    lt.add_argument("--json", action="store_true",
                    help="emit diagnostics as a flat JSON list (legacy; "
                         "prefer --format=json)")
    lt.add_argument("--format", choices=["text", "json"], default="text",
                    help="output format; json emits the stable schema "
                         "{version, findings[], summary} on pure stdout "
                         "(exit codes: 0 clean, 1 findings at/above "
                         "--fail-on, 2 usage error)")
    lt.add_argument("--explain", action="store_true",
                    help="annotate each finding's variable with its "
                         "def-use chain (defined / redefined / last "
                         "read sites) from the dataflow plane")
    lt.add_argument("--mesh-axes", default=None, dest="mesh_axes",
                    help="comma-separated valid sharding axis names "
                         "(default: parallel.mesh.CANONICAL_ORDER, with "
                         "unknown axes reported as warnings)")
    lt.set_defaults(fn=cmd_lint)

    mm = sub.add_parser("merge_model")
    common(mm)
    mm.add_argument("--model_path", required=True)
    mm.add_argument("--output_dir", required=True)
    mm.set_defaults(fn=cmd_merge_model)

    md = sub.add_parser("make_diagram")
    common(md)
    md.add_argument("--output", default=None)
    md.set_defaults(fn=cmd_make_diagram)

    pf = sub.add_parser("profile", help="run N profiled steps and print a "
                        "top-k per-op device report with Program-site "
                        "attribution (obs/xplane.py; docs/design/"
                        "observability.md)")
    pf.add_argument("--config", default=None,
                    help="profile this config's training step")
    pf.add_argument("--decode", default=None, metavar="B,PROMPT,NEW",
                    help="profile a fused-decode serve workload instead: "
                         "batch, prompt length, new tokens (random-init "
                         "TransformerLM from the model flags + --seed)")
    pf.add_argument("--steps", type=int, default=3,
                    help="profiled steps (the report amortizes over them)")
    pf.add_argument("--warmup", type=int, default=2,
                    help="unprofiled steps first, so compiles stay out")
    pf.add_argument("--topk", type=int, default=15)
    pf.add_argument("--trace-dir", default=None, dest="trace_dir",
                    help="keep the raw profiler output here (default: a "
                         "fresh temp dir; the .xplane.pb path prints)")
    pf.add_argument("--vocab", type=int, default=256)
    pf.add_argument("--d_model", type=int, default=128)
    pf.add_argument("--n_heads", type=int, default=4)
    pf.add_argument("--n_layers", type=int, default=2)
    pf.add_argument("--max_len", type=int, default=512)
    pf.add_argument("--kv_dtype", choices=["int8"], default=None)
    pf.add_argument("--seed", type=int, default=0)
    pf.set_defaults(fn=cmd_profile)

    cg = sub.add_parser("checkgrad")
    common(cg)
    cg.add_argument("--eps", type=float, default=5e-3)
    cg.add_argument("--rtol", type=float, default=5e-2)
    cg.add_argument("--checks_per_param", type=int, default=3)
    cg.set_defaults(fn=cmd_checkgrad)

    ct = sub.add_parser("cluster_train")
    ct.add_argument("script", help="training script run by every worker")
    ct.add_argument("script_args", nargs="*",
                    help="args passed through to the script (put them after "
                         "a -- separator if they start with a dash)")
    # None default = "not passed": --hosts mode warns on ANY explicit value
    # (a hard-coded sentinel of 2 could not tell `--num_workers 2` from the
    # default); local mode resolves it to 2
    ct.add_argument("--num_workers", type=int, default=None)
    ct.add_argument("--devices_per_worker", type=int, default=0,
                    help="force N virtual CPU devices per worker (testing; "
                         "0 = use the worker's real accelerators)")
    ct.add_argument("--timeout", type=float, default=600.0)
    ct.add_argument("--grace", type=float, default=10.0,
                    help="seconds survivors get to run their teardown hook "
                         "(SIGTERM) before SIGKILL when a peer fails")
    ct.add_argument("--restart-on-failure", type=int, default=0,
                    metavar="N", dest="restart_on_failure",
                    help="elastic recovery: relaunch the whole job (fresh "
                         "coordinator, scripts resume from their latest "
                         "checkpoint) up to N times after a worker failure")
    ct.add_argument("--hosts", default=None,
                    help="comma-separated host list: launch one node per "
                         "host over ssh (multi-host jax.distributed mode)")
    ct.add_argument("--hostfile", default=None,
                    help="file with one host per line ('#' comments) — the "
                         "reference launcher's conf.py HOSTS")
    ct.add_argument("--ssh-template", default=None, dest="ssh_template",
                    help="per-host command template with {host} and {cmd} "
                         "placeholders (default: \"ssh {host} {cmd}\"); "
                         "e.g. \"ssh -p 2222 -i key {host} {cmd}\" or "
                         "\"bash -c {cmd}\" for local testing")
    ct.add_argument("--coordinator-port", type=int, default=7164,
                    dest="coordinator_port",
                    help="jax.distributed coordinator port on node 0's host "
                         "(the reference's PADDLE_PORT)")
    ct.add_argument("--remote-python", default="python3",
                    dest="remote_python",
                    help="python interpreter to invoke on each host")
    ct.add_argument("--dry-run", action="store_true", dest="dry_run",
                    help="print the rendered per-host commands and exit "
                         "(for inspection or external schedulers)")
    ct.set_defaults(fn=cmd_cluster_train)

    ob = sub.add_parser("obs", help="inspect/convert/serve observability "
                                    "dumps (JSONL from ObsSession.save / "
                                    "train --obs_out / flight recorder)")
    obsub = ob.add_subparsers(dest="obs_cmd", required=True)
    os_ = obsub.add_parser("summary", help="human metric/span table "
                                           "(subsumes StatSet.report)")
    os_.add_argument("--input", required=True, action="append",
                     help="JSONL dump to summarize (repeat to merge a "
                          "multi-process run into one cluster view)")
    os_.set_defaults(fn=cmd_obs)
    oe = obsub.add_parser("export", help="convert the dump(s) for other "
                                         "tools")
    oe.add_argument("--input", action="append",
                    help="JSONL dump to convert (repeat to merge: one "
                         "Chrome lane per process + client->server flow "
                         "arrows)")
    oe.add_argument("--xplane", action="append", metavar="TRACE.xplane.pb",
                    help="merge a jax.profiler trace's device planes as "
                         "extra process lanes beside the host spans "
                         "(paddle_tpu profile writes one)")
    oe.add_argument("--format", choices=["chrome", "prom", "jsonl"],
                    default="chrome",
                    help="chrome: trace_event JSON for Perfetto; prom: "
                         "Prometheus text; jsonl: normalized stream")
    oe.add_argument("--output", default=None,
                    help="output path (default: stdout)")
    oe.set_defaults(fn=cmd_obs)
    osv = obsub.add_parser("serve", help="read-only HTTP endpoint: /metrics "
                                         "(prometheus), /trace (chrome "
                                         "json), /summary")
    osv.add_argument("--input", action="append",
                     help="JSONL dump(s) to serve (re-read per request)")
    osv.add_argument("--master", default=None,
                     help="host:port of a live MasterServer — serve its "
                          "merged obs_push fleet view")
    osv.add_argument("--host", default="127.0.0.1")
    osv.add_argument("--port", type=int, default=0,
                     help="0 binds an ephemeral port (printed on start)")
    osv.set_defaults(fn=cmd_obs_serve)
    otr = obsub.add_parser("trace", help="print one request's stitched "
                                         "cross-worker timeline (phases, "
                                         "re-route legs, TTFT breakdown)")
    otr.add_argument("key", help="submit_key to trace (a re-route leg key "
                                 "like KEY#r1 resolves to its base request)")
    otr.add_argument("--input", action="append",
                     help="JSONL dump(s) holding request timelines "
                          "(--obs_out files, flight rings)")
    otr.add_argument("--master", default=None,
                     help="host:port of a live MasterServer — trace from "
                          "its aggregated request store")
    otr.set_defaults(fn=cmd_obs_trace)
    ot = obsub.add_parser("top", help="live per-worker fleet table: "
                                      "goodput, mfu, queue, straggler "
                                      "score, active alerts")
    ot.add_argument("--input", action="append",
                    help="JSONL dump(s) to read (re-read per refresh)")
    ot.add_argument("--master", default=None,
                    help="host:port of a live MasterServer — renders its "
                         "obs_stats + obs_health fleet view")
    ot.add_argument("--interval", type=float, default=2.0,
                    help="refresh period in seconds")
    ot.add_argument("--once", action="store_true",
                    help="print one table and exit (scripts, tests)")
    ot.set_defaults(fn=cmd_obs_top)

    sv = sub.add_parser("serve", help="serving daemon: paged KV-cache "
                        "continuous batching behind the native RPC plane "
                        "(srv_submit/srv_poll/srv_cancel; "
                        "docs/design/serving.md)")
    sv.add_argument("--config", default=None,
                    help="Python script exposing `model` and `params`: "
                    "any model that states "
                    "its cache rows and slot rows and offers prefill and "
                    "decode_step_paged (TransformerLM, DeepseekV3LM, "
                    "Lfm2MoeLM; the interface is in `serve`'s docstring); "
                    "weights and cache keep the dtype of `params`; omitted "
                    "= random-init TransformerLM from the flags")
    sv.add_argument("--host", default="127.0.0.1")
    sv.add_argument("--port", type=int, default=0)
    sv.add_argument("--vocab", type=int, default=50257)
    sv.add_argument("--d_model", type=int, default=768)
    sv.add_argument("--n_heads", type=int, default=12)
    sv.add_argument("--n_layers", type=int, default=12)
    sv.add_argument("--max_len", type=int, default=1024)
    sv.add_argument("--seed", type=int, default=0)
    sv.add_argument("--slots", type=int, default=8)
    sv.add_argument("--segment", type=int, default=32)
    sv.add_argument("--page_block", type=int, default=64,
                    help="KV page size in tokens; must divide --max_len "
                         "and --cache_bucket")
    sv.add_argument("--pages", type=int, default=None,
                    help="pool pages incl. the null page (default: worst "
                    "case slots*max_len/page_block + 1)")
    sv.add_argument("--cache_bucket", type=int, default=256)
    sv.add_argument("--prompt_buckets", type=_int_list,
                    default=(32, 64, 128, 256, 512), metavar="N,N,...",
                    help="ascending prompt lengths an admission is padded "
                    "to, one compiled admit program each; the longest "
                    "bounds nothing: a longer prompt compiles its own "
                    "length")
    sv.add_argument("--kv_dtype", choices=["int8"], default=None)
    sv.add_argument("--queue_cap", type=int, default=64)
    sv.add_argument("--no_prefix_cache", action="store_true",
                    help="disable the copy-on-write prefix radix index "
                    "(default ON for the daemon: requests sharing a "
                    "prompt prefix share KV pages and prefill only the "
                    "suffix; docs/design/serving.md); required for a model "
                    "without prefill_paged (DeepseekV3LM, Lfm2MoeLM)")
    sv.add_argument("--interactive_weight", type=float, default=4.0,
                    help="weighted-fair service share of slo=interactive "
                    "requests vs slo=batch (deficit scheduling at slot "
                    "assignment)")
    sv.add_argument("--batch_weight", type=float, default=1.0)
    sv.add_argument("--max_tenants", type=int, default=32,
                    help="distinct tenant labels this daemon will mint "
                    "metric series for (bounded-cardinality contract; "
                    "further tenants are refused at submit)")
    sv.add_argument("--request_timeout", type=float, default=None,
                    help="default per-request deadline (seconds); "
                    "timed-out requests free their slot and pages")
    sv.add_argument("--drain", type=float, default=10.0,
                    help="seconds to let in-flight requests finish (and "
                    "clients collect them) on SIGTERM before severing "
                    "connections; 0 = stop immediately")
    sv.add_argument("--obs_out", default=None)
    sv.add_argument("--router", default=None, metavar="HOST:PORT",
                    help="join this serving router's membership table "
                    "(paddle_tpu route); the router then places client "
                    "submits here by windowed health trends")
    sv.add_argument("--role", choices=["decode", "prefill"],
                    default="decode",
                    help="decode (default): the full engine; prefill: a "
                    "pool-only worker that admits prompts, exports the "
                    "KV pages and ships them to the router-chosen "
                    "decode worker (requires --router)")
    sv.add_argument("--worker", default=None,
                    help="membership worker name (default: "
                    "serve-<port> / prefill-<port>)")
    sv.set_defaults(fn=cmd_serve)

    rt = sub.add_parser("route", help="serving router: model-free "
                        "placement over joined prefill/decode serving "
                        "workers — health-trend spread, backpressure "
                        "aggregation, re-route on eviction "
                        "(docs/design/serving.md)")
    rt.add_argument("--host", default="127.0.0.1")
    rt.add_argument("--port", type=int, default=0)
    rt.add_argument("--ttl", type=float, default=3.0,
                    help="membership lease TTL (seconds); workers "
                    "heartbeat at ttl/3 and are evicted — their streams "
                    "re-routed — after ttl without one")
    rt.add_argument("--scrape_interval", type=float, default=0.25,
                    help="seconds between srv_stats health scrapes (the "
                    "windowed trend data placement scores read)")
    rt.add_argument("--obs_out", default=None)
    rt.set_defaults(fn=cmd_route)

    cl = sub.add_parser("cluster", help="fleet lifecycle: the actor that "
                        "closes the autoscale loop (docs/design/fleet.md)")
    clsub = cl.add_subparsers(dest="cluster_cmd", required=True)
    ca = clsub.add_parser("autoscale", help="watch the membership + "
                          "health planes and spawn/drain workers to the "
                          "hysteresis-stable recommendation and SLO "
                          "burn-rate alerts")
    ca.add_argument("--actor", default="autoscale-actor",
                    help="actor name for act_register (single-writer: a "
                    "newer registration deposes this one)")
    ca.add_argument("--train-master", dest="train_master", default=None,
                    metavar="HOST:PORT",
                    help="elastic master whose membership/recommendation "
                    "drives the training population")
    ca.add_argument("--train-cmd", dest="train_cmd", default=None,
                    help="training-worker launch template with a {worker} "
                    "placeholder ({python} expands to this interpreter), "
                    "e.g. '{python} -m paddle_tpu train --config c.py "
                    "--elastic worker --master_addr H:P "
                    "--worker_id {worker}'")
    ca.add_argument("--train-min", dest="train_min", type=int, default=1)
    ca.add_argument("--train-max", dest="train_max", type=int, default=8)
    ca.add_argument("--router", default=None, metavar="HOST:PORT",
                    help="serving router whose decode pool the actor "
                    "keeps at --decode-target (scaling out on TTFT/TPOT "
                    "SLO burn)")
    ca.add_argument("--decode-cmd", dest="decode_cmd", default=None,
                    help="decode-worker launch template with a {worker} "
                    "placeholder, e.g. '{python} -m paddle_tpu serve "
                    "--router H:P --worker {worker} ...'")
    ca.add_argument("--decode-target", dest="decode_target", type=int,
                    default=1, help="steady-state decode pool size")
    ca.add_argument("--serve-min", dest="serve_min", type=int, default=1)
    ca.add_argument("--serve-max", dest="serve_max", type=int, default=8)
    ca.add_argument("--interval", type=float, default=1.0,
                    help="seconds between actor ticks")
    ca.add_argument("--cooldown", type=float, default=5.0,
                    help="per-(population, action) cooldown: damping on "
                    "top of the recommendation's hysteresis")
    ca.add_argument("--max-churn", dest="max_churn", type=int, default=1,
                    help="max concurrent in-flight spawns+drains across "
                    "the whole fleet")
    ca.add_argument("--spawn-grace", dest="spawn_grace", type=float,
                    default=30.0, help="seconds a spawned worker gets to "
                    "appear in membership before the spawn counts failed")
    ca.add_argument("--drain-grace", dest="drain_grace", type=float,
                    default=30.0, help="seconds a draining worker gets to "
                    "leave membership before escalation to kill")
    ca.add_argument("--total-workers", dest="total_workers", type=int,
                    default=None,
                    help="shared fleet budget: when set, populations "
                    "compete through the weighted-fair deficit scheduler "
                    "and training yields to serving on SLO burn")
    ca.add_argument("--once", action="store_true",
                    help="run one control tick, print committed actions, "
                    "exit (scripts, tests)")
    ca.set_defaults(fn=cmd_cluster_autoscale)

    v = sub.add_parser("version")
    v.set_defaults(fn=cmd_version)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
