#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that paddle_tpu's two main paths run on
a TPU: GPT-2-small training through ``paddle_tpu.Trainer`` and serving through
the ``python -m paddle_tpu serve`` daemon, at full width, from a seed.

    python chip_smoke.py             # one chip: train phase, then serve phase
    python chip_smoke.py --chips 4   # four chips: ONLY the sharded trainer
                                     # and the one-device run it is compared to
    python chip_smoke.py --tiny      # control-flow rehearsal at a toy size;
                                     # runs on the CPU, can never print ok:true

The last line of stdout is one JSON object. On success it is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``
with the device as a child process reported it from ``jax.devices()``;
otherwise ``"ok": false`` and a non-zero exit. A CPU is never reported as
the chip.

This parent process never imports JAX: a chip belongs to one process at a
time, so every phase is a child that takes the chip, finishes and exits
before the next starts. Children print their facts as one ``RESULT {json}``
line; the parent holds them to the checks below.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# GPT-2-small as the repo benchmarks it (benchmarks/transformer_lm.py,
# benchmarks/serving_decode.py); --tiny keeps every code path and cuts sizes
FULL = dict(vocab=32768, d_model=768, n_heads=12, n_layers=12, max_len=1024,
            batch=8, train_steps=8, mesh_steps=3,
            prompt_lens=(32, 96, 160, 256), new_tokens=64,
            slots=8, segment=32, page_block=64, cache_bucket=512)
TINY = dict(vocab=128, d_model=32, n_heads=4, n_layers=2, max_len=256,
            batch=4, train_steps=6, mesh_steps=3,
            prompt_lens=(5, 9, 12, 16), new_tokens=8,
            slots=4, segment=4, page_block=8, cache_bucket=32)

#: The serve phase compares two DIFFERENT programs token for token
#: (generate_cached: dense-row cache, 256-row kernel chunks; the daemon:
#: paged pools, 64-row pages, its own prefill). ``serve`` has no dtype flag,
#: so weights are f32 and at the TPU's default precision every f32 matmul
#: runs as bf16 passes: 1e-6 differences between the two formulations flip
#: bf16 roundings downstream and, with random weights, greedy near-ties —
#: on the chip 3 of 4 requests diverged after 26-30 equal tokens (PERF.md,
#: PR 21). Both serve-phase children are therefore held to true-f32 matmuls,
#: by JAX's own environment variable; nothing else about them changes.
SERVE_ENV = {"JAX_DEFAULT_MATMUL_PRECISION": "highest"}

#: sharded-vs-one-device loss tolerance: the loosest the repo's own
#: sharded-vs-unsharded tests use (tests/test_transformer.py, 3e-4) — compute
#: here is bf16, where theirs is f32
MESH_RTOL = 3e-4


def log(msg):
    print(msg, flush=True)


# --------------------------------------------------------------- children --

def _child_setup(args):
    """Common child prologue: the device as JAX reports it, the compile
    cache, an obs session (kernel-route counters + the step's executable)."""
    import jax
    d = jax.devices()
    device = {"platform": d[0].platform, "kind": d[0].device_kind,
              "count": len(d)}
    if device["platform"] == "cpu" and not args.tiny:
        log(f"chip_smoke child: JAX found no accelerator ({device})")
        sys.exit(3)
    import paddle_tpu
    from paddle_tpu import obs
    from paddle_tpu.obs import roofline
    cache_dir = paddle_tpu.enable_compile_cache()
    session = obs.ObsSession().install()
    kind = device["kind"]
    facts = {"device": device, "cache_dir": cache_dir,
             # 0 = every compile below is cold
             "cache_entries_at_start": len(os.listdir(cache_dir)),
             "peaks_known": bool(roofline.PEAK_TFLOPS.get(kind))
             and bool(roofline.PEAK_HBM_GBPS.get(kind))}
    return jax, session, facts


def _routes(session):
    """kernels.routes_total as {"kernel/route": count}."""
    out = {}
    for s in session.registry.collect():
        if s.get("name") == "kernels.routes_total":
            lb = s["labels"]
            out[f"{lb['kernel']}/{lb['route']}"] = s["value"]
    return out


def _model(cfg):
    from paddle_tpu.models import TransformerLM
    return TransformerLM(cfg["vocab"], d_model=cfg["d_model"],
                         n_heads=cfg["n_heads"], n_layers=cfg["n_layers"],
                         max_len=cfg["max_len"])


def _train(args, cfg, steps, mesh_mode):
    """``steps`` Trainer steps over a seeded synthetic reader; bf16 compute
    on f32 master params + Adam as benchmarks/transformer_lm.py."""
    jax, session, facts = _child_setup(args)
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu import Trainer
    from paddle_tpu.optimizer import Adam
    from paddle_tpu.trainer import event

    model = _model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))

    def loss_fn(params, ids):
        p16 = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16)
            if a.dtype == jnp.float32 else a, params)
        return model.loss(p16, ids)

    # two seeded Zipf-token batches, cycled: the loss has something to
    # learn (the unigram skew, then the batches) within a few steps
    rs = np.random.RandomState(args.seed)
    batches = [np.minimum(rs.zipf(1.2, (cfg["batch"], cfg["max_len"])),
                          cfg["vocab"]).astype(np.int32) - 1
               for _ in range(2)]

    def reader():
        for i in range(steps):
            yield (batches[i % 2],)

    mesh = layout = None
    if mesh_mode == "sharded":
        # the mesh and layout benchmarks/sharded_gpt2.py builds: on four
        # devices tp 2 x fsdp 2; pos_embed pinned replicated
        from jax.sharding import PartitionSpec as P

        from benchmarks.sharded_gpt2 import build_mesh
        from paddle_tpu import parallel as pp
        mesh = build_mesh()
        layout = pp.SpecLayout(rules=[(r"pos_embed$", P())])
        facts["mesh"] = dict(mesh.shape)
    trainer = Trainer(loss_fn, Adam(3e-4), mesh=mesh, layout=layout)

    losses, stamps = [], [time.time()]

    def handler(e):
        if isinstance(e, event.EndIteration):
            losses.append(float(e.cost))
            stamps.append(time.time())

    params, _ = trainer.train(reader, params, event_handler=handler,
                              handle_signals=False)
    step_s = [b - a for a, b in zip(stamps, stamps[1:])]
    leaves = jax.tree_util.tree_leaves(params)
    # the executable the step really ran, from the cost ledger the obs
    # session filled: flash attention must be in it as the Pallas kernel
    step = trainer._dp._step if trainer._dp is not None else trainer._step
    texts = [c.as_text() for c, _ in step.ledger.values()
             if hasattr(c, "as_text")]
    facts.update(
        losses=losses, finite=bool(np.all(np.isfinite(losses))),
        falling=bool(losses and losses[-1] < losses[0]),
        first_step_s=round(step_s[0], 2),          # compile + 1 step
        steady_step_s=round(float(np.median(step_s[1:])), 4),
        param_platforms=sorted({dv.platform for a in leaves
                                for dv in a.devices()}),
        tpu_custom_call=bool(texts) and all("tpu_custom_call" in t
                                            for t in texts),
        routes=_routes(session))
    if mesh_mode == "sharded":
        # code that has never seen more than one chip may put everything on
        # the first: one fsdp-sharded and one tp-sharded leaf must sit on
        # as many distinct devices as the mesh has
        def spread(a):
            return {"spec": str(a.sharding.spec),
                    "devices": sorted({s.device.id
                                       for s in a.addressable_shards}),
                    "shard_shape": list(a.addressable_shards[0].data.shape),
                    "shape": list(a.shape)}
        facts["shards"] = {
            "embed/w": spread(params["embed"]["w"]),
            "blocks_0/mlp_in/w": spread(params["blocks_0"]["mlp_in"]["w"])}
    ok = facts["finite"] and len(losses) == steps
    if mesh_mode is None:
        ok = ok and facts["falling"]
    return facts, ok


def _prompts(args, cfg):
    import numpy as np
    rs = np.random.RandomState(args.seed)
    return [rs.randint(0, cfg["vocab"], (n,)).astype(np.int32)
            for n in cfg["prompt_lens"]]


def child_serve_ref(args, cfg):
    """Reference continuations: model.generate_cached on this device, the
    weights ``serve --seed`` builds (model.init(PRNGKey(seed)), f32)."""
    jax, session, facts = _child_setup(args)
    import numpy as np
    model = _model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    new = cfg["new_tokens"]
    gen = jax.jit(lambda p, x: model.generate_cached(p, x, new))
    t0 = time.time()
    tokens = []
    for prompt in _prompts(args, cfg):
        out = np.asarray(gen(params, prompt[None]))[0]
        tokens.append([int(t) for t in out[prompt.size:]])
    with open(args.out, "w") as f:
        json.dump(tokens, f)
    facts.update(ref_s=round(time.time() - t0, 2), routes=_routes(session),
                 n_tokens=[len(t) for t in tokens])
    return facts, all(len(t) == new for t in tokens)


CHILDREN = {
    "train": lambda a, c: _train(a, c, c["train_steps"], None),
    "serve_ref": child_serve_ref,
    "mesh_single": lambda a, c: _train(a, c, c["mesh_steps"], "single"),
    "mesh_sharded": lambda a, c: _train(a, c, c["mesh_steps"], "sharded")}


# ----------------------------------------------------------------- parent --

class Smoke:
    def __init__(self, args):
        self.args = args
        self.cfg = TINY if args.tiny else FULL
        self.devices = []
        self.failed = []
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = os.pathsep.join(
            [HERE] + [p for p in [self.env.get("PYTHONPATH")] if p])

    def fail(self, what):
        log(f"FAIL {what}")
        self.failed.append(what)

    def check(self, cond, what):
        if not cond:
            self.fail(what)
        return bool(cond)

    def child(self, name, *extra, timeout=900, env=None):
        """Run one phase child to its end; returns its RESULT facts or None."""
        cmd = [sys.executable, os.path.abspath(__file__), "--child", name,
               "--seed", str(self.args.seed), *extra]
        if self.args.tiny:
            cmd.append("--tiny")
        t0 = time.time()
        try:
            r = subprocess.run(cmd, cwd=HERE, env=dict(self.env, **(env or {})),
                               text=True, stdout=subprocess.PIPE,
                               timeout=timeout)
        except subprocess.TimeoutExpired:
            self.fail(f"{name}: no end within {timeout}s")
            return None
        wall = round(time.time() - t0, 1)
        facts = None
        for line in r.stdout.splitlines():
            if line.startswith("RESULT "):
                facts = json.loads(line[len("RESULT "):])
            else:
                log(f"  [{name}] {line}")
        log(f"phase {name}: exit {r.returncode}, wall {wall}s")
        if r.returncode != 0 or facts is None:
            self.fail(f"{name}: exit code {r.returncode}"
                      + ("" if facts else ", no RESULT line"))
            return None
        facts["wall_s"] = wall
        self.devices.append(facts["device"])
        log(f"  device {facts['device']} compile cache {facts['cache_dir']} "
            f"({facts['cache_entries_at_start']} entries at start)")
        self.check(facts["peaks_known"],
                   f"{name}: device_kind {facts['device']['kind']!r} is not "
                   "in obs/roofline.PEAK_TFLOPS / PEAK_HBM_GBPS")
        if facts.get("routes"):
            log(f"  kernels.routes_total {facts['routes']}")
        return facts

    # -- one chip ---------------------------------------------------------
    def phase_train(self):
        f = self.child("train")
        if f is None:
            return
        log(f"  losses {[round(x, 4) for x in f['losses']]}")
        log(f"  first step (compile + step) {f['first_step_s']}s, "
            f"steady step {f['steady_step_s']}s")
        self.check(f["param_platforms"] == ["tpu"],
                   f"train: params live on {f['param_platforms']}, not tpu")
        self.check(f["tpu_custom_call"],
                   "train: no tpu_custom_call in the step's compiled text "
                   "(flash attention is not the Pallas kernel)")

    def phase_serve(self):
        cfg = self.cfg
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            ref_path = os.path.join(tmp, "ref.json")
            f = self.child("serve_ref", "--out", ref_path, env=SERVE_ENV)
            if f is None:
                return
            log(f"  reference generate_cached: {f['ref_s']}s for "
                f"{len(cfg['prompt_lens'])} prompts (compiles included)")
            with open(ref_path) as fh:
                ref = json.load(fh)
            self.serve_daemon(tmp, ref)

    def serve_daemon(self, tmp, ref):
        cfg, args = self.cfg, self.args
        obs_out = os.path.join(tmp, "serve_obs.jsonl")
        cmd = [sys.executable, "-m", "paddle_tpu", "serve",
               "--vocab", cfg["vocab"], "--d_model", cfg["d_model"],
               "--n_heads", cfg["n_heads"], "--n_layers", cfg["n_layers"],
               "--max_len", cfg["max_len"], "--slots", cfg["slots"],
               "--segment", cfg["segment"],
               "--page_block", cfg["page_block"],
               "--cache_bucket", cfg["cache_bucket"],
               "--seed", args.seed, "--obs_out", obs_out]
        t0 = time.time()
        daemon = subprocess.Popen([str(c) for c in cmd], cwd=HERE,
                                  env=dict(self.env, **SERVE_ENV), text=True,
                                  stdout=subprocess.PIPE)
        lines, addr = [], []
        got_addr = threading.Event()

        def pump():
            for line in daemon.stdout:
                line = line.rstrip("\n")
                lines.append(line)
                log(f"  [serve] {line}")
                if line.startswith("SERVING "):
                    addr[:] = line.split()[1:3]
                    got_addr.set()
            got_addr.set()                      # EOF: the daemon is gone

        threading.Thread(target=pump, daemon=True).start()
        try:
            got_addr.wait(timeout=600)
            if not addr:
                self.fail("serve: no 'SERVING <host> <port>' line")
                return
            log(f"  daemon up after {round(time.time() - t0, 1)}s")
            self.drive(addr[0], int(addr[1]), ref)
        finally:
            if daemon.poll() is None:
                daemon.send_signal(signal.SIGTERM)
            try:
                rc = daemon.wait(timeout=120)
            except subprocess.TimeoutExpired:
                daemon.kill()
                rc = daemon.wait()
                self.fail("serve: daemon ignored SIGTERM for 120s")
            log(f"phase serve: daemon exit {rc}, wall "
                f"{round(time.time() - t0, 1)}s")
            self.check(rc == 0, f"serve: daemon exit code {rc}")
        routes = {}
        if os.path.exists(obs_out):
            with open(obs_out) as fh:
                for line in fh:
                    if '"kernels.routes_total"' in line:
                        s = json.loads(line)
                        lb = s.get("labels", {})
                        routes[f"{lb.get('kernel')}/{lb.get('route')}"] = \
                            s.get("value")
        log(f"  daemon kernels.routes_total {routes}")
        self.check(any(k.startswith("paged_decode_attention/")
                       for k in routes),
                   "serve: the daemon's obs dump names no "
                   "paged_decode_attention route")

    def drive(self, host, port, ref):
        """Send the prompts with ServingClient. JAX is pinned to the CPU in
        THIS process first: importing paddle_tpu.serving creates no device
        array (tests/test_chip_smoke.py checks it), and the pin makes sure
        the parent could not take the chip even if that changed."""
        os.environ["JAX_PLATFORMS"] = "cpu"
        sys.path.insert(0, HERE)
        from paddle_tpu.serving import ServingClient
        cfg = self.cfg
        prompts = _prompts(self.args, cfg)      # the reference child's own
        client = ServingClient(host, port)
        t0 = time.time()
        rids = [client.submit_with_backoff(p, cfg["new_tokens"],
                                           timeout_s=900.0)
                for p in prompts]
        got = [[] for _ in rids]
        reason = [None] * len(rids)
        first = [None] * len(rids)
        while any(r is None for r in reason):
            if time.time() - t0 > 900:
                self.fail("serve: requests not finished within 900s")
                break
            for i, rid in enumerate(rids):
                if reason[i] is not None:
                    continue
                toks, done, why = client.poll(rid, len(got[i]))
                if toks and first[i] is None:
                    first[i] = round(time.time() - t0, 2)
                got[i].extend(int(t) for t in toks)
                if done:
                    reason[i] = why
            time.sleep(0.05)
        client.close()
        log(f"  served {len(rids)} requests in "
            f"{round(time.time() - t0, 1)}s (first tokens at {first}s, "
            "compiles included)")
        for i, p in enumerate(prompts):
            same = got[i] == ref[i]
            diverge = next((j for j, (a, b) in enumerate(zip(got[i], ref[i]))
                            if a != b), None)
            log(f"  request {i}: prompt {p.size} tokens -> {len(got[i])} "
                f"tokens, reason={reason[i]!r}, equal to reference: {same}"
                + ("" if same else f" (first difference at {diverge})"))
            self.check(reason[i] is not None and reason[i] != "error",
                       f"serve: request {i} ended with reason={reason[i]!r}")
            self.check(len(got[i]) == cfg["new_tokens"],
                       f"serve: request {i} returned {len(got[i])} of "
                       f"{cfg['new_tokens']} tokens")
            self.check(same, f"serve: request {i} tokens differ from the "
                             "on-chip generate_cached reference")

    # -- four chips -------------------------------------------------------
    def phase_mesh(self):
        single = self.child("mesh_single")
        sharded = self.child("mesh_sharded")
        if single is None or sharded is None:
            return
        a, b = single["losses"], sharded["losses"]
        rel = max(abs(x - y) / abs(x) for x, y in zip(a, b))
        log(f"  one-device losses {a}")
        log(f"  sharded losses    {b}   mesh {sharded['mesh']}")
        log(f"  max relative difference {rel:.3g} (tolerance {MESH_RTOL})")
        self.check(rel <= MESH_RTOL,
                   f"mesh: sharded and one-device losses differ by {rel:.3g}")
        n = 1
        for v in sharded["mesh"].values():
            n *= v
        for name, s in sharded["shards"].items():
            log(f"  {name}: spec {s['spec']} shard {s['shard_shape']} of "
                f"{s['shape']} on devices {s['devices']}")
            self.check(len(s["devices"]) == n and n == self.args.chips,
                       f"mesh: {name} sits on devices {s['devices']}, "
                       f"not on {self.args.chips} distinct ones")
            self.check(s["shard_shape"] != s["shape"],
                       f"mesh: {name} is not sharded at all")
        for f in (single, sharded):
            self.check(f["tpu_custom_call"],
                       "mesh: no tpu_custom_call in a step's compiled text")

    def run(self):
        t0 = time.time()
        if self.args.chips == 4:
            self.phase_mesh()
        else:
            self.phase_train()
            self.phase_serve()
        device = self.devices[0] if self.devices else None
        self.check(device is not None, "no child reported a device")
        self.check(all(d == device for d in self.devices),
                   f"children reported different devices: {self.devices}")
        if device is not None:
            self.check(device["platform"] == "tpu",
                       f"the device is {device['platform']!r}, not a tpu")
            if self.args.chips == 4:
                self.check(device["count"] == 4,
                           f"--chips 4 ran on {device['count']} device(s)")
        log(f"total wall {round(time.time() - t0, 1)}s")
        if self.failed:
            print(json.dumps({"ok": False, "device": device,
                              "failed": self.failed}), flush=True)
            return 1
        print(json.dumps({"ok": True, "device": device}), flush=True)
        return 0


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        description="Run paddle_tpu's main paths once on the TPU and check "
                    "what comes out (see the module docstring).")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): train phase + serve phase on one "
                         "chip. 4: only the sharded Trainer and the "
                         "one-device run it is compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds weights, training batches and prompts")
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes for rehearsing the control flow without "
                         "a chip; the CPU is accepted by the children but "
                         "the result stays ok:false")
    ap.add_argument("--child", choices=sorted(CHILDREN), help=argparse.SUPPRESS)
    ap.add_argument("--out", help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if args.child:
        sys.path.insert(0, HERE)
        facts, ok = CHILDREN[args.child](args, TINY if args.tiny else FULL)
        print("RESULT " + json.dumps(facts), flush=True)
        return 0 if ok else 1
    return Smoke(args).run()


if __name__ == "__main__":
    sys.exit(main())
