"""mode ``serve_lfm2``: chipbench/modes/serve.py's run — the same daemon
child, warm-up plan, open loop, sampling and comparison — for a cell whose
configuration is of the ``lfm2_moe`` family.

As modes/serve_deepseek_v3.py does for its family (chipbench/configs/
README.md says why a family brings a mode): a ``Daemon`` that starts
``paddle_tpu serve`` on chipbench/serve_model_lfm2.py with the cell's flags
— among them ``--prompt_buckets`` (the cell's prompts reach 4,096 tokens;
``serve``'s defaults stop at 512) and ``--no_prefix_cache`` (a convolution's
tail cannot be shared by page: the pool refuses a prefix index over a model
without suffix admission) — and a ``run_reference`` that starts
chipbench/ref_child_lfm2.py. While ``serve.run`` (or the knee sweep) runs,
the two names it looks up are these.

The knee sweep of a cell of this mode:

    python -m chipbench.modes.serve_lfm2 --workload <cell> \
        --rates 6,8,10,12 --seconds 50 --seed 1 [--out sweep.json]

is chipbench/sweep.py under the same two names: the knee is the highest
rate whose backlog does not grow.
"""

import contextlib
import json
import os
import subprocess
import sys
import threading
from unittest import mock

from chipbench import harness
from chipbench.modes import serve

MODEL_SCRIPT = "serve_model_lfm2.py"
REF_CHILD = "chipbench.ref_child_lfm2"


class Daemon(serve.Daemon):
    """serve.Daemon with this family's model script and the cell's own
    flag list; ``_pump``, ``wait_up`` and ``stop`` are inherited."""

    def __init__(self, loaded, args, run_dir, log, extra_env=None):
        cell, flags = loaded["cell"], loaded["cell"]["flags"]
        self.log, self.run_dir = log, run_dir
        self.obs_out = os.path.join(run_dir, "serve_obs.jsonl")
        spec = {"config": loaded["config"],
                "seed": harness.program_seed(args.seed)}
        env = dict(os.environ, CHIPBENCH_MODEL_SPEC=json.dumps(spec),
                   PYTHONPATH=os.pathsep.join(
                       [loaded["root"]] + [p for p in [os.environ.get(
                           "PYTHONPATH")] if p]))
        env.pop("JAX_PLATFORMS", None)
        if args.rehearsal:
            env["JAX_PLATFORMS"] = "cpu"
        env.update(extra_env or {})
        cmd = [sys.executable, "-m", "chipbench.serve_child",
               "--run_dir", run_dir]
        if args.trace:
            cmd += ["--trace_seconds", str(cell["trace_seconds"])]
        if args.rehearsal:
            cmd.append("--rehearsal")
        cmd += ["--", "serve", "--config",
                os.path.join(loaded["base"], MODEL_SCRIPT),
                "--obs_out", self.obs_out, "--prompt_buckets",
                ",".join(str(b) for b in flags["prompt_buckets"])]
        if flags.get("no_prefix_cache"):
            cmd.append("--no_prefix_cache")
        for k in ("slots", "pages", "segment", "page_block", "cache_bucket",
                  "queue_cap"):
            cmd += [f"--{k}", str(flags[k])]
        log("daemon: " + " ".join(cmd[2:]))
        self.proc = subprocess.Popen(cmd, cwd=loaded["root"], env=env,
                                     text=True, stdout=subprocess.PIPE)
        self.addr, self._up = None, threading.Event()
        threading.Thread(target=self._pump, daemon=True).start()


def run_reference(spec, work_dir, root, rehearsal, timeout, tag="ref"):
    """harness.run_reference over this family's reference child."""
    spec_path = os.path.join(work_dir, f"{tag}_spec.json")
    out_path = os.path.join(work_dir, f"{tag}_out.json")
    with open(spec_path, "w") as f:
        json.dump(dict(spec, rehearsal=rehearsal), f)
    env = dict(os.environ, PYTHONPATH=root)
    env.pop("JAX_PLATFORMS", None)
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-m", REF_CHILD, spec_path,
                        out_path], cwd=root, env=env, timeout=timeout)
    if r.returncode == 3:       # the child found no chip and said so
        raise SystemExit(3)
    if r.returncode != 0:
        raise harness.BenchError(
            f"the reference child ended with {r.returncode}")
    return harness.load_json(out_path)


@contextlib.contextmanager
def family():
    """serve.py's two family-bound names, for as long as it runs."""
    with mock.patch.object(serve, "Daemon", Daemon), \
            mock.patch.object(harness, "run_reference", run_reference):
        yield


def run(loaded, args, log=print, **kw):
    with family():
        return serve.run(loaded, args, log=log, **kw)


def sweep(argv=None):
    from chipbench import sweep as sweep_mod
    with family():
        return sweep_mod.main(argv)


if __name__ == "__main__":
    sys.exit(sweep())
