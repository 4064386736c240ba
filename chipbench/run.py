"""The one command:

    python -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run is one new process tree on a machine that holds the cell's chips.
The last line of stdout is the result object; everything else (device,
routes, generator lag, the numbers compared beside their limits,
compilations inside the window) goes on earlier lines. Without the chips
the cell asks for the run exits non-zero and prints no result.

``--tiny`` rehearses the control flow at the cell's ``tiny`` sizes on
whatever JAX finds (the CPU here); its line always says ``correct: false``
and it exits 4: a rehearsal is never a measurement.
"""

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time

T_START = time.time()

from chipbench import harness  # noqa: E402


def log(msg):
    print(msg, flush=True)


def apply_tiny(loaded):
    """The cell at its rehearsal sizes (``tiny`` in the cell file)."""
    tiny = loaded["cell"].get("tiny")
    if tiny is None:
        raise harness.BenchError(f"cell {loaded['name']} has no tiny sizes")
    for part in ("config", "traffic", "cell"):
        for key, value in tiny.get(part, {}).items():
            if isinstance(value, dict) and isinstance(
                    loaded[part].get(key), dict):
                loaded[part][key] = dict(loaded[part][key], **value)
            else:
                loaded[part][key] = value
    return loaded


def run_cell(name, seed, seconds, trace, rehearsal=False, root=None,
             log=log, t_start=None, **mode_kw):
    """Load the cell by name, run its mode, read its metrics; returns
    (result line dict, the mode's raw result)."""
    loaded = harness.load_cell(name, root)
    if rehearsal:
        apply_tiny(loaded)
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=bool(trace),
                              rehearsal=rehearsal,
                              t_start=t_start or time.time())
    log(f"cell {name}: config {loaded['cell']['config']}, traffic "
        f"{loaded['cell']['traffic']}, mode {loaded['cell']['mode']}, "
        f"seed {seed}, {seconds}s, trace {int(bool(trace))}"
        + (" [REHEARSAL at tiny sizes: not a measurement]"
           if rehearsal else ""))
    with tempfile.TemporaryDirectory(prefix="chipbench_") as work:
        args.work_dir = work
        try:
            raw = harness.mode_for(loaded).run(loaded, args, log=log,
                                               **mode_kw)
        finally:
            _keep(work, name, seed, log)
    if trace:
        metrics = harness.read_layer_metrics(loaded, raw["ctx"], log)
    else:
        metrics = {m["name"]: {"value": float(raw["values"][m["name"]]),
                               "unit": m["unit"]}
                   for m in loaded["end_to_end"]}
    line = {"correct": all(ok for *_, ok in raw["checks"]),
            "attempted": int(raw["attempted"]), "failed": int(raw["failed"]),
            "metrics": metrics, "device": raw["device"]}
    if trace and raw.get("breakdown"):
        line["breakdown"] = raw["breakdown"]
    return line, raw


def _keep(work, name, seed, log):
    """``CHIPBENCH_KEEP=<dir>``: copy the run's trace, obs dump and child
    report there before the work directory goes (a debugging aid; the
    driver never sets it)."""
    dest = os.environ.get("CHIPBENCH_KEEP")
    if not dest:
        return
    dest = os.path.join(dest, f"{name}-{seed}")
    os.makedirs(dest, exist_ok=True)
    for pat in ("**/*.xplane.pb", "*.jsonl", "*.json"):
        for f in glob.glob(os.path.join(work, pat), recursive=True):
            shutil.copy(f, dest)
    log(f"kept the run's artifacts in {dest}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse at the cell's tiny sizes; never correct")
    a = ap.parse_args(argv)
    harness.pin_compile_cache()
    try:
        seconds = a.seconds if a.seconds is not None else \
            harness.load_benchmark()["run_seconds"]
        line, _ = run_cell(a.workload, a.seed, seconds, a.trace,
                           rehearsal=a.tiny, t_start=T_START)
    except harness.BenchError as e:
        log(f"chipbench: {e}. No result.")
        return 2
    if a.tiny:
        line["correct"] = False
        line["rehearsal"] = True
        print(json.dumps(line), flush=True)
        return 4
    if line["device"]["platform"] == "cpu":
        log("chipbench: the run ended on a CPU. No result.")
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
