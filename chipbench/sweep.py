"""Find a serve cell's knee once, on the chip, with ONE set-up for all rates:

    python -m chipbench.sweep --workload <cell> --rates 2,3,4,5,6,7 \
        --seconds 30 --seed 1 [--out sweep.json]

The daemon is started and warmed as in a run; then each rate in turn offers
the cell's traffic mix for ``--seconds`` and the table says what the open
loop left behind: the backlog at the end of the window (requests sent and
not finished, and of those how many were still queued), tokens a second,
and the tails. The knee is the highest rate at which the backlog does not
grow over the window; the cell's fixed rate is 0.8 of it and is written,
with the table, into the traffic file's ``arrivals`` (``rate_per_s``,
``sweep``). Nothing here is part of a run.
"""

import argparse
import copy
import json
import os
import sys
import tempfile
import time

from chipbench import harness
from chipbench.modes import serve


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None)
    ap.add_argument("--tiny", action="store_true")
    a = ap.parse_args(argv)
    log = lambda m: print(m, flush=True)
    harness.pin_compile_cache()
    loaded = harness.load_cell(a.workload)
    if a.tiny:
        from chipbench import run as run_mod
        run_mod.apply_tiny(loaded)
    cell, config = loaded["cell"], loaded["config"]
    gen = harness.generator_for(loaded)
    seed = harness.program_seed(a.seed)
    rates = [float(r) for r in a.rates.split(",")]
    mixes = []
    for r in rates:
        t = copy.deepcopy(loaded["traffic"])
        t["arrivals"]["rate_per_s"] = r
        mixes.append(gen.generate(t, seed + len(mixes), a.seconds,
                                  config["vocab_size"]))
    plan = serve.warmup_plan(gen.length_range(loaded["traffic"]),
                             cell["flags"], config["n_positions"])
    table = []
    with tempfile.TemporaryDirectory(prefix="chipbench_sweep_") as work:
        args = argparse.Namespace(seed=a.seed, seconds=a.seconds, trace=False,
                                  rehearsal=a.tiny, t_start=time.time(),
                                  work_dir=work)
        os.environ["JAX_PLATFORMS"] = "cpu"
        everything = [r for m in mixes for r in m]
        daemon, ctl, addr = serve.start_and_warm(loaded, args, everything,
                                                 plan, log)
        try:
            for rate, requests in zip(rates, mixes):
                backlog = {}

                def at_close(t0, records):
                    s = ctl.serving_stats()
                    sent = [r for r in records if r["sent"] is not None]
                    backlog.update(
                        unfinished=sum(1 for r in sent if r["n"] < r["want"]),
                        queued=int(s["queue_depth"]),
                        slots_live=int(s["slots_live"]))
                t0, records = serve.offer(addr, requests, a.seconds,
                                          f"s{rate}", cell["drain_s"], log,
                                          at_close=at_close)
                s = serve.summarise(records, a.seconds)
                row = dict(rate_per_s=rate, **backlog, **{
                    k: s[k] for k in ("attempted", "failed",
                                      "serve_tokens_per_s", "ttft_p50_ms",
                                      "ttft_p95_ms", "tpot_p50_ms",
                                      "tpot_p95_ms", "lag_max_ms")})
                table.append(row)
                log("SWEEP " + json.dumps(row))
                if backlog["queued"] > 2 * cell["flags"]["slots"]:
                    log("backlog far past the slots: higher rates can only "
                        "be worse; the sweep ends here")
                    break
                serve._drained(ctl, timeout=120.0)
        finally:
            ctl.close()
            log(f"daemon exit code {daemon.stop()}")
    if a.out:
        with open(a.out, "w") as f:
            json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
