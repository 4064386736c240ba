"""Flagship benchmarks — prints one JSON line per metric.

Secondary metrics first; LAST is always the flagship LSTM text-classification
row (BASELINE.md: 83 ms/batch @ bs=64, hidden=256 — benchmark/README.md:115-119),
the line a tail-parser records. vs_baseline > 1 means we are faster than the
reference by that factor.

Methodology notes live in each benchmarks/*.py docstring (varied lengths,
train-mode BN with stat updates, distinct rotating device-staged batches,
on-device-loop differencing timing).

**Every row runs in its own subprocess with a timeout.** A chip belongs to
one process at a time: this parent never initialises a JAX backend, each
row's child takes the chip, measures, and exits before the next starts, and
a row that hangs is killed at its limit so it costs a row, not the run. A row
that fails, times out or is skipped for lack of budget makes the exit code
non-zero — no other row is measured in its place.

**The flagship row is measured FIRST but printed LAST** via an atexit +
SIGTERM hook: if a caller's timeout reaps the run mid-suite, the final
printed line is still the flagship (only SIGKILL can break the contract).

Default run = one representative row per family. ``python bench.py --full``
runs every published reference row — use that when refreshing BASELINE.md.
"""

from __future__ import annotations

import atexit
import json
import os
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ROW_TIMEOUT = 420.0        # compile (~40-90 s) + measure, with slack
# host_embedding measured 110 s end-to-end once the native zero-fill path
# removed the 20 GB numpy+memcpy init (was ~90 s of the old ~200 s); 300
# declares honest headroom so the default budget run keeps the row
BIG_TIMEOUT = 300.0
# Global wall budget for the SECONDARY rows: the flagship is measured first
# and guaranteed; once the budget is gone the remaining secondaries are
# skipped (loudly, and the exit code says so). The full-suite refresh
# (--full) has no budget; the default's can be raised via env.
BUDGET_S = float(os.environ.get("PADDLE_TPU_BENCH_BUDGET_S", "1350"))


# the live row child, visible to the SIGTERM handler: when the run is killed
# the in-flight row's subprocess MUST die too, or it keeps the chip
_current_child = None

# span evidence riding along with the numbers: every row's subprocess runs
# under an ObsSession + flight recorder and saves its JSONL dump here, so a
# future perf trajectory can ask "where did the time go" of any past
# BENCH_*.json row (inspect: paddle_tpu obs summary --input <file>).
# Set PADDLE_TPU_BENCH_OBS_DIR="" to disable.
#
# Measurement-conditions note (rows from PR 4 on): the session is live
# DURING the timed loops, so instrumented paths (obs.span/obs.count call
# sites) pay the recording path — a few µs per event against multi-ms
# batches, and zero for the raw-jax device loops most rows time. When
# comparing against pre-PR-4 BENCH_*.json rows, treat sub-percent deltas
# on instrumented paths as noise from this change, not a regression.
OBS_DIR = os.environ.get("PADDLE_TPU_BENCH_OBS_DIR", ROOT)


def _slug(expr: str) -> str:
    """Stable filesystem tag for a row expression. The short expr digest
    keeps parameterized rows (bench_row('alexnet', 256) vs ('googlenet',
    128)) from overwriting each other's span-evidence dumps."""
    import hashlib
    import re
    m = re.findall(r"benchmarks\.(\w+)|\.(\w+)\(", expr)
    parts = [a or b for a, b in m]
    digest = hashlib.md5(expr.encode()).hexdigest()[:6]
    return ("_".join(parts) or "row") + "_" + digest


def _capture_row(expr: str, timeout: float = ROW_TIMEOUT) -> list:
    """Run one bench row in a subprocess of its own; return its JSON lines
    (empty when the row failed or ran past ``timeout``)."""
    global _current_child
    obs_prelude = obs_coda = ""
    if OBS_DIR:
        obs_path = os.path.join(OBS_DIR, f"BENCH_OBS_{_slug(expr)}.jsonl")
        # flight recorder armed first: a row killed at its time limit
        # cannot dump (nothing survives SIGKILL), but a row that dies on
        # an exception leaves its span ring behind
        obs_prelude = (
            "from paddle_tpu import obs as _obs\n"
            "_s = _obs.ObsSession(registry=_obs.MetricsRegistry())"
            ".install()\n"
            f"_fr = _obs.FlightRecorder(_s, {obs_path!r}).arm()\n")
        # never let a telemetry write discard a completed measurement: the
        # JSON result lines print even if the dump path is unwritable
        obs_coda = ("_fr.disarm()\n_s.uninstall()\n"
                    "try:\n"
                    f"    _s.save({obs_path!r})\n"
                    "except Exception as _e:\n"
                    "    print('bench: obs dump failed:', _e, "
                    "file=sys.stderr)\n")
    # the compile cache before the row's first compile, by the repo's one
    # rule (paddle_tpu.enable_compile_cache)
    code = (f"import sys, json\nsys.path.insert(0, {ROOT!r})\n"
            "import paddle_tpu\npaddle_tpu.enable_compile_cache()\n"
            + obs_prelude
            + f"_r = {expr}\n"
            + obs_coda
            + "for _d in (_r if isinstance(_r, list) else [_r]):\n"
            "    print(json.dumps(_d), flush=True)\n")
    p = subprocess.Popen([sys.executable, "-c", code],
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                         text=True, cwd=ROOT)
    _current_child = p
    try:
        out, err = p.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        p.kill()
        p.communicate()
        print(f"bench: row {expr!r} timed out after {timeout:.0f}s — "
              "killed its process, chip freed", file=sys.stderr, flush=True)
        return []
    finally:
        _current_child = None
    lines = _validate_lines(
        expr, [l for l in out.splitlines() if l.startswith("{")])
    if not lines or p.returncode != 0:
        tail = "\n".join(err.splitlines()[-5:])
        print(f"bench: row {expr!r} failed rc={p.returncode}:\n{tail}",
              file=sys.stderr, flush=True)
        return []
    return lines


def _validate_lines(expr: str, lines: list) -> list:
    """Bench-row schema gate (benchmarks/schema.py): a malformed row is
    DROPPED loudly — and the row expression then retries/fails like any
    other row failure — instead of printing a dict that silently lacks the
    columns the trend tooling keys on. `paddle_tpu lint --bench-rows`
    runs the same check statically over saved BENCH files."""
    from benchmarks.schema import validate_row
    kept = []
    for line in lines:
        try:
            problems = validate_row(json.loads(line))
        except ValueError as e:
            problems = [f"not valid JSON: {e}"]
        if problems:
            print(f"bench: row {expr!r} emitted a malformed row "
                  f"(dropped): {'; '.join(problems)}\n  {line[:200]}",
                  file=sys.stderr, flush=True)
        else:
            kept.append(line)
    return kept


def _row(expr: str, timeout: float = ROW_TIMEOUT) -> bool:
    lines = _capture_row(expr, timeout)
    for line in lines:
        print(line, flush=True)
    return bool(lines)


# Representative rows per family for the default (driver-budget) run; the
# reference numbers live in the benchmarks' own tables (single source of
# truth — the keys here only SELECT rows).
QUICK_IMAGE_KEYS = {("alexnet", 256), ("googlenet", 128)}
QUICK_LSTM_KEYS = {(128, 512)}


def main(full: bool = False):
    t0 = time.monotonic()      # the budget covers the WHOLE run
    from benchmarks.image_suite import ROWS as IMAGE_ROWS
    from benchmarks.lstm_textcls import FLAGSHIP_METRIC
    from benchmarks.lstm_textcls import SUITE_ROWS as LSTM_ROWS

    # ---- the last-line contract is armed BEFORE any chip work: on ANY
    # exit (normal, SIGTERM/SIGINT from a caller's timeout, unhandled
    # exception) the last stdout line is the flagship row. The handler
    # uses raw os.write — a signal landing mid-print of a secondary row
    # would make print() raise CPython's reentrant-buffered-IO guard and
    # lose the line — and marks itself done only AFTER the write, so the
    # atexit copy retries if the handler ever failed. If the kill lands
    # before the flagship measurement finishes, an honest null-value row
    # is emitted (never a fabricated number). Only SIGKILL can break this.
    flagship = []          # JSON lines, filled once measured
    _done = []

    def _emit_flagship():
        if _done:
            return
        lines = flagship or [json.dumps(
            {"metric": FLAGSHIP_METRIC,
             "value": None, "unit": "ms/batch", "vs_baseline": None,
             "note": "killed before the flagship measurement completed"})]
        # leading \n: stdout may hold a partially-printed secondary row
        os.write(1, ("\n" + "\n".join(lines) + "\n").encode())
        _done.append(True)

    atexit.register(_emit_flagship)

    def _on_term(signum, frame):
        child = _current_child
        if child is not None:
            try:
                child.kill()     # free the chip before reporting success
            except OSError:
                pass
        _emit_flagship()
        # 128+signum: a reaped run must not be rc-indistinguishable from a
        # clean one — the tail JSON line stays the honest success signal,
        # the return code says HOW the process ended (driver contract,
        # docs/design/bench_contract.md)
        os._exit(128 + signum)

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)

    # ---- flagship FIRST (it is the cheapest row), printed LAST via the
    # hook above. If it fails the null row is the last line and the exit
    # code is non-zero: no other row stands in for it.
    failed = []            # row expressions that produced no row
    flagship_expr = "__import__('benchmarks.lstm_textcls', fromlist=['x']).run()"
    flagship += _capture_row(flagship_expr)
    if not flagship:
        failed.append(flagship_expr)

    # ---- secondary metrics, printed as they complete, within the budget
    image = [r for r in IMAGE_ROWS
             if full or (r[0], r[1]) in QUICK_IMAGE_KEYS]
    lstm = [r for r in LSTM_ROWS if full or (r[0], r[1]) in QUICK_LSTM_KEYS]

    rows = []
    for model_key, bs, ref in image:
        rows.append((f"__import__('benchmarks.image_suite', fromlist=['x'])"
                     f".bench_row({model_key!r}, {bs}, {ref})", ROW_TIMEOUT))
    for bs, hidden, ref in lstm:
        rows.append((f"__import__('benchmarks.lstm_textcls', fromlist=['x'])"
                     f".bench_row({bs}, {hidden}, {ref})", ROW_TIMEOUT))
    mods = ["transformer_lm", "resnet50", "seq2seq_nmt", "transformer_nmt",
            "serving_decode", "fluid_executor", "sharded_gpt2"]
    if full:
        mods.append("fused_rnn")
    for name in mods:
        rows.append((f"__import__('benchmarks.{name}', fromlist=['x'])"
                     ".run()", ROW_TIMEOUT))
    # the decode-roofline rows (ROADMAP item 3): int8-KV decode (cache
    # read halved) and speculative decoding (target weights stream once
    # per round) next to the full-precision decode row above
    rows.append(("__import__('benchmarks.serving_decode', fromlist=['x'])"
                 ".run_quantized()", ROW_TIMEOUT))
    rows.append(("__import__('benchmarks.speculative_decode', "
                 "fromlist=['x']).run()", ROW_TIMEOUT))
    rows.append(("__import__('benchmarks.serving_decode', fromlist=['x'])"
                 ".run_continuous()", ROW_TIMEOUT))
    # the serving-plane rows (ROADMAP item 2): paged-vs-pinned residency
    # on the same mixed workload, and the daemon's client-measured SLOs
    rows.append(("__import__('benchmarks.serving_decode', fromlist=['x'])"
                 ".run_paged()", ROW_TIMEOUT))
    rows.append(("__import__('benchmarks.serving_daemon', fromlist=['x'])"
                 ".run()", ROW_TIMEOUT))
    # the disaggregation row (ROADMAP item 2): 1 prefill + 2 decode pools
    # behind the serving router — client-measured SLOs over the real
    # wire, the ship/adopt hop priced into TTFT
    rows.append(("__import__('benchmarks.serving_router', fromlist=['x'])"
                 ".run()", ROW_TIMEOUT))
    # the prefix-cache rows (ROADMAP item 2): zipf shared-prefix workload
    # warm-vs-cold — TTFT p50 and prefill FLOPs/token vs hit rate
    rows.append(("__import__('benchmarks.serving_prefix', fromlist=['x'])"
                 ".run()", ROW_TIMEOUT))
    # the fleet-actor row (ROADMAP item 2): kill half the decode pool,
    # count alert windows until the actor restores membership + SLO
    rows.append(("__import__('benchmarks.fleet_autoscale', fromlist=['x'])"
                 ".run()", ROW_TIMEOUT))
    if full:
        # the remaining BASELINE.md rows, so a --full session covers the
        # whole measured table in one output
        rows.append(("__import__('benchmarks.seq2seq_nmt', fromlist=['x'])"
                     ".run(batch=256)", ROW_TIMEOUT))
        for bs in (8, 32):
            rows.append((f"__import__('benchmarks.serving_decode', "
                         f"fromlist=['x']).run_config({bs})", ROW_TIMEOUT))
        rows.append(("__import__('benchmarks.serving_decode', "
                     "fromlist=['x']).run_config(8, bucket=None)",
                     ROW_TIMEOUT))
        rows.append(("__import__('benchmarks.speculative_decode', "
                     "fromlist=['x']).run_tiny_draft()", ROW_TIMEOUT))
        rows.append(("__import__('benchmarks.resnet50', fromlist=['x'])"
                     ".run_with_infeed()", ROW_TIMEOUT))
        rows.append(("__import__('benchmarks.transformer_lm', "
                     "fromlist=['x']).run_long()", ROW_TIMEOUT))
    rows.append(("__import__('benchmarks.host_embedding', fromlist=['x'])"
                 ".run()", BIG_TIMEOUT))

    budget = float("inf") if full else BUDGET_S
    for i, (expr, timeout) in enumerate(rows):
        left = budget - (time.monotonic() - t0)
        if left < 90:
            print(f"bench: budget exhausted ({BUDGET_S:.0f}s) — skipping "
                  f"remaining secondary rows from {expr!r} on; the flagship "
                  "was measured first and prints last (raise "
                  "PADDLE_TPU_BENCH_BUDGET_S or use --full for the long "
                  "suite)", file=sys.stderr, flush=True)
            failed += [e for e, _ in rows[i:]]
            break
        if left < 0.5 * timeout:
            # a clamped window well below the row's declared timeout is a
            # guaranteed timeout (compile alone is 40-90 s) — skip rather
            # than burn the budget tail measuring nothing
            print(f"bench: skipping {expr!r} — needs ~{timeout:.0f}s, only "
                  f"{left:.0f}s of budget left", file=sys.stderr, flush=True)
            failed.append(expr)
            continue
        if not _row(expr, timeout=min(timeout, left)):
            failed.append(expr)
    if failed:
        print(f"bench: {len(failed)} row(s) missing: " + "; ".join(failed),
              file=sys.stderr, flush=True)
    # atexit prints the flagship as the last line
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(full="--full" in sys.argv))
