"""mode ``serve``: the daemon is ``paddle_tpu.cli``'s own ``serve`` command,
started as a child (chipbench/serve_child.py) that holds the chip; this
process never touches the chip (JAX is pinned to the CPU before
``paddle_tpu.serving`` is imported) and is the load: an OPEN loop at the
cell's fixed rate, one ``ServingClient.stream`` and one connection per
request at the client's default 20 ms poll, every request timed from when
it was DUE.

Set-up warms exactly the programs the cell's lengths can reach (one request
per prompt bucket, then whatever reaches every cache bucket), waits for the
engine to drain, and only then opens the window. After the window every
request due in it is waited for, the daemon is stopped (SIGTERM: it drains
and writes its obs dump), and the plain reference (chipbench/ref_child.py)
takes the chip to check a seeded sample of the served tokens.
"""

import functools
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np

from chipbench import harness, trace_reduce


# -- what the engine will compile, worked out from the cell's own numbers ---

def cache_len(pos, flags, max_len):
    cb, seg = flags["cache_bucket"], flags["segment"]
    return min(-(-(pos + seg + 1) // cb) * cb, max_len)


def touched(plen, max_new, flags, max_len):
    """The cache lengths a request ALONE in the pool decodes at (PagePool.
    run_segment's rule): the first token comes from the prefill, each
    segment then advances ``segment`` positions."""
    out, left, pos, skip = [], min(max_new, max_len - plen) - 1, plen, 1
    while left > 0:
        out.append(cache_len(pos, flags, max_len))
        left -= min(flags["segment"] - skip, left)
        skip, pos = 0, pos + flags["segment"]
    return out


def prompt_bucket(n, buckets):
    return next((b for b in buckets if n <= b), n)


def warmup_plan(length_range, flags, max_len):
    """[(prompt length, max_new)] sent one at a time: one request per prompt
    bucket the mix can reach, then one per cache length not yet touched."""
    pmin, pmax, total = length_range
    buckets = flags["prompt_buckets"]
    want_admit = sorted({prompt_bucket(p, buckets)
                         for p in range(pmin, pmax + 1)})
    want_cache = sorted({cache_len(p, flags, max_len)
                         for p in range(pmin, total)})
    plan, seen = [], set()
    for b in want_admit:
        plan.append((min(b, pmax), 2))
        seen.update(touched(min(b, pmax), 2, flags, max_len))
    for c in want_cache:
        if c in seen:
            continue
        plen = max(pmin, min(pmax, c - flags["cache_bucket"]))
        n = next(n for n in range(2, max_len - plen + 1)
                 if c in touched(plen, n, flags, max_len))
        plan.append((plen, n))
        seen.update(touched(plen, n, flags, max_len))
    return plan


# -- client side -------------------------------------------------------------

def summarise(records, seconds):
    """End-to-end arithmetic over the window's requests. A record is a dict
    with ``due`` (s from window start), ``sent``, ``first``, ``last`` (same
    clock, None when it never happened), ``n`` tokens received, ``want``
    tokens asked, ``stamps`` [(t, n tokens seen by then)] and ``error``.
    A request that failed, was refused or did not finish is MISSING: it is
    counted in ``failed`` and its latency counts as infinite in the tails."""
    def ok(r):
        return r["error"] is None and r["n"] == r["want"]
    failed = sum(not ok(r) for r in records)
    inf = float("inf")
    ttft = [(r["first"] - r["due"]) * 1e3 if ok(r) else inf
            for r in records]
    tpot = [(r["last"] - r["first"]) / (r["n"] - 1) * 1e3 if ok(r) else inf
            for r in records if r["want"] > 1]
    in_window = sum(n for r in records for t, n in r["stamps"]
                    if t <= seconds)
    lag = [(r["sent"] - r["due"]) * 1e3 for r in records
           if r["sent"] is not None]
    return {"attempted": len(records), "failed": failed,
            "ttft_p95_ms": harness.percentile(ttft, 95) if ttft else inf,
            "tpot_p95_ms": harness.percentile(tpot, 95) if tpot else inf,
            "ttft_p50_ms": harness.percentile(ttft, 50) if ttft else inf,
            "tpot_p50_ms": harness.percentile(tpot, 50) if tpot else inf,
            "serve_tokens_per_s": in_window / seconds,
            "lag_p50_ms": harness.percentile(lag, 50) if lag else inf,
            "lag_max_ms": max(lag) if lag else inf}


@functools.lru_cache(maxsize=None)
def _keyed_client_class():
    """ServingClient whose submit carries OUR key, so the daemon's request
    ledger can be matched to this client's clock; stream() is unchanged.
    (Made on first use: importing paddle_tpu.serving has to wait until this
    process is pinned to the CPU.)"""
    from paddle_tpu.serving import ServingClient

    class Keyed(ServingClient):
        submit_key = None

        def submit(self, prompt, max_new, **kw):
            kw["submit_key"] = self.submit_key
            return super().submit(prompt, max_new, **kw)
    return Keyed


def _one_request(addr, req, key, t0, rec):
    client = _keyed_client_class()(addr[0], addr[1])
    client.submit_key = key
    try:
        rec["sent"] = time.time() - t0
        for tok in client.stream(req["prompt"], req["max_new"]):
            now = time.time() - t0
            if rec["first"] is None:
                rec["first"] = now
            rec["last"] = now
            rec["tokens"].append(int(tok))
            if rec["stamps"] and rec["stamps"][-1][0] == now:
                rec["stamps"][-1][1] += 1
            else:
                rec["stamps"].append([now, 1])
    except Exception as e:  # a failed request is a result, not a crash
        rec["error"] = repr(e)
    finally:
        rec["n"] = len(rec["tokens"])
        client.close()


def offer(addr, requests, seconds, tag, drain_s, log, at_close=None):
    """The open loop: start each request's own thread when it is due,
    whatever the earlier ones are doing; then wait for all of them.
    ``at_close(t0, records)`` is called once when the window closes (the
    sweep reads the backlog there)."""
    _keyed_client_class()
    t0 = time.time() + 0.05
    records, threads = [], []
    for i, req in enumerate(requests):
        rec = {"due": req["due_s"], "sent": None, "first": None,
               "last": None, "tokens": [], "stamps": [], "error": None,
               "n": 0, "want": req["max_new"], "key": f"{tag}-{i}",
               "plen": int(req["prompt"].size)}
        delay = t0 + req["due_s"] - time.time()
        if delay > 0:
            time.sleep(delay)
        th = threading.Thread(target=_one_request, daemon=True,
                              args=(addr, req, rec["key"], t0, rec))
        th.start()
        records.append(rec)
        threads.append(th)
    time.sleep(max(0.0, t0 + seconds - time.time()))
    if at_close is not None:
        at_close(t0, records)
    deadline = t0 + seconds + drain_s
    for th in threads:
        th.join(max(0.0, deadline - time.time()))
    late = sum(th.is_alive() for th in threads)
    if late:
        log(f"{late} request(s) still unfinished {drain_s}s after the "
            "window closed: counted as failed")
        for th, rec in zip(threads, records):
            if th.is_alive():
                rec["error"] = "unfinished"
    return t0, records


# -- the daemon child ---------------------------------------------------------

class Daemon:
    def __init__(self, loaded, args, run_dir, log, extra_env=None):
        cell, flags = loaded["cell"], loaded["cell"]["flags"]
        self.log, self.run_dir = log, run_dir
        self.obs_out = os.path.join(run_dir, "serve_obs.jsonl")
        spec = {"config": loaded["config"],
                "seed": harness.program_seed(args.seed)}
        env = dict(os.environ, CHIPBENCH_MODEL_SPEC=json.dumps(spec),
                   PADDLE_TPU_AUTOTUNE_CACHE=os.path.join(
                       run_dir, "no_autotune.json"),
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
                os.path.join(loaded["base"], "serve_model.py"),
                "--obs_out", self.obs_out]
        for k in ("slots", "pages", "segment", "page_block", "cache_bucket",
                  "queue_cap"):
            cmd += [f"--{k}", str(flags[k])]
        log("daemon: " + " ".join(cmd[2:]))
        self.proc = subprocess.Popen(cmd, cwd=loaded["root"], env=env,
                                     text=True, stdout=subprocess.PIPE)
        self.addr, self._up = None, threading.Event()
        threading.Thread(target=self._pump, daemon=True).start()

    def _pump(self):
        for line in self.proc.stdout:
            line = line.rstrip("\n")
            self.log(f"  [serve] {line}")
            if line.startswith("SERVING "):
                _, host, port = line.split()[:3]
                self.addr = (host, int(port))
                self._up.set()
        self._up.set()

    def wait_up(self, timeout):
        self._up.wait(timeout)
        if self.addr is None:
            self.stop()
            raise harness.BenchError(
                "the serve child printed no 'SERVING <host> <port>' line "
                f"(exit code {self.proc.poll()})")
        return self.addr

    def stop(self):
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
            return -9


def _drained(client, timeout=60.0):
    end = time.time() + timeout
    while time.time() < end:
        s = client.serving_stats()
        if not s["slots_live"] and not s["queue_depth"]:
            return True
        time.sleep(0.05)
    return False


def read_obs(path):
    """The daemon's obs dump (JSONL, one ``kind`` a line) back into
    {"meta", "metrics", "events", "requests"}."""
    out = {"meta": {}, "metrics": [], "events": [], "requests": []}
    where = {"metric": "metrics", "span": "events", "instant": "events",
             "request": "requests"}
    if not os.path.exists(path):
        return out
    with open(path) as f:
        for line in f:
            row = json.loads(line)
            kind = row.pop("kind", None)
            if kind == "meta":
                out["meta"] = row
            elif kind in where:
                out[where[kind]].append(row)
    return out


def start_and_warm(loaded, args, requests, plan, log, daemon_env=None):
    """Start the daemon child, send the warm-up plan one request at a time
    (first tokens unlike any of ``requests``'), wait for the engine to
    drain. Returns (daemon, control client, address)."""
    cell, config = loaded["cell"], loaded["config"]
    gen = harness.generator_for(loaded)
    seed = harness.program_seed(args.seed)
    daemon = Daemon(loaded, args, args.work_dir, log, daemon_env)
    try:
        addr = daemon.wait_up(cell["startup_timeout_s"])
        from paddle_tpu.serving import ServingClient
        ctl = ServingClient(addr[0], addr[1], call_timeout=120.0)
        rs = np.random.RandomState(seed ^ 0x5EED)
        firsts = gen.distinct_first_tokens(
            rs, config["vocab_size"], len(plan),
            taken=[int(r["prompt"][0]) for r in requests])
        t_w = time.time()
        for (plen, n), first in zip(plan, firsts):
            prompt = rs.randint(0, config["vocab_size"], plen)
            prompt[0] = first
            got = ctl.generate(prompt.astype(np.int32), n)
            if len(got) != n or not _drained(ctl):
                raise harness.BenchError(
                    f"warm-up request ({plen}, {n}) did not finish")
        log(f"daemon up after {t_w - args.t_start:.1f}s; warm-up of "
            f"{len(plan)} requests took {time.time() - t_w:.1f}s")
    except BaseException:
        daemon.stop()
        raise
    return daemon, ctl, addr


def sample_for_check(records, seed, want_tokens, max_rows):
    """A seeded sample of the finished requests, the longest always in it,
    grown until it holds ``want_tokens`` served tokens (or ``max_rows``)."""
    done = [r for r in records if r["error"] is None and r["n"] == r["want"]]
    if not done:
        return []
    longest = max(done, key=lambda r: r["plen"] + r["n"])
    rest = [r for r in done if r is not longest]
    picks, tokens = [longest], longest["n"]
    for i in np.random.RandomState(seed).permutation(len(rest)):
        if tokens >= want_tokens or len(picks) >= max_rows:
            break
        picks.append(rest[i])
        tokens += rest[i]["n"]
    return picks


def run(loaded, args, log=print, daemon_env=None, alter=None):
    """``daemon_env``/``alter`` exist for the tests that break the timed
    path underneath (an environment for the child; a function over the
    window's records before they are checked)."""
    t_start = args.t_start
    os.environ["JAX_PLATFORMS"] = "cpu"       # this process stays off the chip
    cell, config, traffic = loaded["cell"], loaded["config"], loaded["traffic"]
    flags, max_len = cell["flags"], config["n_positions"]
    gen = harness.generator_for(loaded)
    seed = harness.program_seed(args.seed)
    requests = gen.generate(traffic, seed, args.seconds, config["vocab_size"])
    plan = warmup_plan(gen.length_range(traffic), flags, max_len)
    log(f"traffic: {len(requests)} requests due in {args.seconds}s "
        f"({traffic['arrivals']['rate_per_s']}/s, cv "
        f"{traffic['arrivals'].get('cv', 1.0)}); warm-up plan {plan}")

    run_dir = args.work_dir
    daemon, ctl, addr = start_and_warm(loaded, args, requests, plan, log,
                                       daemon_env)
    try:
        if args.trace:
            open(os.path.join(run_dir, "trace.go"), "w").close()
        t0, records = offer(addr, requests, args.seconds,
                            f"w{seed}", cell["drain_s"], log)
        stats = ctl.serving_stats()
        ctl.close()
    finally:
        rc = daemon.stop()
    log(f"daemon exit code {rc}")
    report_path = os.path.join(run_dir, "child_report.json")
    if rc != 0 or not os.path.exists(report_path):
        raise harness.BenchError(f"the serve child ended with {rc}")
    report = harness.load_json(report_path)
    obs = read_obs(daemon.obs_out)
    device = report["device"]
    if alter is not None:
        alter(records)

    t1 = t0 + args.seconds
    in_window = [e for e in report["compile_events"]
                 if e[0] == "backend_compile_duration"
                 and t0 <= e[1] <= t1 + cell["drain_s"]]
    summary = summarise(records, args.seconds)
    log(f"window: {summary['attempted']} requests, {summary['failed']} "
        f"failed; generator lag median {summary['lag_p50_ms']:.2f} ms, "
        f"max {summary['lag_max_ms']:.2f} ms; ttft p50 "
        f"{summary['ttft_p50_ms']:.1f} p95 {summary['ttft_p95_ms']:.1f} ms, "
        f"tpot p50 {summary['tpot_p50_ms']:.2f} p95 "
        f"{summary['tpot_p95_ms']:.2f} ms ({summary['attempted']} samples "
        "behind each percentile)")
    log(f"daemon's {report['cache_line']}")
    log(f"compilations inside the window: {len(in_window)}; end-of-run "
        f"stats {json.dumps(stats)}")
    routes = {f"{m['labels'].get('kernel')}/{m['labels'].get('route')}":
              m.get("value") for m in obs["metrics"]
              if m.get("name") == "kernels.routes_total"}
    log(f"kernels.routes_total {routes}")

    # -- the output check, on the chip the daemon has left -------------
    rows = sample_for_check(records, seed, cell["check_tokens"],
                            cell["check_rows_max"])
    ref = {"rows": [], "seconds": 0.0}
    if rows:
        spec = {"kind": "serve", "config": config, "seed": seed,
                "control": (cell["control_operand"]
                            if os.environ.get("CHIPBENCH_CONTROL")
                            else None),
                "rows": [{"prompt": [int(t) for t in
                                     requests[int(r["key"].rsplit(
                                         "-", 1)[1])]["prompt"]],
                          "tokens": r["tokens"]} for r in rows]}
        ref = harness.run_reference(spec, run_dir, loaded["root"],
                                    args.rehearsal,
                                    cell["reference_timeout_s"])
        if ref["device"] != device:
            raise harness.BenchError(
                f"reference ran on {ref['device']}, daemon on {device}")
    gaps = [g for row in ref["rows"] for g in row["gaps"]]
    log(f"reference: {len(ref['rows'])} requests, {len(gaps)} served "
        f"tokens checked in {ref['seconds']:.1f}s (not in setup_s)")
    limits = cell["limits"]

    def gap_rows(gaps):
        """Two numbers of the served tokens' shortfall below the reference's
        best logit: the mean over all checked tokens (steady, and what a
        lower precision moves) and the widest (what an altered token
        moves)."""
        if not gaps:
            return [("served_gap_mean", float("inf")),
                    ("served_gap_widest", float("inf"))]
        return [("served_gap_mean", sum(gaps) / len(gaps)),
                ("served_gap_widest", max(gaps))]
    checks = [(n, v, limits[n], v <= limits[n]) for n, v in gap_rows(gaps)]
    checks += [("failed_requests", float(summary["failed"]), 0.0,
                summary["failed"] == 0),
               ("compilations_in_window", float(len(in_window)), 0.0,
                not in_window)]
    for name, value, limit, ok in checks:
        log(f"compared {name} = {value:.6g}  limit {limit:.6g}  "
            f"{'ok' if ok else 'FAIL'}")
    cgaps = [g for row in ref["rows"] for g in row.get("control_gaps", [])]
    if cgaps:
        for n, v in gap_rows(cgaps):
            log(f"control[{cell['control_operand']}] {n} = {v:.6g}  limit "
                f"{limits[n]:.6g}  {'passes' if v <= limits[n] else 'fails'}")

    values = {k: summary[k] for k in ("ttft_p50_ms", "tpot_p50_ms",
                                      "serve_tokens_per_s")}
    values["setup_s"] = t0 - t_start - report["runtime_up_s"]
    log(f"daemon's accelerator runtime came up in "
        f"{report['runtime_up_s']:.2f}s (its own start: not in setup_s)")
    ctx = {"mode": "serve", "cell": cell, "config": config,
           "traffic": traffic, "device": device, "base": loaded["base"],
           "obs": obs, "values": values, "records": records,
           "window": (t0, t1), "trace": None, "stats": stats,
           "summary": summary}
    result_device = dict(device,
                         memory_peak_bytes=report["memory_peak_bytes"])
    breakdown = None
    if args.trace and report.get("trace"):
        tr = trace_reduce.reduce_dir(report["trace"]["dir"], obs,
                                     report["trace"]["span"][0])
        ctx["trace"] = tr
        if tr is not None:
            result_device.update(busy_s=tr["busy_s"],
                                 window_s=tr["window_s"])
            breakdown = tr["breakdown"]
            log(f"trace: {tr['summary']}")
    return {"checks": checks, "attempted": summary["attempted"],
            "failed": summary["failed"], "values": values, "ctx": ctx,
            "device": result_device, "breakdown": breakdown}
