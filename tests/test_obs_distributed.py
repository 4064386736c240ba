"""Distributed-tracing chaos e2e (ISSUE 4 acceptance): trainer and master
run as REAL separate processes (pattern of tests/test_multiprocess_dp.py),
the faults plane kills the worker mid-pass, and the surviving artifacts —
the worker's crash flight-recorder dump + the master's session dump —
merge into one Chrome trace with spans from two pids where the master's
server-side dispatch span is parented (via wire context) under the
worker's ``rpc.call`` span.
"""

import json
import os
import subprocess
import sys

import pytest

from paddle_tpu import cli, obs

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NODE = os.path.join(REPO, "tests", "obs_cluster_node.py")


@pytest.mark.chaos
def test_worker_crash_leaves_stitchable_cross_process_trace(tmp_path):
    master_out = str(tmp_path / "master.jsonl")
    worker_out = str(tmp_path / "worker.jsonl")
    done = str(tmp_path / "done")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PADDLE_TPU_TRACE_ID"] = "e2e0feedfacef00d"

    master = subprocess.Popen(
        [sys.executable, NODE, "master", master_out, done],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    worker = None
    try:
        line = master.stdout.readline().strip()
        assert line.startswith("ADDR "), line
        _, host, port = line.split()

        worker = subprocess.Popen(
            [sys.executable, NODE, "worker", worker_out, host, port],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        wlog, _ = worker.communicate(timeout=240)
        # the chaos worked: the worker DIED on the injected fault
        assert worker.returncode != 0, wlog
        assert "injected fault at step.grad" in wlog, wlog

        open(done, "w").close()
        mlog, _ = master.communicate(timeout=120)
        assert master.returncode == 0, mlog
    finally:
        for p in (worker, master):
            if p is not None and p.poll() is None:
                p.kill()

    # the worker left a flight dump (no clean save ever ran)
    wdump = obs.read_jsonl(worker_out)
    assert wdump["meta"]["flight"] is True
    assert wdump["meta"]["reason"].startswith(("fault:step.grad",
                                               "exception:"))
    assert wdump["meta"]["trace_id"] == "e2e0feedfacef00d"
    mdump = obs.read_jsonl(master_out)
    assert not mdump["meta"].get("flight")

    # ---- the acceptance assertions, on the merged view -------------------
    merged = obs.merge_dumps([wdump, mdump])
    spans = [e for e in merged["events"] if e["kind"] == "span"]
    pids = {e["pid"] for e in spans}
    assert len(pids) >= 2, pids
    by_key = {(e["pid"], e["id"]): e for e in spans}
    wpid, mpid = wdump["meta"]["pid"], mdump["meta"]["pid"]
    stitched = []
    for e in spans:
        r = e.get("remote")
        if not r or e["pid"] != mpid:
            continue
        client = by_key.get((r["pid"], r["span"]))
        if client is not None:
            stitched.append((e, client))
    # at least one server span is parented under a worker rpc.call span
    # from a DIFFERENT pid
    assert any(e["name"] == "master.dispatch"
               and c["name"] == "rpc.call" and c["pid"] == wpid
               for e, c in stitched), [(e["name"], c["name"])
                                       for e, c in stitched]

    # ---- and the CLI converts the pair into one stitched Chrome trace ----
    trace_path = str(tmp_path / "trace.json")
    assert cli.main(["obs", "export", "--input", worker_out,
                     "--input", master_out, "--format", "chrome",
                     "--output", trace_path]) == 0
    trace = json.load(open(trace_path))
    evs = trace["traceEvents"]
    xs_pids = {e["pid"] for e in evs if e["ph"] == "X"}
    assert len(xs_pids) >= 2
    lanes = {e["pid"]: e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert lanes[wpid] == "worker-0" and lanes[mpid] == "master"
    # the cross-process flow arrow both starts and finishes
    assert any(e["ph"] == "s" for e in evs)
    assert any(e["ph"] == "f" for e in evs)
    # merged metrics keep per-process series distinct
    workers = {m["labels"].get("worker") for m in merged["metrics"]}
    assert {"worker-0", "master"} <= workers
