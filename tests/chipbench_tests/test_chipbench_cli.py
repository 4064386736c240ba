"""The command itself: no chip means no result, a rehearsal is never
``correct``, and the program must be there."""

import json
import os
import shutil
import subprocess
import sys

from chipbench import harness

ENV = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=harness.ROOT)
CMD = [sys.executable, "-m", "chipbench.run", "--workload", "gpt2m-train-1k",
       "--seed", "4", "--seconds", "1", "--trace", "0"]


def _last_json(text):
    lines = [l for l in text.strip().splitlines() if l.strip()]
    try:
        return json.loads(lines[-1])
    except ValueError:
        return None


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    r = subprocess.run(CMD, cwd=harness.ROOT, env=ENV, text=True,
                       stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                       timeout=300)
    assert r.returncode == 3
    assert _last_json(r.stdout) is None
    assert "train_tokens_per_s" not in r.stdout


def test_tiny_rehearsal_ends_without_correct_true():
    r = subprocess.run(CMD + ["--tiny"], cwd=harness.ROOT, env=ENV, text=True,
                       stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                       timeout=600)
    line = _last_json(r.stdout)
    assert r.returncode == 4 and line is not None
    assert line["correct"] is False and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)


def test_benchmark_alone_without_the_program_fails(tmp_path):
    shutil.copytree(os.path.join(harness.ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    r = subprocess.run(CMD + ["--tiny"], cwd=tmp_path,
                       env=dict(ENV, PYTHONPATH=str(tmp_path)), text=True,
                       stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                       timeout=300)
    assert r.returncode != 0 and _last_json(r.stdout) is None
