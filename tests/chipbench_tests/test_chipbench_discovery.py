"""Driven by data: a configuration, a cell, a traffic mix, a generator, a mode
and a per-layer metric are each added as NEW files (plus BENCHMARK.json
entries) in a copy of the benchmark, and the harness runs them with no edit
to a file that was there."""

import hashlib
import json
import os
import shutil

import pytest

from chipbench import harness, run

NEW_FILES = {
    "chipbench/configs/toy.json": json.dumps(
        {"source": "https://example.org/toy", "vocab_size": 11, "reduced": []}),
    "chipbench/traffic/drip.json": json.dumps(
        {"generator": "drip", "every_s": 0.25}),
    "chipbench/generators/drip.py":
        "def ticks(traffic, seed, seconds):\n"
        "    n = int(seconds / traffic['every_s'])\n"
        "    return [(seed + i) % 7 for i in range(n)]\n",
    "chipbench/modes/echo.py":
        "from chipbench import harness\n"
        "def run(loaded, args, log=print):\n"
        "    ticks = harness.generator_for(loaded).ticks(\n"
        "        loaded['traffic'], args.seed, args.seconds)\n"
        "    ctx = {'ticks': ticks, 'vocab': loaded['config']['vocab_size']}\n"
        "    return {'checks': [('echo', 0.0, 0.0, True)],\n"
        "            'attempted': len(ticks), 'failed': 0, 'ctx': ctx,\n"
        "            'values': {'ticks_per_s': len(ticks) / args.seconds,\n"
        "                       'setup_s': 0.5},\n"
        "            'device': {'platform': 'toy', 'kind': 'toy', 'count': 1,\n"
        "                       'memory_peak_bytes': 1}, 'breakdown': None}\n",
    "chipbench/metrics/tick_sum.py":
        "def read(ctx):\n    return sum(ctx['ticks']) + ctx['vocab']\n",
    "chipbench/metrics/never_there.py":
        "def read(ctx):\n    return None\n",
    "chipbench/workloads/toy-drip.json": json.dumps(
        {"config": "toy", "traffic": "drip", "chips": 1, "mode": "echo"}),
}


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return out


@pytest.fixture()
def copy(tmp_path):
    shutil.copytree(os.path.join(harness.ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    return tmp_path


def test_new_files_and_entries_run_with_no_edit_to_an_existing_file(copy):
    before = _digest(copy / "chipbench")
    for rel, text in NEW_FILES.items():
        assert not (copy / rel).exists()
        (copy / rel).write_text(text)
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy", "source": "https://example.org",
                             "file": "chipbench/configs/toy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy-drip", "config": "toy",
                               "traffic": "drip", "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "ticks_per_s", "unit": "1/s",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["toy-drip"]})
    for name in ("tick_sum", "never_there"):
        bench["per_layer"].append({"name": name, "unit": "ticks",
                                   "better": "higher",
                                   "source": "program_counter",
                                   "layer": "toy", "moves": "ticks_per_s",
                                   "workloads": ["toy-drip"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))

    quiet = lambda m: None
    line, _ = run.run_cell("toy-drip", 3, 2.0, 0, root=str(copy), log=quiet)
    assert line["correct"] is True and line["attempted"] == 8
    assert line["metrics"] == {
        "ticks_per_s": {"value": 4.0, "unit": "1/s"},
        "setup_s": {"value": 0.5, "unit": "s"}}
    traced, _ = run.run_cell("toy-drip", 3, 2.0, 1, root=str(copy), log=quiet)
    # a reader that finds nothing to read is left out of the line
    assert traced["metrics"] == {"tick_sum": {
        "value": float(sum((3 + i) % 7 for i in range(8)) + 11),
        "unit": "ticks"}}
    after = _digest(copy / "chipbench")
    assert {k: v for k, v in after.items() if k in before} == before


def test_an_unknown_cell_or_file_says_what_is_there(copy):
    with pytest.raises(harness.BenchError, match="gpt2m-train-1k"):
        harness.load_cell("no-such-cell", str(copy))
    with pytest.raises(harness.BenchError, match="poisson_lengths"):
        harness.load_module("generators", "nope", str(copy / "chipbench"))


def test_a_cell_file_that_disagrees_with_benchmark_json_is_refused(copy):
    p = copy / "chipbench" / "workloads" / "gpt2m-train-1k.json"
    cell = json.loads(p.read_text())
    cell["chips"] = 4
    p.write_text(json.dumps(cell))
    with pytest.raises(harness.BenchError, match="chips"):
        harness.load_cell("gpt2m-train-1k", str(copy))
