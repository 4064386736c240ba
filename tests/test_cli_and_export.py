"""CLI + inference-export tests (paddle CLI submit_local.sh.in job parity;
merged inference model of MergeModel.cpp/capi)."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu.fluid as fluid

CONFIG = textwrap.dedent("""
    import paddle_tpu.v2 as paddle
    from paddle_tpu.data.dataset import uci_housing

    x = paddle.layer.data("x", paddle.data_type.dense_vector(13))
    y = paddle.layer.data("y", paddle.data_type.dense_vector(1))
    pred = paddle.layer.fc(x, 1)
    cost = paddle.layer.square_error_cost(pred, y)
    optimizer = paddle.optimizer.SGD(0.05)
    train_reader = paddle.batch(uci_housing.train(128), 32)
    test_reader = paddle.batch(uci_housing.test(64), 32)
    feeding = [x, y]
    outputs = [pred]
""")


@pytest.fixture
def config_file(tmp_path):
    p = tmp_path / "cfg.py"
    p.write_text(CONFIG)
    return str(p)


def _run(*argv, **env_over):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_over)
    r = subprocess.run([sys.executable, "-m", "paddle_tpu", *argv],
                       capture_output=True, text=True, env=env, timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout


def test_cli_version():
    out = _run("version")
    assert "paddle_tpu" in out


def test_cli_serve_bad_flags_structured_error():
    """`serve` answers an invalid flag combination (page_block off the
    max_len grid) with the same structured stderr + exit 2 as a bad
    --config, not a construction traceback."""
    r = subprocess.run([sys.executable, "-m", "paddle_tpu", "serve",
                        "--vocab", "67", "--d_model", "16",
                        "--n_heads", "2", "--n_layers", "1",
                        "--max_len", "128", "--page_block", "48"],
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 2
    assert "serve: page_block 48" in r.stderr
    assert "Traceback" not in r.stderr
    # a bind failure (port already in use) gets the same structured
    # refusal, not a traceback with a half-started engine behind it
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    s.listen(1)
    try:
        r = subprocess.run([sys.executable, "-m", "paddle_tpu", "serve",
                            "--vocab", "67", "--d_model", "16",
                            "--n_heads", "2", "--n_layers", "1",
                            "--max_len", "128",
                            "--port", str(s.getsockname()[1])],
                           capture_output=True, text=True, timeout=240)
    finally:
        s.close()
    assert r.returncode == 2
    assert "serve: cannot bind" in r.stderr
    assert "Traceback" not in r.stderr


def test_lint_bench_rows_schema(tmp_path):
    """`paddle_tpu lint --bench-rows` (no --config needed): well-formed
    rows pass; a row missing its family's roofline column (mfu for
    *_train_*, hbm_bw_util for *_decode_*) or a required key fails with
    B001 findings — malformed rows die in CI, not in the trend data."""
    import json

    good = tmp_path / "good.jsonl"
    good.write_text(
        json.dumps({"metric": "x_train_ms_per_batch", "value": 1.0,
                    "unit": "ms", "vs_baseline": None, "mfu": 0.2,
                    "methodology": "measured"}) + "\n"
        + json.dumps({"metric": "z_serve_daemon_tokens_per_sec",
                      "value": 9.0, "unit": "tok/s", "vs_baseline": None,
                      "ttft_p50_ms": 12.0, "tpot_p50_ms": 3.0,
                      "methodology": "measured"}) + "\n"
        + json.dumps({"metric": "r_route_disagg_tokens_per_sec",
                      "value": 7.0, "unit": "tok/s", "vs_baseline": None,
                      "ttft_p50_ms": 20.0, "tpot_p50_ms": 4.0,
                      "n_decode_workers": 2,
                      "ttft_breakdown": {"queued": 1.0, "prefill": 12.0,
                                         "ship": 4.0, "adopt": 2.0}})
        + "\n")
    bad = tmp_path / "bad.jsonl"
    bad.write_text(
        json.dumps({"metric": "y_decode_tokens_per_sec", "value": 5.0,
                    "unit": "tok/s", "vs_baseline": None}) + "\n"
        + json.dumps({"metric": "z_serve_daemon_tokens_per_sec",
                      "value": 9.0, "unit": "tok/s",
                      "vs_baseline": None}) + "\n"
        + json.dumps({"metric": "w_train_ms_per_batch", "value": 1.0,
                      "unit": "ms", "vs_baseline": None, "mfu": 0.2,
                      "methodology": "guessed"}) + "\n"
        + json.dumps({"metric": "r_route_disagg_tokens_per_sec",
                      "value": 7.0, "unit": "tok/s", "vs_baseline": None,
                      "ttft_p50_ms": 20.0, "tpot_p50_ms": 4.0}) + "\n")
    out = _run("lint", "--bench-rows", str(good))
    assert "0 problem(s)" in out
    r = subprocess.run([sys.executable, "-m", "paddle_tpu", "lint",
                        "--bench-rows", str(bad)],
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 1
    assert "B001" in r.stdout and "hbm_bw_util" in r.stdout
    # the _serve_ family rule (PR 8): a serving row without its SLO pair
    # (ttft_p50_ms / tpot_p50_ms) is rejected
    assert "ttft_p50_ms" in r.stdout and "tpot_p50_ms" in r.stdout
    # methodology is required on roofline/SLO rows and must be one of
    # measured|modeled — on-chip vs projected stays distinguishable
    assert "methodology" in r.stdout and "guessed" in r.stdout
    # the _route_ family rule (disaggregated serving): a routed row
    # without the fleet size it was spread over is not comparable, and
    # without its phase-decomposed TTFT (request-timeline ledger) a
    # routed-TTFT regression can't name which hop moved
    assert "n_decode_workers" in r.stdout
    assert "ttft_breakdown" in r.stdout


def test_cli_train_test_time_dump(config_file, tmp_path):
    save = str(tmp_path / "out")
    cc = str(tmp_path / "compile_cache")
    out = _run("train", "--config", config_file, "--num_passes", "2",
               "--save_dir", save, "--log_period", "2",
               JAX_COMPILATION_CACHE_DIR=cc)
    # train enables the persistent compile cache before its first compile
    # (paddle_tpu.enable_compile_cache; $JAX_COMPILATION_CACHE_DIR places
    # it): the run persists its XLA executables for a resume to reload
    assert os.path.isdir(cc) and os.listdir(cc)
    assert "pass 1 done" in out
    assert os.path.exists(os.path.join(save, "pass-00001", "params.tar"))
    assert os.path.exists(os.path.join(save, "inference", "model.json"))

    out = _run("test", "--config", config_file, "--init_model_path",
               os.path.join(save, "pass-00001", "params.tar"))
    assert json.loads(out.strip().splitlines()[-1])["cost"] >= 0

    out = _run("time", "--config", config_file, "--iters", "4")
    assert json.loads(out.strip().splitlines()[-1])["ms_per_batch"] > 0

    out = _run("dump_config", "--config", config_file)
    d = json.loads(out)
    assert d["blocks"][0]["ops"]


def test_cli_train_local_master(config_file, tmp_path):
    """One-binary bring-up (TrainerMain.cpp:32-49 --start_pserver analog):
    one `train --local_master` process self-hosts the task-master RPC plane
    and trains from it, multi-pass, same artifacts as a plain train.
    ``--obs_out`` rides along: the run arms a flight recorder, obs_pushes
    its snapshots to the in-process master, and leaves a dump the obs CLI
    reads back (the ISSUE 4 smoke)."""
    save = str(tmp_path / "out")
    obs_out = str(tmp_path / "run.jsonl")
    out = _run("train", "--config", config_file, "--num_passes", "2",
               "--save_dir", save, "--local_master",
               "--samples_per_chunk", "2", "--obs_out", obs_out)
    assert "local master:" in out            # chunks really dispatched
    assert "pass 1 done" in out              # second pass got data
    assert os.path.exists(os.path.join(save, "pass-00001", "params.tar"))
    assert "observability dump written" in out
    from paddle_tpu import obs
    dump = obs.read_jsonl(obs_out)
    # clean exit: the FULL session dump superseded the flight ring
    assert not dump["meta"].get("flight")
    names = {m["name"] for m in dump["metrics"]}
    # the v2 CLI trainer drives the fluid Executor + RPC data plane
    assert "fluid.runs_total" in names
    assert "rpc.calls_total" in names
    # the obs_push path really ran against the in-process master
    assert "obs.pushes_total" in names
    assert "master.requests_total" in names
    out = _run("obs", "summary", "--input", obs_out)
    assert "fluid.runs_total" in out
    out = _run("obs", "export", "--input", obs_out, "--format", "prom")
    assert "paddle_tpu_fluid_runs_total" in out


def test_export_load_inference_model(tmp_path):
    fluid.reset_default_programs()
    fluid.executor._global_scope = fluid.Scope()
    x = fluid.layers.data("x", shape=(4,))
    h = fluid.layers.fc(x, 8, act="tanh")
    out = fluid.layers.fc(h, 2)
    loss = fluid.layers.mean(out)
    fluid.SGDOptimizer(0.1).minimize(loss)   # training ops present
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    xs = np.ones((3, 4), np.float32)
    d = str(tmp_path / "model")
    fluid.io.export_inference_model(d, ["x"], [out], exe)
    # reference forward via the pruned program (running the full training
    # block would also fire the sgd op and mutate params)
    infer_prog = fluid.default_main_program().prune([out.name])
    ref = exe.run(infer_prog, feed={"x": xs}, fetch_list=[out])[0]

    # fresh scope + executor; the loaded program must not contain training ops
    exe2 = fluid.Executor(scope=fluid.Scope())
    prog, feeds, fetches = fluid.io.load_inference_model(d, exe2)
    assert feeds == ["x"] and fetches == [out.name]
    types = {op.type for op in prog.global_block().ops}
    assert "autodiff_grad" not in types and "sgd" not in types
    got = exe2.run(prog, feed={"x": xs}, fetch_list=fetches)[0]
    np.testing.assert_allclose(got, ref, rtol=1e-6)


def test_export_keeps_lstm_fused_auto(tmp_path):
    """Inference bundles leave recurrent ops on fused=auto: the runtime
    picks the Pallas whole-sequence kernel for small latency-bound batches
    and XLA's scan for large ones (the measured crossover is documented in
    docs/design/fused_rnn_bench.md). An explicit fused attr would pin one
    path for every deployment batch size — exactly what the bench showed
    to be wrong."""
    import json

    import numpy as np

    from paddle_tpu.v2 import layer as L
    from paddle_tpu.v2.data_type import dense_vector_sequence

    fluid.reset_default_programs()
    x = L.data("x", dense_vector_sequence(4))
    h = L.lstmemory(x, 6)
    out = L.last_seq(h)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    d = str(tmp_path / "m")
    fluid.io.export_inference_model(d, ["x", "x__len__"], [out.var], exe)

    meta = json.load(open(d + "/model.json"))
    lstm_ops = [op for blk in meta["program"]["blocks"]
                for op in blk["ops"] if op["type"] == "lstm"]
    assert lstm_ops and all("fused" not in op["attrs"] for op in lstm_ops)

    # loaded bundle still computes the same numbers (kernel == scan math)
    exe2 = fluid.Executor()
    prog, feeds, fetches = fluid.io.load_inference_model(d, exe2)
    xs = np.random.RandomState(0).randn(3, 5, 4).astype(np.float32)
    lens = np.array([5, 3, 2], np.int32)
    got = exe2.run(prog, feed={"x": xs, "x__len__": lens},
                   fetch_list=fetches)[0]
    ref = exe.run(fluid.default_main_program().prune([out.var.name]),
                  feed={"x": xs, "x__len__": lens},
                  fetch_list=[out.var.name])[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_mnist_lenet_example_config(tmp_path):
    """examples/mnist_lenet.py (v1_api_demo/mnist analog) trains through
    the CLI; with PADDLE_TPU_MNIST_DIR unset it uses the synthetic
    fallback (the real-idx path is covered by test_data_parsers)."""
    cfg = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "examples", "mnist_lenet.py")
    out = _run("train", "--config", cfg, "--num_passes", "1",
               "--log_period", "16")
    assert "pass 0 done" in out


def test_traffic_prediction_example_config(tmp_path):
    """examples/traffic_prediction.py (v1_api_demo/traffic_prediction
    analog): LSTM time-series regression trains through the CLI."""
    cfg = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "examples", "traffic_prediction.py")
    out = _run("train", "--config", cfg, "--num_passes", "1",
               "--log_period", "8")
    assert "pass 0 done" in out


@pytest.mark.slow
def test_gan_vae_example_smoke():
    """examples/gan_vae_mnist.py (v1_api_demo/{gan,vae} analog): both
    demos train mechanically on short budgets.

    slow: ~13s example smoke; the generative-model substance is tier-1
    in tests/test_generative.py and the example-runner plumbing in the
    sibling example smokes (PR 7 precedent: sequence_tagging/serving_llm
    demotions; PR 12 --durations=25 triage)."""
    import importlib
    mod = importlib.import_module("examples.gan_vae_mnist")
    mod.train_gan(steps=40)
    mod.train_vae(steps=150)


def test_model_zoo_features_example():
    """examples/model_zoo_features.py (v1_api_demo/model_zoo analog):
    params-tar round trip into a fresh topology + multi-layer feature
    fetch; consumer predictions match the publisher."""
    import importlib
    mod = importlib.import_module("examples.model_zoo_features")
    mod.main()


def test_cluster_train_num_workers_warning_sentinel():
    """--hosts mode warns on ANY explicitly-passed --num_workers —
    including the old default value 2 (the sentinel is now None, resolved
    to 2 only in local mode; ADVICE r5)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def run(*extra):
        return subprocess.run(
            [sys.executable, "-m", "paddle_tpu", "cluster_train", "s.py",
             "--hosts", "h1,h2", "--dry-run", *extra],
            capture_output=True, text=True, env=env, timeout=120)

    r = run("--num_workers", "2")
    assert r.returncode == 0
    assert "ignoring --num_workers 2" in r.stderr
    r = run("--num_workers", "5")
    assert "ignoring --num_workers 5" in r.stderr
    r = run()                                    # not passed: no warning
    assert r.returncode == 0
    assert "ignoring --num_workers" not in r.stderr
    assert len([l for l in r.stdout.splitlines() if l.strip()]) == 2
