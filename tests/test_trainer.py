"""Trainer-driver tests: events, evaluators, checkpoints, checkgrad, test loop.

Shaped like the reference's trainer tests (SURVEY.md §4.4 test_Trainer.cpp,
test_TrainerOnePass.cpp — tiny end-to-end trainings with embedded data)."""

import io
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import data as pdata
from paddle_tpu import parallel as pp
from paddle_tpu.data import DataFeeder, DenseSlot, IndexSlot, batch
from paddle_tpu.data.dataset import mnist
from paddle_tpu.nn import Linear, Module
from paddle_tpu.optimizer import Adam, SGD
from paddle_tpu.trainer import (ClassificationErrorEvaluator, EvaluatorGroup,
                                SumEvaluator, Trainer, event, from_tar,
                                latest_pass, load_checkpoint, save_checkpoint,
                                to_tar)


class _MLP(Module):
    def __init__(self):
        super().__init__()
        self.l1 = Linear(784, 64, act=jax.nn.relu)
        self.l2 = Linear(64, 10)

    def __call__(self, params, x, **kw):
        return self.l2(params["l2"], self.l1(params["l1"], x))


def _loss(model):
    def loss(params, x, y):
        logp = jax.nn.log_softmax(model(params, x))
        return -jnp.take_along_axis(logp, y[:, None], axis=1).mean()
    return loss


def _outputs(model):
    def outputs(params, x, y):
        return {"logits": model(params, x), "labels": y}
    return outputs


def _reader():
    return batch(mnist.train(512), 64, drop_last=True)


_feeder = DataFeeder([DenseSlot(784), IndexSlot()])


def test_train_events_and_learning():
    model = _MLP()
    trainer = Trainer(_loss(model), Adam(1e-3), outputs_fn=_outputs(model),
                      evaluators=[ClassificationErrorEvaluator(), SumEvaluator()])
    seen = []
    costs = []

    def handler(e):
        seen.append(type(e).__name__)
        if isinstance(e, event.EndIteration):
            costs.append(e.cost)
            assert e.evaluator_result is not None

    params = model.init(jax.random.PRNGKey(0))
    params, _ = trainer.train(_reader(), params, num_passes=2,
                              event_handler=handler,
                              feeder=lambda rows: _feeder.feed(rows))
    assert "BeginPass" in seen and "EndPass" in seen
    assert "BeginIteration" in seen and "EndIteration" in seen
    assert costs[-1] < costs[0]  # it learns
    # evaluator accumulated over the pass
    res = trainer.evaluators.result()
    assert 0.0 <= res["classification_error"] <= 1.0


def test_test_loop():
    model = _MLP()
    trainer = Trainer(_loss(model), SGD(0.1), outputs_fn=_outputs(model),
                      evaluators=[ClassificationErrorEvaluator()])
    params = model.init(jax.random.PRNGKey(0))
    out = trainer.test(lambda: batch(mnist.test(128), 64)(), params,
                       feeder=lambda rows: _feeder.feed(rows))
    assert out["cost"] > 0
    assert "classification_error" in out["evaluator_result"]


def test_tar_roundtrip_and_crc():
    params = {"a": {"w": np.arange(6, dtype=np.float32).reshape(2, 3)},
              "b": [np.ones(2), np.zeros(3)]}
    buf = io.BytesIO()
    to_tar(buf, params)
    buf.seek(0)
    back = from_tar(buf)
    np.testing.assert_allclose(back["a"]["w"], params["a"]["w"])
    assert isinstance(back["b"], list)
    np.testing.assert_allclose(back["b"][1], np.zeros(3))
    # corrupt a byte -> CRC failure
    raw = bytearray(buf.getvalue())
    # flip a byte inside the first npy payload (past the 512-byte tar header)
    raw[600] ^= 0xFF
    with pytest.raises(ValueError):
        from_tar(io.BytesIO(bytes(raw)))


def test_checkpoint_save_resume(tmp_path):
    out = str(tmp_path / "ckpt")
    model = _MLP()
    trainer = Trainer(_loss(model), Adam(1e-3), output_dir=out)
    params = model.init(jax.random.PRNGKey(0))
    params, _ = trainer.train(_reader(), params, num_passes=2,
                              feeder=lambda rows: _feeder.feed(rows))
    assert latest_pass(out) == 1
    p2, s2, st = load_checkpoint(out)
    assert st["pass_id"] == 1
    # resume continues at pass 2
    trainer2 = Trainer(_loss(model), Adam(1e-3), output_dir=out)
    passes = []
    trainer2.train(_reader(), model.init(jax.random.PRNGKey(1)), num_passes=1,
                   event_handler=lambda e: passes.append(e.pass_id)
                   if isinstance(e, event.BeginPass) else None,
                   feeder=lambda rows: _feeder.feed(rows), resume=True)
    assert passes == [2]


def test_checkgrad():
    # smooth activations only — finite differences straddle relu kinks
    class Smooth(Module):
        def __init__(self):
            super().__init__()
            self.l1 = Linear(784, 32, act=jnp.tanh)
            self.l2 = Linear(32, 10)

        def __call__(self, params, x, **kw):
            return self.l2(params["l2"], self.l1(params["l1"], x))

    model = Smooth()
    trainer = Trainer(_loss(model), SGD(0.1))
    params = model.init(jax.random.PRNGKey(0))
    rows = list(batch(mnist.train(32), 32)())[0]
    b = _feeder.feed(rows)
    assert trainer.check_gradient(params, b, max_checks_per_param=3)


def test_trainer_with_mesh_dp():
    mesh = pp.make_mesh(data=8)
    model = _MLP()
    trainer = Trainer(_loss(model), SGD(0.1), mesh=mesh)
    params = model.init(jax.random.PRNGKey(0))
    costs = []
    trainer.train(_reader(), params, num_passes=1,
                  event_handler=lambda e: costs.append(e.cost)
                  if isinstance(e, event.EndIteration) else None,
                  feeder=lambda rows: _feeder.feed(rows))
    assert costs[-1] < costs[0]


def test_benchmark_job():
    model = _MLP()
    trainer = Trainer(_loss(model), SGD(0.1))
    params = model.init(jax.random.PRNGKey(0))
    r = trainer.benchmark(lambda: batch(mnist.train(128), 64, drop_last=True)(),
                          params, feeder=lambda rows: _feeder.feed(rows),
                          warmup=1, iters=3)
    assert r["ms_per_batch"] > 0


def test_tar_preserves_empty_containers_and_tuples():
    """SGD optimizer state has {} slots per param: structure (incl. empty
    containers and tuple-ness) must survive to_tar/from_tar so resume works
    (ADVICE r1 high)."""
    from paddle_tpu.optimizer import SGD
    params = {"fc": {"w": np.ones((3, 2), np.float32),
                     "b": np.zeros((2,), np.float32)}}
    opt = SGD(0.1)
    state = opt.init(params)
    buf = io.BytesIO()
    to_tar(buf, state)
    buf.seek(0)
    back = from_tar(buf)
    assert (jax.tree_util.tree_structure(back)
            == jax.tree_util.tree_structure(state))
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    opt.update(grads, back, params)  # must not KeyError
    # tuples round-trip as tuples
    tup = {"pair": (np.ones(2, np.float32), np.zeros(3, np.float32)), "empty": []}
    buf = io.BytesIO()
    to_tar(buf, tup)
    buf.seek(0)
    back = from_tar(buf)
    assert isinstance(back["pair"], tuple) and back["empty"] == []


def test_nan_guard_raises():
    """Non-finite loss must abort the pass loop — the feenableexcept
    (TrainerMain.cpp:49) analog."""
    model = _MLP()

    def bad_loss(params, x, y):
        return _loss(model)(params, x, y) / 0.0   # inf/nan every batch

    trainer = Trainer(bad_loss, SGD(0.1))
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(FloatingPointError, match="non-finite"):
        trainer.train(_reader(), params, num_passes=1,
                      feeder=lambda rows: _feeder.feed(rows))


def test_eval_outputs_fused_into_step():
    """Evaluator outputs must come from the SAME jitted step (no second
    forward dispatch) — the round-1 double-forward fix."""
    model = _MLP()
    calls = {"n": 0}
    base_outputs = _outputs(model)

    def counting_outputs(params, x, y):
        calls["n"] += 1          # traced once per jit compile, not per batch
        return base_outputs(params, x, y)

    trainer = Trainer(_loss(model), SGD(0.1), outputs_fn=counting_outputs,
                      evaluators=[ClassificationErrorEvaluator()])
    params = model.init(jax.random.PRNGKey(0))
    trainer.train(_reader(), params, num_passes=1,
                  feeder=lambda rows: _feeder.feed(rows))
    # traced by the fused train step -> at most a couple of traces (train step
    # compile + optional standalone uses), NOT once per batch
    assert calls["n"] <= 2, f"outputs_fn traced {calls['n']} times"


# slow: profiler-smoke variant of the benchmark path (18s)
@pytest.mark.slow
def test_benchmark_with_xla_profile(tmp_path):
    """--job=time with an XLA trace (hl_profiler / test_GpuProfiler.cpp
    analog): trace artifacts must land in the log dir."""
    from paddle_tpu.utils import profiler

    model = _MLP()
    trainer = Trainer(_loss(model), SGD(0.1))
    params = model.init(jax.random.PRNGKey(0))
    d = str(tmp_path / "trace")
    res = trainer.benchmark(_reader(), params,
                            feeder=lambda rows: _feeder.feed(rows),
                            warmup=1, iters=2, profile_dir=d)
    assert res["ms_per_batch"] > 0
    files = profiler.trace_files(d)
    assert files, f"no .xplane.pb produced under {d}"


def test_device_memory_stats_and_profile(tmp_path):
    """HBM observability (allocator-counter analog): live stats dict and a
    pprof memory profile dump."""
    import jax.numpy as jnp

    from paddle_tpu.utils import profiler

    keep = jnp.ones((256, 256))          # something alive on the device
    stats = profiler.device_memory_stats()
    assert isinstance(stats, dict)       # CPU backend may report {}
    p = profiler.save_device_memory_profile(str(tmp_path / "mem.pprof"),
                                            backend="cpu")
    assert os.path.exists(p) and os.path.getsize(p) > 0
    del keep


def test_param_stats_period_logs_magnitudes():
    """--show_parameter_stats_period analog (TrainerInternal.cpp:80-87):
    per-parameter absmax/absmean lines every N batches."""
    import logging

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu import nn
    from paddle_tpu.optimizer import SGD
    from paddle_tpu.trainer.trainer import Trainer

    class Net(nn.Module):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(4, 2)

        def __call__(self, params, x, **kw):
            return self.fc(params["fc"], x)

    model = Net()

    def loss(params, x, y):
        return jnp.mean((model(params, x) - y) ** 2)

    t = Trainer(loss, SGD(0.1), param_stats_period=2)
    rs = np.random.RandomState(0)

    def reader():
        for _ in range(4):
            yield (rs.randn(8, 4).astype(np.float32),
                   rs.randn(8, 2).astype(np.float32))

    # the package logger sets propagate=False (glog-style), so capture with
    # a handler attached directly rather than caplog
    records = []

    class Grab(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    lg = logging.getLogger("paddle_tpu.trainer.trainer")
    h = Grab(level=logging.INFO)
    lg.addHandler(h)
    try:
        t.train(reader, model.init(jax.random.PRNGKey(0)), num_passes=1)
    finally:
        lg.removeHandler(h)
    lines = [m for m in records if m.startswith("param ")]
    assert any("fc.w" in ln and "absmax" in ln for ln in lines)
    assert len(lines) >= 4          # 2 params x 2 dumps (batches 2 and 4)


def test_trainer_layout_shards_params_and_slots(tmp_path):
    """Trainer(mesh=..., layout=...): params AND Adam moments place
    sharded per the SpecLayout, training still converges, and a
    checkpoint-resume re-places onto the current mesh."""
    from jax.sharding import PartitionSpec as P

    model = _MLP()
    mesh = pp.make_mesh(data=2, fsdp=2, tp=2)
    layout = pp.SpecLayout()
    trainer = Trainer(_loss(model), Adam(1e-3), mesh=mesh, layout=layout,
                      output_dir=str(tmp_path))
    params, opt_state = trainer.train(_reader(), model.init(jax.random.PRNGKey(0)),
                                      num_passes=1, feeder=_feeder)
    w1 = params["l1"]["w"]                      # (784, 64): (fsdp, tp)
    assert w1.sharding.spec == P("fsdp", "tp")
    assert w1.addressable_shards[0].data.shape == (392, 32)
    m = opt_state["slots"]["l1"]["w"]["m"]      # Adam moment follows
    assert m.sharding.spec == P("fsdp", "tp")
    # resume: checkpoint gathered on save, re-placed sharded on restore
    trainer2 = Trainer(_loss(model), Adam(1e-3), mesh=mesh, layout=layout,
                       output_dir=str(tmp_path))
    params2, _ = trainer2.train(_reader(), model.init(jax.random.PRNGKey(1)),
                                num_passes=1, resume=True, feeder=_feeder)
    assert params2["l1"]["w"].sharding.spec == P("fsdp", "tp")
