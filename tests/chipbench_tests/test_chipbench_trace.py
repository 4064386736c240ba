"""trace_reduce on recorded traces: busy union, idle share, time by
operation, gaps named by the host span that covers them."""

import glob
import os

import pytest

from chipbench import harness, trace_reduce as tr

TINY = os.path.join(harness.ROOT, "tests", "fixtures", "tiny.xplane.pb")
CHIP = sorted(glob.glob(os.path.join(harness.ROOT, "chipbench", "fixtures",
                                     "*.xplane.pb")))


def test_union_and_stable_names():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5),
                                                              (3, 4)]
    assert tr.stable_name("%fusion.123 = f32[8] fusion(...)") == "fusion"
    assert tr.stable_name("custom-call.2/b1_op0_lstm_fused") == \
        "custom-call/b1_op0_lstm_fused"
    assert tr.stable_name("%while.2 = (s32[], f32[105,64]) while(...)") \
        in tr.CONTAINERS


def test_tiny_fixture_busy_idle_and_kernel_time():
    r = tr.reduce(TINY)
    assert r["chips"] == 1
    # four op events: 400 + 200 + 250 + 50 us, back to back
    assert r["busy_s"] == pytest.approx(900e-6, rel=1e-3)
    assert r["window_s"] == pytest.approx(900e-6, rel=1e-3)
    assert r["idle_pct"] == pytest.approx(0.0, abs=0.1)
    fused = [d for name, _, d in r["raw_ops"] if "lstm_fused" in name]
    assert fused == [pytest.approx(250e-6, rel=1e-3)]
    assert r["breakdown"]["device_ops"][0][0] == "fusion/b0_op3_mul"
    assert len(r["breakdown"]["device_ops"]) <= 10


def test_gaps_are_named_after_the_host_span_that_covers_them(monkeypatch):
    ops = [("a", 10.0, 1.0), ("b", 12.0, 1.0), ("c", 13.5, 0.5),
           ("d", 20.0, 1.0)]
    # trace time starts at the profiler's start, 1000.0 on the unix clock;
    # three programs ran, each inside the host span that dispatched it
    modules = [("jit_step", 10.0, 1.0), ("jit_step", 12.0, 2.0),
               ("jit_step", 20.0, 1.0)]
    monkeypatch.setattr(tr, "device_events", lambda path: {
        "/device:TPU:0": {"ops": ops, "modules": modules}})
    obs = {"meta": {"clock_origin_unix": 1010.0 - 0.0004},
           "events": [{"name": "outer", "ts": 0.0, "dur": 11.0},
                      {"name": "serving.prefill", "ts": 1.0, "dur": 1.0},
                      {"name": "reader", "ts": 4.2, "dur": 5.0},
                      {"name": "step", "ts": 0.0, "dur": 1.01},
                      {"name": "step", "ts": 2.0, "dur": 2.01},
                      {"name": "step", "ts": 10.0, "dur": 1.01}]}
    assert tr.reduce("ignored", obs)["breakdown"]["idle_gaps"] == [
        ["host:none", pytest.approx(7.5)]]      # no start time: not aligned
    r = tr.reduce("ignored", obs, started_unix=1000.3)
    assert r["shift"] == pytest.approx(1000.0, abs=1e-3)
    assert r["busy_s"] == pytest.approx(3.5)
    assert r["window_s"] == pytest.approx(11.0)
    assert r["idle_pct"] == pytest.approx(100 * 7.5 / 11.0)
    gaps = dict(r["breakdown"]["idle_gaps"])
    # 11-12 lies inside both outer and serving.prefill: the inner one names
    # it; 13-13.5 is inside the second step span; 14-20 is mostly the reader's
    assert gaps == pytest.approx({"serving.prefill": 1.0, "step": 0.5,
                                  "reader": 6.0}, abs=1e-3)


@pytest.mark.parametrize("path", CHIP or [None])
def test_trace_recorded_on_the_chip(path):
    """Three train steps of a two-layer model on a TPU v5e (my chip run,
    PR 23), with the Trainer's spans of the same run beside it."""
    if path is None:
        pytest.skip("no trace recorded on the chip is kept here yet")
    obs = harness.load_json(path.replace(".xplane.pb", ".obs.json"))
    r = tr.reduce(path, obs, started_unix=obs["started_unix"])
    assert r is not None and r["chips"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 0 <= r["idle_pct"] < 100
    assert len(r["modules"]) == 3                 # three runs of the step
    assert r["breakdown"]["device_ops"] and all(
        s > 0 for _, s in r["breakdown"]["device_ops"])
    # the clock shift was found from the programs themselves, and the gaps
    # between the steps fall to the Trainer's own spans
    assert r["shift"] is not None
    assert abs(r["shift"] - obs["started_unix"]) < 2.0
    named = {name for name, _ in r["breakdown"]["idle_gaps"]}
    assert named & {"trainer.pass", "trainer.step", "trainer.device_step",
                    "trainer.host_sync"}
    # the flash kernels are custom calls in the step
    kernels = [d for name, _, d in r["raw_ops"] if " custom-call(" in name]
    assert kernels and 0 < sum(kernels) < r["busy_s"]
