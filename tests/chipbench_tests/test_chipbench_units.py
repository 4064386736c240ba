"""The yardstick's arithmetic: traffic, percentiles and due times, FLOPs,
peaks, the warm-up plan."""

import json
import os

import numpy as np
import pytest

from chipbench import flops, harness
from chipbench.generators import poisson_lengths as gen
from chipbench.modes import serve

ROOT = harness.ROOT


def _traffic(name):
    return harness.load_named("traffic", name)


@pytest.mark.parametrize("mix", ["chat", "longprompt"])
def test_traffic_is_a_pure_function_of_the_seed(mix):
    t = _traffic(mix)
    a = gen.generate(t, 2**31 + 17, 20.0, 50257)
    b = gen.generate(t, 2**31 + 17, 20.0, 50257)
    c = gen.generate(t, 5, 20.0, 50257)
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))
    assert any((x["prompt"] != y["prompt"]).any() for x, y in zip(a, c))


@pytest.mark.parametrize("mix", ["chat", "longprompt"])
def test_traffic_honours_its_clips_and_the_total(mix):
    t = _traffic(mix)
    reqs = gen.generate(t, 3, 40.0, 50257)
    assert len(reqs) == round(t["arrivals"]["rate_per_s"] * 40.0)
    due = [r["due_s"] for r in reqs]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 40.0
    for r in reqs:
        assert t["prompt"]["low"] <= r["prompt"].size <= t["prompt"]["high"]
        assert 1 <= r["max_new"] <= t["output"]["high"]
        assert r["prompt"].size + r["max_new"] <= t["max_total"]
        assert r["prompt"].dtype == np.int32 and r["prompt"].max() < 50257


def test_every_seed_gets_the_same_schedule_and_other_tokens():
    t = _traffic("chat")
    a = gen.generate(t, 1, 30.0, 50257)
    b = gen.generate(t, 2, 30.0, 50257)
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    assert [(r["prompt"].size, r["max_new"]) for r in a] == \
        [(r["prompt"].size, r["max_new"]) for r in b]
    assert any((x["prompt"] != y["prompt"]).any() for x, y in zip(a, b))


def test_no_two_prompts_start_alike_and_the_total_can_bind():
    t = dict(_traffic("chat"), max_total=300)
    reqs = gen.generate(t, 9, 30.0, 50257)
    firsts = [int(r["prompt"][0]) for r in reqs]
    assert len(set(firsts)) == len(firsts)
    assert all(r["prompt"].size + r["max_new"] <= 300 for r in reqs)
    with pytest.raises(ValueError):
        gen.generate(t, 9, 30.0, vocab=8)


def test_percentile_is_numpys():
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    for q in (0, 50, 95, 100):
        assert harness.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def _rec(due, sent, first, last, n, want, error=None):
    stamps = [] if first is None else [[first, 1]] + (
        [[last, n - 1]] if n > 1 else [])
    return {"due": due, "sent": sent, "first": first, "last": last, "n": n,
            "want": want, "stamps": stamps, "error": error}


def test_latency_runs_from_the_due_time_and_a_failure_is_a_miss():
    recs = [_rec(0.0, 0.5, 1.0, 3.0, 5, 5),       # generator 0.5 s late
            _rec(1.0, 1.0, 1.2, 2.2, 11, 11),
            _rec(2.0, 2.0, None, None, 0, 4, error="refused"),
            _rec(3.0, 3.0, 3.1, 9.0, 3, 8)]       # unfinished: short
    s = serve.summarise(recs, seconds=4.0)
    assert (s["attempted"], s["failed"]) == (4, 2)
    # the late generator's half second is IN the first request's ttft
    assert s["ttft_p50_ms"] == float("inf")       # between 1000 and a miss
    assert serve.summarise(recs[:3], 4.0)["ttft_p50_ms"] == \
        pytest.approx(1000.0)                     # 200, 1000, miss
    assert s["ttft_p95_ms"] == float("inf")       # two of four missed
    assert s["lag_max_ms"] == pytest.approx(500.0)
    # tokens seen by the clients inside [0, 4): 5 + 11 + 1 of the third
    assert s["serve_tokens_per_s"] == pytest.approx((5 + 11 + 1) / 4.0)
    ok = serve.summarise(recs[:2], seconds=4.0)
    assert ok["failed"] == 0
    assert ok["tpot_p50_ms"] == pytest.approx(np.percentile(
        [2000.0 / 4, 1000.0 / 10], 50))


def _cfg(name):
    return harness.load_json(os.path.join(ROOT, "chipbench", "configs",
                                          name + ".json"))


@pytest.mark.parametrize("name,millions,d,L", [("gpt2-medium", 355, 1024, 24),
                                               ("gpt2-large", 774, 1280, 36)])
def test_flops_against_a_hand_count(name, millions, d, L):
    cfg = _cfg(name)
    assert (cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]) == (d, L, 50257)
    assert cfg["n_embd"] // cfg["n_head"] == 64 and cfg["reduced"] == []
    assert flops.param_count(cfg) / 1e6 == pytest.approx(millions, abs=1.0)
    # by hand: 12 d^2 a block in matrices, the tied head once
    matmul = 12 * d * d * L + 50257 * d
    assert flops.matmul_params(cfg) == matmul
    attn = 12 * d * 1024 / 2 * L          # 3 passes x 2 products x 2 flops
    assert flops.train_flops_per_token(cfg, 1024) == pytest.approx(
        6 * matmul + attn)


def test_kernel_costs_and_roofline_share():
    f, b = flops.flash_attention_cost(8, 16, 1024, 1024, 64, 2)
    assert f == 2 * 2 * 8 * 16 * 1024 * 1024 / 2 * 64
    assert b == 4 * 8 * 16 * 1024 * 64 * 2
    fb, bb = flops.flash_attention_cost(8, 16, 1024, 1024, 64, 2,
                                        backward=True)
    assert (fb, bb) == (2.5 * f, 2 * b)
    f, b = flops.paged_decode_cost(4000, 20, 64, 4)
    assert (f, b) == (4 * 4000 * 1280, 2 * 4000 * 1280 * 4)
    peaks = harness.peaks_for("TPU v5 lite")
    share, bound = flops.roofline_share(f, b, 2 * b / 819e9, peaks)
    assert share == pytest.approx(50.0) and bound == "memory"


def test_a_device_that_is_not_in_the_table_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    for kind in ("cpu", "TPU v9", "_source"):
        with pytest.raises(harness.BenchError):
            harness.peaks_for(kind)


def test_warmup_plan_reaches_every_program_the_mix_can():
    flags = {"segment": 32, "cache_bucket": 256,
             "prompt_buckets": [32, 64, 128, 256, 512]}
    for mix, admits, caches in (
            ("chat", {32, 64, 128, 256, 512}, {256, 512, 768, 1024}),
            ("longprompt", {512}, {512, 768})):
        rng = gen.length_range(_traffic(mix))
        plan = serve.warmup_plan(rng, flags, 1024)
        got_admit = {serve.prompt_bucket(p, flags["prompt_buckets"])
                     for p, _ in plan}
        got_cache = {c for p, n in plan
                     for c in serve.touched(p, n, flags, 1024)}
        assert got_admit == admits and got_cache == caches, (mix, plan)
        assert all(rng[0] <= p <= rng[1] and p + n <= 1024 for p, n in plan)


def test_benchmark_json_names_files_that_exist():
    bench = harness.load_benchmark()
    for w in bench["workloads"]:
        loaded = harness.load_cell(w["name"])
        assert loaded["cell"]["chips"] == 1
        assert len(w["why"]) <= 200
        assert any(m["name"] == "setup_s" for m in loaded["end_to_end"])
        assert len(loaded["end_to_end"]) >= 2 and loaded["per_layer"]
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "chipbench", "metrics",
                                           m["name"] + ".py")), m["name"]
    assert len(json.dumps(bench)) < 64 * 1024


def test_the_runtimes_own_start_is_timed_apart_and_a_cpu_is_refused():
    from chipbench import device as dev
    device, seconds = dev.start_runtime(rehearsal=True)
    assert device["platform"] == "cpu" and 0.0 <= seconds < 60.0
    with pytest.raises(SystemExit):
        dev.start_runtime(rehearsal=False)
