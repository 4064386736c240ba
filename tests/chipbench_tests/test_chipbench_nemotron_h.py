"""The ``nemotron_h`` family's part of the benchmark, all of it NEW files: the
cell is found by name and rehearsed end to end on the CPU at its tiny sizes
(the real ``serve`` daemon on the family's model script with the cell's
prompt buckets, the open loop in bursts, the family's reference child; exit
4), its control is a lower precision, its weights are seeded, its parameter
count is the configuration file's arithmetic, ``flops_nemotron_h.py`` counts
what a hand counts, and its three readers read what the program emits and
return nothing where the program emits nothing (the parent)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops_lfm2, flops_nemotron_h, harness, run
from chipbench import weights_nemotron_h as weights
from chipbench.reference import nemotron_h as ref

CELL = "nemotron3-ep8-serve-chatburst"
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def _tiny_config():
    return run.apply_tiny(harness.load_cell(CELL))["config"]


def test_cell_is_found_by_name_with_its_mode_traffic_and_readers():
    loaded = harness.load_cell(CELL)
    cell, cfg = loaded["cell"], loaded["config"]
    assert cell["mode"] == "serve_nemotron_h" and cell["chips"] == 1
    assert callable(harness.mode_for(loaded).run)
    assert callable(harness.mode_for(loaded).sweep)
    assert harness.generator_for(loaded).length_range(loaded["traffic"]) \
        == (32, 2048, 2816)
    reported = {m["name"] for m in loaded["end_to_end"]}
    assert {"tpot_p50_ms", "serve_tokens_per_s", "setup_s"} <= reported
    assert "train_tokens_per_s" not in reported
    names = {m["name"] for m in loaded["per_layer"]}
    assert {"ssm_decode_roofline", "ssd_prefill_roofline",
            "relu2_expert_matmul_roofline", "gqa_head_dim_decode_roofline",
            "decode_step_ms", "tpot_p95_ms",
            "slots_live_mean", "segment_host_ms", "device_idle.serve",
            "setup_trace_lower_s"} <= names
    # readers that count another model's kernels are not asked of this cell
    assert not {"paged_decode_roofline", "mla_decode_roofline",
                "gqa_decode_roofline", "expert_matmul_roofline",
                "prefill_expert_matmul_roofline",
                "expert_load_max_over_mean"} & names
    for m in loaded["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"],
                                            loaded["base"]).read)


def test_configuration_holds_every_published_key_and_cuts_two():
    loaded = harness.load_cell(CELL)
    cfg = loaded["config"]
    entry = next(c for c in harness.load_benchmark()["configs"]
                 if c["name"] == loaded["cell"]["config"])
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted(
        cfg["published"]) == ["n_routed_experts", "vocab_size"]
    assert cfg["published"] == {"n_routed_experts": 128,
                                "vocab_size": 131072}
    assert entry["source"] == cfg["source"]
    # every width, the whole pattern, the router and its top-6 as published
    assert cfg["hybrid_override_pattern"] == PATTERN
    assert (len(PATTERN), PATTERN.count("M"), PATTERN.count("E"),
            PATTERN.count("*")) == (52, 23, 23, 6)
    assert (cfg["num_hidden_layers"], cfg["hidden_size"], cfg["head_dim"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
            cfg["ssm_state_size"], cfg["conv_kernel"], cfg["chunk_size"],
            cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
            cfg["mlp_hidden_act"], cfg["layer_norm_epsilon"]) == (
        52, 2688, 128, 32, 2, 64, 64, 8, 128, 4, 128, 1856, 3712, 6, 2.5,
        "relu2", 1e-5)
    assert cfg["router_width"] == 128 and cfg["n_routed_experts"] == 16
    assert cfg["experts_held"] == list(range(16))
    assert cfg["vocab_size"] == 131072 // 8 and cfg["n_positions"] == 2816
    for key in ("assumed", "departures", "deployment", "dtype",
                "parameters_arithmetic", "changed"):
        assert cfg[key], key
    # the pool for the worst case: no request waits for pages; the prompt
    # buckets cover the mix; one segment program
    f = loaded["cell"]["flags"]
    assert f["pages"] == f["slots"] * (cfg["n_positions"]
                                       // f["page_block"]) + 1
    # the issue's four widths (PERF.md section 6, PR 35, has the 1,024 one)
    assert f["prompt_buckets"] == [256, 512, 1024, 2048]
    assert f["cache_bucket"] == cfg["n_positions"] and f["no_prefix_cache"]
    traffic = loaded["traffic"]
    assert traffic["generator"] == "poisson_lengths"
    assert traffic["prompt"] == {"dist": "lognormal", "median": 384,
                                 "sigma": 0.9, "low": 32, "high": 2048}
    assert traffic["output"] == {"dist": "lognormal", "median": 160,
                                 "sigma": 0.7, "low": 16, "high": 768}
    assert traffic["max_total"] == 2816
    arr = traffic["arrivals"]
    assert arr["cv"] in (2.0, 3.0)          # bursts: no other mix has them
    assert arr["rate_per_s"] == pytest.approx(0.8 * arr["knee_per_s"],
                                              abs=0.05)


def test_bursty_schedule_is_the_mixes_own_and_the_same_for_every_seed():
    loaded = harness.load_cell(CELL)
    gen = harness.generator_for(loaded)
    a = gen.generate(loaded["traffic"], 1, 50, 16384)
    b = gen.generate(loaded["traffic"], 2**31 + 5, 50, 16384)
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    assert [r["prompt"].size for r in a] == [r["prompt"].size for r in b]
    gaps = np.diff([0.0] + [r["due_s"] for r in a])
    cv = gaps.std() / gaps.mean()
    assert cv > 1.5                         # Poisson arrivals give ~1
    assert all(int(r["prompt"].max()) < 16384 for r in a)


def test_parameter_count_is_the_configuration_files_arithmetic():
    cfg = harness.load_cell(CELL)["config"]
    _, shapes = weights.model_and_shapes(cfg)
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    d = 2688
    expert = 2 * d * 1856
    moe = 16 * expert + d * 128 + 128 + 2 * d * 3712
    mamba = d * 10304 + 6144 * 4 + 6144 + 3 * 64 + 4096 + 4096 * d
    attn = d * 4608 + 4096 * d
    by_hand = 2 * 16384 * d + d + 23 * (moe + d) + 23 * (mamba + d) \
        + 6 * (attn + d)
    assert n == by_hand == flops_nemotron_h.param_count(cfg) \
        == 5_258_420_544
    assert round(n / 1e6) == cfg["parameters_millions"] == 5258
    f32 = {"e_bias", "dt_bias", "a_log", "d"}
    for path, s in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        last = jax.tree_util.keystr(path).rsplit("['", 1)[1][:-2]
        assert s.dtype == (jnp.float32 if last in f32 else jnp.bfloat16)


def test_seeded_weights():
    cfg = _tiny_config()
    _, shapes = weights.model_and_shapes(cfg)
    a, b, c = (weights.make(shapes, s, cfg) for s in (5, 5, 6))
    la, lb, lc = (jax.tree_util.tree_leaves(t) for t in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert any(not np.array_equal(x, y) for x, y in zip(la, lc))
    mixer, moe = a["blocks_0"]["mixer"], a["blocks_1"]["moe"]
    assert "attn" in a["blocks_3"]
    assert float(a["blocks_0"]["norm"]["gamma"].min()) == 1.0
    assert float(mixer["norm_gamma"].min()) == 1.0 == float(mixer["d"].max())
    a_log = np.asarray(mixer["a_log"])
    assert a_log.dtype == np.float32 and 0.0 <= a_log.min() \
        and a_log.max() <= np.log(16.0) + 1e-6
    step = np.log1p(np.exp(np.asarray(mixer["dt_bias"], np.float64)))
    assert 1e-3 - 1e-6 <= step.min() and step.max() <= 0.1 + 1e-6
    taps = np.asarray(mixer["w_conv"], np.float32)
    assert taps.shape == (96, 4) and np.abs(taps).max() <= 0.5 + 1e-2
    assert np.abs(np.asarray(mixer["b_conv"], np.float32)).max() > 0
    bias = np.asarray(moe["e_bias"])
    assert bias.dtype == np.float32 and 0.002 < bias.std() < 0.03
    assert moe["w_up"].shape == (4, 16, 32) == moe["w_down"].shape
    assert "w_gate" not in moe and set(moe["shared"]) == {"w_up", "w_down"}
    w = np.asarray(moe["w_up"], np.float32)
    assert moe["w_up"].dtype == jnp.bfloat16 and 0.015 < w.std() < 0.025


def test_the_control_is_a_lower_precision_than_the_reference():
    cfg = _tiny_config()
    _, shapes = weights.model_and_shapes(cfg)
    params = weights.make(shapes, 3, cfg)
    params = jax.tree_util.tree_map(
        lambda a: (a.astype(jnp.float32) * 5).astype(a.dtype)
        if a.ndim >= 2 else a, params)
    ids = jax.random.randint(jax.random.PRNGKey(1), (32,), 0,
                             cfg["vocab_size"])
    hp = ref.hparams(cfg)
    sound = ref.forward(params, ids, hp)
    low = ref.forward(params, ids, hp, "fp8")
    assert sound.dtype == low.dtype == jnp.float32
    err = float(jnp.max(jnp.abs(sound - low)))
    assert 1e-3 < err < 5.0
    best, served, pick = ref.token_gaps(params, ids, hp, "fp8")
    assert pick is not None and best.shape == (31,)
    assert float(jnp.min(best - served)) >= 0.0
    assert float(jnp.min(best - pick)) >= 0.0


def test_tiny_rehearses_the_cell_end_to_end_and_exits_4(monkeypatch, capsys):
    got = {}
    real = run.run_cell

    def spy(*a, **kw):
        kw["log"] = lambda m: None
        got["line"], got["raw"] = real(*a, **kw)
        return got["line"], got["raw"]
    monkeypatch.setattr(run, "run_cell", spy)
    for var in ("JAX_COMPILATION_CACHE_DIR", "JAX_COMPILATION_CACHE_MAX_SIZE",
                "JAX_PLATFORMS"):               # run.main pins these
        monkeypatch.setenv(var, os.environ.get(var, ""))
    rc = run.main(["--workload", CELL, "--tiny", "--seed", str(2**31 + 17),
                   "--seconds", "2", "--trace", "1"])
    assert rc == 4
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False and line["rehearsal"] is True
    raw = got["raw"]
    assert all(ok for *_, ok in raw["checks"]), raw["checks"]
    assert line["attempted"] == 16 and line["failed"] == 0
    assert {"decode_step_ms", "slots_live_mean", "tpot_p95_ms",
            "segment_host_ms", "setup_trace_lower_s"} <= set(line["metrics"])
    # no device trace on the CPU: the rooflines have nothing to read
    assert not {"ssm_decode_roofline", "ssd_prefill_roofline",
                "relu2_expert_matmul_roofline"} & set(line["metrics"])
    obs_dump = raw["ctx"]["obs"]
    metrics = {(m["name"], m["labels"].get("program")
                or m["labels"].get("kernel") or m["labels"].get("state")):
               m.get("value") for m in obs_dump["metrics"]}
    assert {("moe.assignments_total", "segment"),
            ("moe.assignments_here_total", "admit"),
            ("ssm.state_updates_total", "segment"),
            ("ssm.scan_tokens_total", "real"),
            ("ssm.scan_tokens_total", "padded"),
            ("kernels.routes_total", "ssm_state_update"),
            ("kernels.routes_total", "ssd_chunk_scan"),
            ("kernels.routes_total", "paged_decode_attention"),
            ("kernels.routes_total", "expert_grouped_matmul"),
            ("kernels.bytes_total", "ssm_state_update"),
            ("kernels.bytes_total", "ssd_chunk_scan")} <= set(metrics)
    # every admission wrote a carry: warm-up requests and the window's
    assert metrics[("serving.slot_state_writes_total", None)] >= 16
    # 3 Mamba layers x (2 x 16 x 16 f32 + 3 x 96 bf16) x 4 slots
    assert metrics[("serving.slot_state_bytes_held", None)] \
        == 3 * (2 * 16 * 16 * 4 + 3 * 96 * 2) * 4
    builds = {e["args"]["kind"] for e in obs_dump["events"]
              if e.get("name") == "serving.program_build"}
    assert builds == {"admit", "segment"}
    # the counts ride the spans: the expert layer's on both, the admitted
    # rows and prompt tokens on the admissions
    for span, more in (("serving.prefill", {"rows", "prompt_tokens"}),
                       ("serving.segment", {"live"})):
        args = [e.get("args", {}) for e in obs_dump["events"]
                if e.get("name") == span]
        assert args and all(
            {"routed_here", "experts_touched", "load_max"} | more <= set(a)
            for a in args), span
    real_tokens = metrics[("ssm.scan_tokens_total", "real")]
    prompt_tokens = sum(e["args"]["prompt_tokens"]
                        for e in obs_dump["events"]
                        if e.get("name") == "serving.prefill")
    assert real_tokens == 3 * prompt_tokens > 0


# -- the readers, on a made-up trace and obs dump ------------------------------

def _ctx(events=(), raw_ops=(), config=None):
    loaded = harness.load_cell(CELL)
    records = [{"key": "w-0", "plen": 300}, {"key": "w-1", "plen": 1500}]
    requests = [
        {"key": "w-0", "events": [{"phase": "first_token", "t": 100.0},
                                  {"phase": "done", "t": 104.0,
                                   "tokens": 100}]},
        {"key": "w-1", "events": [{"phase": "first_token", "t": 100.0},
                                  {"phase": "done", "t": 104.0,
                                   "tokens": 400}]}]
    return {"cell": loaded["cell"], "config": config or loaded["config"],
            "base": loaded["base"], "device": {"kind": "TPU v5e"},
            "window": (100.0, 150.0), "records": records,
            "obs": {"meta": {"clock_origin_unix": 100.0},
                    "events": list(events), "requests": requests,
                    "metrics": []},
            "trace": {"raw_ops": list(raw_ops), "chips": 1, "shift": 100.0,
                      "busy_s": 1.0}}


def _read(name, ctx):
    return harness.load_module("metrics", name, ctx["base"]).read(ctx)


NEW = ("ssm_decode_roofline", "ssd_prefill_roofline",
       "relu2_expert_matmul_roofline", "gqa_head_dim_decode_roofline")


def test_readers_return_nothing_where_the_program_emits_nothing():
    """The parent: no NemotronHLM, so no run of this configuration, no such
    kernel and no such span argument. Nothing raises, nothing is
    reported; and a configuration of another family reads nothing even
    where a kernel of that name ran."""
    admit = {"kind": "span", "name": "serving.prefill", "ts": 1.0,
             "dur": 0.5, "args": {"batch": 2}}          # no counts
    seg = {"kind": "span", "name": "serving.segment", "ts": 1.6, "dur": 0.3,
           "args": {"live": 2}}
    other = [("%fusion.7 = f32[8] fusion(...)", 0.9, 1e-4),
             ("%fusion.8 = f32[8] fusion(...)", 2.5, 1e-4)]
    ctx = _ctx([admit, seg], other)
    for name in NEW:
        assert _read(name, ctx) is None
    rag = json.load(open(os.path.join(
        harness.ROOT, "chipbench/configs/lfm2-8b-a1b-13l.json")))
    seg["args"].update(routed_here=300, experts_touched=200)
    ops = other + [
        ("%expert_grouped_matmul.3 = f32[640,1792] custom-call(...)", 1.7,
         1e-3)]
    assert _read("relu2_expert_matmul_roofline",
                 _ctx([admit, seg], ops, config=rag)) is None
    assert _read("relu2_expert_matmul_roofline",
                 _ctx([admit, seg], ops)) is not None
    # rag states no head size: its paged read is gqa_decode_roofline's
    pd = [("%paged_decode_attention.3 = f32[32,4,8,64] custom-call(...)",
           1.7, 1e-3)]
    assert _read("gqa_head_dim_decode_roofline",
                 _ctx([admit, seg], pd, config=rag)) is None
    assert _read("gqa_head_dim_decode_roofline",
                 _ctx([admit, seg], pd)) is not None
    ctx["trace"] = None
    for name in NEW:
        assert _read(name, ctx) is None


def test_the_four_readers_on_a_made_up_run():
    admits = [{"kind": "span", "name": "serving.prefill", "ts": t,
               "dur": 0.4, "args": {"batch": 2, "rows": 2,
                                    "prompt_tokens": 700,
                                    "routed_here": 500,
                                    "experts_touched": 300,
                                    "load_max": 40}}
              for t in (1.0, 2.0, 2.5)]
    segs = [{"kind": "span", "name": "serving.segment", "ts": t, "dur": 0.4,
             "args": {"live": live, "routed_here": 4000,
                      "experts_touched": 2500, "load_max": 9}}
            for t, live in ((1.5, 20), (2.45, 12))]
    upd = "%ssm_state_update.{} = (f32[32,32,128], f32[32,32,128,128]) " \
          "custom-call(...)"
    scan = "%ssd_chunk_scan.{} = (f32[8,256,4096], f32[8,32,128,128]) " \
           "custom-call(...)"
    gm = "%expert_grouped_matmul.{} = f32[448,1856] custom-call(...)"
    pd = "%paged_decode_attention.{} = f32[32,16,2,128] custom-call(...)"
    # the trace runs 0.9 .. 2.6: admissions 1 and 2 and the first segment
    # lie wholly inside it; the third admission (2.5 .. 2.9) and the second
    # segment (2.45 .. 2.85) are cut and count nowhere, their events too
    ops = [("%fusion.1 = f32[8] fusion(...)", 0.9, 0.01),
           (scan.format(1), 1.05, 0.004), (scan.format(2), 1.10, 0.006),
           (gm.format(3), 1.12, 0.02),                  # an admission's
           (upd.format(4), 1.60, 0.010), (upd.format(5), 1.70, 0.012),
           (gm.format(6), 1.62, 0.030), (gm.format(7), 1.72, 0.020),
           (pd.format(12), 1.70, 0.001), (pd.format(13), 1.71, 0.001),
           (scan.format(8), 2.10, 0.005),
           (upd.format(9), 2.50, 0.010), (gm.format(10), 2.52, 0.030),
           (scan.format(11), 2.55, 0.010),
           ("%fusion.2 = f32[8] fusion(...)", 2.59, 0.01)]
    ctx = _ctx(admits + segs, ops)

    updates = 20 * 32 * 23
    f, b = flops_nemotron_h.ssm_update_cost(updates, 64, 64, 8, 128)
    want = 100.0 * max(f / 197e12, b / 819e9) / 0.022
    assert _read("ssm_decode_roofline", ctx) == pytest.approx(want)

    f, b = flops_nemotron_h.ssd_scan_cost(2 * 700 * 23, 64, 64, 8, 128, 128,
                                          2)
    want = 100.0 * max(f / 197e12, b / 819e9) / 0.015
    assert _read("ssd_prefill_roofline", ctx) == pytest.approx(want)

    f, b = flops_nemotron_h.relu2_expert_matmul_cost(4000, 2500, 2688, 1856,
                                                     2)
    want = 100.0 * max(f / 197e12, b / 819e9) / 0.050
    assert _read("relu2_expert_matmul_roofline", ctx) == pytest.approx(want)

    # live rows over the kernel's own span [101.70, 101.711]: two requests
    # part-way through their answers (the ledger's interpolation); K and V
    # of 2 heads x 128 (the STATED head size, not 2688 / 32) once a call
    t = 101.7055
    rows = (300 + 100 * (t - 100) / 4) + (1500 + 400 * (t - 100) / 4)
    f, b = flops_lfm2.gqa_decode_cost(rows, 32, 2, 128, 2)
    assert b == pytest.approx(rows * 2 * 2 * 128 * 2)
    want = 100.0 * max(2 * f / 197e12, 2 * b / 819e9) / 0.002
    assert _read("gqa_head_dim_decode_roofline", ctx) \
        == pytest.approx(want, rel=1e-3)
    assert len(ctx["notes"]) == 4


def test_cost_functions_against_hand_counts():
    # one slot's state of one layer for one step: 64 x 64 x 128 float32
    # read and written, and the step's x, y (4096 each), B, C (1024 each)
    # and dt (64) in float32
    f, b = flops_nemotron_h.ssm_update_cost(1, 64, 64, 8, 128)
    assert b == 2 * 64 * 64 * 128 * 4 + (4096 + 4096 + 1024 + 1024 + 64) * 4
    assert b == 4_235_520 and f == 5 * 64 * 64 * 128
    # the cell's slot state: 23 layers of it a slot = 48.2 MB of carry
    assert 23 * 64 * 64 * 128 * 4 == 48_234_496
    # a prompt token a layer, the issue's four products
    f, b = flops_nemotron_h.ssd_scan_cost(1, 64, 64, 8, 128, 128, 2)
    assert f == 2 * 128 * 128 * 8 + 3 * (2 * 128 * 64 * 64) == 3_407_872
    assert b == (4096 + 2048) * 2 + (64 + 4096) * 4
    # 10 pairs over 4 expert visits at this model's widths: TWO matrices
    f, b = flops_nemotron_h.relu2_expert_matmul_cost(10, 4, 2688, 1856, 2)
    assert f == 4.0 * 10 * 2688 * 1856
    assert b == 2 * 4 * 2688 * 1856 * 2 + 10 * 2688 * 6
    assert flops_nemotron_h.layer_counts(
        {"hybrid_override_pattern": PATTERN}) == {"M": 23, "E": 23, "*": 6}


def test_the_familys_files_are_additions():
    """'A later PR adds files and edits no file that is there'
    (chipbench/README.md), read off the names."""
    here = os.path.join(harness.ROOT, "chipbench")
    added = ["reference/nemotron_h.py", "weights_nemotron_h.py",
             "serve_model_nemotron_h.py", "ref_child_nemotron_h.py",
             "flops_nemotron_h.py", "modes/serve_nemotron_h.py",
             "configs/nemotron3-nano-30b-ep8.json", "traffic/chatburst.json",
             f"workloads/{CELL}.json", "metrics/ssm_decode_roofline.py",
             "metrics/ssd_prefill_roofline.py",
             "metrics/relu2_expert_matmul_roofline.py"]
    assert all(os.path.exists(os.path.join(here, f)) for f in added)
    # the mode reuses the lfm2 mode's daemon and reference runner under its
    # own two names, and restores them
    from chipbench.modes import serve_lfm2, serve_nemotron_h
    with serve_nemotron_h.family():
        assert serve_lfm2.MODEL_SCRIPT == "serve_model_nemotron_h.py"
        assert serve_lfm2.REF_CHILD == "chipbench.ref_child_nemotron_h"
    assert serve_lfm2.MODEL_SCRIPT == "serve_model_lfm2.py"
    assert serve_lfm2.REF_CHILD == "chipbench.ref_child_lfm2"
