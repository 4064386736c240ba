"""The ``afmoe`` family's part of the benchmark, all of it NEW files: the cell
is found by name and rehearsed end to end on the CPU at its tiny sizes (the
real ``serve`` daemon on the family's model script with the cell's prompt
buckets, a ring a slot for the sliding layers, the family's reference child;
exit 4), its controls are a lower precision and a forgotten window, its
parameter count is the configuration file's arithmetic, ``flops_afmoe.py``
counts what a hand counts, and its three readers read what the program
emits and return nothing where the program emits nothing (the parent)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops_afmoe, flops_lfm2, harness, run
from chipbench import weights_afmoe as weights
from chipbench.reference import afmoe as ref

CELL = "trinity-ep8-serve-mixedlen"
NEW = ("window_decode_roofline", "window_flash_prefill_roofline",
       "flash_head_dim_prefill_roofline")


def _tiny_config():
    return run.apply_tiny(harness.load_cell(CELL))["config"]


def test_cell_is_found_by_name_with_its_mode_traffic_and_readers():
    loaded = harness.load_cell(CELL)
    cell = loaded["cell"]
    assert cell["mode"] == "serve_afmoe" and cell["chips"] == 1
    assert callable(harness.mode_for(loaded).run)
    assert callable(harness.mode_for(loaded).sweep)
    assert harness.generator_for(loaded).length_range(loaded["traffic"]) \
        == (128, 8192, 8960)
    reported = {m["name"] for m in loaded["end_to_end"]}
    assert {"tpot_p50_ms", "serve_tokens_per_s", "setup_s"} <= reported
    assert "train_tokens_per_s" not in reported
    names = {m["name"] for m in loaded["per_layer"]}
    assert set(NEW) | {
        "gqa_head_dim_decode_roofline", "expert_matmul_roofline",
        "prefill_expert_matmul_roofline", "expert_load_max_over_mean",
        "decode_step_ms", "tpot_p95_ms", "slots_live_mean",
        "segment_host_ms", "device_idle.serve",
        "setup_trace_lower_s"} <= names
    # readers that count another model's kernels, or take the head as
    # hidden_size // heads, are not asked of this cell; nor the iteration's
    # four accounts, whose lists tests/chipbench_tests/
    # test_chipbench_iteration_account.py pins to the five older serve cells
    assert not {"prefill_fill", "segment_fill", "tpot_admission_ms",
                "tpot_decode_ms",
                "paged_decode_roofline", "mla_decode_roofline",
                "gqa_decode_roofline", "flash_prefill_roofline",
                "ssm_decode_roofline",
                "relu2_expert_matmul_roofline"} & names
    for m in loaded["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"],
                                            loaded["base"]).read)


def test_configuration_holds_every_published_key_and_cuts_two():
    loaded = harness.load_cell(CELL)
    cfg = loaded["config"]
    entry = next(c for c in harness.load_benchmark()["configs"]
                 if c["name"] == loaded["cell"]["config"])
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted(
        cfg["published"]) == ["num_experts", "vocab_size"]
    assert cfg["published"] == {"num_experts": 128, "vocab_size": 200192}
    assert entry["source"] == cfg["source"] == \
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"
    # every width, all 32 layers 3 : 1, the router and its top-8 as published
    kinds = cfg["layer_types"]
    assert len(kinds) == cfg["num_hidden_layers"] == 32
    assert all(k == ("full_attention" if i % 4 == 3 else "sliding_attention")
               for i, k in enumerate(kinds))
    assert flops_afmoe.layer_counts(cfg) == {"sliding": 24, "full": 8}
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["num_shared_experts"], cfg["num_dense_layers"],
            cfg["sliding_window"], cfg["route_scale"], cfg["rope_theta"],
            cfg["rms_norm_eps"], cfg["score_func"], cfg["mup_enabled"]) == (
        2048, 128, 32, 4, 6144, 1024, 8, 1, 2, 2048, 2.826, 10000, 1e-5,
        "sigmoid", True)
    assert cfg["router_width"] == 128 and cfg["num_experts"] == 16
    assert cfg["experts_held"] == list(range(16))
    assert cfg["vocab_size"] == 200192 // 8 and cfg["n_positions"] == 8960
    assert cfg["first_k_dense_replace"] == cfg["num_dense_layers"]
    for key in ("assumed", "departures", "deployment", "dtype",
                "parameters_arithmetic", "changed"):
        assert cfg[key], key
    # the pool for the worst case: no request waits for pages; the prompt
    # buckets cover the mix; one segment program
    f = loaded["cell"]["flags"]
    assert f["pages"] == f["slots"] * (cfg["n_positions"]
                                       // f["page_block"]) + 1
    assert f["prompt_buckets"] == [512, 1024, 2048, 4096, 8192]
    assert f["cache_bucket"] == cfg["n_positions"] and f["no_prefix_cache"]
    traffic = loaded["traffic"]
    assert traffic["generator"] == "poisson_lengths"
    assert traffic["prompt"] == {"dist": "lognormal", "median": 2048,
                                 "sigma": 1.1, "low": 128, "high": 8192}
    assert traffic["output"] == {"dist": "lognormal", "median": 192,
                                 "sigma": 0.7, "low": 32, "high": 768}
    assert traffic["max_total"] == 8960
    arr = traffic["arrivals"]
    assert arr["cv"] == 1.0
    assert arr["rate_per_s"] == pytest.approx(0.8 * arr["knee_per_s"],
                                              abs=0.05)


def test_the_mix_puts_short_and_long_prompts_in_one_queue():
    """Half the requests never leave the window, half do, and some sit at
    the longest bucket — with the same schedule for every seed."""
    loaded = harness.load_cell(CELL)
    gen = harness.generator_for(loaded)
    traffic = dict(loaded["traffic"], arrivals=dict(
        loaded["traffic"]["arrivals"], rate_per_s=20.0))
    a = gen.generate(traffic, 1, 50, 25024)
    b = gen.generate(traffic, 2**31 + 5, 50, 25024)
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    lens = np.asarray([r["prompt"].size for r in a])
    assert lens.tolist() == [r["prompt"].size for r in b]
    assert 0.35 < (lens <= 2048).mean() < 0.65
    assert (lens == 8192).mean() > 0.04 and lens.min() >= 128
    assert all(r["prompt"].size + r["max_new"] <= 8960 for r in a)
    assert all(int(r["prompt"].max()) < 25024 for r in a)


def test_parameter_count_is_the_configuration_files_arithmetic():
    cfg = harness.load_cell(CELL)["config"]
    _, shapes = weights.model_and_shapes(cfg)
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    d = 2048
    attn = 3 * d * 4096 + 2 * d * 512 + 2 * 128
    expert = 3 * d * 1024
    moe_layer = 16 * expert + expert + d * 128 + 128 + attn + 4 * d
    dense_layer = attn + 3 * d * 6144 + 4 * d
    by_hand = 30 * moe_layer + 2 * dense_layer + 2 * 25024 * d + d
    assert n == by_hand == flops_afmoe.param_count(cfg) == 4_267_194_112
    assert round(n / 1e6) == cfg["parameters_millions"] == 4267
    whole = dict(cfg, experts_held=list(range(128)), vocab_size=200192)
    assert round(flops_afmoe.param_count(whole) / 1e9, 1) \
        == cfg["parameters_published_billions"] == 26.1
    for path, s in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        last = jax.tree_util.keystr(path).rsplit("['", 1)[1][:-2]
        assert s.dtype == (jnp.float32 if last == "e_bias"
                           else jnp.bfloat16)


def test_seeded_weights():
    cfg = _tiny_config()
    _, shapes = weights.model_and_shapes(cfg)
    a, b, c = (weights.make(shapes, s) for s in (5, 5, 6))
    la, lb, lc = (jax.tree_util.tree_leaves(t) for t in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert any(not np.array_equal(x, y) for x, y in zip(la, lc))
    blk = a["blocks_2"]
    assert set(blk) == {"input_norm", "post_attn_norm", "pre_mlp_norm",
                        "post_mlp_norm", "attn", "moe"}
    assert "ffn" in a["blocks_1"] and "moe" not in a["blocks_1"]
    assert all(float(blk[nm]["gamma"].min()) == 1.0 for nm in blk
               if nm.endswith("norm"))
    assert blk["attn"]["w_qkvg"].shape == (32, 2 * (4 + 2) * 8)
    bias = np.asarray(blk["moe"]["e_bias"])
    assert bias.dtype == np.float32 and 0.002 < bias.std() < 0.03
    assert blk["moe"]["w_gate"].shape == (4, 32, 16)
    assert set(blk["moe"]["shared"]) == {"w_gate", "w_up", "w_down"}
    w = np.asarray(blk["moe"]["w_up"], np.float32)
    assert blk["moe"]["w_up"].dtype == jnp.bfloat16 and 0.015 < w.std() < 0.025


def test_the_controls_are_a_lower_precision_and_a_forgotten_window():
    from chipbench import ref_child_afmoe
    cfg = _tiny_config()
    _, shapes = weights.model_and_shapes(cfg)
    params = weights.make(shapes, 3)
    params = jax.tree_util.tree_map(
        lambda a: (a.astype(jnp.float32) * 5).astype(a.dtype)
        if a.ndim >= 2 else a, params)
    ids = jax.random.randint(jax.random.PRNGKey(1), (48,), 0,
                             cfg["vocab_size"])
    hp = ref.hparams(cfg)
    sound = ref.forward(params, ids, hp)
    low = ref.forward(params, ids, hp, "fp8")
    assert sound.dtype == low.dtype == jnp.float32
    assert 1e-3 < float(jnp.max(jnp.abs(sound - low))) < 5.0
    best, served, pick = ref.token_gaps(params, ids, hp, "fp8")
    assert pick is not None and best.shape == (47,)
    assert float(jnp.min(best - served)) >= 0.0
    assert float(jnp.min(best - pick)) >= 0.0
    # without the window nothing moves inside it (16 positions), and the
    # logits past it do
    wide = ref.forward(params, ids, dict(hp, window=None))
    moved = np.abs(np.asarray(wide - sound)).max(-1)
    assert moved[:16].max() == 0.0 and moved[16:].max() > 1e-3
    rows = [{"prompt": [int(t) for t in ids[:30]],
             "tokens": [int(t) for t in ids[30:]]}]
    out = ref_child_afmoe.gaps_for(params, dict(cfg, n_positions=64), rows,
                                   "no_window")
    assert len(out[0]["gaps"]) == len(out[0]["control_gaps"]) == 18
    assert min(out[0]["control_gaps"]) >= 0.0


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
            "segment_host_ms", "setup_trace_lower_s",
            "expert_load_max_over_mean"} <= set(line["metrics"])
    # no device trace on the CPU: the rooflines have nothing to read
    assert not set(NEW) & set(line["metrics"])
    obs_dump = raw["ctx"]["obs"]
    metrics = {(m["name"], m["labels"].get("program")
                or m["labels"].get("kernel") or m["labels"].get("state")
                or m["labels"].get("kind")):
               m.get("value") for m in obs_dump["metrics"]}
    assert {("moe.assignments_total", "segment"),
            ("moe.assignments_here_total", "admit"),
            ("serving.cache_rows_read_total", "window"),
            ("serving.cache_rows_read_total", "full"),
            ("kernels.routes_total", "paged_window_attention"),
            ("kernels.routes_total", "paged_decode_attention"),
            ("kernels.routes_total", "expert_grouped_matmul"),
            ("kernels.bytes_total", "paged_window_attention"),
            ("kernels.bytes_total", "paged_decode_attention"),
            ("kernels.routes_total", "flash_window_attention_fwd"),
            ("kernels.bytes_total", "flash_window_attention_fwd")} \
        <= set(metrics)
    # 6 sliding layers' k and v, 2 heads x 8 f32... bf16: 4 slots x ring 4
    # + the null page, pages of 8 rows
    assert metrics[("serving.ring_bytes_held", None)] \
        == (4 * 4 + 1) * 8 * 6 * 2 * (2 * 8 * 2)
    assert metrics[("serving.cache_rows_read_total", "full")] \
        >= metrics[("serving.cache_rows_read_total", "window")] > 0
    builds = {e["args"]["kind"] for e in obs_dump["events"]
              if e.get("name") == "serving.program_build"}
    assert builds == {"admit", "segment"}
    # the counts ride the spans: the expert layer's on both, the rows the
    # two kinds of read covered on the segments
    for span, more in (("serving.prefill", {"rows", "prompt_tokens"}),
                       ("serving.segment", {"live", "window_rows",
                                            "full_rows"})):
        args = [e.get("args", {}) for e in obs_dump["events"]
                if e.get("name") == span]
        assert args and all(
            {"routed_here", "experts_touched", "load_max"} | more <= set(a)
            for a in args), span
    segs = [e["args"] for e in obs_dump["events"]
            if e.get("name") == "serving.segment"]
    assert all(a["window_rows"] <= a["full_rows"] for a in segs)
    assert all(a["window_rows"] <= 16 * 4 * a["live"] for a in segs)


# -- the readers, on a made-up trace and obs dump ------------------------------

def _ctx(events=(), raw_ops=(), config=None):
    loaded = harness.load_cell(CELL)
    records = [{"key": "w-0", "plen": 300}, {"key": "w-1", "plen": 5000}]
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


def test_readers_return_nothing_where_the_program_emits_nothing():
    """The parent: no AfmoeLM, so no run of this configuration, no such
    kernel and no such span argument. Nothing raises, nothing is reported;
    and a configuration of another family reads nothing even where a
    kernel of that name ran."""
    admit = {"kind": "span", "name": "serving.prefill", "ts": 1.0,
             "dur": 0.5, "args": {"batch": 2}}
    seg = {"kind": "span", "name": "serving.segment", "ts": 1.6, "dur": 0.3,
           "args": {"live": 2}}                         # no window_rows
    other = [("%fusion.7 = f32[8] fusion(...)", 0.9, 1e-4),
             ("%fusion.8 = f32[8] fusion(...)", 2.5, 1e-4)]
    ctx = _ctx([admit, seg], other)
    for name in NEW:
        assert _read(name, ctx) is None
    rag = json.load(open(os.path.join(
        harness.ROOT, "chipbench/configs/lfm2-8b-a1b-13l.json")))
    ops = other + [
        ("%paged_window_attention.3 = f32[12,32,128] custom-call(...)", 1.7,
         1e-3),
        ("%flash_window_attention_fwd.4 = (bf16[32,2048,128], "
         "f32[32,2048,1]) custom-call(...)", 1.2, 1e-3),
        ("%flash_attention_fwd.5 = (bf16[32,2048,128], f32[32,2048,1]) "
         "custom-call(...)", 1.3, 1e-3)]
    seg["args"]["window_rows"] = 5000
    for name in NEW:                    # rag states no window, no head size
        assert _read(name, _ctx([admit, seg], ops, config=rag)) is None
        assert _read(name, _ctx([admit, seg], ops)) is not None
    ctx["trace"] = None
    for name in NEW:
        assert _read(name, ctx) is None


def test_the_three_readers_on_a_made_up_run():
    admits = [{"kind": "span", "name": "serving.prefill", "ts": t,
               "dur": 0.4, "args": {"batch": 1, "rows": 1,
                                    "prompt_tokens": 3000}}
              for t in (1.0, 2.0, 2.5)]
    segs = [{"kind": "span", "name": "serving.segment", "ts": t, "dur": 0.4,
             "args": {"live": live, "window_rows": rows,
                      "full_rows": 3 * rows}}
            for t, live, rows in ((1.5, 10, 600_000), (2.45, 12, 700_000))]
    band = "%flash_window_attention_fwd.{} = (bf16[{},{},128], " \
           "f32[{},{},1]) custom-call(...)"
    full = "%flash_attention_fwd.{} = (bf16[{},{},128], f32[{},{},1]) " \
           "custom-call(...)"
    win = "%paged_window_attention.{} = f32[12,32,128] custom-call(...)"
    # the trace runs 0.9 .. 2.6: admissions 1 and 2 and the first segment
    # lie wholly inside it; the third admission (2.5 .. 2.9) and the second
    # segment (2.45 .. 2.85) are cut and count nowhere, their events too
    ops = [("%fusion.1 = f32[8] fusion(...)", 0.9, 0.01),
           (band.format(1, 32, 4096, 32, 4096), 1.05, 0.004),
           (full.format(2, 32, 4096, 32, 4096), 1.10, 0.006),
           (band.format(3, 128, 512, 128, 512), 2.10, 0.001),
           (win.format(4), 1.60, 0.002), (win.format(5), 1.70, 0.003),
           (win.format(6), 2.50, 0.002),
           (band.format(7, 32, 8192, 32, 8192), 2.55, 0.010),
           ("%fusion.2 = f32[8] fusion(...)", 2.59, 0.01)]
    ctx = _ctx(admits + segs, ops)

    # a known band: T 4096 through a window of 2048 sees 2048 * 2049 / 2 +
    # 2048 * 2048 (query, key) pairs; 4 rows of 512 see whole triangles
    assert flops_afmoe.band_keys(4096, 2048) == 2048 * 2049 // 2 + 2048 ** 2
    assert flops_afmoe.band_keys(512, 2048) == 512 * 513 // 2
    f1, b1 = flops_afmoe.window_flash_cost(1, 32, 4, 4096, 2048, 128, 2)
    f2, b2 = flops_afmoe.window_flash_cost(4, 32, 4, 512, 2048, 128, 2)
    assert f1 == 4.0 * 32 * 128 * (2048 * 2049 // 2 + 2048 ** 2)
    assert b1 == 2 * 4096 * 128 * 2 * (32 + 4)
    want = 100.0 * max((f1 + f2) / 197e12, (b1 + b2) / 819e9) / 0.005
    assert _read("window_flash_prefill_roofline", ctx) == pytest.approx(want)

    # known window_rows: the first segment's 600,000 a layer, 24 sliding
    # layers, K and V of 4 heads x 128 in bf16 = 2,048 B a row
    f, b = flops_afmoe.window_decode_cost(600_000 * 24, 32, 4, 128, 2)
    assert b == 600_000 * 24 * 2048
    want = 100.0 * max(f / 197e12, b / 819e9) / 0.005
    assert _read("window_decode_roofline", ctx) == pytest.approx(want)

    # the full layers' causal square at the STATED head size (128, not
    # 2048 / 32 = 64, under which flash_prefill_roofline reads nothing)
    f, b = flops_lfm2.flash_prefill_cost(1, 32, 4, 4096, 128, 2)
    want = 100.0 * max(f / 197e12, b / 819e9) / 0.006
    assert _read("flash_head_dim_prefill_roofline", ctx) \
        == pytest.approx(want)
    assert _read("flash_prefill_roofline", ctx) is None
    assert len(ctx["notes"]) == 3


def test_the_familys_files_are_additions():
    """'A later PR adds files and edits no file that is there'
    (chipbench/README.md), read off the names."""
    here = os.path.join(harness.ROOT, "chipbench")
    added = ["reference/afmoe.py", "weights_afmoe.py",
             "serve_model_afmoe.py", "ref_child_afmoe.py", "flops_afmoe.py",
             "modes/serve_afmoe.py", "configs/trinity-mini-26b-ep8.json",
             "traffic/mixedlen.json", f"workloads/{CELL}.json"] \
        + [f"metrics/{m}.py" for m in NEW]
    assert all(os.path.exists(os.path.join(here, f)) for f in added)
    # the mode reuses the lfm2 mode's daemon and reference runner under its
    # own two names, and restores them
    from chipbench.modes import serve_afmoe, serve_lfm2
    with serve_afmoe.family():
        assert serve_lfm2.MODEL_SCRIPT == "serve_model_afmoe.py"
        assert serve_lfm2.REF_CHILD == "chipbench.ref_child_afmoe"
    assert serve_lfm2.MODEL_SCRIPT == "serve_model_lfm2.py"
    assert serve_lfm2.REF_CHILD == "chipbench.ref_child_lfm2"
