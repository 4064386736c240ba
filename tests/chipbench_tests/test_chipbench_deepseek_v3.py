"""The ``deepseek_v3`` family's part of the benchmark, all of it NEW files:
the cell is found by name and rehearsed end to end on the CPU at its tiny
sizes (the real ``serve`` daemon on the family's model script, the open
loop, the family's reference child), its control is a lower precision, its
weights are seeded, and its three readers read what the program emits and
return nothing where the program emits nothing (the parent)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops_deepseek_v3, harness, run
from chipbench import weights_deepseek_v3 as weights
from chipbench.reference import deepseek_v3 as ref

CELL = "gigachat-ep16-serve-longout"
QUIET = lambda m: None


def _tiny_config():
    loaded = run.apply_tiny(harness.load_cell(CELL))
    return loaded["config"]


def test_cell_is_found_by_name_with_its_mode_traffic_and_readers():
    loaded = harness.load_cell(CELL)
    cell, cfg = loaded["cell"], loaded["config"]
    assert cell["mode"] == "serve_deepseek_v3" and cell["chips"] == 1
    assert callable(harness.mode_for(loaded).run)
    assert callable(harness.mode_for(loaded).sweep)
    assert harness.generator_for(loaded).length_range(loaded["traffic"]) \
        == (16, 512, 2048)
    reported = {m["name"] for m in loaded["end_to_end"]}
    # ttft_p50_ms is left out: it spread by more than half its bound
    # between equal runs of this cell (PERF.md section 6, PR 26)
    assert reported == {"tpot_p50_ms", "serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in loaded["per_layer"]}
    assert {"mla_decode_roofline", "expert_matmul_roofline",
            "expert_load_max_over_mean", "decode_step_ms",
            "device_idle.serve"} <= names
    assert "paged_decode_roofline" not in names
    for m in loaded["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"],
                                            loaded["base"]).read)
    # the published widths are uncut; what is cut is listed
    entry = next(c for c in harness.load_benchmark()["configs"]
                 if c["name"] == cell["config"])
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted(
        cfg["published"])
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["router_width"],
            cfg["num_experts_per_tok"], cfg["n_group"],
            cfg["topk_group"]) == (7168, 64, 1536, 512, 128, 64, 192, 18432,
                                   2048, 256, 8, 8, 4)
    assert len(cfg["experts_held"]) == cfg["n_routed_experts"] == 16
    # the pool for the worst case: no request waits for pages
    f = cell["flags"]
    assert f["pages"] == f["slots"] * (cfg["n_positions"]
                                       // f["page_block"]) + 1


def test_parameter_count_is_the_configuration_files():
    cfg = harness.load_cell(CELL)["config"]
    _, shapes = weights.model_and_shapes(cfg)
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert round(n / 1e6) == cfg["parameters_millions"]
    assert all(s.dtype == jnp.bfloat16 or "e_bias" in jax.tree_util.keystr(p)
               for p, s in jax.tree_util.tree_flatten_with_path(shapes)[0])


def test_seeded_weights():
    _, shapes = weights.model_and_shapes(_tiny_config())
    a, b, c = (weights.make(shapes, s) for s in (5, 5, 6))
    la, lb, lc = (jax.tree_util.tree_leaves(t) for t in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert any(not np.array_equal(x, y) for x, y in zip(la, lc))
    assert float(a["blocks_0"]["attn_norm"]["gamma"].min()) == 1.0
    bias = np.asarray(a["blocks_1"]["moe"]["e_bias"])
    assert bias.dtype == np.float32 and 0.003 < bias.std() < 0.03
    w = np.asarray(a["blocks_1"]["moe"]["w_gate"], np.float32)
    assert a["blocks_1"]["moe"]["w_gate"].dtype == jnp.bfloat16
    assert 0.015 < w.std() < 0.025
    # two leaves of one shape are different draws
    assert not np.array_equal(a["blocks_1"]["moe"]["w_gate"],
                              a["blocks_1"]["moe"]["w_up"])


def test_the_control_is_a_lower_precision_than_the_reference():
    cfg = _tiny_config()
    _, shapes = weights.model_and_shapes(cfg)
    params = weights.make(shapes, 3)
    params = jax.tree_util.tree_map(
        lambda a: (a.astype(jnp.float32) * 5).astype(a.dtype)
        if a.ndim >= 2 else a, params)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0,
                             cfg["vocab_size"])
    hp = ref.hparams(cfg)
    sound = ref.forward(params, ids, hp)
    low = ref.forward(params, ids, hp, "fp8")
    assert sound.dtype == low.dtype == jnp.float32
    err = float(jnp.max(jnp.abs(sound - low)))
    assert 1e-3 < err < 5.0
    best, served, pick = ref.token_gaps(params, ids, hp, "fp8")
    assert pick is not None and best.shape == (1, 31)
    assert float(jnp.min(best - served)) >= 0.0
    assert float(jnp.min(best - pick)) >= 0.0


def _run(**kw):
    real = harness.load_cell

    def tiny_limits(name, root=None):
        loaded = real(name, root)
        loaded["cell"]["limits"] = {"served_gap_mean": 1e-5,
                                    "served_gap_widest": 1e-4}
        return loaded
    harness.load_cell = tiny_limits
    try:
        return run.run_cell(CELL, 2**31 + 13, 2.0, 1, rehearsal=True,
                            log=QUIET, **kw)
    finally:
        harness.load_cell = real


def test_rehearsal_sound_then_an_altered_token():
    line, raw = _run()
    assert all(ok for *_, ok in raw["checks"]), raw["checks"]
    assert line["correct"] is True
    assert line["attempted"] == 16 and line["failed"] == 0
    assert {"decode_step_ms", "slots_live_mean", "tpot_p95_ms",
            "expert_load_max_over_mean"} <= set(line["metrics"])
    assert line["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0
    # no device trace on the CPU: the two rooflines have nothing to read
    assert "mla_decode_roofline" not in line["metrics"]
    obs_dump = raw["ctx"]["obs"]
    counters = {(m["name"], m["labels"].get("program")) for m in
                obs_dump["metrics"] if m["name"].startswith("moe.")}
    assert {("moe.assignments_total", "segment"),
            ("moe.assignments_here_total", "admit"),
            ("moe.experts_touched_total", "segment")} <= counters
    routes = {m["labels"]["kernel"] for m in obs_dump["metrics"]
              if m["name"] == "kernels.routes_total"}
    assert {"paged_latent_attention", "expert_grouped_matmul"} <= routes
    assert any(m["name"] == "kernels.bytes_total"
               and m["labels"].get("kernel") == "paged_latent_attention"
               for m in obs_dump["metrics"])
    builds = {e["args"]["kind"] for e in obs_dump["events"]
              if e.get("name") == "serving.program_build"}
    assert builds == {"admit", "segment"}

    def alter(records):
        for rec in records:
            rec["tokens"][-1] = (rec["tokens"][-1] + 1) % 128
    line, raw = _run(alter=alter)
    rows = {name: ok for name, _, _, ok in raw["checks"]}
    assert rows["served_gap_widest"] is False and line["correct"] is False


# -- the readers, on a made-up trace and obs dump ------------------------------

def _ctx(events=(), raw_ops=()):
    loaded = harness.load_cell(CELL)
    return {"cell": loaded["cell"], "config": loaded["config"],
            "base": loaded["base"], "device": {"kind": "TPU v5e"},
            "window": (100.0, 150.0), "records": [],
            "obs": {"meta": {"clock_origin_unix": 100.0},
                    "events": list(events), "requests": [], "metrics": []},
            "trace": {"raw_ops": list(raw_ops), "chips": 1, "shift": 100.0,
                      "busy_s": 1.0}}


def _read(name, ctx):
    return harness.load_module("metrics", name, ctx["base"]).read(ctx)


def test_readers_return_nothing_where_the_program_emits_nothing():
    seg = {"kind": "span", "name": "serving.segment", "ts": 1.0, "dur": 0.5,
           "args": {"live": 3}}                 # the parent's span: no counts
    gpt = ("%paged_decode_attention.3 = f32[16,20,64] custom-call(...)", 1.0,
           1e-4)
    ctx = _ctx([seg], [gpt])
    for name in ("mla_decode_roofline", "expert_matmul_roofline",
                 "expert_load_max_over_mean"):
        assert _read(name, ctx) is None
    ctx["trace"] = None
    assert _read("mla_decode_roofline", ctx) is None
    assert _read("expert_matmul_roofline", ctx) is None


def test_expert_readers_on_a_made_up_run():
    segs = [{"kind": "span", "name": "serving.segment", "ts": t, "dur": 0.5,
             "args": {"live": 32, "routed_here": 800, "experts_touched": 500,
                      "load_max": 20}} for t in (1.0, 1.5, 2.0)]
    # 80 cells: mean 10 a segment, busiest 20
    ctx = _ctx(segs)
    assert _read("expert_load_max_over_mean", ctx) == pytest.approx(2.0)
    # the trace runs from 0.9 to 2.3: two segments lie wholly inside it,
    # the third is cut; an admission's kernel event (at 0.95, inside no
    # segment) and the cut segment's are left out of the time
    name = "%expert_grouped_matmul.{} = f32[512,2048] custom-call(...)"
    ops = [("%fusion.1 = f32[8] fusion(...)", 0.9, 0.01),
           (name.format(1), 0.95, 0.04), (name.format(2), 1.1, 0.2),
           (name.format(3), 1.6, 0.25), (name.format(4), 2.1, 0.2)]
    ctx = _ctx(segs, ops)
    f, b = flops_deepseek_v3.expert_matmul_cost(1600, 1000, 7168, 2048, 2)
    want = 100.0 * max(f / 197e12, b / 819e9) / 0.45
    assert _read("expert_matmul_roofline", ctx) == pytest.approx(want)


def test_cost_functions():
    f, b = flops_deepseek_v3.mla_decode_cost(1000, 64, 576, 512, 2)
    assert f == 1000 * 64 * (2 * 576 + 2 * 512) and b == 1000 * 576 * 2
    f, b = flops_deepseek_v3.expert_matmul_cost(10, 4, 7168, 2048, 2)
    assert f == 6.0 * 10 * 7168 * 2048
    assert b == 3 * 4 * 7168 * 2048 * 2 + 10 * 7168 * 6


def test_the_familys_files_and_the_mix_as_the_issue_gives_it():
    """The family's files are additions: the README's rule, 'a later PR adds
    files and edits no file that is there', read off the names."""
    here = os.path.join(harness.ROOT, "chipbench")
    added = ["reference/deepseek_v3.py", "weights_deepseek_v3.py",
             "serve_model_deepseek_v3.py", "ref_child_deepseek_v3.py",
             "flops_deepseek_v3.py", "modes/serve_deepseek_v3.py",
             "configs/gigachat3.1-702b-ep16.json", "traffic/longout.json",
             f"workloads/{CELL}.json", "metrics/mla_decode_roofline.py",
             "metrics/expert_matmul_roofline.py",
             "metrics/expert_load_max_over_mean.py",
             "metrics/_deepseek_v3_common.py"]
    assert all(os.path.exists(os.path.join(here, f)) for f in added)
    traffic = json.load(open(os.path.join(here, "traffic/longout.json")))
    assert traffic["generator"] == "poisson_lengths"
    assert traffic["prompt"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.8, "low": 16, "high": 512}
    assert traffic["output"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 0.7, "low": 64, "high": 1536}
    arr = traffic["arrivals"]
    assert arr["cv"] == 1.0 and arr["rate_per_s"] == pytest.approx(
        0.8 * arr["knee_per_s"])
