"""The ``lfm2_moe`` family's part of the benchmark, all of it NEW files: the
cell is found by name and rehearsed end to end on the CPU at its tiny sizes
(the real ``serve`` daemon on the family's model script with the cell's
prompt buckets, the open loop, the family's reference child; exit 4), its
control is a lower precision, its weights are seeded, its parameter count
is the configuration file's arithmetic, ``flops_lfm2.py`` counts what a
hand counts, and its three readers read what the program emits and return
nothing where the program emits nothing (the parent)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops, flops_lfm2, harness, run
from chipbench import weights_lfm2 as weights
from chipbench.reference import lfm2 as ref

CELL = "lfm2-serve-rag"


def _tiny_config():
    return run.apply_tiny(harness.load_cell(CELL))["config"]


def test_cell_is_found_by_name_with_its_mode_traffic_and_readers():
    loaded = harness.load_cell(CELL)
    cell, cfg = loaded["cell"], loaded["config"]
    assert cell["mode"] == "serve_lfm2" and cell["chips"] == 1
    assert callable(harness.mode_for(loaded).run)
    assert callable(harness.mode_for(loaded).sweep)
    assert harness.generator_for(loaded).length_range(loaded["traffic"]) \
        == (512, 4096, 4608)
    reported = {m["name"] for m in loaded["end_to_end"]}
    assert {"tpot_p50_ms", "serve_tokens_per_s", "setup_s"} <= reported
    names = {m["name"] for m in loaded["per_layer"]}
    assert {"gqa_decode_roofline", "prefill_expert_matmul_roofline",
            "flash_prefill_roofline", "expert_matmul_roofline",
            "expert_load_max_over_mean", "decode_step_ms", "tpot_p95_ms",
            "slots_live_mean", "segment_host_ms", "device_idle.serve",
            "setup_trace_lower_s"} <= names
    assert not {"paged_decode_roofline", "mla_decode_roofline"} & names
    for m in loaded["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"],
                                            loaded["base"]).read)
    # every published key as the catalog row has it; the cut is depth only
    entry = next(c for c in harness.load_benchmark()["configs"]
                 if c["name"] == cell["config"])
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted(
        cfg["published"]) == ["num_dense_layers", "num_hidden_layers"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["conv_L_cache"],
            cfg["vocab_size"], cfg["rope_theta"], cfg["norm_eps"],
            cfg["max_position_embeddings"]) == (
        2048, 32, 8, 7168, 1792, 32, 4, 3, 65536, 1000000, 1e-5, 128000)
    assert len(cfg["layer_types"]) == 24                # kept whole
    assert cfg["experts_held"] == list(range(32)) and \
        cfg["router_width"] == 32
    kinds = ref.layer_types(cfg)
    assert len(kinds) == 13 and kinds[0] == "conv"
    assert kinds[1:] == ("full_attention", "conv", "conv", "conv") * 3
    # the pool for the worst case: no request waits for pages; the prompt
    # buckets cover the mix; one segment program
    f = cell["flags"]
    assert f["pages"] == f["slots"] * (cfg["n_positions"]
                                       // f["page_block"]) + 1
    assert f["prompt_buckets"] == [512, 1024, 2048, 4096]
    assert f["cache_bucket"] == cfg["n_positions"] == 4608
    traffic = loaded["traffic"]
    assert traffic["generator"] == "poisson_lengths"
    assert traffic["prompt"] == {"dist": "lognormal", "median": 1536,
                                 "sigma": 0.6, "low": 512, "high": 4096}
    assert traffic["output"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.6, "low": 32, "high": 512}
    assert traffic["max_total"] == 4608 and traffic["arrivals"]["cv"] == 1.0


def test_parameter_count_is_the_configuration_files_arithmetic():
    cfg = harness.load_cell(CELL)["config"]
    _, shapes = weights.model_and_shapes(cfg)
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    embed = 65536 * 2048
    conv = 2048 * 6144 + 2048 * 3 + 2048 * 2048
    attn = 2048 * 3072 + 2 * 64 + 2048 * 2048
    dense, expert = 3 * 2048 * 7168, 3 * 2048 * 1792
    moe = 32 * expert + 2048 * 32 + 32
    by_hand = (embed + 10 * conv + 3 * attn + dense + 12 * moe
               + 13 * 2 * 2048 + 2048)
    assert n == by_hand == flops_lfm2.param_count(cfg) == 4_606_249_728
    assert round(n / 1e6) == cfg["parameters_millions"]
    assert all(s.dtype == jnp.bfloat16 or "e_bias" in jax.tree_util.keystr(p)
               for p, s in jax.tree_util.tree_flatten_with_path(shapes)[0])


def test_seeded_weights():
    _, shapes = weights.model_and_shapes(_tiny_config())
    a, b, c = (weights.make(shapes, s) for s in (5, 5, 6))
    la, lb, lc = (jax.tree_util.tree_leaves(t) for t in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert any(not np.array_equal(x, y) for x, y in zip(la, lc))
    assert float(a["blocks_0"]["op_norm"]["gamma"].min()) == 1.0
    assert "conv" in a["blocks_0"] and "ffn" in a["blocks_0"]
    assert "attn" in a["blocks_1"] and "moe" in a["blocks_1"]
    bias = np.asarray(a["blocks_1"]["moe"]["e_bias"])
    assert bias.dtype == np.float32 and 0.002 < bias.std() < 0.03
    w = np.asarray(a["blocks_1"]["moe"]["w_gate"], np.float32)
    assert a["blocks_1"]["moe"]["w_gate"].dtype == jnp.bfloat16
    assert 0.015 < w.std() < 0.025
    taps = np.asarray(a["blocks_0"]["conv"]["w_conv"], np.float32)
    assert taps.shape == (32, 3) and np.abs(taps).max() <= 3 ** -0.5 + 1e-2
    assert 0.25 < taps.std() < 0.40             # uniform(+-0.577): 0.333


def test_the_control_is_a_lower_precision_than_the_reference():
    cfg = _tiny_config()
    _, shapes = weights.model_and_shapes(cfg)
    params = weights.make(shapes, 3)
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
            "segment_host_ms", "setup_trace_lower_s",
            "expert_load_max_over_mean"} <= set(line["metrics"])
    # no device trace on the CPU: the rooflines have nothing to read
    assert not {"gqa_decode_roofline", "flash_prefill_roofline",
                "prefill_expert_matmul_roofline",
                "expert_matmul_roofline"} & set(line["metrics"])
    obs_dump = raw["ctx"]["obs"]
    metrics = {(m["name"], m["labels"].get("program")
                or m["labels"].get("kernel")): m.get("value")
               for m in obs_dump["metrics"]}
    assert {("moe.assignments_total", "segment"),
            ("moe.assignments_here_total", "admit"),
            ("moe.experts_touched_total", "segment"),
            ("kernels.routes_total", "paged_decode_attention"),
            ("kernels.routes_total", "expert_grouped_matmul"),
            ("kernels.bytes_total", "paged_decode_attention")} \
        <= set(metrics)
    # every admission wrote a tail: warm-up requests and the window's
    assert metrics[("serving.slot_state_writes_total", None)] >= 16
    # 4 conv layers x 2 rows x 32 wide x 4 slots in bfloat16
    assert metrics[("serving.slot_state_bytes_held", None)] \
        == 4 * 2 * 32 * 4 * 2
    builds = {e["args"]["kind"] for e in obs_dump["events"]
              if e.get("name") == "serving.program_build"}
    assert builds == {"admit", "segment"}
    # the expert layer's counts ride the prefill spans as well as the
    # segments'
    for span in ("serving.prefill", "serving.segment"):
        args = [e.get("args", {}) for e in obs_dump["events"]
                if e.get("name") == span]
        assert args and all({"routed_here", "experts_touched", "load_max"}
                            <= set(a) for a in args), span


# -- the readers, on a made-up trace and obs dump ------------------------------

def _ctx(events=(), raw_ops=(), config=None):
    loaded = harness.load_cell(CELL)
    records = [{"key": "w-0", "plen": 1000}, {"key": "w-1", "plen": 3000}]
    requests = [
        {"key": "w-0", "events": [{"phase": "first_token", "t": 100.0},
                                  {"phase": "done", "t": 104.0,
                                   "tokens": 200}]},
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


NEW = ("gqa_decode_roofline", "prefill_expert_matmul_roofline",
       "flash_prefill_roofline")


def test_readers_return_nothing_where_the_program_emits_nothing():
    """The parent: no Lfm2MoeLM, so no run of this configuration; and its
    spans carry no counts. Nothing raises, nothing is reported."""
    admit = {"kind": "span", "name": "serving.prefill", "ts": 1.0,
             "dur": 0.5, "args": {"batch": 2}}          # no counts
    other = ("%fusion.7 = f32[8] fusion(...)", 1.1, 1e-4)
    ctx = _ctx([admit], [other])
    for name in NEW:
        assert _read(name, ctx) is None
    # a configuration of another family (no grouped heads to count)
    gpt = json.load(open(os.path.join(harness.ROOT,
                                      "chipbench/configs/gpt2-large.json")))
    ops = [("%paged_decode_attention.3 = f32[16,20,64] custom-call(...)",
            1.0, 1e-4),
           ("%flash_attention_fwd.2 = (f32[320,512,64]) custom-call(...)",
            1.2, 1e-4)]
    ctx = _ctx([admit], ops, config=gpt)
    assert _read("gqa_decode_roofline", ctx) is None
    assert _read("flash_prefill_roofline", ctx) is None
    ctx["trace"] = None
    for name in NEW:
        assert _read(name, ctx) is None


def test_the_three_readers_on_a_made_up_run():
    admits = [{"kind": "span", "name": "serving.prefill", "ts": t,
               "dur": 0.4, "args": {"batch": 1, "routed_here": 96000,
                                    "experts_touched": 384,
                                    "load_max": 400}}
              for t in (1.0, 2.0, 3.0)]
    seg = {"kind": "span", "name": "serving.segment", "ts": 1.5, "dur": 0.4,
           "args": {"live": 2, "routed_here": 3000, "experts_touched": 2000,
                    "load_max": 9}}
    gm = "%expert_grouped_matmul.{} = f32[12288,1792] custom-call(...)"
    fa = ("%flash_attention_fwd.{} = (bf16[{},{},64]{{2,1,0:T(8,128)(2,1)}},"
          " f32[{},{},1]{{2,1,0}}) custom-call(bf16[...] %q), "
          "custom_call_target=\"tpu_custom_call\"")
    pd = "%paged_decode_attention.{} = f32[32,4,8,64] custom-call(...)"
    # the trace runs 0.9 .. 2.6: admissions 1 and 2 lie wholly inside it,
    # the third is cut; the segment's grouped products (at 1.6) are the
    # decode reader's, not the prefill reader's
    ops = [("%fusion.1 = f32[8] fusion(...)", 0.9, 0.01),
           (gm.format(1), 1.05, 0.02), (gm.format(2), 1.10, 0.03),
           (gm.format(3), 1.60, 0.05), (gm.format(4), 2.10, 0.05),
           (fa.format(5, 32, 2048, 32, 2048), 1.20, 0.004),
           (fa.format(6, 128, 512, 128, 512), 2.20, 0.002),
           (fa.format(7, 32, 4096, 32, 4096), 3.10, 0.010),
           (pd.format(8), 1.70, 0.001), (pd.format(9), 1.71, 0.001),
           ("%fusion.2 = f32[8] fusion(...)", 2.59, 0.01)]
    ctx = _ctx(admits + [seg], ops)

    f, b = flops_lfm2.expert_matmul_cost(2 * 96000, 2 * 384, 2048, 1792, 2)
    want = 100.0 * max(f / 197e12, b / 819e9) / 0.10
    assert _read("prefill_expert_matmul_roofline", ctx) == pytest.approx(want)

    f1, b1 = flops_lfm2.flash_prefill_cost(1, 32, 8, 2048, 64, 2)
    f2, b2 = flops_lfm2.flash_prefill_cost(4, 32, 8, 512, 64, 2)
    want = 100.0 * max((f1 + f2) / 197e12, (b1 + b2) / 819e9) / 0.006
    assert _read("flash_prefill_roofline", ctx) == pytest.approx(want)

    # live rows over the kernel's own span [101.70, 101.711]: two requests
    # part-way through their answers (the ledger's interpolation)
    t = 101.7055
    rows = (1000 + 200 * (t - 100) / 4) + (3000 + 400 * (t - 100) / 4)
    f, b = flops_lfm2.gqa_decode_cost(rows, 32, 8, 64, 2)
    want = 100.0 * max(2 * f / 197e12, 2 * b / 819e9) / 0.002
    assert _read("gqa_decode_roofline", ctx) == pytest.approx(want, rel=1e-3)
    assert len(ctx["notes"]) == 3


def test_cost_functions_against_hand_counts():
    # a decode call over 1000 live rows: K and V of 8 heads x 64 in bf16
    # once; 32 query heads x (q.k + p.v) x 64 x 2 flops
    f, b = flops_lfm2.gqa_decode_cost(1000, 32, 8, 64, 2)
    assert b == 1000 * 2 * 8 * 64 * 2 == 2_048_000
    assert f == 1000 * 32 * 64 * 4
    # a 4096-token causal forward of one row: 2 products x 2 flops x
    # 32 heads x T^2 / 2 pairs x 64; q, o of 32 heads, k, v of 8
    f, b = flops_lfm2.flash_prefill_cost(1, 32, 8, 4096, 64, 2)
    assert f == 2 * 2 * 32 * (4096 * 4096 / 2) * 64
    assert b == 4096 * 64 * 2 * (2 * 32 + 2 * 8)
    assert f == flops.flash_attention_cost(1, 32, 4096, 4096, 64, 2)[0]
    # 10 pairs over 4 expert visits at this model's widths
    f, b = flops_lfm2.expert_matmul_cost(10, 4, 2048, 1792, 2)
    assert f == 6.0 * 10 * 2048 * 1792
    assert b == 3 * 4 * 2048 * 1792 * 2 + 10 * 2048 * 6


def test_the_familys_files_are_additions():
    """'A later PR adds files and edits no file that is there'
    (chipbench/README.md), read off the names."""
    here = os.path.join(harness.ROOT, "chipbench")
    added = ["reference/lfm2.py", "weights_lfm2.py", "serve_model_lfm2.py",
             "ref_child_lfm2.py", "flops_lfm2.py", "modes/serve_lfm2.py",
             "configs/lfm2-8b-a1b-13l.json", "traffic/rag.json",
             f"workloads/{CELL}.json", "metrics/gqa_decode_roofline.py",
             "metrics/prefill_expert_matmul_roofline.py",
             "metrics/flash_prefill_roofline.py", "metrics/_lfm2_common.py"]
    assert all(os.path.exists(os.path.join(here, f)) for f in added)
    arr = json.load(open(os.path.join(here, "traffic/rag.json")))["arrivals"]
    assert arr["rate_per_s"] == pytest.approx(0.8 * arr["knee_per_s"],
                                              abs=0.05)
