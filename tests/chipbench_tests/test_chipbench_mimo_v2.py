"""The ``mimo_v2_flash`` family's part of the benchmark, all of it NEW files:
the cell is found by name — in the repository and in a temp copy — and
rehearsed end to end on the CPU at its tiny sizes (the real ``serve`` daemon
on the family's model script, pages for the global layers and a ring for the
sliding ones, the family's reference child; exit 4), its controls are a
lower precision and a forgotten sink, its parameter count is the issue's
table to the parameter, ``flops_mimo_v2.py`` counts what a hand counts, and
its five readers read what the program emits and return nothing where the
program emits nothing (the parent)."""

import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops_mimo_v2, harness, run
from chipbench import weights_mimo_v2 as weights
from chipbench.reference import mimo_v2 as ref

CELL = "mimo-ep16-serve-agentctx"
NEW = ("sink_window_decode_roofline", "split_width_decode_roofline",
       "sink_window_flash_prefill_roofline",
       "split_width_flash_prefill_roofline", "attention_busy_share")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _tiny_config():
    return run.apply_tiny(harness.load_cell(CELL))["config"]


def test_cell_is_found_by_name_with_its_mode_traffic_and_readers():
    loaded = harness.load_cell(CELL)
    cell = loaded["cell"]
    assert cell["mode"] == "serve_mimo_v2" and cell["chips"] == 1
    assert callable(harness.mode_for(loaded).run)
    assert callable(harness.mode_for(loaded).sweep)
    assert harness.generator_for(loaded).length_range(loaded["traffic"]) \
        == (4096, 49152, 50176)
    reported = {m["name"] for m in loaded["end_to_end"]}
    assert reported == {"tpot_p50_ms", "serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in loaded["per_layer"]}
    assert set(NEW) <= names
    assert {"decode_step_ms", "tpot_p95_ms", "slots_live_mean",
            "segment_host_ms", "device_idle.serve", "setup_trace_lower_s",
            "expert_load_max_over_mean",
            "prefill_expert_matmul_roofline"} <= names
    # NOT the one-width rooflines: they count one d_head for k and v
    assert not {"window_decode_roofline", "gqa_head_dim_decode_roofline",
                "window_flash_prefill_roofline",
                "flash_head_dim_prefill_roofline"} & names
    for m in loaded["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"],
                                            loaded["base"]).read)
    bench = harness.load_benchmark()
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"
            assert m["layer"] == "kernels"
    assert CELL in [w["name"] for w in bench["workloads"]]


def test_the_cells_before_this_one_are_found_as_they_were():
    """Every line of test_chipbench_keye_vl2.py's first test but the two
    that took the benchmark's counts of ITS day (8 cells, 7 configurations,
    its own cell last: tests/conftest.py), asserted again of keye's cell —
    and the order the cells were added in, with no count of THIS day."""
    keye = "keye-ep8-serve-longctx"
    keye_new = ("index_score_roofline", "sparse_decode_roofline",
                "sparse_prefill_roofline", "selected_keys_share",
                "sparse_busy_share")
    bench = harness.load_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[:8] == [
        "gpt2m-train-1k", "gpt2l-serve-chat", "gpt2l-serve-longprompt",
        "gigachat-ep16-serve-longout", "lfm2-serve-rag",
        "nemotron3-ep8-serve-chatburst", "trinity-ep8-serve-mixedlen", keye]
    assert cells[8] == CELL and len(bench["configs"]) >= 8
    loaded = harness.load_cell(keye)
    cell = loaded["cell"]
    assert cell["mode"] == "serve_keye_vl2" and cell["chips"] == 1
    assert callable(harness.mode_for(loaded).run)
    assert callable(harness.mode_for(loaded).sweep)
    assert harness.generator_for(loaded).length_range(loaded["traffic"]) \
        == (4096, 32768, 33792)
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "tpot_p50_ms", "serve_tokens_per_s", "setup_s"}
    assert {m["name"] for m in loaded["per_layer"]} == set(keye_new) | {
        "prefill_expert_matmul_roofline", "expert_load_max_over_mean",
        "decode_step_ms", "tpot_p95_ms", "slots_live_mean",
        "segment_host_ms", "device_idle.serve", "setup_trace_lower_s"}
    for m in loaded["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"],
                                            loaded["base"]).read)
    for m in bench["per_layer"]:
        if m["name"] in keye_new:
            assert m["workloads"] == [keye] and m["moves"] == "tpot_p50_ms"


def test_configuration_holds_every_published_key_and_cuts_three_things():
    loaded = harness.load_cell(CELL)
    cfg = loaded["config"]
    entry = next(c for c in harness.load_benchmark()["configs"]
                 if c["name"] == loaded["cell"]["config"])
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted(
        cfg["published"]) == ["n_routed_experts", "num_hidden_layers",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 256,
                                "vocab_size": 152576}
    assert entry["source"] == cfg["source"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "MiMo-V2-Flash")
        assert cfg["source"] == row["source_url"]
        moved = set(cfg["reduced"])
        for key, value in row["config"].items():
            assert key in cfg, key
            if key not in moved:
                assert cfg[key] == value, key
    # every width as published: both kinds of layer, the router
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"],
            cfg["v_head_dim"], cfg["num_key_value_heads"],
            cfg["swa_num_key_value_heads"], cfg["sliding_window"],
            cfg["partial_rotary_factor"], cfg["rope_theta"],
            cfg["swa_rope_theta"], cfg["attention_value_scale"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"]) == (
        4096, 64, 192, 128, 4, 8, 128, 0.334, 5000000, 10000, 0.707, 16384,
        2048, 8)
    assert cfg["router_width"] == 256 and cfg["n_routed_experts"] == 16
    assert cfg["experts_held"] == list(range(16))
    assert cfg["num_hidden_layers"] == 11
    # the published lists whole; the served layers are their first 11:
    # layer 0, the short run 1-4, the whole period 5-10
    assert len(cfg["hybrid_layer_pattern"]) == 48
    assert cfg["hybrid_layer_pattern"][:11] == [0, 1, 1, 1, 1, 0, 1, 1, 1, 1,
                                                1]
    assert cfg["moe_layer_freq"][:11] == [0] + [1] * 10
    assert flops_mimo_v2.layer_counts(cfg) == {"sliding": 9, "full": 2}
    assert cfg["vocab_size"] == 152576 // 8 and cfg["n_positions"] == 50176
    assert cfg["first_k_dense_replace"] == 1
    for key in ("assumed", "departures", "deployment", "dtype",
                "parameters_arithmetic", "changed"):
        assert cfg[key], key
    for key in ("rope", "window edge", "value scale", "sink"):
        assert cfg["assumed"][key], key
    # the pool for the worst case: no request waits for pages; ONE prompt
    # bucket (a row pays for its own blocks) and one segment program
    f = loaded["cell"]["flags"]
    assert f["pages"] == f["slots"] * (cfg["n_positions"]
                                       // f["page_block"]) + 1
    assert f["prompt_buckets"] == [49152]
    assert f["prompt_buckets"][0] % cfg["block_tokens"] == 0
    assert f["cache_bucket"] == cfg["n_positions"] and f["no_prefix_cache"]
    traffic = loaded["traffic"]
    assert traffic["generator"] == "poisson_lengths"
    assert traffic["prompt"] == {"dist": "lognormal", "median": 16384,
                                 "sigma": 0.7, "low": 4096, "high": 49152}
    assert traffic["output"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.7, "low": 32, "high": 1024}
    assert traffic["max_total"] == 50176
    arr = traffic["arrivals"]
    assert arr["cv"] == 1.0
    assert arr["rate_per_s"] == pytest.approx(0.8 * arr["knee_per_s"],
                                              abs=0.02)
    swept = [r["rate_per_s"] for r in arr["sweep"]["rows"]]
    assert arr["knee_per_s"] in swept and max(swept) > arr["knee_per_s"]


def test_parameter_count_is_the_issues_table_to_the_parameter():
    cfg = harness.load_cell(CELL)["config"]
    model, shapes = weights.model_and_shapes(cfg)
    leaves = jax.tree_util.tree_leaves(shapes)
    n = sum(int(np.prod(s.shape)) for s in leaves)
    assert n == flops_mimo_v2.param_count(cfg) == 5_422_283_840
    assert round(n / 1e6) == cfg["parameters_millions"]
    glob = 4096 * 12288 + 4096 * 768 + 4096 * 512 + 8192 * 4096
    slid = 4096 * 12288 + 4096 * 1536 + 4096 * 1024 + 8192 * 4096 + 64
    moe = 4096 * 256 + 256 + 16 * 3 * 4096 * 2048
    assert (glob, slid, moe) == (89_128_960, 94_371_904, 403_702_016)
    assert n == (glob + 8192 + 3 * 4096 * 16384) + 9 * (slid + 8192 + moe) \
        + (glob + 8192 + moe) + 2 * 19072 * 4096 + 4096
    # all bfloat16 but the 9 x 64 sink logits and the 10 router biases
    assert sum(s.dtype != jnp.bfloat16 for s in leaves) == 9 + 10
    # a cached token: 2 global layers x 4 heads x (192 + 128), 9 sliding
    # layers x 8 heads
    rows = model.cache_rows({"embed": {"w": jnp.zeros((1,), jnp.bfloat16)}})
    grow = [r for r in rows if r.window is None]
    assert sum(int(np.prod(r.shape)) * 2 for r in grow) == 2 * 2560
    assert sum(int(np.prod(r.shape)) * 2 for r in rows
               if r.window == 128) == 9 * 5120
    # a key row held at the chip's lanes
    assert {r.held for r in rows if r.name.startswith("k")} \
        == {(4, 256), (8, 256)}


def test_seeded_weights_draw_the_sinks_wide():
    cfg = _tiny_config()
    _, shapes = weights.model_and_shapes(cfg)
    a, b, c = (weights.make(shapes, s, weights.sink_mean(cfg))
               for s in (3, 3, 4))
    la, lb, lc = (jax.tree_util.tree_leaves(t) for t in (a, b, c))
    assert all((x == y).all() for x, y in zip(la, lb))
    assert any((x != y).any() for x, y in zip(la, lc))
    assert "sink" not in a["blocks_0"]["attn"] and "ffn" in a["blocks_0"]
    sinks = np.concatenate([np.asarray(a[f"blocks_{i}"]["attn"]["sink"])
                            for i in (1, 2, 3, 4, 6)])
    # drawn round ln(window): a window's worth of keys of score 0
    assert weights.sink_mean(cfg) == pytest.approx(np.log(8))
    assert 0.5 < sinks.std() < 1.6 and sinks.dtype == np.float32
    assert abs(sinks.mean() - np.log(8)) < 0.7
    blk = a["blocks_1"]
    assert (blk["input_norm"]["gamma"] == 1).all()
    assert "e_bias" in blk["moe"] and "shared" not in blk["moe"]
    assert float(jnp.std(blk["attn"]["w_qkv"].astype(jnp.float32))) \
        == pytest.approx(0.02, rel=0.2)


def test_the_controls_are_a_lower_precision_and_a_lost_sink():
    from chipbench import ref_child_mimo_v2 as child
    cfg = dict(_tiny_config(), n_positions=64)
    _, shapes = weights.model_and_shapes(cfg, jnp.float32)
    params = weights.make(shapes, 3, weights.sink_mean(cfg))
    ids = np.random.RandomState(2).randint(0, 128, 48)
    hp = ref.hparams(cfg)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(ref.forward(params, jnp.asarray(ids), hp))
        served = np.concatenate([ids[:30], np.argmax(logits, -1)[29:47]])
        rows = [{"prompt": [int(t) for t in served[:30]],
                 "tokens": [int(t) for t in served[30:]]}]
        # teacher forcing changes the later logits: take the first token
        sound = child.gaps_for(params, cfg, rows)[0]
        assert sound["gaps"][0] == 0.0 and "control_gaps" not in sound
        assert len(sound["gaps"]) == 18
        # the cell LISTS its controls: each comes back under its own name
        listed = harness.load_cell(CELL)["cell"]["control_operand"]
        assert listed == ["fp8", "no_sink"]
        both = child.gaps_for(params, cfg, rows, listed)[0]
        assert list(both["controls"]) == listed and "control_gaps" not in both
        assert both["gaps"] == sound["gaps"]
        for control in listed:          # ... and one NAMED, the old contract
            out = child.gaps_for(params, cfg, rows, control)[0]
            assert len(out["gaps"]) == len(out["control_gaps"]) == 18
            assert min(out["control_gaps"]) >= -1e-6
            assert out["control_gaps"] == both["controls"][control]
        assert both["controls"]["fp8"] != both["controls"]["no_sink"]
        child.PAD, pad = 16, child.PAD          # another padded length
        try:
            again = child.gaps_for(params, cfg, rows)[0]
        finally:
            child.PAD = pad
        # only the served positions go through the head
        part = np.asarray(ref.forward(params, jnp.asarray(ids), hp,
                                      rows=(29, 47)))
    np.testing.assert_allclose(again["gaps"], sound["gaps"], atol=1e-5)
    np.testing.assert_allclose(part, logits[29:47], atol=1e-6)


def test_flops_counts_against_hand_arithmetic():
    cfg = harness.load_cell(CELL)["config"]
    # a decode step, a slot at 20,000 positions: the two global layers read
    # 2 x 20,000 rows of 2,560 B; the nine sliding ones 9 x 128 of 5,120 B
    f, b = flops_mimo_v2.decode_read_cost(2 * 20000, cfg, "full")
    assert (f, b) == (2 * 40000 * 64 * 320, 40000 * 2560)
    f, b = flops_mimo_v2.decode_read_cost(9 * 128, cfg, "sliding")
    assert (f, b) == (2 * 9 * 128 * 64 * 320, 9 * 128 * 5120)
    # an admission's pairs: 64 heads x 640 operations each
    f, b = flops_mimo_v2.flash_cost(1000, 10, cfg, "full")
    assert (f, b) == (1000 * 64 * 640, 10 * 320 * 2 * 68)
    f, b = flops_mimo_v2.flash_cost(1000, 10, cfg, "sliding")
    assert (f, b) == (1000 * 64 * 640, 10 * 320 * 2 * 72)
    # the issue's arithmetic: a 21k-token admission ~ 75 TFLOP
    n = 21000
    attn = flops_mimo_v2.flash_cost(2 * n * (n + 1) / 2, 0, cfg, "full")[0]
    assert 17e12 < attn < 19e12


def test_tiny_rehearses_the_cell_end_to_end_and_exits_4(monkeypatch, capsys):
    got, logged = {}, []
    real = run.run_cell

    def spy(*a, **kw):
        kw["log"] = logged.append
        got["line"], got["raw"] = real(*a, **kw)
        return got["line"], got["raw"]
    monkeypatch.setattr(run, "run_cell", spy)
    monkeypatch.setenv("CHIPBENCH_CONTROL", "1")
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
    # each listed control is logged under its own name, beside the limits
    said = [m.split(" = ")[0] for m in logged if m.startswith("control[")]
    assert said == [f"control[{c}] served_gap_{n}" for c in ("fp8", "no_sink")
                    for n in ("mean", "widest")]
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
            ("attention.sink_rows_total", "admit"),
            ("attention.sink_rows_total", "segment"),
            ("kernels.routes_total", "paged_decode_attention"),
            ("kernels.routes_total", "paged_window_attention"),
            ("kernels.routes_total", "flash_window_attention_fwd"),
            ("kernels.bytes_total", "paged_decode_attention"),
            ("kernels.bytes_total", "paged_window_attention"),
            ("kernels.bytes_total", "flash_window_attention_fwd"),
            ("serving.cache_rows_read_total", "window"),
            ("serving.cache_rows_read_total", "full")} <= set(metrics)
    assert metrics[("attention.sink_rows_total", "segment")] > 0
    # 2 global layers' pages (2 heads x (24 + 16)) and 5 sliding layers'
    # rings (4 heads), as STATED: 65 pages and 4 x 3 + 1 ring pages of 8
    assert metrics[("serving.pool_bytes_held", "logical")] \
        == (65 * 2 * 2 + 13 * 5 * 4) * 8 * 40 * 2
    builds = {e["args"]["kind"] for e in obs_dump["events"]
              if e.get("name") == "serving.program_build"}
    assert builds == {"admit", "segment"}
    for span, more in (("serving.prefill", {"rows", "prompt_tokens",
                                            "pairs_causal", "pairs_band",
                                            "sink_rows"}),
                       ("serving.segment", {"live", "window_rows",
                                            "full_rows", "sink_rows"})):
        args = [e.get("args", {}) for e in obs_dump["events"]
                if e.get("name") == span]
        assert args and all(
            {"routed_here", "experts_touched", "load_max"} | more <= set(a)
            for a in args), span
    pre = [e["args"] for e in obs_dump["events"]
           if e.get("name") == "serving.prefill"]
    assert all(0 < a["pairs_band"] <= a["pairs_causal"] for a in pre)
    assert all(a["sink_rows"] == 5 * a["prompt_tokens"] for a in pre)
    # an admission runs a row's own blocks of 16, not its bucket of 64
    assert all(a["positions"] < 64 * a["rows"] or a["prompt_tokens"]
               > 48 * a["rows"] for a in pre)


# -- the readers, on a made-up trace and obs dump ------------------------------

def _ctx(events=(), raw_ops=(), config=None):
    loaded = harness.load_cell(CELL)
    return {"cell": loaded["cell"], "config": config or loaded["config"],
            "base": loaded["base"], "device": {"kind": "TPU v5e"},
            "window": (100.0, 150.0), "records": [],
            "obs": {"meta": {"clock_origin_unix": 100.0},
                    "events": list(events), "requests": [], "metrics": []},
            "trace": {"raw_ops": list(raw_ops), "chips": 1, "shift": 100.0,
                      "busy_s": 1.0}}


def _read(name, ctx):
    return harness.load_module("metrics", name, ctx["base"]).read(ctx)


def test_readers_return_nothing_where_the_program_emits_nothing():
    """The parent: spans without the counts, a trace without the kernels,
    a configuration of another family, no trace at all."""
    spans = [{"name": "serving.segment", "ts": 1.0, "dur": 0.5,
              "args": {"live": 3}},
             {"name": "serving.prefill", "ts": 2.0, "dur": 0.5,
              "args": {"rows": 1}}]
    ops = [("%fusion.1 = fusion(...)", 0.5, 0.1),
           ("%expert_grouped_matmul.3 = custom-call(...)", 1.1, 0.2)]
    ctx = _ctx(spans, ops)
    for name in NEW:
        assert _read(name, ctx) is None, name
    ctx = _ctx(spans, ops)
    ctx["trace"] = None
    for name in NEW:
        assert _read(name, ctx) is None, name
    # trinity's run: the same kernel names, another family's configuration
    other = harness.load_cell("trinity-ep8-serve-mixedlen")["config"]
    spans = [{"name": "serving.segment", "ts": 1.0, "dur": 0.5,
              "args": {"window_rows": 100, "full_rows": 500}}]
    ops = [("%paged_window_attention.3 = custom-call(...)", 1.1, 0.2),
           ("%paged_decode_attention.4 = custom-call(...)", 1.3, 0.1)]
    for name in NEW:
        assert _read(name, _ctx(spans, ops, other)) is None, name


def test_the_five_readers_on_a_made_up_run():
    peaks = harness.peaks_for("TPU v5e")
    seg = {"live": 4, "window_rows": 32 * 4 * 128,
           "full_rows": 32 * 4 * 20000}
    n = 20000
    pre = {"rows": 1, "positions": 20480, "prompt_tokens": n,
           "pairs_causal": n * (n + 1) // 2,
           "pairs_band": 128 * 129 // 2 + (n - 128) * 128}
    spans = [{"name": "serving.segment", "ts": 1.0, "dur": 1.0, "args": seg},
             {"name": "serving.prefill", "ts": 3.0, "dur": 1.0, "args": pre},
             # cut by the trace's edge: left out of counts and time alike
             {"name": "serving.segment", "ts": 9.5, "dur": 1.0, "args": seg}]
    ops = [("%fusion.1 = fusion(...)", 0.5, 0.01),
           ("%paged_window_attention.2 = custom-call(...)", 1.1, 0.02),
           ("%paged_decode_attention.4 = custom-call(...)", 1.2, 0.04),
           ("%flash_window_attention_fwd.7 = custom-call(...)", 3.1, 0.05),
           ("%flash_attention_fwd.8 = custom-call(...)", 3.2, 0.2),
           ("%flash_attention_fwd.9 = custom-call(...)", 3.5, 0.1),
           ("%paged_decode_attention.11 = custom-call(...)", 9.8, 0.05),
           ("%fusion.12 = fusion(...)", 10.0, 0.01)]
    ctx = _ctx(spans, ops)
    bw, peak = peaks["hbm_bytes_per_s"], peaks["bf16_flops_per_s"]
    assert _read("sink_window_decode_roofline", ctx) == pytest.approx(
        100 * 32 * 4 * 128 * 9 * 5120 / bw / 0.02)
    assert _read("split_width_decode_roofline", ctx) == pytest.approx(
        100 * 32 * 4 * 20000 * 2 * 2560 / bw / 0.04)
    # a band of 128 is MEMORY-bound: 5.2 MFLOP a position a layer (26 ns at
    # the peak) beside q, o of 64 heads and k, v of 8, 46 KB (56 ns)
    assert pre["pairs_band"] * 9 * 64 * 640 / peak \
        < 20480 * 9 * 320 * 2 * 72 / bw
    assert _read("sink_window_flash_prefill_roofline", ctx) == pytest.approx(
        100 * 20480 * 9 * 320 * 2 * 72 / bw / 0.05)
    assert _read("split_width_flash_prefill_roofline", ctx) == pytest.approx(
        100 * pre["pairs_causal"] * 2 * 64 * 640 / peak / 0.3)
    assert _read("attention_busy_share", ctx) == pytest.approx(
        100 * (0.02 + 0.04 + 0.05 + 0.2 + 0.1 + 0.05) / 1.0)
    assert len(ctx["notes"]) == 5
    for name in NEW[:4]:
        assert _read(name, ctx) < 100.0


def test_the_cell_is_discovered_in_a_temp_copy(tmp_path):
    """What the driver's checkout does: BENCHMARK.json and chipbench/ copied
    elsewhere find the cell, its configuration, mix, mode and readers."""
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(harness.ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    loaded = harness.load_cell(CELL, str(tmp_path))
    assert loaded["base"] == str(tmp_path / "chipbench")
    assert loaded["config"]["v_head_dim"] == 128
    assert loaded["traffic"]["generator"] == "poisson_lengths"
    assert callable(harness.mode_for(loaded).run)
    for m in loaded["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"],
                                            loaded["base"]).read)


def test_the_familys_files_are_additions():
    """'A later PR adds files and edits no file that is there'
    (chipbench/README.md), read off the names."""
    here = os.path.join(harness.ROOT, "chipbench")
    added = ["reference/mimo_v2.py", "weights_mimo_v2.py",
             "serve_model_mimo_v2.py", "ref_child_mimo_v2.py",
             "flops_mimo_v2.py", "modes/serve_mimo_v2.py",
             "configs/mimo-v2-flash-ep16-11l.json",
             "configs/README_mimo_v2.md", "traffic/agentctx.json",
             f"workloads/{CELL}.json", "metrics/_mimo_v2_common.py"] \
        + [f"metrics/{m}.py" for m in NEW]
    assert all(os.path.exists(os.path.join(here, f)) for f in added)
    from chipbench.modes import serve_lfm2, serve_mimo_v2
    with serve_mimo_v2.family():
        assert serve_lfm2.MODEL_SCRIPT == "serve_model_mimo_v2.py"
        assert serve_lfm2.REF_CHILD == "chipbench.ref_child_mimo_v2"
    assert serve_lfm2.MODEL_SCRIPT == "serve_model_lfm2.py"
    assert serve_lfm2.REF_CHILD == "chipbench.ref_child_lfm2"
