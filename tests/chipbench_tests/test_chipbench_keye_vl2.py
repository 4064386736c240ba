"""The ``keye_vl2`` family's part of the benchmark, all of it NEW files: the
cell is found by name — in the repository and in a temp copy — and rehearsed
end to end on the CPU at its tiny sizes (the real ``serve`` daemon on the
family's model script, three rows a layer in the pool, the family's
reference child; exit 4), its controls are a lower precision, a forgotten
selection and a window passed off as one, its parameter count is the
configuration file's arithmetic, ``flops_keye_vl2.py`` counts what a hand
counts, and its five readers read what the program emits and return nothing
where the program emits nothing (the parent)."""

import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops_keye_vl2, harness, run
from chipbench import weights_keye_vl2 as weights
from chipbench.reference import keye_vl2 as ref

CELL = "keye-ep8-serve-longctx"
NEW = ("index_score_roofline", "sparse_decode_roofline",
       "sparse_prefill_roofline", "selected_keys_share", "sparse_busy_share")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _tiny_config():
    return run.apply_tiny(harness.load_cell(CELL))["config"]


def test_cell_is_found_by_name_with_its_mode_traffic_and_readers():
    loaded = harness.load_cell(CELL)
    cell = loaded["cell"]
    assert cell["mode"] == "serve_keye_vl2" and cell["chips"] == 1
    assert callable(harness.mode_for(loaded).run)
    assert callable(harness.mode_for(loaded).sweep)
    assert harness.generator_for(loaded).length_range(loaded["traffic"]) \
        == (4096, 32768, 33792)
    reported = {m["name"] for m in loaded["end_to_end"]}
    assert reported == {"tpot_p50_ms", "serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in loaded["per_layer"]}
    assert names == set(NEW) | {
        "prefill_expert_matmul_roofline", "expert_load_max_over_mean",
        "decode_step_ms", "tpot_p95_ms", "slots_live_mean",
        "segment_host_ms", "device_idle.serve", "setup_trace_lower_s"}
    # NOT ``expert_matmul_roofline``: a decode step's grouped products are
    # 7.5 us events here (two touched experts of 3.1 MB a call) and the
    # reader's share read 89.9 and 101.2 % on the chip (PERF.md section 7)
    for m in loaded["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"],
                                            loaded["base"]).read)
    bench = harness.load_benchmark()
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert len(bench["workloads"]) == 8 and len(bench["configs"]) == 7


def test_configuration_holds_every_published_key_and_cuts_three_things():
    loaded = harness.load_cell(CELL)
    cfg = loaded["config"]
    entry = next(c for c in harness.load_benchmark()["configs"]
                 if c["name"] == loaded["cell"]["config"])
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted(
        cfg["published"]) == ["num_experts", "num_hidden_layers",
                              "num_local_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "num_local_experts": 128,
                                "vocab_size": 151936}
    assert entry["source"] == cfg["source"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert cfg["source"] == row["source_url"]
        moved = set(cfg["reduced"])
        for key, value in row["config"].items():
            assert key in cfg, key
            if key not in moved:
                assert cfg[key] == value, key
    # every width as published: the block, the indexer, the router
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["rope_theta"],
            cfg["rms_norm_eps"], cfg["norm_topk_prob"]) == (
        2048, 128, 32, 4, 768, 8, 10000000, 1e-6, True)
    assert cfg["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert cfg["router_width"] == 128 and cfg["num_experts"] == 16
    assert cfg["experts_held"] == list(range(16))
    assert cfg["num_hidden_layers"] == 12
    assert cfg["vocab_size"] == 151936 // 8 and cfg["n_positions"] == 33792
    assert cfg["first_k_dense_replace"] == 0
    for key in ("assumed", "departures", "deployment", "dtype",
                "parameters_arithmetic", "changed"):
        assert cfg[key], key
    # the pool for the worst case: no request waits for pages; ONE prompt
    # bucket (a row pays for its own blocks) and one segment program
    f = loaded["cell"]["flags"]
    assert f["pages"] == f["slots"] * (cfg["n_positions"]
                                       // f["page_block"]) + 1 == 4225
    assert f["prompt_buckets"] == [32768]
    assert f["prompt_buckets"][0] % cfg["block_tokens"] == 0
    assert f["cache_bucket"] == cfg["n_positions"] and f["no_prefix_cache"]
    traffic = loaded["traffic"]
    assert traffic["generator"] == "poisson_lengths"
    assert traffic["prompt"] == {"dist": "lognormal", "median": 12288,
                                 "sigma": 0.6, "low": 4096, "high": 32768}
    assert traffic["output"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.7, "low": 32, "high": 1024}
    assert traffic["max_total"] == 33792
    arr = traffic["arrivals"]
    assert arr["cv"] == 1.0
    assert arr["rate_per_s"] == pytest.approx(0.8 * arr["knee_per_s"],
                                              abs=0.02)


def test_every_request_of_the_mix_lives_past_topk():
    """Every prompt is past ``topk``, so no decode step of the window reads
    through the dense kernel; the same schedule for every seed."""
    loaded = harness.load_cell(CELL)
    gen = harness.generator_for(loaded)
    traffic = dict(loaded["traffic"], arrivals=dict(
        loaded["traffic"]["arrivals"], rate_per_s=10.0))
    a = gen.generate(traffic, 1, 50, 18992)
    b = gen.generate(traffic, 2**31 + 5, 50, 18992)
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    lens = np.asarray([r["prompt"].size for r in a])
    assert lens.tolist() == [r["prompt"].size for r in b]
    assert lens.min() >= 4096 > loaded["config"]["sa_config"]["topk"]
    assert 10000 < np.median(lens) < 15000 and (lens == 32768).mean() > 0.02
    assert all(r["prompt"].size + r["max_new"] <= 33792 for r in a)
    assert all(int(r["prompt"].max()) < 18992 for r in a)


def test_parameter_count_is_the_configuration_files_arithmetic():
    cfg = harness.load_cell(CELL)["config"]
    _, shapes = weights.model_and_shapes(cfg)
    leaves = jax.tree_util.tree_leaves(shapes)
    n = sum(int(np.prod(s.shape)) for s in leaves)
    assert n == flops_keye_vl2.param_count(cfg)
    assert round(n / 1e6) == cfg["parameters_millions"] == 1241
    assert all(s.dtype == jnp.bfloat16 for s in leaves)
    layer = 2048 * 4096 * 2 + 2 * 2048 * 512 + 256 \
        + 2048 * (16 * 64 + 64 + 16) + 64 + 2048 * 128 + 4096 \
        + 16 * 3 * 2048 * 768
    assert n == 12 * layer + 2 * 18992 * 2048 + 2048
    # a cached token: k and v of 4 heads of 128 and the indexer's 64
    model, _ = weights.model_and_shapes(cfg)
    rows = model.cache_rows({"embed": {"w": jnp.zeros((1,), jnp.bfloat16)}})
    assert sum(int(np.prod(r.shape)) * 2 for r in rows) == 12 * 2176


def test_seeded_weights():
    cfg = _tiny_config()
    _, shapes = weights.model_and_shapes(cfg)
    a, b, c = (weights.make(shapes, s) for s in (3, 3, 4))
    la, lb, lc = (jax.tree_util.tree_leaves(t) for t in (a, b, c))
    assert all((x == y).all() for x, y in zip(la, lb))
    assert any((x != y).any() for x, y in zip(la, lc))
    blk = a["blocks_0"]
    assert (blk["idx"]["k_norm"]["gamma"] == 1).all()
    assert "e_bias" not in blk["moe"] and "shared" not in blk["moe"]
    assert float(jnp.std(blk["idx"]["w_idx"].astype(jnp.float32))) \
        == pytest.approx(0.02, rel=0.2)


def test_the_controls_are_a_lower_precision_and_two_lost_selections():
    from chipbench import ref_child_keye_vl2 as child
    cfg = dict(_tiny_config(), n_positions=64)
    _, shapes = weights.model_and_shapes(cfg, jnp.float32)
    params = weights.make(shapes, 3)
    for i in range(cfg["num_hidden_layers"]):
        w = params[f"blocks_{i}"]["idx"]
        w["w_idx"] = 10.0 * w["w_idx"]
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
        for control in ("fp8", "all", "recent"):
            out = child.gaps_for(params, cfg, rows, control)[0]
            assert len(out["gaps"]) == len(out["control_gaps"]) == 18
            assert min(out["control_gaps"]) >= -1e-6
        child.PAD, pad = 16, child.PAD          # another padded length
        try:
            again = child.gaps_for(params, cfg, rows)[0]
        finally:
            child.PAD = pad
    np.testing.assert_allclose(again["gaps"], sound["gaps"], atol=1e-5)


def test_flops_counts_against_hand_arithmetic():
    # a decode step, a layer, a slot at 15,000 positions: 15,000 keys of
    # 128 B scored against 16 queries of 64; 2,048 rows of 2,048 B read
    f, b = flops_keye_vl2.index_score_cost(15000, 16, 64, 2)
    assert (f, b) == (2 * 15000 * 16 * 64, 15000 * 128)
    f, b = flops_keye_vl2.sparse_decode_cost(2048, 1, 32, 4, 128, 2)
    assert f == 4 * 2048 * 32 * 128
    assert b == 2048 * 2048 + 2 * 32 * 128 * 4
    # an admission's selected pairs: 4 x 32 x 128 operations each
    f, b = flops_keye_vl2.sparse_prefill_cost(1000, 32, 128)
    assert (f, b) == (1000 * 16384, 0.0)
    # the bytes the value read streams fall with topk / context
    dense = 2 * 32768 * 4 * 128 * 2
    assert flops_keye_vl2.sparse_decode_cost(2048, 0, 32, 4, 128, 2)[1] \
        == dense / 16


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
            "expert_load_max_over_mean", "selected_keys_share"} \
        <= set(line["metrics"])
    # most contexts are past the tiny topk of 16: a share well under 100
    assert 10 < line["metrics"]["selected_keys_share"]["value"] < 90
    # no device trace on the CPU: the rooflines have nothing to read
    assert not {"index_score_roofline", "sparse_decode_roofline",
                "sparse_prefill_roofline", "sparse_busy_share"} \
        & set(line["metrics"])
    obs_dump = raw["ctx"]["obs"]
    metrics = {(m["name"], m["labels"].get("program")
                or m["labels"].get("kernel") or m["labels"].get("state")):
               m.get("value") for m in obs_dump["metrics"]}
    assert {("moe.assignments_total", "segment"),
            ("moe.assignments_here_total", "admit"),
            ("sparse.keys_scored_total", "segment"),
            ("sparse.keys_selected_total", "segment"),
            ("sparse.keys_scored_total", "admit"),
            ("sparse.keys_selected_total", "admit"),
            ("kernels.routes_total", "index_scores"),
            ("kernels.routes_total", "index_scores_paged"),
            ("kernels.routes_total", "select_topk"),
            ("kernels.routes_total", "selected_flash_attention"),
            ("kernels.routes_total", "sparse_decode_attention"),
            ("kernels.bytes_total", "index_scores_paged"),
            ("kernels.bytes_total", "sparse_decode_attention"),
            ("kernels.bytes_total", "selected_flash_attention")} \
        <= set(metrics)
    assert metrics[("sparse.keys_selected_total", "segment")] \
        < metrics[("sparse.keys_scored_total", "segment")]
    # three rows a layer, the third held 128 wide: 2 layers, 65 pages of 8
    assert metrics[("serving.pool_bytes_held", "logical")] \
        == 65 * 8 * 2 * (2 * 2 * 8 + 8) * 2
    builds = {e["args"]["kind"] for e in obs_dump["events"]
              if e.get("name") == "serving.program_build"}
    assert builds == {"admit", "segment"}
    for span, more in (("serving.prefill", {"rows", "prompt_tokens",
                                            "pairs_selected",
                                            "pairs_causal"}),
                       ("serving.segment", {"live", "keys_scored",
                                            "keys_selected", "sparse_steps",
                                            "dense_steps", "dense_rows"})):
        args = [e.get("args", {}) for e in obs_dump["events"]
                if e.get("name") == span]
        assert args and all(
            {"routed_here", "experts_touched", "load_max"} | more <= set(a)
            for a in args), span
    pre = [e["args"] for e in obs_dump["events"]
           if e.get("name") == "serving.prefill"]
    assert all(0 < a["pairs_selected"] <= a["pairs_causal"] for a in pre)
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
    a configuration without an indexer, no trace at all."""
    spans = [{"name": "serving.segment", "ts": 1.0, "dur": 0.5,
              "args": {"live": 3}},
             {"name": "serving.prefill", "ts": 2.0, "dur": 0.5,
              "args": {"rows": 1}}]
    ops = [("%fusion.1 = fusion(...)", 0.5, 0.1),
           ("%paged_decode_attention.3 = custom-call(...)", 1.1, 0.2)]
    ctx = _ctx(spans, ops)
    for name in NEW:
        assert _read(name, ctx) is None, name
    ctx = _ctx(spans, ops)
    ctx["trace"] = None
    for name in NEW:
        assert _read(name, ctx) is None, name
    other = dict(harness.load_cell(CELL)["config"])
    del other["sa_config"]
    for name in ("index_score_roofline", "sparse_decode_roofline",
                 "sparse_prefill_roofline"):
        assert _read(name, _ctx(spans, ops, other)) is None


def test_the_five_readers_on_a_made_up_run():
    cfg = harness.load_cell(CELL)["config"]
    peaks = harness.peaks_for("TPU v5e")
    seg = {"live": 2, "keys_scored": 12 * 64 * 15000, "dense_rows": 0,
           "keys_selected": 12 * 64 * 2048, "sparse_steps": 64,
           "dense_steps": 0}
    pre = {"rows": 1, "pairs_selected": 12 * 10 ** 7,
           "pairs_causal": 12 * 3 * 10 ** 7}
    spans = [{"name": "serving.segment", "ts": 1.0, "dur": 1.0, "args": seg},
             {"name": "serving.prefill", "ts": 3.0, "dur": 1.0, "args": pre},
             # cut by the trace's edge: left out of counts and time alike
             {"name": "serving.segment", "ts": 9.5, "dur": 1.0, "args": seg}]
    ops = [("%fusion.1 = fusion(...)", 0.5, 0.01),
           ("%index_scores_paged.2 = custom-call(...)", 1.1, 0.02),
           ("%select_topk.4 = custom-call(...)", 1.2, 0.01),
           ("%sparse_decode_attention.5 = custom-call(...)", 1.3, 0.05),
           ("%index_scores.7 = custom-call(...)", 3.1, 0.02),
           ("%select_topk.8 = custom-call(...)", 3.2, 0.03),
           ("%selected_flash_attention.9 = custom-call(...)", 3.3, 0.1),
           ("%sparse_decode_attention.11 = custom-call(...)", 9.8, 0.05),
           ("%fusion.12 = fusion(...)", 10.0, 0.01)]
    ctx = _ctx(spans, ops)
    bw, peak = peaks["hbm_bytes_per_s"], peaks["bf16_flops_per_s"]
    assert _read("index_score_roofline", ctx) == pytest.approx(
        100 * 12 * 64 * 15000 * 128 / bw / 0.02)
    rows = 12 * 64 * 2048
    assert _read("sparse_decode_roofline", ctx) == pytest.approx(
        100 * (rows * 2048 + 2 * 64 * 12 * 32 * 128 * 4) / bw / 0.05)
    assert _read("sparse_prefill_roofline", ctx) == pytest.approx(
        100 * 12 * 10 ** 7 * 16384 / peak / 0.1)
    # the window's segments, cut by the trace or not: 2,048 of 15,000
    assert _read("selected_keys_share", ctx) == pytest.approx(
        100 * 2048 / 15000)
    assert _read("sparse_busy_share", ctx) == pytest.approx(
        100 * (0.02 + 0.01 + 0.05 + 0.02 + 0.03 + 0.1 + 0.05) / 1.0)
    assert len(ctx["notes"]) == 4
    for name in ("index_score_roofline", "sparse_decode_roofline",
                 "sparse_prefill_roofline"):
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
    assert loaded["config"]["sa_config"]["topk"] == 2048
    assert loaded["traffic"]["generator"] == "poisson_lengths"
    assert callable(harness.mode_for(loaded).run)
    for m in loaded["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"],
                                            loaded["base"]).read)


def test_the_familys_files_are_additions():
    """'A later PR adds files and edits no file that is there'
    (chipbench/README.md), read off the names."""
    here = os.path.join(harness.ROOT, "chipbench")
    added = ["reference/keye_vl2.py", "weights_keye_vl2.py",
             "serve_model_keye_vl2.py", "ref_child_keye_vl2.py",
             "flops_keye_vl2.py", "modes/serve_keye_vl2.py",
             "configs/keye-vl2-30b-ep8-12l.json", "traffic/longctx.json",
             f"workloads/{CELL}.json", "metrics/_keye_vl2_common.py"] \
        + [f"metrics/{m}.py" for m in NEW]
    assert all(os.path.exists(os.path.join(here, f)) for f in added)
    from chipbench.modes import serve_keye_vl2, serve_lfm2
    with serve_keye_vl2.family():
        assert serve_lfm2.MODEL_SCRIPT == "serve_model_keye_vl2.py"
        assert serve_lfm2.REF_CHILD == "chipbench.ref_child_keye_vl2"
    assert serve_lfm2.MODEL_SCRIPT == "serve_model_lfm2.py"
    assert serve_lfm2.REF_CHILD == "chipbench.ref_child_lfm2"
