"""The serve iteration's accounts (ISSUE 37): the positions an admission ran
against the prompt tokens it admitted — from the walk the program itself
runs, for each of the four served model classes and for the prefix-hit
program; the slot-steps a segment ran against the tokens it delivered; and
on every finished request the seconds of its decode life spent in its own
segments and behind other requests' admissions.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import obs
from paddle_tpu.models import DeepseekV3LM, TransformerLM
from paddle_tpu.models import paged_lm, transformer
from paddle_tpu.models.paged_lm import live_row_walk
from paddle_tpu.obs.requests import RequestLedger, format_timeline, stitch
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.paged import PagePool

VOCAB = 97


# -- a request's decode life ------------------------------------------------------

def _scripted_engine(model, params, t, **kw):
    """An engine on the hand-set clock ``t[0]`` whose admissions take 0.5 s
    and whose segments take 0.25 s (the pool's two operations advance the
    clock; everything else takes no time)."""
    eng = ServingEngine(model, params, segment=8, page_block=8,
                        cache_bucket=32, clock=lambda: t[0], **kw)
    admit, run_segment = eng.pool.admit, eng.pool.run_segment

    def slow_admit(group):
        t[0] += 0.5
        return admit(group)

    def slow_segment(live, steps=None):
        t[0] += 0.25
        return run_segment(live, steps)
    eng.pool.admit, eng.pool.run_segment = slow_admit, slow_segment
    return eng


def test_decode_life_splits_into_segments_admissions_and_host(
        paged_model_and_params):
    """A arrives alone, B while A's first segment runs. A is live through
    two segments and B's admission: its ``done`` record says 0.5 s in its
    own segments, 0.5 s behind B's admission, and the host's 0.125 s (the
    step's own time before the second round) is what is left of done -
    first token, exactly. B's own admission is TTFT's, not in B's
    ``stalled_s``."""
    model, params = paged_model_and_params
    rs = np.random.RandomState(3)
    t = [0.0]
    reg = obs.MetricsRegistry()
    with obs.ObsSession(registry=reg, clock=lambda: t[0]).installed():
        led = RequestLedger(clock=lambda: t[0], ident="eng").install()
        try:
            eng = _scripted_engine(model, params, t, slots=2)
            a = eng.submit(rs.randint(0, VOCAB, 9), 12, submit_key="a")
            t[0] += 0.125
            eng.step()              # admit A (0.5), segment (0.25)
            b = eng.submit(rs.randint(0, VOCAB, 9), 5, submit_key="b")
            t[0] += 0.125
            eng.step()              # admit B (0.5), segment (0.25): both end
            assert eng.poll(a)[1] and eng.poll(b)[1]
            times = {k: eng.timings(r) for k, r in (("a", a), ("b", b))}
            done = {k: {ev["phase"]: ev for ev in led.get(k)["events"]}["done"]
                    for k in "ab"}
            printed = format_timeline(stitch([led.get("a")]))
        finally:
            led.uninstall()
    # the record holds all six extras (a seventh would be dropped unsaid)
    assert {k: v for k, v in done["a"].items()
            if k not in ("phase", "t", "dur")} == {
        "reason": "length", "tokens": 12, "decode_s": 0.5, "stalled_s": 0.5,
        "segments": 2, "admissions_waited": 1}
    life = times["a"]["t_done"] - times["a"]["t_first"]
    assert life == 1.125
    assert life - done["a"]["decode_s"] - done["a"]["stalled_s"] == 0.125
    assert {k: done["b"][k] for k in ("tokens", "decode_s", "stalled_s",
                                      "segments", "admissions_waited")} == {
        "tokens": 5, "decode_s": 0.25, "stalled_s": 0.0, "segments": 1,
        "admissions_waited": 0}
    assert times["b"]["t_done"] - times["b"]["t_first"] == 0.25
    # beside serving.tpot_seconds, the part of it behind admissions
    hist = {s["name"]: s for s in reg.collect()
            if s["name"].startswith("serving.tpot_")}
    assert hist["serving.tpot_seconds"]["count"] == 2
    assert hist["serving.tpot_stalled_seconds"]["count"] == 2
    assert hist["serving.tpot_stalled_seconds"]["sum"] \
        == pytest.approx(0.5 / 11)
    row = next(r for r in printed.splitlines()[1:] if " done " in r)
    assert "decode=500.00ms/2seg stalled=500.00ms/1adm host=125.00ms" in row


# -- a segment's work -----------------------------------------------------------

def test_slot_steps_are_emitted_plus_overshoot_plus_idle(
        paged_model_and_params):
    """Three requests on three of four slots: one ends by EOS in its second
    segment (the first token of a run without one that differs from those
    before it: the tenth), one mid-segment on its budget, one runs two
    whole segments and a CUT one, alone. Every slot-step the programs ran
    is one of the three states, on the spans and on the counter alike, and
    ``emitted`` is the tokens requests received after their first."""
    model, params = paged_model_and_params
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, VOCAB, n) for n in (9, 13, 6)]
    solo = np.asarray(model.generate_cached(
        params, jnp.asarray(prompts[0])[None], 12))[0, 9:]
    stop = next(k for k in range(1, 12) if solo[k] not in solo[:k])
    assert stop == 9
    reg = obs.MetricsRegistry()
    with obs.ObsSession(registry=reg).installed() as s:
        eng = ServingEngine(model, params, slots=4, segment=8, page_block=8,
                            cache_bucket=32)
        rids = [eng.submit(prompts[0], 30, eos_id=int(solo[stop])),
                eng.submit(prompts[1], 5),
                eng.submit(prompts[2], 20)]
        for _ in range(20):
            if all(eng.poll(r)[1] for r in rids):
                break
            eng.step()
        got = [eng.poll(r) for r in rids]
        emits = [e["args"] for e in s.tracer.snapshot()
                 if e["name"] == "serving.emit"
                 and e["args"]["after"] == "segment"]
    assert [(len(toks), why) for toks, _, why in got] == [
        (9, "eos"), (5, "length"), (20, "length")]
    # the last segment runs the 4 steps its one request still needs
    assert [a["steps"] for a in emits] == [8, 8, 4]
    assert [a["slot_steps"] for a in emits] == [32, 32, 16]
    assert [a["live_steps"] for a in emits] == [24, 16, 4]
    # after the first tokens (the admission's): 7 + 4 + 7, then 1 + 8, then 4
    assert [a["emitted"] for a in emits] == [18, 9, 4]
    assert sum(a["emitted"] for a in emits) \
        == sum(len(toks) for toks, _, _ in got) - 3
    counted = {m["labels"]["state"]: m["value"] for m in reg.collect()
               if m["name"] == "serving.segment_slot_steps_total"}
    assert counted == {"emitted": 31, "overshoot": 44 - 31, "idle": 80 - 44}
    assert sum(counted.values()) == sum(a["slot_steps"] for a in emits)
    steps = {m["labels"]["state"]: m["value"] for m in reg.collect()
             if m["name"] == "serving.segment_steps_total"}
    assert steps == {"run": 20, "cut": 4}


# -- an admission's work --------------------------------------------------------

def _deepseek(max_len=64):
    return DeepseekV3LM(
        64, d_model=32, n_heads=4, n_layers=3, n_dense=1, dense_width=64,
        expert_width=16, n_experts=16, experts_held=[0, 1, 2, 3], top_k=4,
        n_group=4, topk_group=2, q_rank=24, kv_rank=16, d_nope=8, d_rope=8,
        d_v=16, rope_theta=1e5, max_len=max_len, rope_scaling={
            "factor": 64, "beta_fast": 32, "beta_slow": 1, "mscale": 1,
            "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
            "rope_type": "yarn"})


def _lfm2(max_len=64):
    from chipbench import weights_lfm2
    return weights_lfm2.model_and_shapes({
        "vocab_size": 96, "hidden_size": 32, "intermediate_size": 48,
        "moe_intermediate_size": 16, "num_hidden_layers": 7,
        "num_dense_layers": 1, "first_layer": 1,
        "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                        "conv", "full_attention", "conv"],
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_experts": 8, "router_width": 8,
        "experts_held": list(range(8)), "num_experts_per_tok": 2,
        "norm_topk_prob": True, "routed_scaling_factor": 1,
        "conv_L_cache": 3, "norm_eps": 1e-5, "rope_theta": 1000000,
        "n_positions": max_len}, jnp.float32)[0]


def _nemotron_h(max_len=64):
    from chipbench import weights_nemotron_h
    return weights_nemotron_h.model_and_shapes({
        "vocab_size": 96, "hidden_size": 32,
        "hybrid_override_pattern": "MEM*EME", "layer_norm_epsilon": 1e-5,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
        "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
        "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
        "moe_intermediate_size": 24,
        "moe_shared_expert_intermediate_size": 40, "router_width": 8,
        "experts_held": [0, 2, 3, 5, 7], "num_experts_per_tok": 2,
        "norm_topk_prob": True, "routed_scaling_factor": 2.5,
        "time_step_min": 0.001, "time_step_max": 0.1,
        "time_step_floor": 1e-4, "n_positions": max_len}, jnp.float32)[0]


def _count_positions_walked(model, monkeypatch):
    """Make the model's admit program say how many positions went through
    its depth: every chunk of the traced walk reports the size of the ids
    it was handed in place of its live-token count, the walk sums the
    chunks, and the host's side of the stats keeps the sum."""
    walked = []
    sequence, note = model._sequence, model.note_program_stats

    def sized(params, ids, *rest):
        h, state, stats = sequence(params, ids, *rest)
        return h, state, dict(stats, tokens=jnp.asarray(ids.size, jnp.int32))

    def keep(stats, program):
        if program == "admit":
            walked.append(int(stats["tokens"]))
        return note(stats, program)
    monkeypatch.setattr(model, "_sequence", sized)
    monkeypatch.setattr(model, "note_program_stats", keep)
    return walked


#: model -> how to build it. Chunks hold 32 tokens here: two rows of the
#: 16 bucket, one of the 32 bucket (for NemotronHLM also by
#: ``SOLO_ROW_TOKENS``, which makes a 32-wide row walk alone)
WALKERS = {"deepseek_v3": _deepseek, "lfm2": _lfm2,
           "nemotron_h": _nemotron_h}


@pytest.mark.parametrize("case", ["transformer", "transformer-prefix-hit",
                                  *WALKERS])
def test_positions_are_the_walk_the_program_runs(case, monkeypatch,
                                                 paged_model_and_params):
    """``positions`` on the admission equals rows x width x chunks as the
    PROGRAM walked them: for the models on ``prefill_live_rows`` the
    traced walk and the host's count are fed by one function
    (``live_row_walk``) — ``TransformerLM``'s miss program among them;
    its prefix-hit program (``prefill_paged``) runs every row of the pool
    at the suffix bucket's width, and an admission with a hit and a miss
    sums the two."""
    reg = obs.MetricsRegistry()
    kw = dict(slots=4, segment=4, page_block=8, cache_bucket=32,
              prompt_buckets=(16, 32))
    lens = (5, 13, 9)
    if case in WALKERS:
        monkeypatch.setattr(paged_lm, "PREFILL_TOKENS", 32)
        monkeypatch.setattr(paged_lm, "SOLO_ROW_TOKENS", 32)
        model = WALKERS[case]()
        params = model.init(jax.random.PRNGKey(0))
        walked = _count_positions_walked(model, monkeypatch)
        pool = PagePool(model, params, **kw)
        with obs.ObsSession(registry=reg).installed():
            # three rows of the 16 bucket: two chunks of two rows
            pool.admit([(s, pool.plan_admission(
                np.arange(n, dtype=np.int32), 4)) for s, n in enumerate(lens)])
            first = dict(pool.last_stats)
            # one row of the 32 bucket beside them: one chunk of one row
            pool.admit([(3, pool.plan_admission(
                np.arange(20, dtype=np.int32), 4))])
            second = dict(pool.last_stats)
        assert live_row_walk(4, 16, 32, 3) == (2, 2)
        assert live_row_walk(4, 32, 32, 1) == (1, 1)
        assert (first["rows"], first["prompt_tokens"]) == (3, sum(lens))
        assert first["positions"] == walked[0] == 2 * 2 * 16
        assert (second["rows"], second["prompt_tokens"]) == (1, 20)
        assert second["positions"] == walked[1] == 1 * 1 * 32
        want = {"prompt": sum(lens) + 20}
        want["padding"] = 64 + 32 - want["prompt"]
    else:
        model, params = paged_model_and_params
        shapes = []
        # chunks hold 32 tokens here too, and never one row alone: two
        # rows of the 16 bucket, two of the 32 bucket
        monkeypatch.setattr(transformer, "LM_PREFILL_TOKENS", 32)

        # a model of its own: the programs are traced here, through the spies
        model = TransformerLM(VOCAB, d_model=32, n_heads=4, n_layers=2,
                              max_len=128)
        chunk, prefill_paged = model._prompt_rows, model.prefill_paged

        def seen(params, ids, *a, **k):
            shapes.append(("miss", ids.shape))      # a CHUNK of the walk
            return chunk(params, ids, *a, **k)

        def seen_paged(params, pools, tokens, *a, **k):
            shapes.append(("hit", tokens.shape))
            return prefill_paged(params, pools, tokens, *a, **k)
        monkeypatch.setattr(model, "_prompt_rows", seen)
        monkeypatch.setattr(model, "prefill_paged", seen_paged)
        hit = case.endswith("prefix-hit")
        pool = PagePool(model, params, prefix_cache=hit, **kw)
        rs = np.random.RandomState(9)
        shared = rs.randint(0, VOCAB, 12)
        with obs.ObsSession(registry=reg).installed():
            pool.admit([(0, pool.plan_admission(shared, 4))])
            first = dict(pool.last_stats)
            # with the index on, the second wave holds a hit on the first
            # prompt's whole page (8 of its 12 tokens are not run again)
            # AND a miss of the wide bucket: two programs, one account
            again = np.concatenate([shared[:8], rs.randint(0, VOCAB, 3)])
            wide = rs.randint(0, VOCAB, 20)
            pool.admit([(1, pool.plan_admission(again, 4)),
                        (2, pool.plan_admission(wide, 4))])
            second = dict(pool.last_stats)
        # one live row of the 16 bucket: one chunk of two rows
        assert first == {"rows": 1, "prompt_tokens": 12, "positions": 2 * 16}
        assert shapes[0] == ("miss", (2, 16))
        if hit:
            # the hit program at slots x width, the miss's one chunk
            assert sorted(shapes[1:]) == [("hit", (4, 16)),
                                          ("miss", (2, 32))]
            assert second == {"rows": 2, "prompt_tokens": 3 + 20,
                              "positions": 4 * 16 + 2 * 32}
            want = {"prompt": 12 + 3 + 20}
            want["padding"] = 32 + 64 + 64 - want["prompt"]
        else:
            # two live rows of the 32 bucket: one chunk of two rows
            assert shapes[1:] == [("miss", (2, 32))]
            assert second == {"rows": 2, "prompt_tokens": 11 + 20,
                              "positions": 2 * 32}
            want = {"prompt": 12 + 11 + 20}
            want["padding"] = 32 + 64 - want["prompt"]
    counted = {m["labels"]["state"]: m["value"] for m in reg.collect()
               if m["name"] == "serving.admit_positions_total"}
    assert counted == want
    # an admission with nothing to run (adopted pages only) says so
    pool.admit([])
    assert pool.last_stats == {"rows": 0, "prompt_tokens": 0, "positions": 0}
