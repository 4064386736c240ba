"""A decode segment runs the steps its live requests can still use (ISSUE
43): ``ServingEngine.decode_segment`` hands ``PagePool.run_segment`` the
longest remaining budget (with the first token a request's first segment
re-emits), at most ``segment``; the count is a traced ARGUMENT of the table
width's one program. For each of the six served model classes, one engine
run under an obs session — a request alone that needs 2 steps, one alone
that needs a whole segment and then 1 step, then a crowd with a queue —
and three things read off it: the tokens are the solo decode's, the
accounts follow the steps that ran, and every program was built once.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import obs
from paddle_tpu.models import TransformerLM
from paddle_tpu.serving import ServingEngine

import test_afmoe
import test_keye_vl2
import test_mimo_v2
from test_serving_account import _deepseek, _lfm2, _nemotron_h

SEGMENT, MAX_LEN, PROMPT = 32, 128, 11
#: budgets in the order they are offered: alone, alone, then five at once
#: on four slots
WAVES = ((2,), (33,), (1, 7, 31, 32, 70))


def _initialised(model):
    return model, model.init(jax.random.PRNGKey(0))


#: a FRESH model instance a case (the pool shares programs per instance:
#: one another suite has used would hide the builds this file counts)
MODELS = {
    "transformer": lambda: _initialised(TransformerLM(
        97, d_model=32, n_heads=4, n_layers=2, max_len=MAX_LEN)),
    "deepseek_v3": lambda: _initialised(_deepseek(MAX_LEN)),
    "lfm2": lambda: _initialised(_lfm2(MAX_LEN)),
    "nemotron_h": lambda: _initialised(_nemotron_h(MAX_LEN)),
    "afmoe": lambda: test_afmoe.build(),
    "keye_vl2": lambda: test_keye_vl2.build()[:2],
    "mimo_v2": lambda: test_mimo_v2.build(),
}


@pytest.fixture(scope="module", params=list(MODELS))
def run(request):
    model, params = MODELS[request.param]()
    vocab = 90
    rs = np.random.RandomState(3)
    out = types.SimpleNamespace(segments=[], got=[], want=[])
    reg = obs.MetricsRegistry()
    with obs.ObsSession(registry=reg).installed() as s:
        eng = ServingEngine(model, params, slots=4, segment=SEGMENT,
                            page_block=8, cache_bucket=MAX_LEN,
                            prompt_buckets=(16, 32))
        pool = eng.pool
        run_segment = pool.run_segment

        def watched(live, steps=None):
            owed = max(r.left + r.skip for r in eng._live.values())
            before = pool.pos[list(live)].copy()
            block = run_segment(live, steps)
            out.segments.append(dict(
                asked=steps, owed=owed, block=block.shape,
                moved=(pool.pos[list(live)] - before).tolist()))
            return block
        pool.run_segment = watched
        for wave in WAVES:
            prompts = [rs.randint(0, vocab, PROMPT) for _ in wave]
            rids = [eng.submit(p, n) for p, n in zip(prompts, wave)]
            for _ in range(40):
                if all(eng.poll(r)[1] for r in rids):
                    break
                eng.step()
            out.got += [eng.poll(r) for r in rids]
            out.want += [(p, n) for p, n in zip(prompts, wave)]
        out.events = s.tracer.snapshot()
        out.programs = dict(pool._fns)
    out.metrics = reg.collect()
    out.model, out.params, out.slots = model, params, pool.n_slots
    return out


def _counter(run, name):
    return {m["labels"].get("state"): m["value"] for m in run.metrics
            if m["name"] == name}


def test_served_tokens_are_the_solo_decode(run):
    """Budgets 1, 2, 7, 31, 32, 33 and 70 round a segment of 32: request by
    request the engine's tokens are ``generate_cached``'s, whatever the
    segments were cut to."""
    kw = {} if isinstance(run.model, TransformerLM) else dict(page_block=8)
    solo = np.asarray(run.model.generate_cached(
        run.params, jnp.asarray([p for p, _ in run.want]), 70, **kw))[
            :, PROMPT:]
    for (toks, done, why), (_, budget), alone in zip(run.got, run.want,
                                                     solo):
        assert done and why == "length"
        np.testing.assert_array_equal(np.asarray(toks), alone[:budget])


def test_a_segment_runs_the_steps_owed_and_the_accounts_follow(run):
    """Every dispatch ran ``min(segment, max(left + skip))`` steps: the
    pool was asked for that, returned a block that wide and moved every
    live slot's ``pos`` by it; the ``serving.segment`` span and the
    ``serving.emit`` behind it say the same, slot-steps split into
    emitted + overshoot + idle with nothing negative, and the counter
    splits whole segments into steps run and steps cut."""
    ran = [min(SEGMENT, s["owed"]) for s in run.segments]
    assert [s["asked"] for s in run.segments] == ran
    assert ran == [2, SEGMENT, 1, SEGMENT, SEGMENT, SEGMENT, 6]
    for s, n in zip(run.segments, ran):
        assert s["block"] == (run.slots, n)
        assert set(s["moved"]) == {n}
    spans = [e["args"] for e in run.events
             if e["name"] == "serving.segment" and e.get("kind") == "span"]
    emits = [e["args"] for e in run.events if e["name"] == "serving.emit"
             and e["args"]["after"] == "segment"]
    assert [a["steps"] for a in spans] == ran
    assert [a["steps"] for a in emits] == ran
    for span, emit, n in zip(spans, emits, ran):
        assert emit["slot_steps"] == run.slots * n
        assert emit["live_steps"] == span["live"] * n
        assert 0 <= emit["emitted"] <= emit["live_steps"]
    # every token but a request's first came out of a segment
    assert sum(a["emitted"] for a in emits) \
        == sum(n - 1 for _, n in run.want)
    states = _counter(run, "serving.segment_slot_steps_total")
    assert states["emitted"] == sum(a["emitted"] for a in emits)
    assert min(states.values()) >= 0
    assert sum(states.values()) == run.slots * sum(ran)
    assert _counter(run, "serving.segment_steps_total") == {
        "run": sum(ran), "cut": SEGMENT * len(ran) - sum(ran)}


def test_every_program_is_built_once_whatever_the_steps(run):
    """The step count is an argument, not a program: the run's seven
    dispatches with their four different counts traced and lowered ONE
    segment program (one table width here), the cost ledger holds one AOT
    executable for it under one signature — no fall-back to the plain jit,
    no failed cost analysis — and the admit programs likewise."""
    def instants(name, fun):
        return [e for e in run.events if e["name"] == name
                and e.get("args", {}).get("fun_name") == fun]
    builds = [e["args"] for e in run.events
              if e["name"] == "serving.program_build"]
    segs = [k for k in run.programs if k[0] == "seg"]
    assert len(segs) == 1
    assert [b["kind"] for b in builds].count("segment") == 1
    assert len(builds) == len(run.programs)
    assert len(instants("jax.trace", "seg")) == 1
    assert len(instants("jax.lower", "jit(seg)")) == 1
    admits = len(run.programs) - 1
    assert len(instants("jax.trace", "admit")) == admits
    assert len(instants("jax.lower", "jit(admit)")) == admits
    for fn in run.programs.values():
        (call, cost), = fn.ledger.values()
        assert call is not fn._jitted and cost is not None
    assert not _counter(run, "roofline.cost_analysis_failures_total")
