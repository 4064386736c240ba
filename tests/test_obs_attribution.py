"""What the host was doing, and why a request waited (ISSUE 24): the leaf
spans inside ``Trainer.train``, ``PagePool`` and the engine's scheduler nest
under their envelopes; an admission round stamps what it leaves queued and
the ``queued`` record carries ``blocked_s`` / ``blocked_by``; every span is
also a ``jax.profiler.TraceAnnotation`` (and none is made with no session
installed); ``jax.trace`` / ``jax.lower`` instants name the function.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import Trainer, obs
from paddle_tpu.obs import trace as obs_trace
from paddle_tpu.obs.requests import RequestLedger
from paddle_tpu.optimizer import SGD
from paddle_tpu.serving import ServingEngine

VOCAB = 97


def _ticking(step=0.001):
    """A clock that advances ``step`` every time it is read, so every span
    has a length and every start is distinct; ``t[0]`` is the time."""
    t = [0.0]

    def clock():
        t[0] += step
        return t[0]
    return clock, t


def _by_name(events):
    out = {}
    for e in events:
        out.setdefault(e["name"], []).append(e)
    return out


def _inside(child, parent):
    return (parent["ts"] <= child["ts"]
            and child["ts"] + child.get("dur", 0.0)
            <= parent["ts"] + parent["dur"])


# -- Trainer.train --------------------------------------------------------------

def _train_two_steps(clock):
    rs = np.random.RandomState(0)
    batches = [(rs.randn(8, 4).astype(np.float32),
                rs.randn(8, 1).astype(np.float32)) for _ in range(2)]
    seen = []
    s = obs.ObsSession(registry=obs.MetricsRegistry(), clock=clock)
    with s.installed():
        Trainer(lambda p, x, y: jnp.mean((x @ p["w"] - y) ** 2),
                SGD(0.1)).train(lambda: iter(batches),
                                {"w": jnp.zeros((4, 1))},
                                event_handler=seen.append,
                                handle_signals=False)
    return s.tracer.snapshot(), seen


LEAVES_OF = {"trainer.input": "trainer.pass",
             "trainer.handler": "trainer.pass",
             "trainer.dispatch": "trainer.device_step",
             "trainer.device_wait": "trainer.device_step",
             "trainer.release": "trainer.step"}


@pytest.fixture(scope="module")
def train_events():
    return _train_two_steps(_ticking()[0])


@pytest.mark.parametrize("leaf", sorted(LEAVES_OF))
def test_trainer_leaf_span_nests_under_its_envelope(train_events, leaf):
    spans = _by_name(e for e in train_events[0] if e["kind"] == "span")
    ids = {e["id"]: e for e in train_events[0] if e["kind"] == "span"}
    assert leaf in obs.SPANS
    got = spans[leaf]
    # two steps: one dispatch and one wait each; three pulls (the last
    # finds the reader empty); a handler call for every event
    want = {"trainer.input": 3, "trainer.dispatch": 2,
            "trainer.device_wait": 2, "trainer.release": 2,
            "trainer.handler": len(train_events[1])}[leaf]
    assert len(got) == want
    for e in got:
        parent = ids[e["parent"]]
        assert parent["name"] == LEAVES_OF[leaf]
        assert _inside(e, parent)


def test_trainer_handler_spans_name_their_event_and_old_spans_stay(
        train_events):
    events, seen = train_events
    spans = _by_name(e for e in events if e["kind"] == "span")
    assert ([e["args"]["event"] for e in spans["trainer.handler"]]
            == [type(ev).__name__ for ev in seen])
    for name, n in (("trainer.pass", 1), ("trainer.step", 2),
                    ("trainer.device_step", 2), ("trainer.host_sync", 2)):
        assert len(spans[name]) == n
    # dispatch ends before the wait for the same step begins
    for d, w in zip(spans["trainer.dispatch"], spans["trainer.device_wait"]):
        assert d["parent"] == w["parent"]
        assert d["ts"] + d["dur"] <= w["ts"]


# -- PagePool and the engine's scheduler ------------------------------------------

def _serve(model, params, clock, t, *, slots, pages=None, requests=()):
    """Drive an engine by hand under ONE clock (tracer, ledger, engine);
    returns (events, {key: {phase: record}}, registry samples)."""
    reg = obs.MetricsRegistry()
    s = obs.ObsSession(registry=reg, clock=clock)
    with s.installed():
        led = RequestLedger(clock=clock, ident="eng").install()
        try:
            eng = ServingEngine(model, params, slots=slots, segment=8,
                                page_block=8, cache_bucket=32, pages=pages,
                                prefix_cache=True, clock=clock)
            rids = [eng.submit(p, n, submit_key=k) for k, p, n in requests]
            for _ in range(200):
                if all(eng.poll(r)[1] for r in rids):
                    break
                eng.step()
            assert all(eng.poll(r)[1] for r in rids)
            tls = {k: {ev["phase"]: ev for ev in led.get(k)["events"]}
                   for k, _, _ in requests}
        finally:
            led.uninstall()
    return s.tracer.snapshot(), tls, reg.collect()


@pytest.fixture(scope="module")
def serve_run(paged_model_and_params):
    model, params = paged_model_and_params
    rs = np.random.RandomState(7)
    clock, t = _ticking()
    return _serve(model, params, clock, t, slots=2, requests=[
        ("a", rs.randint(0, VOCAB, 9), 12),
        ("b", rs.randint(0, VOCAB, 17), 5)])


SERVE_LEAVES = [("serving.stage", "serving.prefill"),
                ("serving.dispatch", "serving.prefill"),
                ("serving.fetch", "serving.prefill"),
                ("serving.index", "serving.prefill"),
                ("serving.stage", "serving.segment"),
                ("serving.dispatch", "serving.segment"),
                ("serving.fetch", "serving.segment")]


@pytest.mark.parametrize("leaf,envelope", SERVE_LEAVES,
                         ids=[f"{a.split('.')[1]}-in-{b.split('.')[1]}"
                              for a, b in SERVE_LEAVES])
def test_pool_leaf_span_nests_under_the_engines_envelope(serve_run, leaf,
                                                         envelope):
    events = [e for e in serve_run[0] if e["kind"] == "span"]
    ids = {e["id"]: e for e in events}
    assert leaf in obs.SPANS
    kids = [e for e in events if e["name"] == leaf
            and ids.get(e["parent"], {}).get("name") == envelope]
    envelopes = [e for e in events if e["name"] == envelope]
    assert envelopes and kids
    for e in kids:
        assert _inside(e, ids[e["parent"]])
    # every envelope holds the leaf, and leaves of one envelope do not
    # overlap (they are stretches of one thread's time)
    for env in envelopes:
        mine = sorted((e for e in kids if e["parent"] == env["id"]),
                      key=lambda e: e["ts"])
        assert mine, (leaf, envelope)
    program = {"serving.prefill": {"admit", "admit_prefix"},
               "serving.segment": {"segment"}}[envelope]
    if leaf in ("serving.dispatch", "serving.fetch"):
        assert {e["args"]["program"] for e in kids} <= program


def test_scheduler_iteration_runs_inside_leaf_spans(serve_run):
    """schedule and emit stand beside the envelopes (no parent), and between
    the first admission and the last hand-out the leaves leave no stretch
    of the scheduler's time uncovered longer than a clock tick or two."""
    events = sorted((e for e in serve_run[0] if e["kind"] == "span"
                     and e["name"].startswith("serving.")),
                    key=lambda e: e["ts"])
    top = [e for e in events if e["parent"] is None]
    assert {e["name"] for e in top} == {"serving.schedule", "serving.prefill",
                                        "serving.emit", "serving.segment"}
    assert {e["args"]["phase"] for e in top
            if e["name"] == "serving.schedule"} == {"reap", "admit"}
    assert {e["args"]["after"] for e in top
            if e["name"] == "serving.emit"} == {"prefill", "segment"}
    envelopes = ("serving.prefill", "serving.segment")
    leaves = [e for e in events if e["name"] not in envelopes]
    for env in (e for e in events if e["name"] in envelopes):
        inside = sorted((e for e in leaves if e["parent"] == env["id"]),
                        key=lambda e: e["ts"])
        covered = sum(e["dur"] for e in inside)
        # what is outside a leaf is a handful of reads of the ticking
        # clock (span bookkeeping), never a stretch of work
        assert env["dur"] - covered <= 0.002 * (2 * len(inside) + 4)


def test_program_build_instant_names_the_shape_bucket():
    from paddle_tpu.models import TransformerLM
    model = TransformerLM(VOCAB, d_model=32, n_heads=4, n_layers=1,
                          max_len=64)
    params = model.init(jax.random.PRNGKey(1))
    clock, t = _ticking()
    events, _, _ = _serve(model, params, clock, t, slots=2, requests=[
        ("p", np.arange(5) % VOCAB, 3)])
    builds = [e["args"] for e in events if e["kind"] == "instant"
              and e["name"] == "serving.program_build"]
    assert {b["kind"] for b in builds} == {"admit", "segment"}
    assert all(("tpad" in b) != ("nb" in b) for b in builds)
    # a second engine over the same model builds nothing again
    events, _, _ = _serve(model, params, clock, t, slots=2, requests=[
        ("q", np.arange(5) % VOCAB, 3)])
    assert not [e for e in events if e["name"] == "serving.program_build"]


@pytest.mark.parametrize("state", ["logical", "device"])
def test_pool_bytes_held_gauge_and_admit_page_counters(serve_run, state):
    """The pool says what its page arrays hold as stated and as they lie
    on the device (equal on the CPU, where nothing pads), and an
    admission counts the pages it wrote beside its bucket's."""
    got = {(smp["name"], smp["labels"].get("state")): smp.get("value")
           for smp in serve_run[2]}
    # 2 slots x 16 pages + null, pages of 8 rows x 4 heads x 8, k and v
    # of 2 layers, f32
    assert got[("serving.pool_bytes_held", state)] == 4 * 33 * 8 * 4 * 8 * 4
    # prompts of 9 and 17 tokens: 2 + 3 pages written of the admissions'
    # buckets
    assert got[("serving.admit_pages_written_total", None)] == 5
    assert got[("serving.admit_pages_bucket_total", None)] >= 2 * 3


# -- why a request waited -------------------------------------------------------------

def _blocked_run(model, params, *, slots, pages, sizes):
    rs = np.random.RandomState(11)
    t = [0.0]

    def clock():
        return t[0]
    reg = obs.MetricsRegistry()
    with obs.ObsSession(registry=reg, clock=clock).installed():
        led = RequestLedger(clock=clock, ident="eng").install()
        try:
            eng = ServingEngine(model, params, slots=slots, segment=8,
                                page_block=8, cache_bucket=32, pages=pages,
                                clock=clock)
            rids = [eng.submit(rs.randint(0, VOCAB, p), n, submit_key=k)
                    for k, p, n in sizes]
            while not all(eng.poll(r)[1] for r in rids):
                t[0] += 0.25                  # one round every 250 ms
                eng.step()
            tls = {k: {ev["phase"]: ev for ev in led.get(k)["events"]}
                   for k, _, _ in sizes}
        finally:
            led.uninstall()
    blocked = {s["labels"]["reason"]: s["value"] for s in reg.collect()
               if s["name"] == "serving.admit_blocked_total"}
    return tls, blocked


@pytest.mark.parametrize("reason,slots,pages", [("slots", 1, None),
                                                ("pages", 2, 5)])
def test_blocked_s_and_blocked_by(paged_model_and_params, reason, slots,
                                  pages):
    """Two requests arrive together. The first is taken at its first
    boundary (blocked_s 0, no blocked_by); the second is passed over — for
    want of a slot with one slot, for want of pages with two slots and a
    pool that holds one request's worst case — and its queued record says
    for how long and by what."""
    model, params = paged_model_and_params
    tls, blocked = _blocked_run(model, params, slots=slots, pages=pages,
                                sizes=[("first", 9, 12), ("second", 9, 12)])
    q1, q2 = tls["first"]["queued"], tls["second"]["queued"]
    assert q1["blocked_s"] == 0.0 and "blocked_by" not in q1
    assert q2["blocked_by"] == reason
    assert q2["blocked_s"] > 0
    # boundary wait + capacity wait IS the queue wait (one clock)
    assert q2["dur"] == pytest.approx(0.25 + q2["blocked_s"], abs=1e-9)
    assert q1["dur"] == pytest.approx(0.25, abs=1e-9)
    # one count per request and round it was left in the queue
    assert blocked == {reason: pytest.approx(q2["blocked_s"] / 0.25)}


# -- the profiler's timeline ------------------------------------------------------------

class _CountingAnnotation:
    made = []

    def __init__(self, name):
        self.made.append(name)
        self.entered = self.exited = False

    def __enter__(self):
        self.entered = True
        return self

    def __exit__(self, *exc):
        self.exited = True
        return False


def test_no_session_no_annotation_and_installed_span_enters_one(monkeypatch):
    monkeypatch.setattr(obs_trace, "_ANNOTATION", _CountingAnnotation)
    _CountingAnnotation.made.clear()
    assert not obs.is_active()
    sp = obs.span("trainer.step")
    assert sp is obs.NULL_SPAN
    with sp:
        pass
    assert _CountingAnnotation.made == []
    with obs.ObsSession(registry=obs.MetricsRegistry()).installed():
        with obs.span("serving.stage", what="tables") as sp:
            assert sp._note.entered and not sp._note.exited
        assert sp._note.exited
    assert _CountingAnnotation.made == ["serving.stage"]


def test_span_annotation_is_jaxs_and_lands_in_a_profiler_trace(tmp_path):
    """With a profiler session running, the program's spans are on the
    trace's host plane under their own names (here on the CPU backend; on
    the chip the same plane shares the device planes' clock)."""
    from jax.profiler import ProfileData, TraceAnnotation
    assert obs_trace._annotation_class() is TraceAnnotation
    from chipbench import trace_reduce
    with obs.ObsSession(registry=obs.MetricsRegistry()).installed():
        jax.profiler.start_trace(str(tmp_path))
        try:
            with obs.span("serving.emit", after="segment"):
                jnp.ones(8).block_until_ready()
        finally:
            jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(str(tmp_path))
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert "serving.emit" in names


def test_installed_span_with_annotation_stays_in_budget():
    """An installed span (record + annotation) measured ~7 us here, the
    annotation ~0.6 us of it; the bound is 10x slack for noisy neighbours
    and still catches an annotation that starts doing work."""
    s = obs.ObsSession(registry=obs.MetricsRegistry())

    def per_span(n=2000):
        t0 = time.perf_counter()
        for _ in range(n):
            with obs.span("serving.stage"):
                pass
        return (time.perf_counter() - t0) / n

    with s.installed():
        assert obs_trace._annotation_class()
        cost = min(per_span() for _ in range(3))
    assert cost < 70e-6, cost
    assert min(per_span() for _ in range(3)) < 5e-6      # uninstalled


# -- set-up from inside -------------------------------------------------------------------

@pytest.mark.parametrize("instant,hist", [("jax.trace", "jax.trace_seconds"),
                                          ("jax.lower", "jax.lower_seconds")])
def test_first_jit_call_leaves_trace_and_lower_instants(instant, hist):
    reg = obs.MetricsRegistry()
    s = obs.ObsSession(registry=reg)

    def attribution_probe(x):
        return x * 3 + 1
    with s.installed():
        jax.jit(attribution_probe)(jnp.ones(3)).block_until_ready()
    mine = [e["args"] for e in s.tracer.snapshot()
            if e["kind"] == "instant" and e["name"] == instant
            and "attribution_probe" in e["args"].get("fun_name", "")]
    assert len(mine) == 1 and mine[0]["duration_secs"] > 0
    assert hist in obs.CATALOGUE
    assert sum(m["count"] for m in reg.collect() if m["name"] == hist) >= 1


def test_xplane_dump_puts_the_programs_spans_beside_the_device_lanes():
    """``obs export --format=chrome --xplane``: a trace's host plane holds
    the program's spans (as annotations) on the device planes' clock; the
    dump made from the trace alone shows both, and nothing else of the
    host plane's."""
    from paddle_tpu.obs import xplane as xp
    ns = 1_000_000
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "timestamp_ns": 5 * ns, "events": [
                {"name": "fusion.1", "offset_ps": 0,
                 "duration_ps": 2 * ns * 1000}]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "timestamp_ns": 4 * ns, "events": [
                {"name": "serving.fetch", "offset_ps": 0,
                 "duration_ps": 4 * ns * 1000},
                {"name": "PjitFunction(jit(seg))", "offset_ps": 0,
                 "duration_ps": ns * 1000}]}]}]
    dump = xp.xplane_dump(xp.read_xspace(xp.encode_xspace(planes)))
    assert sorted(dump["meta"]["processes"].values()) == [
        "/device:TPU:0", "/host:CPU (program spans)"]
    by = {e["name"]: e for e in dump["events"]}
    assert set(by) == {"fusion.1", "serving.fetch"}
    # the fetch began 1 ms before the device op and outlasts it: one axis
    assert by["fusion.1"]["ts"] - by["serving.fetch"]["ts"] == \
        pytest.approx(1e-3)
    assert by["serving.fetch"]["pid"] != by["fusion.1"]["pid"]
