"""The seven readers of the program's leaf spans, admission stamps and
trace/lower instants, each over a synthetic ``ctx``: the value from a dump
that has what it reads, and nothing (None, so the metric is left out of the
line) from a dump of a program that lacks it — as the parent of the PR that
added them does."""

import os

import pytest

from chipbench import harness

ORIGIN = 1_000_000.0
WINDOW = (ORIGIN + 10.0, ORIGIN + 60.0)
NEW = ("step_host_ms", "input_wait_ms", "prefill_host_ms", "segment_host_ms",
       "boundary_wait_p50_ms", "capacity_wait_p95_ms", "setup_trace_lower_s")


class _Dump:
    """Builds an obs dump the way a serve cell reads it back (events
    without ``kind``: a span has ``dur``, an instant has not)."""

    def __init__(self):
        self.events, self.requests, self._id = [], [], 0

    def span(self, name, ts, dur, parent=None, tid=1, **args):
        self._id += 1
        self.events.append({"name": name, "ts": ts, "dur": dur, "tid": tid,
                            "id": self._id, "parent": parent, "args": args})
        return self._id

    def instant(self, name, ts, tid=1, **args):
        self.events.append({"name": name, "ts": ts, "tid": tid,
                            "parent": None, "args": args})

    def request(self, key, queued_dur, **extra):
        self.requests.append({"key": key, "events": [
            {"phase": "admitted", "t": 0.0, "dur": 0.0},
            dict({"phase": "queued", "t": queued_dur, "dur": queued_dur},
                 **extra)]})

    def ctx(self, records=()):
        return {"obs": {"meta": {"clock_origin_unix": ORIGIN},
                        "metrics": [], "events": self.events,
                        "requests": self.requests},
                "window": WINDOW, "records": [{"key": k} for k in records]}


def _train(leaves=True):
    """Three window steps of 200 ms: device_wait ends, then 2 ms handler,
    `pull` ms input, 1 ms dispatch; one more step before the window."""
    d = _Dump()
    for i, (t, pull) in enumerate([(5.0, 0.050), (20.0, 0.004),
                                   (20.2, 0.006), (20.4, 0.010)]):
        step = d.span("trainer.step", t, 0.19)
        dev = d.span("trainer.device_step", t, 0.18, step)
        if leaves:
            d.span("trainer.input", t - 0.001 - pull, pull)
            d.span("trainer.dispatch", t, 0.001, dev)
            d.span("trainer.device_wait", t + 0.001, 0.179, dev)
    return d.ctx()


def _serve(leaves=True, stamps=None):
    d, stamps = _Dump(), leaves if stamps is None else stamps
    for t, fetch, emit in [(2.0, 0.5, 0.5), (12.0, 0.100, 0.002),
                           (13.0, 0.110, 0.004), (14.0, 0.120, 0.003)]:
        p = d.span("serving.prefill", t, fetch + 0.010)
        s = d.span("serving.segment", t + 0.3, fetch + 0.003)
        if leaves:
            d.span("serving.stage", t, 0.004, p, what="prompts")
            d.span("serving.dispatch", t + 0.004, 0.005, p, program="admit")
            d.span("serving.fetch", t + 0.009, fetch, p, program="admit")
            d.span("serving.index", t + 0.009 + fetch, 0.001, p)
            d.span("serving.emit", t + 0.011 + fetch, 0.001,
                   after="prefill")
            d.span("serving.stage", t + 0.3, 0.001, s, what="tables")
            d.span("serving.dispatch", t + 0.301, 0.002, s,
                   program="segment")
            d.span("serving.fetch", t + 0.303, fetch, s, program="segment")
            d.span("serving.emit", t + 0.304 + fetch, emit, after="segment")
    extra = [{"blocked_s": 0.0}, {"blocked_s": 0.0},
             {"blocked_s": 0.3, "blocked_by": "slots"},
             {"blocked_s": 0.9, "blocked_by": "pages"}]
    for i, (dur, x) in enumerate(zip((0.10, 0.12, 0.44, 1.0), extra)):
        d.request(f"w-{i}", dur, **(x if stamps else {}))
    d.request("warmup", 5.0, blocked_s=4.0, blocked_by="slots")
    return d.ctx(records=[f"w-{i}" for i in range(4)])


def _setup(instants=True):
    d = _Dump()
    if instants:
        # an outer trace of 4 s that holds a nested one of 1 s, a lowering
        # of 2 s after it, another thread's 0.5 s, and one in the window
        d.instant("jax.trace", 3.0, duration_secs=1.0, fun_name="inner")
        d.instant("jax.trace", 5.0, duration_secs=4.0, fun_name="_step")
        d.instant("jax.lower", 7.0, duration_secs=2.0, fun_name="jit(_step)")
        d.instant("jax.trace", 4.0, tid=2, duration_secs=0.5, fun_name="f")
        d.instant("jax.trace", 30.0, duration_secs=9.0, fun_name="late")
    d.instant("jax.compile", 8.0, duration_secs=1.0)
    return d.ctx()


CASES = {
    # wait ends at t+0.18 of the step before; dispatch ends at t+0.001
    "step_host_ms": (_train, (0.0 + 20.2 + 0.001 - 20.18) * 1e3),
    "input_wait_ms": (_train, 6.0),
    # span less its fetch: 10 ms whatever the fetch took
    "prefill_host_ms": (_serve, 10.0),
    # 3 ms of the span outside its fetch + the emit that follows: 5, 7, 6
    "segment_host_ms": (_serve, 6.0),
    # queued.dur - blocked_s of w-0..w-3: 100, 120, 140, 100
    "boundary_wait_p50_ms": (_serve, 110.0),
    "capacity_wait_p95_ms": (_serve, harness.percentile(
        [0.0, 0.0, 300.0, 900.0], 95)),
    # union on thread 1: [1, 5] + [5, 7] = 6 s; thread 2: 0.5 s
    "setup_trace_lower_s": (_setup, 6.5),
}


def _reader(name):
    return harness.load_module("metrics", name)


@pytest.mark.parametrize("name", NEW)
def test_reader_value_over_a_synthetic_dump(name):
    build, want = CASES[name]
    ctx = build()
    assert _reader(name).read(ctx) == pytest.approx(want, rel=1e-6)


@pytest.mark.parametrize("name", NEW)
def test_reader_reports_nothing_when_the_program_lacks_what_it_reads(name):
    build, _ = CASES[name]
    assert _reader(name).read(build(False)) is None
    empty = _Dump().ctx()
    assert _reader(name).read(empty) is None


def test_capacity_wait_notes_the_share_blocked_and_by_what():
    ctx = _serve()
    _reader("capacity_wait_p95_ms").read(ctx)
    assert any("2 of 4 requests" in n and "slots" in n and "pages" in n
               for n in ctx["notes"])
    # stamps absent on the requests only: nothing, though spans are there
    assert _reader("boundary_wait_p50_ms").read(
        _serve(leaves=True, stamps=False)) is None


def test_queue_split_adds_up_to_the_queued_record():
    from chipbench.metrics._span_tree import queue_split
    ctx = _serve()
    dur = {tl["key"]: tl["events"][1]["dur"] for tl in ctx["obs"]["requests"]}
    rows = queue_split(ctx)
    assert len(rows) == 4                      # the warm-up's is not ours
    assert sorted(b + c for b, c, _ in rows) == pytest.approx(
        sorted(dur[f"w-{i}"] for i in range(4)))


def test_every_new_per_layer_entry_has_a_reader_and_lists_its_cells():
    bench = harness.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"] for w in bench["workloads"]}
    for name in NEW:
        m = entries[name]
        assert os.path.exists(os.path.join(harness.HERE, "metrics",
                                           name + ".py"))
        assert callable(_reader(name).read)
        assert m["workloads"] and set(m["workloads"]) <= cells
        assert m["better"] == "lower" and m["source"] == "program_span"
        for cell in m["workloads"]:
            assert name in {x["name"] for x in
                            harness.load_cell(cell)["per_layer"]}
