"""Observability-plane tests (ISSUE 3): registry/label semantics, histogram
bucketing, span nesting + Chrome-export schema, zero-cost-when-uninstalled,
retry/StatSet/train_stats satellites, and an end-to-end train-2-passes run
asserting step/RPC/checkpoint metrics — fake clocks, no real sleeps.
"""

import json
import os
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import analysis, cli, faults, obs
from paddle_tpu.optimizer import SGD
from paddle_tpu.trainer import Trainer
from paddle_tpu.utils.retry import RetryBudgetExceeded, RetryPolicy
from paddle_tpu.utils.stats import StatSet, StatSnapshot

pytestmark = pytest.mark.obs


def _fake_clock(step=1.0):
    t = [0.0]

    def clock():
        t[0] += step
        return t[0]

    return clock, t


# -- registry / metric semantics ------------------------------------------------

def test_registry_get_or_create_and_kind_conflict():
    r = obs.MetricsRegistry()
    c1 = r.counter("trainer.steps_total")
    assert r.counter("trainer.steps_total") is c1
    with pytest.raises(ValueError, match="already registered as counter"):
        r.gauge("trainer.steps_total")
    with pytest.raises(ValueError, match="subsystem.noun_qualifier"):
        r.counter("NotSnake.Case")
    with pytest.raises(ValueError, match="subsystem.noun_qualifier"):
        r.counter("nodots")


def test_counter_labels_are_independent_series():
    r = obs.MetricsRegistry()
    c = r.counter("rpc.calls_total")
    c.inc(rpc="master")
    c.inc(2, rpc="coord")
    c.inc()                                     # unlabeled series
    assert c.get(rpc="master") == 1
    assert c.get(rpc="coord") == 2
    assert c.get() == 1
    bound = c.labels(rpc="master")
    bound.inc(3)
    assert bound.get() == 4
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)
    # collect() emits one sample per (metric, label-set)
    samples = [s for s in r.collect() if s["name"] == "rpc.calls_total"]
    assert {frozenset(s["labels"].items()) for s in samples} == {
        frozenset(), frozenset({("rpc", "master")}),
        frozenset({("rpc", "coord")})}


def test_gauge_set_and_high_water():
    r = obs.MetricsRegistry()
    g = r.gauge("data.queue_depth")
    g.set(3)
    g.set(7)
    g.set(2)
    assert g.get() == 2
    assert g.high_water() == 7
    g.inc()
    g.dec(2)
    assert g.get() == 1


def test_histogram_fixed_bucket_boundaries():
    r = obs.MetricsRegistry()
    h = r.histogram("rpc.call_seconds", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 99.0):
        h.observe(v)
    snap = h.snapshot()
    # cumulative le-style counts, overflow in +Inf
    assert snap["buckets"] == [[0.01, 1], [0.1, 3], [1.0, 4], ["+Inf", 5]]
    assert snap["count"] == 5
    assert snap["max"] == 99.0
    assert snap["sum"] == pytest.approx(99.605)
    # boundary value lands in its bucket (le semantics)
    h2 = r.histogram("fluid.run_seconds", buckets=(1.0, 2.0))
    h2.observe(1.0)
    assert h2.snapshot()["buckets"][0] == [1.0, 1]
    # same name + different boundaries is a contract violation
    with pytest.raises(ValueError, match="different bucket"):
        r.histogram("rpc.call_seconds", buckets=(0.5,))
    with pytest.raises(ValueError, match="strictly increasing"):
        obs.Histogram("a.b_seconds", buckets=(1.0, 1.0))


def test_histogram_labelled_series():
    r = obs.MetricsRegistry()
    h = r.histogram("rpc.call_seconds", buckets=(0.1, 1.0))
    h.observe(0.05, rpc="master")
    h.observe(0.5, rpc="coord")
    assert h.snapshot(rpc="master")["count"] == 1
    assert h.snapshot(rpc="coord")["buckets"] == [[0.1, 0], [1.0, 1],
                                                  ["+Inf", 1]]


# -- tracer / spans -------------------------------------------------------------

def test_span_nesting_parent_ids_and_fake_clock():
    clock, _ = _fake_clock()
    tr = obs.Tracer(clock=clock)
    with tr.span("trainer.pass", pass_id=0):
        with tr.span("trainer.step"):
            pass
        with tr.span("trainer.step"):
            pass
    spans = tr.spans()                   # recorded in exit order
    assert [s["name"] for s in spans] == ["trainer.step", "trainer.step",
                                          "trainer.pass"]
    outer = spans[2]
    assert outer["parent"] is None
    assert spans[0]["parent"] == outer["id"] == spans[1]["parent"]
    # fake clock: every enter/exit ticks 1s -> exact durations
    assert spans[0]["dur"] == 1.0
    assert outer["dur"] == 5.0
    assert all(s["tid"] == threading.get_ident() for s in spans)


def test_span_threads_get_independent_stacks():
    tr = obs.Tracer(clock=_fake_clock()[0])
    done = threading.Event()

    def worker():
        with tr.span("data.prefetch"):
            done.set()

    with tr.span("trainer.pass"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    by_name = {s["name"]: s for s in tr.spans()}
    # the worker's span must NOT claim the main thread's open span as parent
    assert by_name["data.prefetch"]["parent"] is None
    assert by_name["data.prefetch"]["tid"] != by_name["trainer.pass"]["tid"]


def test_span_records_error_and_survives_exception():
    tr = obs.Tracer(clock=_fake_clock()[0])
    with pytest.raises(RuntimeError):
        with tr.span("fluid.run"):
            raise RuntimeError("boom")
    (s,) = tr.spans()
    assert s["args"]["error"] == "RuntimeError"


def test_chrome_export_schema():
    clock, _ = _fake_clock()
    r = obs.MetricsRegistry()
    s = obs.ObsSession(registry=r, tracer=obs.Tracer(clock=clock))
    with s.installed():
        with obs.span("trainer.pass", pass_id=3):
            with obs.span("ckpt.publish"):
                pass
        obs.instant("jax.compile", event="e")
        obs.count("faults.injected_total", site="rpc.send", action="raise")
    trace = obs.chrome_trace(s.dump())
    evs = trace["traceEvents"]
    assert json.dumps(trace)             # serializable as-is
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(xs) == {"trainer.pass", "ckpt.publish"}
    # µs timestamps; child contained within parent (what Perfetto nests on)
    par, chd = xs["trainer.pass"], xs["ckpt.publish"]
    assert par["ts"] <= chd["ts"]
    assert chd["ts"] + chd["dur"] <= par["ts"] + par["dur"]
    assert par["args"] == {"pass_id": 3}
    assert [e for e in evs if e["ph"] == "i" and e["name"] == "jax.compile"]
    (c,) = [e for e in evs if e["ph"] == "C"]
    assert c["name"] == "faults.injected_total{action=raise,site=rpc.send}"
    assert c["args"]["value"] == 1
    assert any(e["ph"] == "M" for e in evs)


def test_tracer_caps_events_and_reports_dropped():
    clock, _ = _fake_clock()
    tr = obs.Tracer(clock=clock, max_events=3)
    s = obs.ObsSession(registry=obs.MetricsRegistry(), tracer=tr)
    with s.installed():
        for _ in range(5):
            with obs.span("trainer.step"):
                pass
    assert len(tr.events) == 3           # bounded: telemetry can't OOM
    assert tr.dropped == 2
    assert s.dump()["meta"]["events_dropped"] == 2
    tr.reset()
    assert tr.dropped == 0


def test_summary_quantiles_clamped_to_observed_max():
    r = obs.MetricsRegistry()
    h = r.histogram("trainer.step_seconds", buckets=(0.0005, 1.0))
    h.observe(0.000035)                  # 0.035ms in the le=0.5ms bucket
    dump = {"metrics": r.collect()}
    rep = obs.summary(dump)
    # p50/p99 must not exceed the observed max (0.035ms), not read 0.5ms
    line = next(l for l in rep.splitlines() if "trainer.step_seconds" in l)
    assert "0.035ms" in line and "0.500ms" not in line


def test_read_jsonl_tolerates_torn_tail(tmp_path):
    # a process killed mid-save leaves a partial final line; the dump of
    # exactly that crashed run must still export its intact prefix
    s = obs.ObsSession(registry=obs.MetricsRegistry(),
                       tracer=obs.Tracer(clock=_fake_clock()[0]))
    with s.installed():
        obs.count("trainer.steps_total", 5)
        with obs.span("trainer.pass"):
            pass
    p = s.save(str(tmp_path / "torn.jsonl"))
    raw = open(p, "rb").read()
    open(p, "wb").write(raw[:-5])        # tear the last line
    back = obs.read_jsonl(p)
    assert [m for m in back["metrics"] if m["name"] == "trainer.steps_total"]
    assert cli.main(["obs", "summary", "--input", p]) == 0


def test_jsonl_round_trip(tmp_path):
    clock, _ = _fake_clock()
    s = obs.ObsSession(registry=obs.MetricsRegistry(),
                       tracer=obs.Tracer(clock=clock))
    with s.installed():
        with obs.span("rpc.call", metric="rpc.call_seconds"):
            pass
        obs.count("rpc.calls_total", rpc="master")
    p = s.save(str(tmp_path / "run.jsonl"))
    back = obs.read_jsonl(p)
    assert back["meta"]["version"] == 1
    assert [m for m in back["metrics"] if m["name"] == "rpc.calls_total"]
    hist = [m for m in back["metrics"] if m["name"] == "rpc.call_seconds"]
    assert hist and hist[0]["count"] == 1
    assert [e for e in back["events"] if e["name"] == "rpc.call"]
    # exporters accept the reloaded dump unchanged
    assert "rpc_calls_total" in obs.prometheus_text(back)
    assert obs.chrome_trace(back)["traceEvents"]
    assert "rpc.call_seconds" in obs.summary(back)


# -- zero cost when uninstalled -------------------------------------------------

def test_zero_cost_hooks_are_noops_without_session():
    assert not obs.is_active()
    # hooks must neither raise nor record anywhere
    obs.count("trainer.steps_total")
    obs.gauge_set("data.queue_depth", 5)
    obs.observe("rpc.call_seconds", 0.1)
    obs.instant("jax.compile")
    sp = obs.span("trainer.step", metric="trainer.step_seconds")
    assert sp is obs.NULL_SPAN           # ONE shared object, no allocation
    with sp:
        pass
    r = obs.MetricsRegistry()
    with obs.ObsSession(registry=r).installed():
        pass
    assert r.collect() == []             # nothing leaked into the session


def test_exclusive_install():
    a = obs.ObsSession(registry=obs.MetricsRegistry())
    b = obs.ObsSession(registry=obs.MetricsRegistry())
    with a.installed():
        with pytest.raises(RuntimeError, match="already installed"):
            b.install()
    assert not obs.is_active()


# -- satellites -----------------------------------------------------------------

def test_statset_items_returns_immutable_snapshots():
    ss = StatSet()
    ss.add("TrainBatch", 0.5)
    ss.add("TrainBatch", 1.5)
    items = ss.items()
    snap = items["TrainBatch"]
    assert isinstance(snap, StatSnapshot)
    assert snap.total == 2.0 and snap.count == 2
    assert snap.avg == 1.0 and snap.max == 1.5
    with pytest.raises(AttributeError):
        snap.total = 99.0                # immutable: callers can't corrupt
    ss.add("TrainBatch", 1.0)
    assert snap.total == 2.0             # a snapshot, not a live reference
    assert ss.items()["TrainBatch"].total == 3.0


def test_train_stats_is_readonly_counter_view():
    t = Trainer(lambda p, x: jnp.sum(x), SGD(0.1), nan_guard=False)
    assert dict(t.train_stats) == {"nonfinite_batches": 0,
                                   "skipped_batches": 0, "preemptions": 0}
    with pytest.raises(TypeError):
        t.train_stats["preemptions"] = 1
    t.metrics.counter("trainer.preemptions_total").inc()
    assert t.train_stats["preemptions"] == 1
    # injectable registry
    r = obs.MetricsRegistry()
    t2 = Trainer(lambda p, x: jnp.sum(x), SGD(0.1), metrics=r)
    t2.metrics.counter("trainer.nonfinite_total").inc(2)
    assert t2.train_stats["nonfinite_batches"] == 2
    assert r.counter("trainer.nonfinite_total").get() == 2


def test_retry_policy_observer_no_sleeps():
    sleeps = []
    clock = [0.0]
    events = []
    policy = RetryPolicy(max_attempts=3, base_delay=0.1, multiplier=2.0,
                         jitter=0.0, sleep=sleeps.append,
                         clock=lambda: clock[0],
                         observer=lambda ev, **kw: events.append((ev, kw)))
    calls = [0]

    def flaky():
        calls[0] += 1
        if calls[0] < 3:
            raise ConnectionError("nope")
        return "ok"

    assert policy.call(flaky) == "ok"
    kinds = [e[0] for e in events]
    assert kinds == ["attempt", "attempt", "success"]
    assert events[0][1]["attempt"] == 1
    assert events[0][1]["delay"] == pytest.approx(0.1)
    assert events[1][1]["delay"] == pytest.approx(0.2)
    assert events[2][1]["attempts"] == 3
    events.clear()
    with pytest.raises(RetryBudgetExceeded):
        policy.call(lambda: (_ for _ in ()).throw(ConnectionError("x")))
    assert [e[0] for e in events] == ["attempt", "attempt", "giveup"]
    assert events[-1][1]["attempts"] == 3


def test_retry_observer_bridge_counts_into_session():
    r = obs.MetricsRegistry()
    policy = RetryPolicy(max_attempts=2, base_delay=0.25, jitter=0.0,
                         sleep=lambda s: None, clock=lambda: 0.0,
                         observer=obs.retry_observer("rpc"))
    with obs.ObsSession(registry=r).installed():
        with pytest.raises(RetryBudgetExceeded):
            policy.call(lambda: (_ for _ in ()).throw(OSError("x")))
    assert r.counter("rpc.retries_total").get() == 1
    assert r.counter("rpc.giveups_total").get() == 1
    assert r.counter("rpc.backoff_seconds_total").get() == \
        pytest.approx(0.25)
    # without a session the observer is inert (no import cycle, no cost)
    policy.call(lambda: "fine")


def test_metric_name_lint_L005():
    assert analysis.lint_metric_names(obs.CATALOGUE) == []
    diags = analysis.lint_metric_names({
        "BadName": ("counter", ""),                 # no dot / case
        "three.dots.here": ("counter", ""),         # two dots
        "trainer.steps": ("counter", ""),           # counter w/o _total
        "fluid.run_seconds": ("histogram", ""),     # fine
        "data.queue_total": ("gauge", ""),          # gauge w/ reserved suffix
    })
    assert {d.var for d in diags} == {"BadName", "three.dots.here",
                                      "trainer.steps", "data.queue_total"}
    assert all(d.code == "L005" for d in diags)
    # plain-iterable form: shape check only
    assert analysis.lint_metric_names(["trainer.steps"]) == []
    assert len(analysis.lint_metric_names(["nodots"])) == 1


def test_catalogue_covers_spans_and_lint_catalogue_entry():
    assert "L005" in analysis.LINT_CATALOGUE
    # every span the instrumentation emits is documented
    for name in ("trainer.pass", "trainer.step", "rpc.call", "ckpt.publish",
                 "fluid.run", "fluid.verify"):
        assert name in obs.SPANS


# -- end-to-end: train 2 passes, RPC + checkpoint + step metrics ---------------

def _loss(params, x, y):
    return jnp.mean((x @ params["w"] - y) ** 2)


def _batches(n=3, bs=8, d=4):
    rs = np.random.RandomState(0)
    return [(rs.randn(bs, d).astype(np.float32),
             rs.randn(bs, 1).astype(np.float32)) for _ in range(n)]


def test_e2e_train_two_passes_populates_metrics(tmp_path):
    from paddle_tpu.runtime.coord import CoordServer, _CoordClient
    srv = CoordServer().start()
    client = _CoordClient(*srv.address)
    batches = _batches()

    def reader():
        # an RPC inside the read path: rpc.call spans/latency nest under
        # the open trainer.pass span exactly like a cloud_reader's
        # get_task pulls would
        client.call({"op": "ping"})
        return iter(batches)

    r = obs.MetricsRegistry()
    clock, _ = _fake_clock(0.001)
    try:
        with obs.ObsSession(registry=r, clock=clock).installed() as s:
            t = Trainer(_loss, SGD(0.1), output_dir=str(tmp_path))
            params, _ = t.train(reader,
                                {"w": np.zeros((4, 1), np.float32)},
                                num_passes=2)
    finally:
        client.close()
        srv.stop()
    # step metrics
    assert r.counter("trainer.steps_total").get() == 6
    assert r.counter("trainer.examples_total").get() == 48
    assert r.histogram("trainer.step_seconds").snapshot()["count"] == 6
    # RPC metrics (latency histogram labeled by client)
    assert r.counter("rpc.calls_total").get(rpc="coord rpc", op="ping") == 2
    assert r.histogram("rpc.call_seconds").snapshot(
        rpc="coord rpc")["count"] == 2
    # checkpoint metrics: one save per pass, real bytes, timed members
    assert r.counter("ckpt.saves_total").get() == 2
    assert r.counter("ckpt.bytes_total").get() > 0
    assert r.histogram("ckpt.write_seconds").snapshot()["count"] >= 4
    # span nesting: rpc.call and ckpt.publish both inside trainer.pass
    spans = {e["id"]: e for e in s.dump()["events"] if e["kind"] == "span"}

    def ancestors(e):
        while e.get("parent"):
            e = spans[e["parent"]]
            yield e["name"]

    for name in ("rpc.call", "ckpt.publish"):
        e = next(x for x in spans.values() if x["name"] == name)
        assert "trainer.pass" in list(ancestors(e)), name
    # the summary subsumes StatSet.report(): timers appear next to metrics
    rep = t.summary()
    assert "TrainBatch" in rep and "trainer.steps_total" in rep


def test_chaos_run_exports_chrome_trace_via_cli(tmp_path, capsys):
    plan = faults.FaultPlan(seed=3)
    plan.add("ckpt.write", "corrupt", nth=1)
    plan.add("step.grad", "delay", nth=2, delay_s=0.0)
    r = obs.MetricsRegistry()
    with obs.ObsSession(registry=r).installed() as s, plan.installed():
        t = Trainer(_loss, SGD(0.1), output_dir=str(tmp_path / "out"))
        t.train(lambda: iter(_batches()),
                {"w": np.zeros((4, 1), np.float32)}, num_passes=1)
    # per-site injected-fault counters match the plan's fired log exactly
    fired = {}
    for site, _, action in plan.fired:
        fired[(site, action)] = fired.get((site, action), 0) + 1
    for (site, action), n in fired.items():
        assert r.counter("faults.injected_total").get(
            site=site, action=action) == n
    dump = str(tmp_path / "run.jsonl")
    s.save(dump)
    out = str(tmp_path / "trace.json")
    assert cli.main(["obs", "export", "--input", dump,
                     "--format", "chrome", "--output", out]) == 0
    trace = json.load(open(out))
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert {"trainer.pass", "trainer.step", "trainer.checkpoint",
            "ckpt.publish", "ckpt.member"} <= names
    counters = {e["name"]: e["args"]["value"]
                for e in trace["traceEvents"] if e["ph"] == "C"}
    assert counters[
        "faults.injected_total{action=corrupt,site=ckpt.write}"] == 1
    # prom + summary forms of the same dump
    assert cli.main(["obs", "export", "--input", dump,
                     "--format", "prom"]) == 0
    assert "paddle_tpu_trainer_steps_total" in capsys.readouterr().out
    assert cli.main(["obs", "summary", "--input", dump]) == 0
    assert "trainer.steps_total" in capsys.readouterr().out


def test_no_double_count_when_session_shares_trainer_registry():
    # Trainer(metrics=R) under a session whose registry IS R: the session
    # mirror must be skipped or every counter reads 2x (code-review find)
    r = obs.MetricsRegistry()
    with obs.ObsSession(registry=r).installed():
        t = Trainer(_loss, SGD(0.1), metrics=r)
        t.train(lambda: iter(_batches(2)),
                {"w": np.zeros((4, 1), np.float32)}, num_passes=1)
        t._count("trainer.preemptions_total")
    assert r.counter("trainer.steps_total").get() == 2
    assert t.train_stats["preemptions"] == 1
    # distinct registries: both sides see the count exactly once
    r2, local = obs.MetricsRegistry(), obs.MetricsRegistry()
    with obs.ObsSession(registry=r2).installed():
        t2 = Trainer(_loss, SGD(0.1), metrics=local)
        t2._count("trainer.preemptions_total")
    assert local.counter("trainer.preemptions_total").get() == 1
    assert r2.counter("trainer.preemptions_total").get() == 1


def test_jax_compile_hook_counts_backend_compiles_only():
    from paddle_tpu.obs import jaxhooks
    r = obs.MetricsRegistry()
    with obs.ObsSession(registry=r).installed():
        # one jit emits several duration events; only backend_compile counts
        for ev in ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/mlir_lowering_duration",
                   "/jax/core/compile/backend_compile_duration"):
            jaxhooks._on_duration(ev, 0.5)
    assert r.counter("jax.compiles_total").get() == 1
    assert r.histogram("jax.compile_seconds").snapshot()["count"] == 1


def test_rpc_client_does_not_mutate_caller_policy():
    from paddle_tpu.runtime.master_service import _RpcClient
    mine = RetryPolicy(max_attempts=2)
    c = _RpcClient("127.0.0.1", 1, retry_policy=mine)
    assert mine.observer is None          # caller's shared policy untouched
    c2 = _RpcClient("127.0.0.1", 1)
    assert c2.policy.observer is not None  # our own default gets telemetry
    c.close()
    c2.close()


def test_prefetch_queue_metrics():
    from paddle_tpu.data.prefetch import DoubleBuffer
    r = obs.MetricsRegistry()
    with obs.ObsSession(registry=r).installed():
        got = list(DoubleBuffer(lambda: iter(range(5)), depth=2))
    assert got == list(range(5))
    assert r.counter("data.prefetch_iters_total").get() == 1
    # the first get always races the producer: starvation is >= 1 and the
    # gauge saw some depth (possibly 0) — presence, not exact timing
    assert r.counter("data.starved_total").get() >= 0
    samples = [s for s in r.collect() if s["name"] == "data.queue_depth"]
    assert samples and samples[0]["type"] == "gauge"


# -- ISSUE 4: distributed tracing, cluster aggregation, flight recorder --------

def test_prom_label_value_escaping():
    # regression: values holding '"', '\' or newlines previously emitted
    # unparseable exposition text
    r = obs.MetricsRegistry()
    r.counter("rpc.calls_total").inc(op='we"ird\\path\nx')
    text = obs.prometheus_text({"metrics": r.collect()})
    line = next(l for l in text.splitlines()
                if l.startswith("paddle_tpu_rpc_calls_total{"))
    assert 'op="we\\"ird\\\\path\\nx"' in line
    # escaped text has no raw newline inside the label braces
    assert "\n" not in line


def test_wire_context_shape_and_sanitize():
    assert obs.wire_context(obs.NULL_SPAN) is None   # plane off: no key
    r = obs.MetricsRegistry()
    with obs.ObsSession(registry=r).installed() as s:
        with obs.span("rpc.call") as sp:
            ctx = obs.wire_context(sp)
        assert ctx == {"id": obs.context.trace_id(), "span": sp.id,
                       "pid": os.getpid()}
        # hostile/malformed contexts degrade to no remote, never raise
        for bad in (None, 42, "x", {}, {"id": 1}, {"id": "a", "span": "NaN",
                                                   "pid": 1},
                    {"id": "a", "span": -1, "pid": 1}):
            with obs.server_span("master.dispatch", bad, op="t"):
                pass
        long_id = {"id": "q" * 500, "span": 7, "pid": 8}
        with obs.server_span("master.dispatch", long_id, op="t"):
            pass
    spans = [e for e in s.dump()["events"] if e["name"] == "master.dispatch"]
    assert all("remote" not in e for e in spans[:-1])
    assert spans[-1]["remote"] == {"id": "q" * 64, "span": 7, "pid": 8}


def test_coord_server_span_parents_under_client_rpc_call():
    from paddle_tpu.runtime.coord import CoordServer, _CoordClient
    srv = CoordServer().start()
    client = _CoordClient(*srv.address)
    r = obs.MetricsRegistry()
    try:
        with obs.ObsSession(registry=r).installed() as s:
            client.call({"op": "ping"})
    finally:
        client.close()
        srv.stop()
    spans = {e["id"]: e for e in s.dump()["events"] if e["kind"] == "span"}
    disp = next(e for e in spans.values() if e["name"] == "coord.dispatch")
    # the server-side span names the client's rpc.call span as its remote
    # parent — the cross-process edge (same pid here; the multiprocess
    # e2e in test_obs_distributed.py asserts the distinct-pid case)
    assert spans[disp["remote"]["span"]]["name"] == "rpc.call"
    assert disp["remote"]["id"] == obs.context.trace_id()
    assert disp["args"]["op"] == "ping"
    # per-request-type counters on the server peer
    assert r.counter("coord.requests_total").get(type="ping") == 1
    assert r.counter("coord.request_errors_total").get(type="ping") == 0
    # errors counted too
    srv2 = CoordServer().start()
    c2 = _CoordClient(*srv2.address)
    try:
        with obs.ObsSession(registry=r).installed():
            c2.call({"op": "nope"})
    finally:
        c2.close()
        srv2.stop()
    # arbitrary op strings clamp to "unknown": a hostile peer must not
    # mint unbounded counter series (the L005 cardinality failure mode)
    assert r.counter("coord.request_errors_total").get(type="unknown") == 1
    assert r.counter("coord.requests_total").get(type="nope") == 0


def test_wire_context_absent_from_envelope_without_session():
    # with no session the request bytes must stay identical to an
    # un-instrumented client's: no "trace" key reaches the server
    from paddle_tpu.runtime.coord import CoordServer, _CoordClient
    seen = []
    srv = CoordServer()
    orig = srv._dispatch

    def spy(req):
        seen.append(req)
        return orig(req)

    srv._dispatch = spy
    srv.start()
    client = _CoordClient(*srv.address)
    try:
        assert not obs.is_active()
        client.call({"op": "ping"})
        r = obs.MetricsRegistry()
        with obs.ObsSession(registry=r).installed():
            client.call({"op": "ping"})
    finally:
        client.close()
        srv.stop()
    assert "trace" not in seen[0]
    assert "trace" in seen[1]


def test_merge_dumps_and_multi_pid_chrome_export():
    # two synthetic per-process dumps: worker rpc.call -> master dispatch
    worker = {
        "meta": {"pid": 100, "process": "worker-0",
                 "clock_origin_unix": 1000.0},
        "metrics": [{"type": "counter", "name": "trainer.steps_total",
                     "labels": {}, "value": 3}],
        "events": [{"kind": "span", "name": "rpc.call", "ts": 1.0,
                    "dur": 0.5, "tid": 1, "pid": 100, "id": 7,
                    "parent": None, "args": {"op": "obs_push"}}]}
    master = {
        "meta": {"pid": 200, "process": "master",
                 "clock_origin_unix": 1000.25},
        "metrics": [{"type": "counter", "name": "trainer.steps_total",
                     "labels": {}, "value": 9}],
        "events": [{"kind": "span", "name": "master.dispatch", "ts": 0.9,
                    "dur": 0.1, "tid": 9, "pid": 200, "id": 3,
                    "parent": None, "args": {"op": "obs_push"},
                    "remote": {"id": "t", "span": 7, "pid": 100}}]}
    merged = obs.merge_dumps([worker, master])
    # same-named series stay distinct via the worker label contract
    series = {(m["labels"]["worker"], m["value"])
              for m in merged["metrics"]}
    assert series == {("worker-0", 3), ("master", 9)}
    # clock alignment: master events shift by its later origin
    disp = next(e for e in merged["events"]
                if e["name"] == "master.dispatch")
    assert disp["ts"] == pytest.approx(1.15)
    trace = obs.chrome_trace(merged)
    evs = trace["traceEvents"]
    lanes = {e["pid"]: e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert lanes == {100: "worker-0", 200: "master"}
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert xs["master.dispatch"]["args"]["remote_parent"]["span"] == 7
    # the stitch: a flow arrow from the client slice to the server slice
    s_ev = next(e for e in evs if e["ph"] == "s")
    f_ev = next(e for e in evs if e["ph"] == "f")
    assert s_ev["id"] == f_ev["id"]
    assert s_ev["pid"] == 100 and f_ev["pid"] == 200


def test_master_dispatch_obs_push_and_merged_stats():
    from paddle_tpu.runtime.master_service import MasterServer
    r = obs.MetricsRegistry()
    srv = MasterServer()          # in-process dispatch; no network start
    with obs.ObsSession(registry=r).installed() as s:
        wr = obs.MetricsRegistry()
        wr.counter("trainer.steps_total").inc(5)
        ctx = {"id": "t", "span": 11, "pid": 999}
        resp = srv._dispatch({"op": "obs_push", "worker": "w1",
                              "samples": wr.collect(), "trace": ctx})
        assert resp["ok"] and resp["accepted"] == 1
        # junk samples are filtered, never stored
        assert srv._dispatch({"op": "obs_push", "worker": "w2",
                              "samples": ["junk", {"no_name": 1},
                                          {"name": "a.b_total",
                                           "type": "counter", "value": 2,
                                           "labels": {"x": "y"},
                                           "evil": "dropped"}]}
                             )["accepted"] == 1
        out = srv._dispatch({"op": "obs_stats"})
    assert out["workers"] == ["w1", "w2"]
    by_worker = {}
    for m in out["samples"]:
        by_worker.setdefault(m["labels"]["worker"], []).append(m)
    assert by_worker["w1"][0]["name"] == "trainer.steps_total"
    assert by_worker["w1"][0]["value"] == 5
    assert "evil" not in by_worker["w2"][0]
    # dispatch span carries the wire context; counters tallied by type
    disp = [e for e in s.dump()["events"]
            if e.get("name") == "master.dispatch"]
    assert disp[0]["remote"] == ctx
    assert r.counter("master.requests_total").get(type="obs_push") == 2
    assert r.counter("master.requests_total").get(type="obs_stats") == 1
    assert r.gauge("master.obs_workers").get() == 2


def test_flight_recorder_ring_keeps_tail_and_deltas(tmp_path):
    r = obs.MetricsRegistry()
    clock, _ = _fake_clock(0.001)
    s = obs.ObsSession(registry=r, tracer=obs.Tracer(clock=clock))
    p = str(tmp_path / "flight.jsonl")
    with s.installed():
        r.counter("trainer.steps_total").inc(10)     # pre-arm baseline
        rec = obs.FlightRecorder(s, p, ring_size=4).arm()
        try:
            r.counter("trainer.steps_total").inc(3)
            for i in range(10):
                with obs.span("trainer.step", batch=i):
                    pass
            out = rec.dump("test")
        finally:
            rec.disarm()
    assert out == p
    assert s.tracer.ring is None         # disarm releases the ring too
    back = obs.read_jsonl(p)
    assert back["meta"]["flight"] is True
    assert back["meta"]["reason"] == "test"
    # the ring keeps the END of the run — the last 4 steps, not the first
    assert [e["args"]["batch"] for e in back["events"]] == [6, 7, 8, 9]
    steps = next(m for m in back["metrics"]
                 if m["name"] == "trainer.steps_total")
    assert steps["value"] == 13 and steps["delta"] == 3
    # the flight dump is a normal dump: every exporter accepts it
    assert obs.chrome_trace(back)["traceEvents"]
    assert "trainer_steps_total" in obs.prometheus_text(back)


def test_flight_dump_written_at_injected_fault(tmp_path):
    r = obs.MetricsRegistry()
    s = obs.ObsSession(registry=r)
    p = str(tmp_path / "crash.jsonl")
    plan = faults.FaultPlan().add("rpc.send", "raise", nth=1)
    with s.installed():
        rec = obs.FlightRecorder(s, p, ring_size=16).arm()
        try:
            with plan.installed():
                with obs.span("trainer.step"):
                    with pytest.raises(faults.FaultError):
                        faults.fire("rpc.send")
        finally:
            rec.disarm()
    back = obs.read_jsonl(p)
    assert back["meta"]["reason"] == "fault:rpc.send"
    # the dump precedes the unwind: the enclosing step span is still open
    # (not yet in the ring) but the injected-fault counter is in
    inj = next(m for m in back["metrics"]
               if m["name"] == "faults.injected_total")
    assert inj["labels"] == {"site": "rpc.send", "action": "raise"}
    assert not obs.flight_dump("noop")        # disarmed: hook is inert


def test_flight_recorder_overhead_per_batch():
    # acceptance: the armed ring adds <= ~5µs per batch (5 span records).
    # Measured ~0.5µs on CI-class CPUs; the bound below is 10x slack for
    # noisy neighbours, while still catching an accidental O(ring) cost.
    import time as _t
    s = obs.ObsSession(registry=obs.MetricsRegistry())

    def per_batch(n=300):
        t0 = _t.perf_counter()
        for _ in range(n):
            for _ in range(5):
                with s.tracer.span("trainer.step"):
                    pass
        return (_t.perf_counter() - t0) / n

    with s.installed():
        base = min(per_batch() for _ in range(3))
        s.tracer.enable_ring(2048)
        armed = min(per_batch() for _ in range(3))
    assert armed - base < 50e-6, (base, armed)
    # and the uninstalled fast path is untouched by the feature
    assert obs.span("trainer.step") is obs.NULL_SPAN


def test_metric_lint_flags_unbounded_labels():
    # catalogue-declared label keys from the unbounded set are flagged
    diags = analysis.lint_metric_names({
        "data.reads_total": ("counter", "", ("path",)),
        "rpc.calls_total": ("counter", "", ("rpc", "op")),     # bounded: ok
    })
    assert [d.var for d in diags] == ["data.reads_total"]
    assert all(d.code == "L005" for d in diags)
    # live samples: path-like values and runaway per-key cardinality
    samples = [{"name": "ckpt.saves_total", "type": "counter",
                "labels": {"dest": "/data/run/pass-00001"}, "value": 1}]
    assert len(analysis.lint_metric_names(["ckpt.saves_total"],
                                          samples=samples)) == 1
    many = [{"name": "rpc.calls_total", "type": "counter",
             "labels": {"op": f"op{i}"}, "value": 1} for i in range(40)]
    d = analysis.lint_metric_names(["rpc.calls_total"], samples=many)
    assert len(d) == 1 and "40 distinct values" in d[0].message
    # the shipped catalogue stays clean under the extended lint
    assert analysis.lint_metric_names(obs.CATALOGUE) == []


def test_obs_http_server_serves_metrics_trace_summary():
    import urllib.request

    from paddle_tpu.obs.aggregate import ObsHttpServer
    r = obs.MetricsRegistry()
    s = obs.ObsSession(registry=r, tracer=obs.Tracer(clock=_fake_clock()[0]))
    with s.installed():
        r.counter("trainer.steps_total").inc(4)
        with obs.span("trainer.pass"):
            pass
    srv = ObsHttpServer(s.dump).start()
    host, port = srv.address

    def get(path):
        with urllib.request.urlopen(f"http://{host}:{port}{path}",
                                    timeout=10) as resp:
            return resp.status, resp.read().decode()

    try:
        code, body = get("/metrics")
        assert code == 200
        assert "paddle_tpu_trainer_steps_total 4" in body
        code, body = get("/trace")
        assert code == 200
        assert any(e["name"] == "trainer.pass"
                   for e in json.loads(body)["traceEvents"])
        code, body = get("/summary")
        assert code == 200 and "trainer.steps_total" in body
        with pytest.raises(urllib.error.HTTPError) as ei:
            get("/nope")
        assert ei.value.code == 404
    finally:
        srv.stop()


def test_obs_pusher_pushes_and_counts_failures():
    class FakeClient:
        def __init__(self):
            self.pushed = []
            self.fail = False

        def obs_push(self, worker, samples):
            if self.fail:
                raise ConnectionError("down")
            self.pushed.append((worker, samples))

    from paddle_tpu.obs.aggregate import ObsPusher
    r = obs.MetricsRegistry()
    client = FakeClient()
    with obs.ObsSession(registry=r).installed():
        r.counter("trainer.steps_total").inc()
        pusher = ObsPusher(client, worker="w0", interval=3600)
        assert pusher.push_once()
        client.fail = True
        assert not pusher.push_once()      # counted, never raised
    assert client.pushed[0][0] == "w0"
    assert r.counter("obs.pushes_total").get() == 1
    assert r.counter("obs.push_failures_total").get() == 1


def test_executor_cache_hit_metrics():
    import paddle_tpu.fluid as fluid
    r = obs.MetricsRegistry()
    fluid.reset_default_programs()
    prog = fluid.Program()
    with fluid.program_guard(prog):
        x = fluid.layers.data("x", shape=(2,))
        y = fluid.layers.mean(fluid.layers.elementwise_add(x, x))
    exe = fluid.Executor()
    feed = {"x": np.ones((3, 2), np.float32)}
    with obs.ObsSession(registry=r).installed():
        exe.run(prog, feed=feed, fetch_list=[y])
        exe.run(prog, feed=feed, fetch_list=[y])
    assert r.counter("fluid.runs_total").get() == 2
    # hit/miss counters carry the bucketed label (no BucketSpec -> "false")
    assert r.counter("fluid.cache_misses_total").get(bucketed="false") == 1
    assert r.counter("fluid.cache_hits_total").get(bucketed="false") == 1
    assert r.gauge("fluid.cache_size").get() == 1
    assert r.histogram("fluid.run_seconds").snapshot()["count"] == 2
