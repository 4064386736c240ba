"""The chaos-proved serving router (serving/router.py): disaggregated
prefill/decode placement from health TRENDS, structured backpressure
aggregation, re-route on eviction (graceful leave AND kill -9), router
restart recovery off the submit_key replay ladder — always against the
bar that client-visible greedy tokens bit-equal solo single-engine decode
with zero lost or duplicated tokens."""

import contextlib
import os
import re
import signal
import socket
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import faults
from paddle_tpu.runtime.master_service import MasterClient

VOCAB, D, H, L, MAX_LEN = 97, 32, 4, 2, 128
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def model_and_params():
    from paddle_tpu.models import TransformerLM
    model = TransformerLM(VOCAB, d_model=D, n_heads=H, n_layers=L,
                          max_len=MAX_LEN)
    return model, model.init(jax.random.PRNGKey(0))


def _ref(model, params, prompt, max_new):
    """Solo single-engine greedy decode — the parity bar every routed
    stream is held to, whatever happened to its placement."""
    return np.asarray(model.generate_cached(
        params, jnp.asarray(np.asarray(prompt)[None]),
        steps=max_new))[0, len(prompt):]


@contextlib.contextmanager
def _fleet(model, params, n_decode=2, prefill=False, port=0,
           prefill_prefix_cache=False, **eng_kw):
    """Router + n in-process decode daemons (+ optional prefill worker),
    all joined, behind a private obs registry."""
    from paddle_tpu import obs
    from paddle_tpu.serving import (PagePool, PrefillDaemon, ServingDaemon,
                                    ServingEngine, ServingRouter)
    reg = obs.MetricsRegistry()
    session = obs.ObsSession(registry=reg).install()
    kw = dict(slots=2, segment=8, page_block=8, cache_bucket=32)
    kw.update(eng_kw)
    router = ServingRouter(port=port, ttl=1.0,
                           scrape_interval_s=0.1).start()
    daemons = []
    try:
        for i in range(n_decode):
            d = ServingDaemon(ServingEngine(model, params, **kw)).start()
            d.join_router(router.address, f"d{i}", role="decode")
            daemons.append(d)
        if prefill:
            pool = PagePool(model, params, slots=2, segment=kw["segment"],
                            page_block=kw["page_block"],
                            cache_bucket=kw["cache_bucket"],
                            prefix_cache=prefill_prefix_cache)
            pd = PrefillDaemon(pool).start()
            pd.join_router(router.address, "p0", role="prefill")
            daemons.append(pd)
        yield router, daemons, reg
    finally:
        for d in daemons:
            d.stop()
        router.stop()
        session.uninstall()


def _counter(reg, name, **labels):
    total = 0.0
    for s in reg.collect():
        if s["name"] == name and all(s["labels"].get(k) == v
                                     for k, v in labels.items()):
            total += s["value"]
    return total


def _throttle(daemons, delay_s=0.05):
    """Slow every decode dispatch so streams are reliably MID-flight when
    the chaos lands — a warm compile cache otherwise finishes a whole
    24-token budget faster than one poll round-trip."""
    for d in daemons:
        orig = d.engine.decode_segment

        def slow(o=orig):
            time.sleep(delay_s)
            o()
        d.engine.decode_segment = slow


def _drain_interleaved(client, work, timeout=120.0, cursors=None):
    """Round-robin poll a set of {key: rid} to completion — the cursors
    only ever advance, so any lost or duplicated token breaks parity."""
    cursors = {k: (cursors or {}).get(k, 0) for k in work}
    toks = {k: [] for k in work}
    live = set(work)
    deadline = time.monotonic() + timeout
    while live:
        assert time.monotonic() < deadline, "routed drain timed out"
        for k in list(live):
            got, done, reason = client.poll(work[k], cursors[k])
            toks[k].extend(got)
            cursors[k] += len(got)
            if done:
                assert reason in ("length", "eos"), (k, reason)
                live.discard(k)
        time.sleep(0.02)
    return {k: np.asarray(v, np.int32) for k, v in toks.items()}


def test_disaggregated_fleet_interleaved_streams_exact(model_and_params):
    """The tentpole, end to end in-process: 1 prefill + 2 decode workers
    behind the router; interleaved streams come back bit-equal to solo
    decode; KV pages actually SHIPPED (prefill ran on a different pool
    than decode); stats report the fleet shape; replies carry the
    membership epoch."""
    from paddle_tpu.serving import RouterClient
    model, params = model_and_params
    with _fleet(model, params, n_decode=2, prefill=True) as (router, ds,
                                                             reg):
        c = RouterClient(*router.address, call_timeout=60.0)
        st = c.serving_stats()
        assert st["n_decode_workers"] == 2
        assert st["n_prefill_workers"] == 1
        rs = np.random.RandomState(21)
        reqs = {i: (rs.randint(0, VOCAB, n), g)
                for i, (n, g) in enumerate([(7, 18), (11, 20), (13, 24)])}
        work = {i: c.submit(p, g) for i, (p, g) in reqs.items()}
        got = _drain_interleaved(c, work)
        for i, (p, g) in reqs.items():
            np.testing.assert_array_equal(got[i], _ref(model, params, p, g))
        # the pages went over the wire: prefill-side export counted ship
        # pages, decode-side adoption counted adopts — different pools
        assert _counter(reg, "serving.ship_pages_total") > 0
        assert _counter(reg, "serving.adopted_total") >= len(reqs)
        assert _counter(reg, "router.requests_total", outcome="ok") \
            >= len(reqs)
        assert c.last_epoch is not None       # epoch rode every reply
        c.close()


def test_prefix_hit_rate_preserved_across_the_hop(model_and_params):
    """Disaggregation must not cost the prefix cache: a second prompt
    sharing full blocks with an earlier one HITS the prefill worker's
    radix index (only its suffix re-prefills), and the exported slot
    still decodes token-exact on the far worker — shared pages ship as
    complete rows, not as references into the prefill pool."""
    from paddle_tpu.serving import RouterClient
    model, params = model_and_params
    rs = np.random.RandomState(29)
    base = rs.randint(0, VOCAB, 17).astype(np.int32)   # 2 full blocks + 1
    p2 = np.concatenate([base[:16], rs.randint(0, VOCAB, 3,
                                               dtype=np.int32)])
    with _fleet(model, params, n_decode=1, prefill=True,
                prefill_prefix_cache=True) as (router, ds, reg):
        c = RouterClient(*router.address, call_timeout=60.0)
        got1 = _drain_interleaved(c, {"a": c.submit(base, 12)})["a"]
        np.testing.assert_array_equal(got1, _ref(model, params, base, 12))
        hits0 = _counter(reg, "serving.prefix_hits_total")
        got2 = _drain_interleaved(c, {"b": c.submit(p2, 12)})["b"]
        np.testing.assert_array_equal(got2, _ref(model, params, p2, 12))
        assert _counter(reg, "serving.prefix_hits_total") > hits0
        assert _counter(reg, "serving.adopted_total") >= 2
        c.close()


def test_saturation_structured_overloaded_and_backoff_recovery(
        model_and_params):
    """Saturate BOTH decode pools: the router aggregates the structured
    refusals into one Overloaded (minimum retry_after_s hint, never a
    hang or traceback) on a connection that keeps serving, and
    submit_with_backoff rides the window out once a pool drains."""
    from paddle_tpu.serving import Overloaded, RouterClient
    model, params = model_and_params
    with _fleet(model, params, n_decode=2, queue_cap=2) as (router, ds,
                                                            reg):
        c = RouterClient(*router.address, call_timeout=60.0)
        rs = np.random.RandomState(3)
        rids, refusals = [], []
        for _ in range(16):
            try:
                rids.append(c.submit(rs.randint(0, VOCAB, 5), 80))
            except Overloaded as e:
                refusals.append(e)
        assert rids and refusals              # both sides of the cap seen
        assert all(e.retry_after_s > 0 for e in refusals)
        assert any("saturated" in str(e) for e in refusals)
        # the SAME connection still answers (structured reply, no hangup)
        assert c.serving_stats()["inflight"] >= 1
        assert _counter(reg, "router.requests_total",
                        outcome="overloaded") == len(refusals)
        for rid in rids:
            c.cancel(rid)
        late = c.submit_with_backoff(rs.randint(0, VOCAB, 5), 3)
        got = _drain_interleaved(c, {"late": late})["late"]
        assert got.size == 3
        c.close()


def test_graceful_leave_reroutes_stream_exact(model_and_params):
    """Stop the worker holding a live stream (graceful leave): the
    membership notification marks the record, the next poll re-places it
    on the survivor by re-prefilling prompt + delivered tokens, and the
    client-visible sequence is still exactly solo decode — the seam is
    invisible to cursors."""
    from paddle_tpu.serving import RouterClient
    model, params = model_and_params
    with _fleet(model, params, n_decode=2) as (router, ds, reg):
        c = RouterClient(*router.address, call_timeout=60.0)
        _throttle(ds)
        rs = np.random.RandomState(9)
        prompt, max_new = rs.randint(0, VOCAB, 9), 24
        rid = c.submit(prompt, max_new)
        toks, cursor = [], 0
        deadline = time.monotonic() + 60.0
        while not toks:
            assert time.monotonic() < deadline
            got, done, _ = c.poll(rid, cursor)
            toks.extend(got)
            cursor += len(got)
            assert not done, "stream finished before the kill window"
            time.sleep(0.01)
        rec = router._recs[rid]
        victim = next(d for i, d in enumerate(ds)
                      if f"d{i}" == rec.worker)
        ds.remove(victim)                     # teardown stops the rest
        victim.stop()                         # leave -> immediate eviction
        deadline = time.monotonic() + 20.0
        while len(router._members("decode")) != 1:
            assert time.monotonic() < deadline
            time.sleep(0.05)
        got = _drain_interleaved(c, {"s": rid}, cursors={"s": cursor})["s"]
        full = np.concatenate([np.asarray(toks, np.int32), got])
        np.testing.assert_array_equal(full,
                                      _ref(model, params, prompt, max_new))
        assert rec.reroutes == 1
        assert _counter(reg, "router.reroutes_total", reason="left") >= 1
        c.close()


def test_router_restart_replay_no_double_execution(model_and_params):
    """Kill and restart the ROUTER mid-stream on the same port: the
    client ladder resubmits the ORIGINAL request under the ORIGINAL
    submit_key and keeps its cursor; the worker's replay cache answers
    with the original rid — the engine admits nothing new, and the
    stream's tail re-emerges exactly at the cursor."""
    from paddle_tpu.serving import RouterClient, ServingRouter
    model, params = model_and_params
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    with _fleet(model, params, n_decode=1, port=port) as (router, ds, reg):
        c = RouterClient(*router.address, retries=2, retry_delay=0.05,
                         call_timeout=30.0)
        _throttle(ds)
        rs = np.random.RandomState(31)
        prompt, max_new = rs.randint(0, VOCAB, 9), 24
        gen = c.stream(prompt, max_new, poll_interval_s=0.01,
                       max_recoveries=100)
        toks = [next(gen)]                    # at least one token landed
        admitted = ds[0].engine._next_rid
        router.stop()
        router2 = ServingRouter("127.0.0.1", port, ttl=1.0,
                                scrape_interval_s=0.1).start()
        try:
            toks.extend(gen)                  # recovery ladder drains it
            np.testing.assert_array_equal(
                np.asarray(toks, np.int32),
                _ref(model, params, prompt, max_new))
            # no double execution: the replay cache answered the
            # resubmission — the engine never admitted a second record
            assert ds[0].engine._next_rid == admitted
            # ... and the recovery really ran through router2 (the
            # original submit_key re-registered there)
            assert len(router2._recs) == 1
        finally:
            router2.stop()
        c.close()


def test_replay_prefix_len_hardening_router_and_worker(model_and_params):
    """Satellite: a router-forwarded (or transport-retried) resubmission
    may not inflate its declared prefix_len past the recorded original —
    both the router AND the worker daemon refuse with the same structured
    invalid_argument."""
    model, params = model_and_params
    with _fleet(model, params, n_decode=1) as (router, ds, reg):
        prompt = list(range(1, 10))
        mc = MasterClient(*router.address)
        req = {"op": "route_submit", "prompt": prompt, "max_new": 2,
               "submit_key": "k-route", "prefix_len": 2}
        r1 = mc._call(dict(req))
        assert r1["ok"]
        replay = mc._call(dict(req, prefix_len=8))
        assert not replay["ok"]
        assert replay["code"] == "invalid_argument"
        assert "prefix_len" in replay["error"]
        same = mc._call(dict(req))            # honest replay: original rid
        assert same["ok"] and same["rid"] == r1["rid"]
        mc.close()
        # the worker daemon enforces the same rule on srv_submit replays
        mw = MasterClient(*ds[0].address)
        wreq = {"op": "srv_submit", "prompt": prompt, "max_new": 2,
                "submit_key": "k-worker", "prefix_len": 2}
        w1 = mw._call(dict(wreq))
        assert w1["ok"]
        wre = mw._call(dict(wreq, prefix_len=8))
        assert not wre["ok"] and wre["code"] == "invalid_argument"
        wsame = mw._call(dict(wreq))
        assert wsame["ok"] and wsame["rid"] == w1["rid"]
        assert "_prefix_len" not in wsame     # internal keys never leak
        mw.close()


def test_final_connection_error_reports_attempts_and_epoch():
    """Satellite: the final ConnectionError a client surfaces carries the
    attempt count and the last membership epoch it saw — the two numbers
    that distinguish 'router down' from 'I was partitioned and my view
    is stale'."""
    from paddle_tpu.serving import RouterClient, ServingRouter
    router = ServingRouter().start()
    c = RouterClient(*router.address, retries=3, retry_delay=0.01)
    c.serving_stats()                         # records the stamped epoch
    assert c.last_epoch is not None
    router.stop()
    with pytest.raises(ConnectionError) as ei:
        c.serving_stats()
    msg = str(ei.value)
    assert re.search(r"3 attempt\(s\)", msg), msg
    assert f"last seen membership epoch {c.last_epoch}" in msg
    c.close()


def test_chaos_route_submit_raise_is_structured_and_recoverable(
        model_and_params):
    """A ``route.submit`` raise (the placement hop dying) comes back as a
    structured error on a connection that keeps working; the retry
    places cleanly and streams exactly."""
    from paddle_tpu.serving import RouterClient
    model, params = model_and_params
    with _fleet(model, params, n_decode=1) as (router, ds, reg):
        c = RouterClient(*router.address, call_timeout=60.0)
        rs = np.random.RandomState(17)
        prompt = rs.randint(0, VOCAB, 7)
        plan = faults.FaultPlan().add("route.submit", "raise", nth=1)
        with plan.installed():
            with pytest.raises((ValueError, RuntimeError)):
                c.submit(prompt, 4)
            rid = c.submit(prompt, 4)         # second hit passes clean
            got = _drain_interleaved(c, {"s": rid})["s"]
        np.testing.assert_array_equal(got, _ref(model, params, prompt, 4))
        assert c.serving_stats()["n_decode_workers"] == 1
        c.close()


def test_chaos_adopt_raise_falls_back_and_streams_exact(model_and_params):
    """A ``srv.adopt`` raise (the decode hop dying mid-adopt) must not
    lose the request: the router's prefill forward fails over to direct
    decode-side prefill (degraded but correct) and the stream still
    bit-equals solo decode."""
    from paddle_tpu.serving import RouterClient
    model, params = model_and_params
    with _fleet(model, params, n_decode=1, prefill=True) as (router, ds,
                                                             reg):
        c = RouterClient(*router.address, call_timeout=60.0)
        rs = np.random.RandomState(23)
        prompt, max_new = rs.randint(0, VOCAB, 11), 12
        plan = faults.FaultPlan().add("srv.adopt", "raise", nth=1)
        with plan.installed():
            rid = c.submit_with_backoff(prompt, max_new)
            got = _drain_interleaved(c, {"s": rid})["s"]
        np.testing.assert_array_equal(got,
                                      _ref(model, params, prompt, max_new))
        assert _counter(reg, "router.reroutes_total",
                        reason="prefill_fallback") >= 1
        c.close()


def test_slow_ship_dominates_timeline_exemplar_and_trace_cli(
        model_and_params, capsys):
    """Acceptance (ISSUE 19): force the SHIP phase slow — a ``srv.ship``
    delay fault lands inside the measured ship window — and the stitched
    timeline names ship dominant, the router store captures it as a
    slow exemplar, and ``paddle_tpu obs trace --master`` prints the same
    attribution from the live aggregator."""
    from paddle_tpu import cli
    from paddle_tpu.serving import RouterClient
    model, params = model_and_params
    with _fleet(model, params, n_decode=1, prefill=True) as (router, ds,
                                                             reg):
        c = RouterClient(*router.address, call_timeout=60.0)
        rs = np.random.RandomState(29)
        # warm both pools first so compile walls don't drown the fault
        warm = c.submit_with_backoff(rs.randint(0, VOCAB, 11), 4)
        _drain_interleaved(c, {"w": warm})
        plan = faults.FaultPlan().add("srv.ship", "delay", delay_s=0.25)
        with plan.installed():
            rid = c.submit_with_backoff(rs.randint(0, VOCAB, 11), 8)
            _drain_interleaved(c, {"s": rid})
        key = router._recs[rid].key
        store = router.server.aggregator.requests
        deadline = time.monotonic() + 15.0
        while True:
            st = store.get(key)
            if st is not None and st["done"]:
                break
            assert time.monotonic() < deadline, \
                "slow-ship timeline never stitched done"
            time.sleep(0.05)
        assert st["dominant"] == "ship"
        assert st["breakdown"]["ship"] >= 0.25
        assert st["ttft_s"] >= 0.25           # the hop is IN the TTFT
        # the completed slow request is a window exemplar naming ship
        # (the warm request may out-score it with its compile wall)
        assert any(e["key"] == key and e["dominant"] == "ship"
                   for e in store.exemplars())
        # the live-aggregator CLI surface prints the same attribution
        host, port = router.address
        assert cli.main(["obs", "trace", key,
                         "--master", f"{host}:{port}"]) == 0
        out = capsys.readouterr().out
        assert f"request {key}" in out and "dominant=ship" in out
        assert "ship=" in out
        c.close()


def test_kill9_decode_worker_midstream_streams_exact(model_and_params,
                                                     tmp_path):
    """THE chaos bar: two decode workers (the victim a REAL subprocess
    `paddle_tpu serve --router ...`), kill -9 the one holding live
    streams mid-generation -> heartbeat eviction -> re-route onto the
    survivor -> every client stream completes with exactly the
    solo-decode token sequence: zero lost, zero duplicated tokens."""
    from paddle_tpu import obs
    from paddle_tpu.serving import (RouterClient, ServingDaemon,
                                    ServingEngine, ServingRouter)
    model, params = model_and_params
    reg = obs.MetricsRegistry()
    session = obs.ObsSession(registry=reg).install()
    router = ServingRouter(ttl=1.0, scrape_interval_s=0.1).start()
    host, port = router.address
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # "a-victim" sorts before "z-survivor": with zero history both score
    # 0 and the tiebreak sends the first streams at the victim
    # --segment 1: the victim emits ONE token per dispatch, so a long
    # budget is genuinely in flight for hundreds of milliseconds — the
    # kill lands mid-stream, not in a warm-cache instant finish
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu", "serve",
         "--vocab", str(VOCAB), "--d_model", str(D), "--n_heads", str(H),
         "--n_layers", str(L), "--max_len", str(MAX_LEN), "--seed", "0",
         "--slots", "2", "--segment", "1", "--page_block", "8",
         "--cache_bucket", "32",
         "--router", f"{host}:{port}", "--worker", "a-victim"],
        stdout=subprocess.PIPE, text=True, env=env, cwd=REPO)
    survivor = None
    try:
        line = proc.stdout.readline()
        assert re.match(r"SERVING \S+ \d+", line), line
        line = proc.stdout.readline()
        assert re.match(r"JOINED \S+ epoch \d+", line), line
        survivor = ServingDaemon(ServingEngine(
            model, params, slots=2, segment=8, page_block=8,
            cache_bucket=32)).start()
        survivor.join_router(router.address, "z-survivor", role="decode")
        deadline = time.monotonic() + 30.0
        while len(router._members("decode")) != 2:
            assert time.monotonic() < deadline
            time.sleep(0.05)

        c = RouterClient(host, port, call_timeout=120.0)
        rs = np.random.RandomState(41)
        reqs = {i: (rs.randint(0, VOCAB, n), g)
                for i, (n, g) in enumerate([(9, 96), (13, 80)])}
        work = {i: c.submit(p, g) for i, (p, g) in reqs.items()}
        # poll until at least one stream is MID-flight on the victim:
        # tokens delivered, not done, placed there — that is the stream
        # the kill must not lose a token of
        cursors = {i: 0 for i in work}
        toks = {i: [] for i in work}
        done_f = {i: False for i in work}
        deadline = time.monotonic() + 120.0
        while True:
            assert time.monotonic() < deadline, "no mid-flight stream"
            for i in work:
                if done_f[i]:
                    continue
                got, done, _ = c.poll(work[i], cursors[i])
                toks[i].extend(got)
                cursors[i] += len(got)
                done_f[i] = done
            on_victim = [i for i in work
                         if not done_f[i] and toks[i]
                         and router._recs[work[i]].worker == "a-victim"]
            if on_victim:
                break
            assert not all(done_f.values()), \
                "every stream finished before the kill window"
            time.sleep(0.002)

        # the scrape pump (0.1s) must have pulled the victim's timeline
        # before the kill erases its ledger — that pull is exactly what
        # lets the stitched timeline survive a kill -9
        store = router.server.aggregator.requests
        vkey = router._recs[work[on_victim[0]]].key
        deadline = time.monotonic() + 15.0
        while True:
            stv = store.get(vkey)
            if stv is not None and any(
                    e["phase"] == "first_token"
                    and e.get("worker") == "a-victim"
                    for e in stv["events"]):
                break
            assert time.monotonic() < deadline, \
                "victim's first_token never reached the router store"
            time.sleep(0.02)

        os.kill(proc.pid, signal.SIGKILL)     # no goodbye, no leave
        deadline = time.monotonic() + 30.0
        while len(router._members("decode")) != 1:   # TTL eviction
            assert time.monotonic() < deadline, "eviction never happened"
            time.sleep(0.05)

        live = {i: work[i] for i in work if not done_f[i]}
        rest = _drain_interleaved(c, live, cursors=cursors)
        for i, (p, g) in reqs.items():
            full = np.concatenate([np.asarray(toks[i], np.int32),
                                   rest.get(i, np.zeros(0, np.int32))])
            np.testing.assert_array_equal(full, _ref(model, params, p, g))
        assert _counter(reg, "router.reroutes_total", reason="evicted") \
            >= len(on_victim)

        # satellite (ISSUE 19): the re-routed stream's STITCHED timeline
        # holds both workers' phases — the dead victim's leg 0 (pulled by
        # the scrape pump before the kill) and the survivor's derived
        # {key}#r1 leg — with exactly one canonical first_token
        deadline = time.monotonic() + 15.0
        while True:
            st = store.get(vkey)
            if st is not None and st["done"] and 1 in st["legs"]:
                break
            assert time.monotonic() < deadline, \
                "re-routed leg never stitched done on the router store"
            time.sleep(0.05)
        assert st["legs"] == [0, 1] and st["reroutes"] == 1
        # the victim's identity survives its own death; the in-process
        # survivor's leg is stamped by whichever pump pushed it last
        # (the survivor scrape or the router's own-ledger push)
        assert "a-victim" in st["workers"] and len(st["workers"]) >= 2
        fts = [e for e in st["events"] if e["phase"] == "first_token"]
        assert len(fts) == 2
        assert [bool(e.get("resumed")) for e in fts] == [False, True]
        assert [e["leg"] for e in fts] == [0, 1]
        # phases from every seam survived: router admission + re-route,
        # the victim's admission/decode, the survivor's remainder
        assert {e["phase"] for e in st["events"]} >= {
            "admitted", "route", "reroute", "queued", "first_token",
            "decode", "done"}
        # no gap, no double count: TTFT is the FIRST leg's first token
        assert st["ttft_s"] is not None
        assert 0 < st["ttft_s"] <= st["wall_s"]
        assert fts[0]["t_unix"] - st["t0_unix"] == \
            pytest.approx(st["ttft_s"])
        c.close()
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=30)
        if survivor is not None:
            survivor.stop()
        router.stop()
        session.uninstall()
