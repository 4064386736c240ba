"""The serving daemon over the native RPC plane (serving/daemon.py):
srv_submit/srv_poll/srv_cancel ride the ptms_set_fallback unknown-op path,
backpressure is a STRUCTURED reply (never a dead connection), cancel frees
pages, and the engine's TTFT/TPOT histograms surface through the
master-side cluster aggregator (obs_stats) — the ROADMAP item 2
acceptance surface, end to end."""

import os
import re
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.runtime.master_service import MasterClient, MasterServer

VOCAB, D, H, L, MAX_LEN = 97, 32, 4, 2, 128


@pytest.fixture(scope="module")
def model_and_params():
    from paddle_tpu.models import TransformerLM
    model = TransformerLM(VOCAB, d_model=D, n_heads=H, n_layers=L,
                          max_len=MAX_LEN)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


@pytest.fixture()
def daemon(model_and_params):
    from paddle_tpu import obs
    from paddle_tpu.serving import ServingDaemon, ServingEngine
    model, params = model_and_params
    reg = obs.MetricsRegistry()
    session = obs.ObsSession(registry=reg).install()
    eng = ServingEngine(model, params, slots=2, segment=8, page_block=8,
                        cache_bucket=32, queue_cap=3)
    d = ServingDaemon(eng, obs_interval_s=0.1).start()
    try:
        yield d, reg
    finally:
        d.stop()
        session.uninstall()


def _drain(client, rid, timeout=60.0):
    deadline = time.monotonic() + timeout
    cursor, toks = 0, []
    while True:
        got, done, reason = client.poll(rid, cursor)
        toks.extend(got)
        cursor += len(got)
        if done:
            return np.asarray(toks, np.int32), reason
        assert time.monotonic() < deadline, "poll drain timed out"
        time.sleep(0.02)


def test_daemon_e2e_exact_streaming_and_slo_metrics(daemon,
                                                    model_and_params):
    """Submit/poll over the wire: greedy tokens exactly equal solo decode,
    stats serve, and the TTFT/TPOT histograms appear in the aggregated
    obs_stats view (worker label 'serving')."""
    from paddle_tpu.serving import ServingClient
    model, params = model_and_params
    d, reg = daemon
    c = ServingClient(*d.address)
    rs = np.random.RandomState(5)
    prompt = rs.randint(0, VOCAB, 9)
    out = c.generate(prompt, 20)
    want = np.asarray(model.generate_cached(
        params, jnp.asarray(prompt[None]), steps=20))[0, 9:]
    np.testing.assert_array_equal(out, want)

    st = c.serving_stats()
    assert st["pages_total"] > 0 and st["queue_depth"] == 0
    assert st["rpc_conns"] >= 1          # we are connected right now

    # the daemon pushes the engine registry into the master-side
    # aggregator; obs_stats then serves the SLO pair fleet-style
    deadline = time.monotonic() + 10.0
    names = set()
    mc = MasterClient(*d.address)
    while time.monotonic() < deadline:
        workers, samples = mc.obs_stats()
        names = {s["name"] for s in samples}
        if "serving.ttft_seconds" in names and \
                "serving.tpot_seconds" in names:
            break
        time.sleep(0.1)
    assert "serving.ttft_seconds" in names, names
    assert "serving.tpot_seconds" in names
    assert "serving" in workers
    c.close()
    mc.close()


def test_daemon_backpressure_structured_and_cancel_frees_pages(daemon):
    """Flood past queue_cap: srv_submit answers the structured overloaded
    reply (code + retry_after_s) on a connection that KEEPS working;
    submit_with_backoff eventually lands; cancel frees pages."""
    from paddle_tpu.serving import Overloaded, ServingClient
    d, _ = daemon
    c = ServingClient(*d.address)
    rs = np.random.RandomState(7)
    rids, refused = [], 0
    for _ in range(12):
        try:
            rids.append(c.submit(rs.randint(0, VOCAB, 5), 80))
        except Overloaded as e:
            refused += 1
            assert e.retry_after_s > 0
    assert refused > 0 and rids             # both sides of the cap seen
    # the SAME connection still serves (structured reply, not a hangup)
    assert c.serving_stats()["queue_depth"] > 0
    # backoff-submit rides out the overload window
    late = c.submit_with_backoff(rs.randint(0, VOCAB, 5), 3)
    # cancel everything in flight; pages must all come home
    for rid in rids:
        c.cancel(rid)
    _drain(c, late)
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        st = c.serving_stats()
        if st["pages_used"] == 0 and st["slots_live"] == 0:
            break
        time.sleep(0.05)
    assert st["pages_used"] == 0 and st["pages_reserved"] == 0
    c.close()


def test_daemon_structured_validation_errors(daemon):
    """Malformed submissions come back as code=invalid_argument replies
    (raised client-side as ValueError), unknown rids as not_found."""
    from paddle_tpu.serving import ServingClient
    d, _ = daemon
    c = ServingClient(*d.address)
    with pytest.raises(ValueError, match="empty prompt"):
        c.submit([], 5)
    with pytest.raises(ValueError, match="max_new"):
        c.submit([3, 5], 0)
    with pytest.raises(ValueError, match="max_len"):
        c.submit(list(range(MAX_LEN)), 5)
    with pytest.raises(KeyError):
        c.poll(999999)
    c.close()


def test_submit_idempotent_across_transport_retry(daemon):
    """srv_submit rides the transport's at-least-once retry: replaying the
    SAME submit_key (a lost-reply resend) returns the original rid instead
    of admitting a duplicate generation."""
    d, _ = daemon
    mc = MasterClient(*d.address)
    req = {"op": "srv_submit", "prompt": [3, 5, 7], "max_new": 4,
           "submit_key": "retry-test-key"}
    r1 = mc._call(dict(req))
    r2 = mc._call(dict(req))            # the replay
    assert r1["ok"] and r2["ok"] and r1["rid"] == r2["rid"]
    fresh = mc._call({"op": "srv_submit", "prompt": [3, 5, 7],
                      "max_new": 4, "submit_key": "another-key"})
    assert fresh["rid"] != r1["rid"]
    mc.close()


def test_submit_replay_during_drain_returns_original_rid(daemon):
    """A lost-reply replay of an ALREADY-admitted submit must learn its
    rid even while the daemon is draining — its finished result is exactly
    what the drain window waits for the client to collect. Only NEW work
    gets the structured draining refusal."""
    d, _ = daemon
    req = {"op": "srv_submit", "prompt": [3, 5, 7], "max_new": 4,
           "submit_key": "drain-replay-key"}
    first = d._srv_submit(dict(req))
    assert first["ok"]
    d._draining.set()
    try:
        replay = d._srv_submit(dict(req))
        assert replay.get("ok") and replay["rid"] == first["rid"]
        fresh = d._srv_submit({"op": "srv_submit", "prompt": [3, 5, 7],
                               "max_new": 4, "submit_key": "drain-new-key"})
        assert not fresh["ok"] and fresh["code"] == "overloaded"
    finally:
        d._draining.clear()


def test_abandoned_stream_cancels_server_side(daemon):
    """Breaking out of stream() mid-generation must cancel the request on
    the server — an abandoned consumer must not pin its slot and reserved
    pages for the rest of the budget."""
    from paddle_tpu.serving import ServingClient
    d, _ = daemon
    c = ServingClient(*d.address)
    gen = c.stream([3, 5, 7], 10_000)   # budget far beyond the test
    next(gen)                            # at least one token arrived
    gen.close()                          # GeneratorExit -> srv_cancel
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        st = c.serving_stats()
        if st["slots_live"] == 0 and st["pages_used"] == 0:
            break
        time.sleep(0.05)
    assert st["slots_live"] == 0 and st["pages_used"] == 0, st
    with d.engine._lock:
        reasons = [r.reason for r in d.engine._recs.values() if r.done]
    assert "cancelled" in reasons        # freed by the cancel, not by length
    c.close()


def test_stream_surfaces_cancellation(daemon):
    """A server-side cancel must raise out of stream()/generate(), never
    read as a short-but-normal completion. The cancel fires synchronously
    at submit time (before the scheduler can touch the queued record) —
    a polling killer thread used to lose the race on a compile-warm
    session, where all ~12 segments finish inside one 50 ms poll."""
    from paddle_tpu.serving import ServingClient
    d, _ = daemon
    c = ServingClient(*d.address)
    orig = d.engine.submit

    def submit_then_cancel(*a, **kw):
        rid = orig(*a, **kw)
        assert d.engine.cancel(rid) is True   # queued -> cancel always wins
        return rid

    d.engine.submit = submit_then_cancel
    try:
        with pytest.raises(RuntimeError, match="cancelled"):
            list(c.stream(np.random.RandomState(3).randint(0, VOCAB, 5),
                          100))
    finally:
        d.engine.submit = orig
    c.close()


def test_stop_does_not_deadlock_with_conn_counting_handler():
    """Regression: stop() used to hold _srv_lock across ptms_stop (which
    drains handler threads); a handler inside active_connections() —
    exactly what srv_stats does — blocked on that lock forever, hanging
    every daemon shutdown that raced a stats poll."""
    import threading

    srv = MasterServer()
    entered = threading.Event()

    def slow_conn_handler(req):
        entered.set()
        time.sleep(0.3)                  # let stop() start first
        return {"ok": True, "conns": srv.active_connections()}

    srv.register_op("conn_probe", slow_conn_handler)
    srv.start()
    mc = MasterClient(*srv.address)
    def probe():
        try:
            mc._call({"op": "conn_probe"})
        except ConnectionError:
            pass                         # stop() may win the race; fine

    t = threading.Thread(target=probe, daemon=True)
    t.start()
    assert entered.wait(10.0)
    stopper = threading.Thread(target=srv.stop, daemon=True)
    stopper.start()
    stopper.join(timeout=20.0)
    assert not stopper.is_alive(), "MasterServer.stop() deadlocked"
    mc.close()
    t.join(timeout=10.0)


def test_register_op_rejects_shadowing(model_and_params):
    """The op table is a wire contract: built-ins and earlier
    registrations cannot be silently replaced."""
    srv = MasterServer()
    srv.register_op("my_op", lambda req: {"ok": True})
    with pytest.raises(ValueError, match="already registered"):
        srv.register_op("my_op", lambda req: {"ok": True})
    with pytest.raises(ValueError, match="already registered"):
        srv.register_op("get_task", lambda req: {"ok": True})


@pytest.mark.slow
def test_serve_cli_subprocess_e2e(tmp_path):
    """`paddle_tpu serve` as a real subprocess daemon: parseable SERVING
    line, exact greedy over the wire against the same seed's weights,
    graceful SIGTERM with an obs dump."""
    from paddle_tpu.models import TransformerLM
    from paddle_tpu.serving import ServingClient
    obs_out = str(tmp_path / "serve_obs.jsonl")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu", "serve",
         "--vocab", str(VOCAB), "--d_model", str(D), "--n_heads", str(H),
         "--n_layers", str(L), "--max_len", str(MAX_LEN), "--seed", "0",
         "--slots", "2", "--segment", "8", "--page_block", "8",
         "--cache_bucket", "32", "--obs_out", obs_out],
        stdout=subprocess.PIPE, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    try:
        line = p.stdout.readline()
        m = re.match(r"SERVING (\S+) (\d+)", line)
        assert m, f"bad address line: {line!r}"
        host, port = m.group(1), int(m.group(2))
        c = ServingClient(host, port, call_timeout=60.0)
        model = TransformerLM(VOCAB, d_model=D, n_heads=H, n_layers=L,
                              max_len=MAX_LEN)
        params = model.init(jax.random.PRNGKey(0))
        prompt = np.random.RandomState(1).randint(0, VOCAB, 11)
        out = c.generate(prompt, 15)
        want = np.asarray(model.generate_cached(
            params, jnp.asarray(prompt[None]), steps=15))[0, 11:]
        np.testing.assert_array_equal(out, want)
        c.close()
    finally:
        p.send_signal(signal.SIGTERM)
        assert p.wait(timeout=60) == 0
    assert os.path.exists(obs_out)
