"""Elastic cluster runtime (ISSUE 14): membership, fencing, chaos.

The contract under test (docs/design/elastic.md): workers register under
a heartbeat lease with fencing tokens; every membership change bumps an
epoch, re-buckets the in-flight shard queue, and barriers workers into a
state resync at the next step boundary; the parameter trajectory is
BYTE-STABLE across fleet shapes, kill -9s, rolling restarts, and master
restarts — because the master reduces the fixed shard partition in shard
order and applies the one optimizer update itself.

Thread workers and subprocess workers run the SAME code over the real TCP
RPC plane (tests/elastic_testnet.py is the shared workload); kill -9
chaos uses real OS processes. None of this needs cross-process
collectives, which is exactly the point — elasticity lives in the data
plane, so it works even where multiprocess-on-CPU XLA does not.
"""

import os
import signal
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from elastic_testnet import build
from paddle_tpu import nn, obs
from paddle_tpu.faults import FaultPlan
from paddle_tpu.runtime.master_service import (MasterClient, MasterServer,
                                               StaleMemberError)
from paddle_tpu.runtime.membership import (MembershipService,
                                           autoscale_recommendation)
from paddle_tpu.trainer.elastic import ElasticMaster, ElasticWorker

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER_SCRIPT = os.path.join(REPO, "tests", "elastic_worker_script.py")

LOSS_FN, PARAMS0, MK_OPT, BATCHES = build(steps=6)


def _flat(params):
    return {k: np.asarray(v) for k, v in
            nn.Module.named_parameters(jax.device_get(params))}


def _assert_trees_equal(a, b, *, exact=True):
    fa, fb = _flat(a), _flat(b)
    assert set(fa) == set(fb)
    for k in fa:
        if exact:
            np.testing.assert_array_equal(fa[k], fb[k], err_msg=k)
        else:
            np.testing.assert_allclose(fa[k], fb[k], rtol=2e-5, atol=2e-5,
                                       err_msg=k)


def _thread_worker(host, port, name, stop, mesh=None, layout=None):
    w = ElasticWorker(LOSS_FN, (host, port), worker=name, mesh=mesh,
                      layout=layout)
    t = threading.Thread(target=w.run, kwargs={"stop": stop}, daemon=True)
    t.start()
    return w, t


def _run_static_elastic(n_workers, batches, num_passes=1, shards=4):
    """Reference: a fixed fleet of thread workers, no chaos."""
    em = ElasticMaster(LOSS_FN, MK_OPT(), ttl=5.0, task_timeout_s=10.0,
                       shards_per_step=shards,
                       min_workers=n_workers).start()
    host, port = em.address
    stop = threading.Event()
    pairs = [_thread_worker(host, port, f"static{i}", stop)
             for i in range(n_workers)]
    try:
        params, _, loss = em.fit(batches, PARAMS0(), num_passes=num_passes,
                                 progress_timeout=60.0)
    finally:
        stop.set()
        for _, t in pairs:
            t.join(timeout=10)
        em.stop()
    return params, loss


# ---------------------------------------------------------------------------
# membership service (in-process dispatch, fake clock — no sleeps)
# ---------------------------------------------------------------------------

def test_membership_join_heartbeat_expire_epoch():
    srv = MasterServer()
    clock = [0.0]
    ms = MembershipService(ttl=10.0, clock=lambda: clock[0]).attach(srv)
    r = srv._dispatch({"op": "mbr_join", "worker": "a",
                       "caps": {"devices": 2}})
    assert r["ok"] and r["epoch"] == 1 and r["ttl"] == 10.0
    tok_a = r["member_token"]
    r2 = srv._dispatch({"op": "mbr_join", "worker": "b"})
    assert r2["epoch"] == 2
    view = srv._dispatch({"op": "mbr_view"})
    assert [m["worker"] for m in view["members"]] == ["a", "b"]
    assert view["epoch"] == 2 and view["recommendation"]["action"] in (
        "join", "leave", "hold")
    # heartbeat keeps the lease alive across the clock advance
    clock[0] = 8.0
    assert srv._dispatch({"op": "mbr_heartbeat", "worker": "a",
                          "member_token": tok_a})["ok"]
    clock[0] = 15.0          # b (deadline 10) lapsed; a (deadline 18) lives
    assert ms.expire() == ["b"]
    assert ms.epoch == 3
    assert [m["worker"] for m in ms.members()] == ["a"]
    # the evicted worker's heartbeat is refused with a structured code
    r3 = srv._dispatch({"op": "mbr_heartbeat", "worker": "b",
                        "member_token": r2["member_token"]})
    assert not r3["ok"] and r3["code"] == "unknown_member"
    assert r3["epoch"] == 3
    # graceful leave bumps the epoch once more
    assert srv._dispatch({"op": "mbr_leave", "worker": "a",
                          "member_token": tok_a})["ok"]
    assert ms.epoch == 4 and ms.members() == []


def test_membership_rejoin_fences_old_incarnation():
    srv = MasterServer()
    ms = MembershipService(ttl=10.0).attach(srv)
    t1, e1 = ms.join("w")
    t2, e2 = ms.join("w")           # the newer incarnation wins
    assert t2 > t1 and e2 == e1 + 1
    stale = srv._dispatch({"op": "mbr_heartbeat", "worker": "w",
                           "member_token": t1})
    assert not stale["ok"] and stale["code"] == "stale_member"
    assert srv._dispatch({"op": "mbr_heartbeat", "worker": "w",
                          "member_token": t2})["ok"]
    # epoch fencing: an older view's submission is refused, current passes
    err = ms.fence(e1)
    assert err["code"] == "stale_epoch" and err["epoch"] == e2
    assert ms.fence(e2) is None and ms.fence(None) is None


def test_elastic_grad_submission_fencing():
    em = ElasticMaster(LOSS_FN, MK_OPT())
    join = em.server._dispatch({"op": "mbr_join", "worker": "w"})
    tok, epoch = join["member_token"], join["epoch"]
    # no member / wrong token fence before anything else
    r = em.server._dispatch({"op": "ela_grad", "worker": "ghost",
                             "member_token": 1, "epoch": epoch})
    assert r["code"] == "unknown_member"
    r = em.server._dispatch({"op": "ela_grad", "worker": "w",
                             "member_token": tok + 5, "epoch": epoch})
    assert r["code"] == "stale_member"
    # stale epoch: join another worker (epoch moves), then submit old
    em.server._dispatch({"op": "mbr_join", "worker": "w2"})
    r = em.server._dispatch({"op": "ela_grad", "worker": "w",
                             "member_token": tok, "epoch": epoch})
    assert r["code"] == "stale_epoch" and r["epoch"] == epoch + 1
    # current epoch but no step collecting -> structured stale_step
    r = em.server._dispatch({"op": "ela_grad", "worker": "w",
                             "member_token": tok, "epoch": epoch + 1,
                             "pass": 0, "step": 0, "shard": 0})
    assert r["code"] == "stale_step"
    # a fence-refused submission must requeue its task immediately — NOT
    # strand it in pending until the dispatch timeout (review fix): the
    # shard is still needed and a current worker must get it now
    em.server.master.set_dataset(["shard-payload"])
    tid, _ = em.server.master.get_task()
    assert em.server.master.stats()[:2] == (0, 1)      # dispatched
    r = em.server._dispatch({"op": "ela_grad", "worker": "w",
                             "member_token": tok, "epoch": epoch,
                             "task_id": tid, "pass": 0, "step": 0,
                             "shard": 0, "grad": ""})
    assert r["code"] == "stale_epoch"
    assert em.server.master.stats()[:2] == (1, 0)      # back in todo


def test_mesh_worker_handles_uneven_shard():
    """A worker with a local data mesh must compute a ragged tail shard
    (rows not divisible by the axis) unsharded instead of crashing on the
    placement error (review fix): sharding is an optimization."""
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.trainer.elastic import _pack_arrays
    mesh = make_mesh(data=2)
    w = ElasticWorker(LOSS_FN, ("127.0.0.1", 1), mesh=mesh)
    w._params = jax.device_put(PARAMS0())
    rs = np.random.RandomState(0)
    for rows in (7, 8):                 # ragged tail + divisible shard
        x = rs.randn(rows, 8).astype(np.float32)
        y = rs.randint(0, 2, rows).astype(np.int32)
        loss, grads = w._grad_of({"batch": _pack_arrays([x, y])})
        assert np.isfinite(loss) and jax.tree_util.tree_leaves(grads)


def test_autoscale_recommendation_branches():
    r = autoscale_recommendation(members=0, todo=3, pending=0)
    assert r["action"] == "join"
    r = autoscale_recommendation(members=2, todo=9, pending=1)
    assert r["action"] == "join" and r["backlog_per_worker"] == 5.0
    r = autoscale_recommendation(
        members=3, todo=0, pending=0,
        samples=[{"name": "goodput.ratio", "value": 0.1,
                  "labels": {"worker": "a"}}])
    assert r["action"] == "leave" and r["goodput_ratio"] == 0.1
    r = autoscale_recommendation(
        members=2, todo=0, pending=0,
        samples=[{"name": "data.giveups_total", "value": 4.0}])
    assert r["action"] == "leave" and "starvation" in r["reason"]
    r = autoscale_recommendation(members=2, todo=2, pending=0)
    assert r["action"] == "hold"
    # a lone busy worker is never scaled away
    r = autoscale_recommendation(
        members=1, todo=0, pending=0,
        samples=[{"name": "goodput.ratio", "value": 0.05}])
    assert r["action"] == "hold"


# ---------------------------------------------------------------------------
# MasterClient._call reconnect hardening (ISSUE 14 satellite)
# ---------------------------------------------------------------------------

def test_client_fails_fast_on_structured_fence():
    srv = MasterServer()
    calls = []

    def fenced(req):
        calls.append(1)
        return {"ok": False, "code": "stale_epoch",
                "error": "request epoch 1 != current 7", "epoch": 7}

    # the op name matters: only mbr_*/ela_* replies stamp last_epoch
    # (the built-in "stats" op answers a TaskMaster epoch, not ours)
    srv.register_op("ela_fence", fenced)
    srv.start()
    try:
        c = MasterClient(*srv.address)
        with pytest.raises(StaleMemberError) as ei:
            c._call({"op": "ela_fence"})
        assert ei.value.code == "stale_epoch" and ei.value.epoch == 7
        assert len(calls) == 1          # no reconnect budget burned
        assert c.last_epoch == 7        # the view rode the refusal
        # ...and a stats reply does NOT overwrite it with the queue epoch
        c._call({"op": "stats"})
        assert c.last_epoch == 7
        c.close()
    finally:
        srv.stop()


def test_client_retries_refused_and_reports_attempts_and_epoch():
    srv = MasterServer()
    MembershipService(ttl=10.0).attach(srv)
    srv.start()
    host, port = srv.address
    c = MasterClient(host, port, retries=3, retry_delay=0.01)
    r = c._call({"op": "mbr_join", "worker": "probe"})
    assert r["ok"] and c.last_epoch == 1
    srv.stop()
    t0 = time.monotonic()
    with pytest.raises(ConnectionError) as ei:
        c._call({"op": "mbr_view"})
    msg = str(ei.value)
    # connection-refused was retried (3 attempts), and the final error
    # names both the attempt count and the last membership view we held
    assert "3 attempt(s)" in msg
    assert "last seen membership epoch 1" in msg
    assert time.monotonic() - t0 < 10.0
    c.close()


# ---------------------------------------------------------------------------
# elastic training: equivalence + chaos
# ---------------------------------------------------------------------------

def _sequential_reference(batches, num_passes=1):
    opt = MK_OPT()
    params = jax.device_put(PARAMS0())
    state = opt.init(params)
    upd = jax.jit(lambda g, s, p: opt.update(g, s, p))
    vg = jax.jit(jax.value_and_grad(LOSS_FN))
    loss = float("nan")
    for _ in range(num_passes):
        for bx, by in batches:
            loss, grads = vg(params, bx, by)
            params, state = upd(jax.device_get(grads), state, params)
    return params, float(loss)


def test_elastic_two_workers_matches_sequential():
    """The DP math: shard-ordered weighted reduce == whole-batch gradient
    (to f32 reduction noise), across two real RPC workers."""
    params, loss = _run_static_elastic(2, BATCHES, num_passes=2)
    ref_params, ref_loss = _sequential_reference(BATCHES, num_passes=2)
    _assert_trees_equal(params, ref_params, exact=False)
    assert abs(loss - ref_loss) < 1e-4


@pytest.mark.chaos
def test_kill9_worker_mid_pass_matches_static_run(tmp_path):
    """THE acceptance e2e: 3 subprocess workers under live traffic,
    kill -9 one mid-pass -> heartbeat eviction bumps the epoch, the dead
    worker's in-flight shard re-buckets onto the survivors (dispatch
    timeout deliberately too long to help), the pass completes, and the
    final parameters are BYTE-IDENTICAL to a static 2-worker run's."""
    batches = build(steps=8)[3]
    reg = obs.MetricsRegistry()
    with obs.ObsSession(registry=reg).installed():
        em = ElasticMaster(LOSS_FN, MK_OPT(), ttl=1.2,
                           task_timeout_s=60.0,   # eviction must re-bucket
                           shards_per_step=4, min_workers=3).start()
        host, port = em.address
        env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
        env["JAX_PLATFORMS"] = "cpu"
        procs = [subprocess.Popen(
            [sys.executable, WORKER_SCRIPT, host, str(port), f"kw{i}",
             "180"], env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT) for i in range(3)]
        state = {"killed": False, "epoch_at_kill": None}

        def killer():
            # SIGKILL kw0 the moment it HOLDS an in-flight shard of a
            # step past the first — the step then cannot complete until
            # the eviction re-buckets that shard onto the survivors
            # (task_timeout_s=60 rules the timeout path out)
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                with em._mu:
                    holding = "kw0" in em._assigned.values()
                    step = em._step
                if step >= 1 and holding:
                    state["epoch_at_kill"] = em.membership.epoch
                    os.kill(procs[0].pid, signal.SIGKILL)
                    state["killed"] = True
                    return
                time.sleep(0.001)

        kt = threading.Thread(target=killer, daemon=True)
        kt.start()
        try:
            params, _, loss = em.fit(batches, PARAMS0(), num_passes=1,
                                     progress_timeout=90.0)
            kt.join(timeout=10)
        finally:
            logs = []
            for p in procs[1:]:
                try:
                    out, _ = p.communicate(timeout=30)
                    logs.append(out.decode(errors="replace"))
                except subprocess.TimeoutExpired:
                    p.kill()
                    logs.append("survivor hung")
            procs[0].wait()
            em.stop()
        assert state["killed"]
        # eviction (not graceful leave) bumped the epoch mid-pass
        assert em.membership.epoch > state["epoch_at_kill"], logs
        assert reg.counter("cluster.leaves_total").get(
            reason="evicted") >= 1
        # the pass was finished by exactly the 2 survivors: both (and only
        # they) left through the done path. Counted once they are reaped —
        # len(members()) right after fit() races with their leaving.
        assert reg.counter("cluster.leaves_total").get(
            reason="graceful") == 2, logs
        # the dead worker's in-flight shard re-bucketed via the epoch
        # change (task_timeout_s=60 rules out the timeout path)
        assert reg.counter("cluster.rebucket_tasks_total").get() >= 1
        # survivors exited through the done/leave path
        assert all(p.returncode == 0 for p in procs[1:]), logs

    static_params, static_loss = _run_static_elastic(2, batches)
    _assert_trees_equal(params, static_params, exact=True)
    assert loss == static_loss


@pytest.mark.chaos
def test_rolling_restart_completes_pass_byte_stably():
    """Leave -> rejoin every worker, one at a time, at successive step
    boundaries (the barrier semantics: the cycle runs between updates).
    The pass is never lost or restarted, every rejoin re-fetches and
    re-places the state, and the result is byte-identical to an
    undisturbed fleet's."""
    batches = build(steps=6)[3]
    reg = obs.MetricsRegistry()
    with obs.ObsSession(registry=reg).installed():
        em = ElasticMaster(LOSS_FN, MK_OPT(), ttl=5.0, task_timeout_s=10.0,
                           shards_per_step=4, min_workers=3).start()
        host, port = em.address
        fleet = {}
        for i in range(3):
            stop = threading.Event()
            w, t = _thread_worker(host, port, f"rw{i}", stop)
            fleet[f"rw{i}"] = (w, t, stop)

        def cycle(name):
            w, t, stop = fleet[name]
            stop.set()                      # graceful leave on the way out
            t.join(timeout=10)
            assert not t.is_alive()
            stop2 = threading.Event()
            w2, t2 = _thread_worker(host, port, name, stop2)
            fleet[name] = (w2, t2, stop2)

        def on_step(pass_id, step, loss):
            if step in (1, 2, 3):           # between-update barrier
                cycle(f"rw{step - 1}")

        em.on_step = on_step
        try:
            params, _, loss = em.fit(batches, PARAMS0(), num_passes=1,
                                     progress_timeout=60.0)
        finally:
            for _, t, stop in fleet.values():
                stop.set()
            for _, t, stop in fleet.values():
                t.join(timeout=10)
            em.stop()
        # 3 joins + 3 cycles of (leave + join) = epoch >= 9, no evictions
        assert em.membership.epoch >= 9
        assert reg.counter("cluster.leaves_total").get(
            reason="graceful") >= 3
        assert reg.counter("cluster.joins_total").get() >= 6
        assert reg.counter("cluster.resyncs_total").get() >= 3

    static_params, static_loss = _run_static_elastic(3, batches)
    _assert_trees_equal(params, static_params, exact=True)
    assert loss == static_loss


@pytest.mark.chaos
def test_heartbeat_fault_evicts_and_worker_rejoins():
    """faults-plane chaos on the new ``mbr.heartbeat`` site: injected
    heartbeat failures starve the lease -> the master evicts the worker
    and bumps the epoch; the keeper's next good heartbeat comes back
    ``unknown_member`` and triggers an automatic re-join; the pass
    completes on the re-registered worker."""
    batches = build(steps=10)[3]
    reg = obs.MetricsRegistry()
    plan = FaultPlan(seed=3).add("mbr.heartbeat", "raise", nth=2, count=4)
    with obs.ObsSession(registry=reg).installed(), plan.installed():
        em = ElasticMaster(LOSS_FN, MK_OPT(), ttl=0.75,
                           task_timeout_s=30.0, shards_per_step=2,
                           min_workers=1).start()
        host, port = em.address
        stop = threading.Event()
        w, t = _thread_worker(host, port, "hbw", stop)
        em.on_step = lambda p, s, l: time.sleep(0.2)   # pass spans the chaos
        try:
            params, _, loss = em.fit(batches, PARAMS0(), num_passes=1,
                                     progress_timeout=60.0)
        finally:
            stop.set()
            t.join(timeout=15)
            em.stop()
    assert plan.fired and plan.fired[0][0] == "mbr.heartbeat"
    assert reg.counter("faults.injected_total").get(
        site="mbr.heartbeat", action="raise") >= 1
    # evicted, then re-registered (join counted twice), epoch moved twice+
    assert reg.counter("cluster.leaves_total").get(reason="evicted") >= 1
    assert reg.counter("cluster.joins_total").get() >= 2
    assert em.membership.epoch >= 3
    assert np.isfinite(loss)


@pytest.mark.chaos
def test_master_restart_snapshot_restore_resumes_pass(tmp_path):
    """Master dies mid-pass and restarts on the same port from its
    crash-safe snapshot: workers ride the reconnect budget through the
    refused window, re-register (unknown_member -> re-join), and the SAME
    pass resumes at the snapshotted step — final state byte-identical to
    an uninterrupted run."""
    batches = build(steps=6)[3]
    snap = str(tmp_path / "elastic_snap")
    em1 = ElasticMaster(LOSS_FN, MK_OPT(), ttl=5.0, task_timeout_s=10.0,
                        shards_per_step=4, min_workers=2,
                        snapshot_dir=snap).start()
    host, port = em1.address
    stop = threading.Event()
    pairs = [_thread_worker(host, port, f"mrw{i}", stop) for i in range(2)]
    try:
        em1.fit(batches, PARAMS0(), num_passes=1, max_steps=2,
                progress_timeout=60.0)
        epoch1 = em1.membership.epoch
        em1.stop()                 # connections sever; workers retry
        em2 = ElasticMaster(LOSS_FN, MK_OPT(), host=host, port=port,
                            ttl=5.0, task_timeout_s=10.0,
                            shards_per_step=4, min_workers=2,
                            snapshot_dir=snap).start()
        # restored mid-pass position + persisted epoch (fencing stays
        # monotonic across the restart), members re-register fresh
        assert (em2._pass, em2._step) == (0, 2)
        assert em2.membership.epoch >= epoch1
        params, _, loss = em2.fit(batches, num_passes=1,
                                  progress_timeout=90.0)
        em2.stop()
    finally:
        stop.set()
        for _, t in pairs:
            t.join(timeout=15)
    ref_params, ref_loss = _run_static_elastic(2, batches)
    _assert_trees_equal(params, ref_params, exact=True)
    assert loss == ref_loss


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

ELASTIC_CFG = """
import os, sys
sys.path.insert(0, {tests_dir!r})
from elastic_testnet import build

def elastic_workload():
    loss_fn, params0, mk_opt, batches = build(steps=4)
    return {{"loss_fn": loss_fn, "params": params0(),
             "optimizer": mk_opt(), "batches": batches}}
"""


@pytest.mark.slow
def test_train_elastic_cli_smoke(tmp_path):
    """`paddle_tpu train --elastic master` + a `--elastic worker`
    subprocess complete one pass over the wire and both exit 0."""
    import socket

    from paddle_tpu.cli import main as cli_main
    cfg = tmp_path / "elastic_cfg.py"
    cfg.write_text(ELASTIC_CFG.format(
        tests_dir=os.path.join(REPO, "tests")))
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    worker = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu", "train", "--config", str(cfg),
         "--elastic", "worker", "--master_addr", f"127.0.0.1:{port}",
         "--worker_id", "cli-w0"],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT)
    try:
        rc = cli_main(["train", "--config", str(cfg), "--elastic", "master",
                       "--master_addr", f"127.0.0.1:{port}",
                       "--min_workers", "1", "--num_passes", "1"])
        assert rc == 0
        out, _ = worker.communicate(timeout=60)
        assert worker.returncode == 0, out.decode(errors="replace")
        assert b"job done: True" in out
    finally:
        if worker.poll() is None:
            worker.kill()
