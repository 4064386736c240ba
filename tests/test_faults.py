"""Chaos suite (ISSUE 2): deterministic fault injection against the
checkpoint, RPC, lease and reader layers.

Every test here follows the same discipline:
* failures come from :mod:`paddle_tpu.faults` (seeded, Nth-hit exact) or a
  real SIGKILL/SIGTERM — never from timing races;
* retry/backoff time is driven through fake clocks where possible, so the
  whole file stays inside the tier-1 60s budget;
* the assertion is always *recovery*, not just the failure: training
  resumes byte-identically, the previous good pass survives, the deposed
  holder's write is refused.
"""

import os
import signal
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import faults
from paddle_tpu.data.chunks import (_Starved, chunk_reader, cloud_reader,
                                    dump_to_chunks)
from paddle_tpu.data.prefetch import DoubleBuffer
from paddle_tpu.optimizer import SGD
from paddle_tpu.runtime.coord import CoordServer, NetworkFencedStore, \
    NetworkLease, _CoordClient
from paddle_tpu.runtime.lease import FencedFile, FileLease, LeaseKeeper
from paddle_tpu.trainer import Trainer
from paddle_tpu.trainer.checkpoint import (COMPLETE_MANIFEST, latest_pass,
                                           load_checkpoint, pass_dir,
                                           save_checkpoint, verify_checkpoint)
from paddle_tpu.utils.retry import RetryBudgetExceeded, RetryPolicy

pytestmark = pytest.mark.chaos

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- deterministic tiny training problem ---------------------------------------

def _make_batches(n=4, bs=8, d=4, seed=0):
    rs = np.random.RandomState(seed)
    return [(rs.randn(bs, d).astype(np.float32),
             rs.randn(bs, 1).astype(np.float32)) for _ in range(n)]


def _loss(params, x, y):
    return jnp.mean((x @ params["w"] + params["b"] - y) ** 2)


def _init(d=4):
    return {"w": np.zeros((d, 1), np.float32), "b": np.zeros(1, np.float32)}


def _param_bytes(params):
    return b"".join(np.asarray(jax.device_get(leaf)).tobytes()
                    for leaf in jax.tree_util.tree_leaves(params))


def _fake_time():
    """(sleep, clock) pair over a virtual clock — no real sleeping."""
    t = [0.0]

    def sleep(s):
        t[0] += s

    return sleep, (lambda: t[0]), t


# -- FaultPlan semantics -------------------------------------------------------

def test_fault_plan_nth_count_window():
    plan = faults.FaultPlan()
    plan.add("rpc.send", "truncate", nth=2, count=2, truncate_to=3)
    with plan.installed():
        out = [faults.filter_bytes("rpc.send", b"abcdef") for _ in range(4)]
    assert out == [b"abcdef", b"abc", b"abc", b"abcdef"]
    assert plan.fired == [("rpc.send", 2, "truncate"),
                          ("rpc.send", 3, "truncate")]
    assert plan.hits["rpc.send"] == 4


def test_fault_plan_zero_cost_when_uninstalled():
    plan = faults.FaultPlan()
    plan.add("rpc.send", "raise")
    # not installed: hooks are no-ops and count nothing
    assert faults.filter_bytes("rpc.send", b"x") == b"x"
    faults.fire("rpc.recv")
    assert not faults.is_active()
    assert plan.hits == {}


def test_fault_plan_exclusive_install_and_bad_site():
    with pytest.raises(ValueError, match="unknown injection site"):
        faults.Fault("not.a.site")
    a, b = faults.FaultPlan(), faults.FaultPlan()
    with a.installed():
        with pytest.raises(RuntimeError, match="already installed"):
            b.install()
    assert not faults.is_active()


def test_fault_corrupt_is_seed_deterministic():
    outs = []
    for _ in range(2):
        plan = faults.FaultPlan(seed=42)
        plan.add("rpc.send", "corrupt", nth=1)
        with plan.installed():
            outs.append(faults.filter_bytes("rpc.send", b"hello world"))
    assert outs[0] == outs[1] != b"hello world"


def test_fire_site_rejects_payload_actions():
    plan = faults.FaultPlan()
    plan.add("lease.renew", "truncate")
    with plan.installed():
        with pytest.raises(faults.FaultError, match="only supports"):
            faults.fire("lease.renew")


# -- RetryPolicy ---------------------------------------------------------------

def test_retry_policy_exponential_capped_schedule():
    sleep, clock, t = _fake_time()
    slept = []
    pol = RetryPolicy(max_attempts=5, base_delay=0.1, multiplier=2.0,
                      max_delay=0.3, jitter=0.0,
                      sleep=lambda s: (slept.append(s), sleep(s)),
                      clock=clock)
    with pytest.raises(RetryBudgetExceeded) as ei:
        pol.call(lambda: (_ for _ in ()).throw(OSError("down")),
                 describe="probe")
    assert ei.value.attempts == 5
    assert isinstance(ei.value, ConnectionError)
    assert "5 attempt" in str(ei.value)
    np.testing.assert_allclose(slept, [0.1, 0.2, 0.3, 0.3])  # capped


def test_retry_policy_deadline_bounds_total_wait():
    sleep, clock, t = _fake_time()
    pol = RetryPolicy(max_attempts=None, base_delay=1.0, multiplier=1.0,
                      max_delay=1.0, deadline=3.5, jitter=0.0,
                      sleep=sleep, clock=clock)
    with pytest.raises(RetryBudgetExceeded) as ei:
        pol.call(lambda: (_ for _ in ()).throw(ConnectionError("down")))
    assert t[0] <= 3.5
    assert ei.value.attempts == 4           # t=0,1,2,3 then next would bust


def test_retry_policy_jitter_seeded_deterministic():
    def schedule(seed):
        sleep, clock, _ = _fake_time()
        slept = []
        pol = RetryPolicy(max_attempts=4, base_delay=0.1, jitter=0.5,
                          seed=seed, sleep=lambda s: slept.append(s),
                          clock=clock)
        with pytest.raises(RetryBudgetExceeded):
            pol.call(lambda: (_ for _ in ()).throw(OSError()))
        return slept

    assert schedule(7) == schedule(7)
    assert schedule(7) != schedule(8)


def test_retry_policy_nonretryable_propagates_and_success_returns():
    sleep, clock, _ = _fake_time()
    pol = RetryPolicy(max_attempts=5, jitter=0.0, sleep=sleep, clock=clock)
    with pytest.raises(ValueError):
        pol.call(lambda: (_ for _ in ()).throw(ValueError("logic bug")))
    calls = {"n": 0}
    retries = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    assert pol.call(flaky, on_retry=lambda a, e: retries.append(a)) == "ok"
    assert retries == [1, 2]


# -- crash-safe checkpointing --------------------------------------------------

def test_crash_mid_write_preserves_previous_pass(tmp_path):
    out = str(tmp_path / "ckpt")
    params = _init()
    save_checkpoint(out, 0, params)
    plan = faults.FaultPlan()
    plan.add("ckpt.write", "raise", nth=1, exc=OSError("torn write"))
    with plan.installed():
        with pytest.raises(OSError):
            save_checkpoint(out, 1, params)
    # the torn pass-1 never became visible; pass 0 is intact
    assert latest_pass(out) == 0
    assert os.path.exists(pass_dir(out, 1) + ".tmp")
    assert not os.path.exists(pass_dir(out, 1))
    p, o, st = load_checkpoint(out)
    assert st["pass_id"] == 0 and st["pass_complete"]
    # a later writer reclaims the leftover .tmp and publishes cleanly
    save_checkpoint(out, 1, params)
    assert latest_pass(out) == 1 and verify_checkpoint(pass_dir(out, 1))


def test_truncated_member_fails_verify_and_falls_back(tmp_path):
    out = str(tmp_path / "ckpt")
    good = {"w": np.arange(16, dtype=np.float32)}
    save_checkpoint(out, 0, good)
    plan = faults.FaultPlan()
    plan.add("ckpt.write", "truncate", nth=1, truncate_to=32)
    with plan.installed():
        save_checkpoint(out, 1, good)       # publishes a torn params.tar
    assert latest_pass(out) == 1            # manifest exists...
    assert not verify_checkpoint(pass_dir(out, 1))
    assert latest_pass(out, verify=True) == 0
    p, o, st = load_checkpoint(out)         # ...but load refuses it
    assert st["pass_id"] == 0
    np.testing.assert_array_equal(p["w"], good["w"])
    # an explicit pass_id is gated by the same verification, not an
    # escape hatch around it
    with pytest.raises(ValueError, match="verification"):
        load_checkpoint(out, 1)


def test_resume_with_only_corrupt_checkpoints_starts_fresh(tmp_path):
    out = str(tmp_path / "ckpt")
    plan = faults.FaultPlan()
    plan.add("ckpt.write", "truncate", nth=1, truncate_to=16)
    with plan.installed():
        save_checkpoint(out, 0, _init())    # every member torn
    assert latest_pass(out) == 0 and latest_pass(out, verify=True) is None
    # resume=True must fall through to fresh init, not die on
    # "no verifiable checkpoints"
    t = Trainer(_loss, SGD(0.1), output_dir=out)
    p, _ = t.train(lambda: _make_batches(n=2), _init(), num_passes=1,
                   resume=True, handle_signals=False)
    assert np.all(np.isfinite(np.asarray(p["w"])))


def test_latest_pass_requires_manifest(tmp_path):
    # mere existence of params.tar is not a checkpoint (the old bug)
    d = str(tmp_path / "out")
    torn = os.path.join(d, "pass-00003")
    os.makedirs(torn)
    with open(os.path.join(torn, "params.tar"), "wb") as f:
        f.write(b"\x00" * 100)              # truncated garbage
    assert latest_pass(d) is None
    save_checkpoint(d, 1, _init())
    assert latest_pass(d) == 1              # manifest-bearing pass wins
    p, o, st = load_checkpoint(d)
    assert st["pass_id"] == 1


def test_kill9_mid_checkpoint_write_then_resume(tmp_path):
    """A real SIGKILL lands while pass-1 members are being written: the
    surviving state must resume from pass 0 with no corrupt-tar load and no
    lost completed pass (ISSUE 2 acceptance criterion)."""
    out = str(tmp_path / "ckpt")
    sentinel = str(tmp_path / "inside-write")
    p = subprocess.Popen(
        [sys.executable, os.path.join(_REPO, "tests", "chaos_ckpt_writer.py"),
         out, sentinel],
        cwd=_REPO, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        deadline = time.time() + 60
        while not os.path.exists(sentinel):
            assert p.poll() is None, "writer died before reaching the stall"
            assert time.time() < deadline, "writer never reached the stall"
            time.sleep(0.02)
        p.kill()                            # SIGKILL mid-checkpoint-write
    finally:
        p.wait(timeout=10)
    # pass 1 is torn (params.tar written, no manifest); pass 0 survives
    assert latest_pass(out) == 0
    assert os.path.exists(pass_dir(out, 1) + ".tmp")
    params, opt_state, st = load_checkpoint(out)
    assert st["pass_id"] == 0
    np.testing.assert_array_equal(
        params["w"], np.arange(64, dtype=np.float32).reshape(8, 8))
    # and training picks up where the victim left off
    batches = _make_batches(n=2, d=8, seed=3)
    batches = [(x, y[:, :1]) for x, y in batches]

    def loss8(pp, x, y):
        return jnp.mean((x @ pp["w"] + pp["b"] - y) ** 2)

    t = Trainer(loss8, SGD(0.01), output_dir=out)
    t.train(lambda: batches, None, num_passes=1, resume=True,
            handle_signals=False)
    assert latest_pass(out, verify=True) == 1
    assert verify_checkpoint(pass_dir(out, 1))


# -- trainer preemption + byte-identical resume --------------------------------

def test_sigterm_mid_pass_checkpoints_and_resumes_byte_identical(tmp_path):
    batches = _make_batches(n=4)

    # reference: uninterrupted 2-pass run
    ref = Trainer(_loss, SGD(0.1), output_dir=str(tmp_path / "ref"))
    ref_params, _ = ref.train(lambda: batches, _init(), num_passes=2,
                              handle_signals=False)

    # victim: SIGTERM lands during pass 1, batch 1
    out = str(tmp_path / "victim")
    victim = Trainer(_loss, SGD(0.1), output_dir=out)

    def handler(e):
        from paddle_tpu.trainer import event
        if isinstance(e, event.EndIteration) and e.pass_id == 1 \
                and e.batch_id == 1:
            os.kill(os.getpid(), signal.SIGTERM)

    victim.train(lambda: batches, _init(), num_passes=2,
                 event_handler=handler)
    assert victim.preempted
    assert victim.train_stats["preemptions"] == 1
    # the preemption checkpoint is durable, marked incomplete, mid-pass
    pid = latest_pass(out, verify=True)
    assert pid == 1
    _, _, st = load_checkpoint(out)
    assert st["pass_complete"] is False and st["batch_id"] == 1
    # pass 0's completed checkpoint was NOT lost
    assert verify_checkpoint(pass_dir(out, 0))

    # resume: continues pass 1 at batch 2 — byte-identical to uninterrupted
    resumed = Trainer(_loss, SGD(0.1), output_dir=out)
    res_params, _ = resumed.train(lambda: batches, _init(), num_passes=1,
                                  resume=True, handle_signals=False)
    assert _param_bytes(res_params) == _param_bytes(ref_params)
    # the re-saved pass 1 is now complete
    _, _, st = load_checkpoint(out)
    assert st["pass_id"] == 1 and st["pass_complete"]


def test_signal_handlers_installed_and_restored():
    batches = _make_batches(n=1)
    prev_term = signal.getsignal(signal.SIGTERM)
    t = Trainer(_loss, SGD(0.1))
    t.train(lambda: batches, _init(), num_passes=1)   # handle_signals=True
    assert signal.getsignal(signal.SIGTERM) is prev_term


def test_checkpoint_every_cadence(tmp_path):
    out = str(tmp_path / "ckpt")
    t = Trainer(_loss, SGD(0.1), output_dir=out)
    t.train(lambda: _make_batches(n=2), _init(), num_passes=4,
            checkpoint_every=2, handle_signals=False)
    have = {pid for pid in range(4) if os.path.exists(
        os.path.join(pass_dir(out, pid), COMPLETE_MANIFEST))}
    assert have == {1, 3}                   # every 2nd pass (final included)


# -- non-finite loss policy ----------------------------------------------------

def test_on_nonfinite_skip_drops_batch_exactly(tmp_path):
    batches = _make_batches(n=4)
    poisoned = list(batches)
    x2, y2 = poisoned[2]
    poisoned[2] = (np.full_like(x2, np.inf), y2)

    t = Trainer(_loss, SGD(0.1), on_nonfinite="skip")
    p_skip, _ = t.train(lambda: poisoned, _init(), num_passes=1,
                        handle_signals=False)
    assert t.train_stats["skipped_batches"] == 1
    assert t.train_stats["nonfinite_batches"] == 1

    # dropping the poisoned batch must equal never having seen it
    clean = [b for i, b in enumerate(batches) if i != 2]
    t2 = Trainer(_loss, SGD(0.1))
    p_clean, _ = t2.train(lambda: clean, _init(), num_passes=1,
                          handle_signals=False)
    assert _param_bytes(p_skip) == _param_bytes(p_clean)
    assert np.all(np.isfinite(np.asarray(p_skip["w"])))


def test_on_nonfinite_halt_checkpoints_then_raises(tmp_path):
    out = str(tmp_path / "ckpt")
    plan = faults.FaultPlan()
    plan.add("step.grad", "corrupt", nth=2)   # NaN at batch 1
    t = Trainer(_loss, SGD(0.1), output_dir=out, on_nonfinite="halt")
    with plan.installed():
        with pytest.raises(FloatingPointError, match="non-finite"):
            t.train(lambda: _make_batches(n=4), _init(), num_passes=1,
                    handle_signals=False)
    # state was made durable BEFORE the raise
    _, _, st = load_checkpoint(out)
    assert st["halted"] is True and st["pass_complete"] is False
    assert st["batch_id"] == 1


def test_on_nonfinite_halt_checkpoints_last_finite_state(tmp_path):
    """halt must drop the poisoned update before checkpointing: a durable
    NaN tree would make resume start from garbage — worse than no
    checkpoint at all."""
    out = str(tmp_path / "ckpt")
    batches = _make_batches(n=4)
    poisoned = list(batches)
    x2, y2 = poisoned[2]
    poisoned[2] = (np.full_like(x2, np.inf), y2)
    t = Trainer(_loss, SGD(0.1), output_dir=out, on_nonfinite="halt")
    with pytest.raises(FloatingPointError, match="non-finite"):
        t.train(lambda: poisoned, _init(), num_passes=1,
                handle_signals=False)
    p_halt, _, st = load_checkpoint(out)
    assert st["halted"] is True and st["batch_id"] == 2
    assert np.all(np.isfinite(np.asarray(p_halt["w"])))
    # the checkpoint equals training on the finite prefix alone
    t2 = Trainer(_loss, SGD(0.1))
    p_clean, _ = t2.train(lambda: batches[:2], _init(), num_passes=1,
                          handle_signals=False)
    assert _param_bytes(p_halt) == _param_bytes(p_clean)


def test_torn_swap_is_recovered_on_discovery(tmp_path):
    """Re-publishing a pass swaps dirs with two renames; a crash between
    them leaves the pass only under .old/.tmp names. Discovery must heal
    that window: a verified .tmp rolls forward, else .old rolls back."""
    out = str(tmp_path / "ckpt")
    a = {"w": np.zeros((4, 1), np.float32)}
    b = {"w": np.ones((4, 1), np.float32)}

    # roll-back case: crash after rename(d, old), .tmp not yet complete
    save_checkpoint(out, 0, a)
    os.rename(pass_dir(out, 0), pass_dir(out, 0) + ".old")
    assert latest_pass(out) == 0            # recovery restored .old
    p, _, _ = load_checkpoint(out)
    np.testing.assert_array_equal(p["w"], a["w"])

    # roll-forward case: .tmp carries a full verified manifest, d missing
    scratch = str(tmp_path / "scratch")
    save_checkpoint(scratch, 0, b)
    os.rename(pass_dir(out, 0), pass_dir(out, 0) + ".old")
    os.rename(pass_dir(scratch, 0), pass_dir(out, 0) + ".tmp")
    assert latest_pass(out) == 0
    p, _, _ = load_checkpoint(out)
    np.testing.assert_array_equal(p["w"], b["w"])   # newer write won
    assert not os.path.exists(pass_dir(out, 0) + ".old")
    assert not os.path.exists(pass_dir(out, 0) + ".tmp")


def test_on_nonfinite_default_raise_via_fault():
    plan = faults.FaultPlan()
    plan.add("step.grad", "corrupt", nth=1)
    t = Trainer(_loss, SGD(0.1))
    with plan.installed():
        with pytest.raises(FloatingPointError, match="non-finite"):
            t.train(lambda: _make_batches(n=2), _init(), num_passes=1,
                    handle_signals=False)


# -- RPC chaos -----------------------------------------------------------------

def _fast_policy(attempts=5):
    return RetryPolicy(max_attempts=attempts, base_delay=0.001,
                       max_delay=0.002, jitter=0.0, sleep=lambda s: None)


def test_master_rpc_dropped_requests_are_retried(tmp_path):
    from paddle_tpu.runtime.master_service import MasterClient, MasterServer
    srv = MasterServer(snapshot_path=str(tmp_path / "m.snap"),
                       tick_interval=0.2).start()
    try:
        c = MasterClient(*srv.address, retry_policy=_fast_policy())
        plan = faults.FaultPlan()
        plan.add("rpc.send", "raise", nth=1, count=2,
                 exc=ConnectionError("injected drop"))
        with plan.installed():
            c.set_dataset(["t0", "t1"])     # survives two dropped sends
        assert [f for f in plan.fired
                if f[0] == "rpc.send"] == [("rpc.send", 1, "raise"),
                                           ("rpc.send", 2, "raise")]
        got = []
        while True:
            task = c.get_task()
            if task is None:
                break
            got.append(task[1])
            c.task_finished(task[0])
        assert sorted(got) == ["t0", "t1"]
        c.close()
    finally:
        srv.stop()


def test_master_rpc_budget_exhaustion_surfaces_attempts(tmp_path):
    from paddle_tpu.runtime.master_service import MasterClient, MasterServer
    srv = MasterServer(snapshot_path=str(tmp_path / "m.snap"),
                       tick_interval=0.2).start()
    try:
        c = MasterClient(*srv.address, retry_policy=_fast_policy(attempts=3))
        plan = faults.FaultPlan()
        plan.add("rpc.send", "raise", nth=1, count=99,
                 exc=ConnectionError("injected outage"))
        with plan.installed():
            with pytest.raises(ConnectionError, match="3 attempt"):
                c.stats()
        c.close()
    finally:
        srv.stop()


def test_corrupt_frame_drops_connection_then_recovers():
    """A corrupted request frame must desync-proof the protocol: the server
    severs the connection, the client reconnects and the retried call
    succeeds (CRC-less framing + bit rot handled at the retry layer)."""
    srv = CoordServer().start()
    try:
        c = _CoordClient(*srv.address, retry_policy=_fast_policy())
        plan = faults.FaultPlan()
        plan.add("rpc.send", "corrupt", nth=1)
        with plan.installed():
            r = c.call({"op": "ping"})
        assert r["ok"]
        assert ("rpc.send", 1, "corrupt") in plan.fired
        c.close()
    finally:
        srv.stop()


def test_torn_frame_times_out_then_recovers():
    """A truncated frame (header promises more bytes than arrive) wedges
    the receiver; the sender's per-call socket timeout converts the wedge
    into a retry instead of an indefinite hang."""
    srv = CoordServer().start()
    try:
        c = _CoordClient(*srv.address, call_timeout=0.2,
                         retry_policy=_fast_policy())
        plan = faults.FaultPlan()
        plan.add("rpc.send", "truncate", nth=1, truncate_to=2)
        t0 = time.monotonic()
        with plan.installed():
            r = c.call({"op": "ping"})
        assert r["ok"]
        assert time.monotonic() - t0 < 5.0
        c.close()
    finally:
        srv.stop()


# -- lease renewal stall + fencing ---------------------------------------------

def test_file_lease_renewal_stall_deposes_holder(tmp_path):
    """Renewal stalls past TTL (injected FS outage): the standby takes over
    with a higher token, and the deposed holder's next fenced write is
    refused — the stale master never lands a write."""
    lease_path = str(tmp_path / "lease")
    snap = str(tmp_path / "snap")
    a = FileLease(lease_path, owner="a", ttl=1.0)
    assert a.try_acquire()
    fence = FencedFile(snap)
    assert fence.claim(a.token)
    assert fence.write(a.token, lambda p: open(p, "w").write("gen-a"))

    plan = faults.FaultPlan()
    plan.add("lease.renew", "raise", nth=1, count=99,
             exc=OSError("injected NFS outage"))
    with plan.installed():
        with pytest.raises(OSError):
            a.renew()

    # TTL expires (time travel, no real sleep); standby b takes over
    later = time.time() + a.ttl + 1.0
    b = FileLease(lease_path, owner="b", ttl=1.0)
    assert b.try_acquire(now=later)
    assert b.token > a.token
    assert fence.claim(b.token)

    wrote = {"a": False}

    def stale_writer(p):
        wrote["a"] = True
        with open(p, "w") as f:
            f.write("stale-from-a")

    assert fence.write(a.token, stale_writer) is False
    assert fence.write(b.token, lambda p: open(p, "w").write("gen-b"))
    with open(snap) as f:
        assert f.read() == "gen-b"          # a's write never landed
    # even though a's writer ran, its output was discarded pre-publish
    assert wrote["a"]
    assert fence.write(a.token, stale_writer) is False   # still refused


def test_lease_keeper_declares_lost_after_ttl_of_stalls(tmp_path):
    """LeaseKeeper tolerates transient renew failures only while our TTL
    could still be running; past it, the lease is LOST and on_lost fires."""
    lease = FileLease(str(tmp_path / "lease"), owner="a", ttl=0.45)
    assert lease.try_acquire()
    lost = threading.Event()
    plan = faults.FaultPlan()
    plan.add("lease.renew", "raise", nth=1, count=999,
             exc=OSError("injected stall"))
    keeper = LeaseKeeper(lease, interval=0.1, on_lost=lost.set)
    with plan.installed():
        keeper.start()
        assert lost.wait(timeout=10.0), "keeper never declared the lease lost"
    keeper.stop(release=False)
    assert plan.hits["lease.renew"] >= 2    # it kept trying through the TTL


def test_network_lease_renewal_stall_fenced_write_refused():
    """The NetworkLease variant of the deposition story, server-judged TTL:
    holder a stalls (renewals raise), the lease expires on the server, b
    takes over, and a's fenced snapshot write is refused (ISSUE 2
    satellite)."""
    srv = CoordServer().start()
    try:
        host, port = srv.address
        a = NetworkLease(host, port, owner="a", ttl=0.3)
        assert a.try_acquire()
        store_a = NetworkFencedStore(host, port)
        assert store_a.claim(a.token)
        assert store_a.write(a.token, lambda p: open(p, "w").write("gen-a"))

        plan = faults.FaultPlan()
        plan.add("lease.renew", "raise", nth=1, count=999,
                 exc=ConnectionError("injected stall"))
        with plan.installed():
            with pytest.raises(ConnectionError):
                a.renew()
            time.sleep(0.4)                 # server-side TTL expiry
            b = NetworkLease(host, port, owner="b", ttl=5.0)
            assert b.try_acquire()
            assert b.token > a.token
            store_b = NetworkFencedStore(host, port)
            assert store_b.claim(b.token)
            # deposed holder's write refused; new generation's lands
            assert store_a.write(
                a.token, lambda p: open(p, "w").write("stale")) is False
            assert store_b.write(
                b.token, lambda p: open(p, "w").write("gen-b"))
        import tempfile
        fd, tmp = tempfile.mkstemp()
        os.close(fd)
        try:
            assert store_b.fetch_to(tmp)
            with open(tmp) as f:
                assert f.read() == "gen-b"
        finally:
            os.remove(tmp)
        a.close()
        b.close()
        store_a.close()
        store_b.close()
    finally:
        srv.stop()


# -- reader/prefetch chaos -----------------------------------------------------

class _FakeMaster:
    """Scripted in-process master for reader tests (no network)."""

    def __init__(self, tasks):
        self.todo = dict(tasks)             # id -> payload
        self.pending = {}
        self.failed = []
        self.finished = []

    def get_task(self):
        if not self.todo:
            return None
        tid, payload = next(iter(self.todo.items()))
        self.pending[tid] = self.todo.pop(tid)
        return tid, payload

    def stats(self):
        return len(self.todo), len(self.pending), len(self.finished), 0, 0

    def task_failed(self, tid):
        self.todo[tid] = self.pending.pop(tid)   # immediate re-dispatch
        self.failed.append(tid)
        return False

    def task_finished(self, tid):
        self.finished.append(self.pending.pop(tid))

    def new_pass(self):
        return False


def test_cloud_reader_task_failure_redispatches(tmp_path):
    paths = dump_to_chunks(lambda: iter(range(10)), str(tmp_path / "chunks"),
                           samples_per_chunk=5)
    assert len(paths) == 2
    master = _FakeMaster({i: p for i, p in enumerate(paths)})
    plan = faults.FaultPlan()
    plan.add("reader.next", "raise", nth=1, exc=OSError("injected read error"))
    with plan.installed():
        got = sorted(cloud_reader(master)())
    assert got == list(range(10))           # nothing lost
    assert master.failed == [0]             # first task failed once...
    assert len(master.finished) == 2        # ...then both completed


def test_cloud_reader_starvation_deadline_no_real_sleep():
    sleep, clock, t = _fake_time()

    class Starver:
        def get_task(self):
            return None

        def stats(self):
            return (0, 1, 0, 0, 0)          # pending forever, never done

    policy = RetryPolicy(max_attempts=None, base_delay=0.1, multiplier=1.5,
                         max_delay=1.0, deadline=30.0, jitter=0.0,
                         retryable=_Starved, sleep=sleep, clock=clock)
    with pytest.raises(TimeoutError, match="starved"):
        list(cloud_reader(Starver(), poll_policy=policy)())
    assert t[0] <= 30.0                     # virtual time only


def test_double_buffer_watchdog_times_out():
    stall = threading.Event()

    def wedged():
        yield (np.zeros(2),)
        stall.wait()                        # producer hangs forever

    buf = DoubleBuffer(wedged, depth=2, timeout=0.2)
    it = iter(buf)
    next(it)                                # first batch flows
    with pytest.raises(TimeoutError, match="watchdog"):
        next(it)
    stall.set()                             # release the worker thread
