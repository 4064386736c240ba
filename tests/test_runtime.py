"""Native host-runtime tests — in-process, like the reference's Go
table-driven master/pserver tests (SURVEY.md §4.3: go/master/service_test.go)."""

import os
import threading

import pytest

from paddle_tpu.runtime import (HostArena, RecordReader, RecordWriter,
                                TaskMaster)


def test_master_dispatch_cycle():
    m = TaskMaster(timeout_s=60, failure_max=3)
    m.set_dataset([f"chunk-{i}" for i in range(4)])
    seen = []
    while True:
        t = m.get_task(now=0.0)
        if t is None:
            break
        seen.append(t[1])
        m.task_finished(t[0])
    assert sorted(seen) == [f"chunk-{i}" for i in range(4)]
    assert m.pass_finished()
    # explicit next pass refills todo and bumps epoch (ErrPassAfter analog)
    assert m.new_pass()
    todo, pending, done, disc, epoch = m.stats()
    assert todo == 4 and pending == 0 and epoch == 1


def test_master_timeout_requeue_and_discard():
    m = TaskMaster(timeout_s=10, failure_max=2)
    m.set_dataset(["a"])
    tid, payload = m.get_task(now=0.0)
    assert payload == "a"
    # not yet due
    assert m.tick(now=5.0) == 0
    # overdue -> requeued (failure 1)
    assert m.tick(now=11.0) == 1
    tid2, _ = m.get_task(now=12.0)
    # second timeout hits failure_max -> discarded
    assert m.tick(now=30.0) == 1
    todo, pending, done, disc, epoch = m.stats()
    assert disc == 1 and todo == 0 and pending == 0


def test_master_explicit_failure():
    m = TaskMaster(timeout_s=60, failure_max=3)
    m.set_dataset(["x"])
    tid, _ = m.get_task(now=0.0)
    assert m.task_failed(tid) is False      # requeued
    tid, _ = m.get_task(now=1.0)
    assert m.task_failed(tid) is False
    tid, _ = m.get_task(now=2.0)
    assert m.task_failed(tid) is True       # discarded at failure_max


def test_master_snapshot_restore(tmp_path):
    m = TaskMaster(timeout_s=60, failure_max=3)
    m.set_dataset([f"c{i}" for i in range(6)])
    t1 = m.get_task(now=0.0)
    t2 = m.get_task(now=0.0)
    m.task_finished(t1[0])
    snap = str(tmp_path / "master.snap")
    m.snapshot(snap)

    m2 = TaskMaster(timeout_s=60, failure_max=3)
    m2.restore(snap)
    todo, pending, done, disc, epoch = m2.stats()
    # pending task re-queued as todo on recovery; the finished one preserved
    assert pending == 0 and done == 1 and todo == 5
    payloads = []
    while True:
        t = m2.get_task(now=0.0)
        if t is None:
            break
        payloads.append(t[1])
        m2.task_finished(t[0])
    assert t2[1] in payloads


def test_master_threaded_consumers():
    m = TaskMaster(timeout_s=60, failure_max=3)
    m.set_dataset([f"c{i}" for i in range(64)])
    got = []
    lock = threading.Lock()

    def worker():
        while True:
            t = m.get_task(now=0.0)
            if t is None:
                return
            with lock:
                got.append(t[1])
            m.task_finished(t[0])

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sorted(got) == sorted(f"c{i}" for i in range(64))


def test_recordio_roundtrip_and_corruption(tmp_path):
    path = str(tmp_path / "data.ptr")
    payloads = [b"hello", b"", b"x" * 10000, bytes(range(256))]
    with RecordWriter(path) as w:
        for p in payloads:
            w.write(p)
    with RecordReader(path) as r:
        assert list(r) == payloads
    # flip one payload byte -> CRC error
    raw = bytearray(open(path, "rb").read())
    raw[4 + 8 + 2] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    with RecordReader(path) as r:
        with pytest.raises(IOError):
            list(r)


def test_arena_alloc_free_coalesce():
    a = HostArena(total=1 << 12, min_block=256)
    o1 = a.alloc(256)
    o2 = a.alloc(256)
    o3 = a.alloc(1024)
    assert len({o1, o2, o3}) == 3
    total, in_use, largest = a.stats()
    assert in_use == 256 + 256 + 1024
    a.free(o1)
    a.free(o2)
    a.free(o3)
    total, in_use, largest = a.stats()
    assert in_use == 0 and largest == total   # fully coalesced
    # whole-arena alloc works after coalesce
    big = a.alloc(1 << 12)
    with pytest.raises(MemoryError):
        a.alloc(256)
    a.free(big)
    with pytest.raises(ValueError):
        a.free(12345)


def test_arena_rejects_non_pow2():
    with pytest.raises(ValueError):
        HostArena(total=3000, min_block=256)


def test_host_optimizer_matches_numpy_adam():
    import numpy as np
    from paddle_tpu.runtime import HostOptimizer
    rs = np.random.RandomState(0)
    p0 = rs.randn(32).astype(np.float32)
    opt = HostOptimizer("adam", p0, lr=0.01)
    # numpy reference
    p, m, v = p0.astype(np.float64).copy(), np.zeros(32), np.zeros(32)
    for t in range(1, 6):
        g = rs.randn(32).astype(np.float32)
        opt.update(g)
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        mh = m / (1 - 0.9 ** t)
        vh = v / (1 - 0.999 ** t)
        p -= 0.01 * mh / (np.sqrt(vh) + 1e-6)
    np.testing.assert_allclose(opt.param, p, rtol=1e-4, atol=1e-5)


def test_host_optimizer_serialize_roundtrip():
    import numpy as np
    from paddle_tpu.runtime import HostOptimizer
    rs = np.random.RandomState(1)
    p0 = rs.randn(16).astype(np.float32)
    a = HostOptimizer("adagrad", p0, lr=0.1)
    for _ in range(3):
        a.update(rs.randn(16).astype(np.float32))
    blob = a.serialize()
    b = HostOptimizer("adagrad", p0, lr=0.1)
    b.deserialize(blob)
    g = rs.randn(16).astype(np.float32)
    a.update(g)
    b.update(g)
    np.testing.assert_allclose(a.param, b.param, rtol=1e-6)


def test_host_optimizer_sparse_rows():
    import numpy as np
    from paddle_tpu.runtime import HostOptimizer
    table = np.zeros((8, 4), np.float32)
    opt = HostOptimizer("sgd", table, lr=1.0)
    rows = np.array([1, 5], np.int32)
    grad = np.ones((2, 4), np.float32)
    opt.update_rows(rows, grad)
    out = opt.param
    assert out[1].sum() == -4 and out[5].sum() == -4 and out[0].sum() == 0


def test_master_large_payload_not_truncated():
    """Payloads >= the client's initial 4096-byte buffer must round-trip: the
    C side returns -3 + required length without consuming, client retries
    (recordio peek pattern; ADVICE r1 medium)."""
    m = TaskMaster(timeout_s=60, failure_max=3)
    big = "p" * 20000
    m.set_dataset([big])
    tid, payload = m.get_task(now=0.0)
    assert payload == big
    m.task_finished(tid)
    assert m.pass_finished()


def test_failed_native_build_raises_with_compiler_output(monkeypatch):
    """The library is required: a build that fails surfaces the toolchain's
    own message as NativeLibraryError (and keeps raising it), instead of a
    silent None that every caller had to remember to check."""
    import subprocess

    from paddle_tpu.runtime import NativeLibraryError, lib

    monkeypatch.setattr(lib, "_lib", None)
    monkeypatch.setattr(lib, "_error", None)
    monkeypatch.setattr(lib, "_needs_build", lambda: True)
    calls = []

    def fake_make(cmd, **kw):
        calls.append(cmd)
        return subprocess.CompletedProcess(
            cmd, 2, stdout="", stderr="task_master.cc:1: error: boom\n")

    monkeypatch.setattr(lib.subprocess, "run", fake_make)
    with pytest.raises(NativeLibraryError, match="error: boom"):
        lib.load_library()
    with pytest.raises(NativeLibraryError, match="exit 2"):
        TaskMaster()
    assert len(calls) == 1          # not retried within the process
