"""Master service tests: in-process server + clients, elastic re-dispatch —
the reference's in-process multi-node strategy (SURVEY.md §4.3: pserver
objects on localhost ports inside the test process)."""

import threading
import time

import pytest

from paddle_tpu.runtime.master_service import MasterClient, MasterServer


@pytest.fixture
def server(tmp_path):
    srv = MasterServer(timeout_s=1.0, failure_max=3,
                       snapshot_path=str(tmp_path / "m.snap"),
                       tick_interval=0.2).start()
    yield srv
    srv.stop()


def _client(server):
    return MasterClient(server.address[0], server.address[1])


def test_dispatch_over_network(server):
    c = _client(server)
    c.set_dataset([f"chunk{i}" for i in range(5)])
    got = []
    while True:
        t = c.get_task()
        if t is None:
            break
        got.append(t[1])
        c.task_finished(t[0])
    assert sorted(got) == [f"chunk{i}" for i in range(5)]
    assert c.new_pass()
    assert c.stats()[0] == 5  # todo refilled


def test_elastic_redispatch_on_consumer_death(server):
    """Consumer A leases a task and dies; the lease expires via the server's
    tick thread and consumer B completes the pass."""
    a, b = _client(server), _client(server)
    a.set_dataset(["t0", "t1"])
    dead_task = a.get_task()
    assert dead_task is not None
    a.close()                         # A dies holding its task

    done = []
    deadline = time.time() + 10.0
    while time.time() < deadline:
        t = b.get_task()
        if t is None:
            if b.stats()[2] == 2:     # done == 2
                break
            time.sleep(0.2)
            continue
        done.append(t[1])
        b.task_finished(t[0])
    assert dead_task[1] in done       # the orphaned task was re-dispatched


def test_concurrent_clients(server):
    c0 = _client(server)
    c0.set_dataset([f"c{i}" for i in range(40)])
    got, lock = [], threading.Lock()

    def worker():
        c = _client(server)
        while True:
            t = c.get_task()
            if t is None:
                todo, pending, done, disc, epoch = c.stats()
                if todo == 0 and pending == 0:
                    return
                time.sleep(0.05)
                continue
            with lock:
                got.append(t[1])
            c.task_finished(t[0])

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=20)
    assert sorted(got) == sorted(f"c{i}" for i in range(40))


def test_oversized_response_degrades_to_structured_error(server, monkeypatch):
    """Responses are now checked against the frame limit (ADVICE r5): a
    payload whose JSON escaping expands past it must come back as a
    structured 'payload too large' error the client RAISES — not as a
    >limit frame the client's guard silently drops as a dead connection.
    $PTMS_MAX_RESPONSE_FRAME shrinks the bound so the test stays small."""
    monkeypatch.setenv("PTMS_MAX_RESPONSE_FRAME", "200000")
    c = _client(server)
    # newlines escape 1 -> 2 bytes: 150 KB raw renders as a ~300 KB
    # get_task response, over the armed 200 KB bound
    c.set_dataset(["\n" * 150000, "small"])
    with pytest.raises(RuntimeError, match="payload too large"):
        while True:
            t = c.get_task()      # big task may not be first in the queue
            assert t is not None and t[1] == "small"
            c.task_finished(t[0])
    # the connection survived: the small task still round-trips
    t = c.get_task()
    if t is not None:
        assert t[1] == "small"
    c.close()


def test_snapshot_written_and_recovered(server, tmp_path):
    c = _client(server)
    c.set_dataset(["a", "b", "c"])
    t = c.get_task()
    c.task_finished(t[0])
    time.sleep(0.5)                   # let the housekeeping thread snapshot

    srv2 = MasterServer(timeout_s=1.0, snapshot_path=str(tmp_path / "m.snap"),
                        tick_interval=0.2).start()
    try:
        c2 = _client(srv2)
        todo, pending, done, disc, epoch = c2.stats()
        assert done == 1 and todo == 2 and pending == 0
    finally:
        srv2.stop()


def test_multihost_helpers_single_process():
    import numpy as np

    from paddle_tpu import parallel as pp
    from paddle_tpu.parallel import multihost as mh
    info = mh.initialize()
    assert info["process_count"] == 1
    mesh = mh.global_mesh(data=8)
    sl = mh.process_batch_slice(64)
    assert sl == slice(0, 64)
    arr = mh.make_global_array(np.ones((16, 4), np.float32), mesh)
    assert arr.shape == (16, 4)


def test_master_failover_lease_election(tmp_path):
    """Standby master takes over through the file lease (etcd-election
    analog) and recovers task state from the CRC-checked snapshot; the
    client's endpoint rotation makes the failover transparent."""
    import socket as _socket

    from paddle_tpu.runtime import FileLease
    from paddle_tpu.runtime.master_service import MasterClient, MasterServer

    def free_port():
        s = _socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    pa, pb = free_port(), free_port()
    lease_path = str(tmp_path / "master.lease")
    snap = str(tmp_path / "master.snap")

    lease_a = FileLease(lease_path, owner="master-a", ttl=0.6)
    a = MasterServer(port=pa, snapshot_path=snap, tick_interval=0.05,
                     lease=lease_a).start()
    client = MasterClient(endpoints=[("127.0.0.1", pa), ("127.0.0.1", pb)])
    try:
        client.set_dataset(["chunk-0", "chunk-1", "chunk-2"])
        t0 = client.get_task()
        assert t0 is not None
        time.sleep(0.2)                      # let a snapshot land

        # master A crashes WITHOUT releasing its lease
        a.stop(release_lease=False)

        # standby B can only serve once A's lease expires
        lease_b = FileLease(lease_path, owner="master-b", ttl=0.6)
        assert not lease_b.try_acquire()     # still A's
        assert lease_b.wait_acquire(poll=0.1, timeout=10)
        b = MasterServer(port=pb, snapshot_path=snap, tick_interval=0.05,
                         lease=lease_b).start()
        try:
            # client reconnects by rotating endpoints; ALL chunks are still
            # dispatchable (A's pending task was snapshotted back to todo)
            seen = set()
            for _ in range(6):
                t = client.get_task()
                if t is None:
                    break
                seen.add(t[1])
                client.task_finished(t[0])
            assert seen == {"chunk-0", "chunk-1", "chunk-2"}
        finally:
            b.stop()
    finally:
        client.close()


def test_snapshot_crc_detects_corruption(tmp_path):
    """Flipping a byte in the snapshot body must make restore fail loudly
    (go/pserver/service.go:119-126 CRC discipline)."""
    from paddle_tpu.runtime import TaskMaster

    snap = str(tmp_path / "m.snap")
    m = TaskMaster()
    m.set_dataset(["alpha", "beta"])
    m.snapshot(snap)

    m2 = TaskMaster()
    m2.restore(snap)                         # clean restore works
    assert m2.stats()[0] == 2

    raw = bytearray(open(snap, "rb").read())
    raw[-3] ^= 0xFF                          # corrupt a payload byte
    open(snap, "wb").write(bytes(raw))
    with pytest.raises(IOError):
        TaskMaster().restore(snap)


def test_master_concurrent_consumers_hammer():
    """Thread-safety discipline (utils/Locks.h analog is a std::mutex in
    task_master.cc): many concurrent consumers over one server must neither
    lose nor double-complete tasks."""
    import threading

    from paddle_tpu.runtime.master_service import MasterClient, MasterServer

    N_TASKS, N_WORKERS = 200, 8
    srv = MasterServer(tick_interval=0.05).start()
    try:
        boot = MasterClient(*srv.address)
        boot.set_dataset([f"chunk-{i:04d}" for i in range(N_TASKS)])
        boot.close()

        seen, lock = [], threading.Lock()

        def worker():
            c = MasterClient(*srv.address)
            while True:
                t = c.get_task()
                if t is None:
                    break
                with lock:
                    seen.append(t[1])
                c.task_finished(t[0])
            c.close()

        threads = [threading.Thread(target=worker) for _ in range(N_WORKERS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert len(seen) == N_TASKS                      # no loss, no dupes
        assert len(set(seen)) == N_TASKS
        todo, pending, done, disc, _ = srv.master.stats()
        assert (todo, pending, done, disc) == (0, 0, N_TASKS, 0)
    finally:
        srv.stop()


def test_lease_fencing_token_monotonic(tmp_path):
    """Every acquisition gets a strictly larger fencing token, even across
    release/re-acquire cycles (etcd-revision monotonicity,
    go/master/etcd_client.go)."""
    from paddle_tpu.runtime import FileLease

    path = str(tmp_path / "l.lease")
    a = FileLease(path, owner="a", ttl=5.0)
    assert a.try_acquire()
    t1 = a.token
    assert t1 is not None and t1 >= 1
    a.release()
    assert a.token is None

    b = FileLease(path, owner="b", ttl=5.0)
    assert b.try_acquire()
    assert b.token > t1                       # survives the release gap
    assert b.current_token() == b.token

    # expiry takeover also bumps
    b2 = FileLease(path, owner="b2", ttl=5.0)
    assert not b2.try_acquire()               # live
    c = FileLease(path, owner="c", ttl=5.0)
    assert c.try_acquire(now=time.time() + 10.0)   # b has expired by then
    assert c.token > b.token


def test_deposed_master_writes_are_fenced(tmp_path):
    """A master that stalls past its TTL (paused keeper) and wakes after a
    standby took over must have BOTH its snapshot writes and its mutating
    RPCs refused — the fencing-token discipline the reference gets from
    etcd revisions (go/master/etcd_client.go)."""
    import socket as _socket

    from paddle_tpu.runtime import FileLease
    from paddle_tpu.runtime.master_service import MasterClient, MasterServer

    def free_port():
        s = _socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    pa, pb = free_port(), free_port()
    lease_path = str(tmp_path / "master.lease")
    snap = str(tmp_path / "master.snap")

    lease_a = FileLease(lease_path, owner="master-a", ttl=0.5)
    # long tick_interval: housekeeping never runs, so the only fence checks
    # are the explicit ones below (deterministic)
    a = MasterServer(port=pa, snapshot_path=snap, tick_interval=60.0,
                     lease=lease_a).start()
    ca = MasterClient("127.0.0.1", pa)
    try:
        ca.set_dataset(["chunk-0", "chunk-1"])
        assert a.try_snapshot()               # current master writes fine

        # simulate a GC-pause: renewal stops but the server keeps running
        a._keeper.stop(release=False)
        a._keeper = None
        deadline = time.time() + 10
        lease_b = FileLease(lease_path, owner="master-b", ttl=5.0)
        while not lease_b.try_acquire():
            assert time.time() < deadline
            time.sleep(0.1)

        b = MasterServer(port=pb, snapshot_path=snap, tick_interval=60.0,
                         lease=lease_b).start()
        try:
            assert b.fence_token > a.fence_token
            # the paused master wakes up: its snapshot write is refused and
            # the snapshot file still belongs to generation B
            assert b.try_snapshot()
            gen_b = open(snap, "rb").read()
            assert not a.try_snapshot()
            assert open(snap, "rb").read() == gen_b

            # ...and its mutating RPCs are refused too
            r = a._dispatch({"op": "set_dataset", "payloads": ["rogue"]})
            assert r["ok"] is False and "fenced" in r["error"]
            r = a._dispatch({"op": "task_finished", "task_id": 0})
            assert r["ok"] is False
            # read-only ops still answer (harmless)
            assert a._dispatch({"op": "stats"})["ok"] is True
        finally:
            b.stop()
    finally:
        ca.close()
        a.stop(release_lease=False)


# ---------------------------------------------------------------------------
# native server robustness (master_server.cc): hostile/degenerate wire input
# must never wedge the C++ accept/dispatch plane (ProtoServer.h:36 analog —
# a control-plane daemon shared by every trainer).
# ---------------------------------------------------------------------------

def _raw(addr, payload: bytes, half_close: bool = False):
    import socket
    import struct

    from paddle_tpu.runtime.master_service import _recv_exact

    s = socket.create_connection(addr, timeout=10.0)
    try:
        s.sendall(payload)
        if half_close:
            s.shutdown(socket.SHUT_WR)   # EOF: no more bytes are coming
        hdr = _recv_exact(s, 4)
        if hdr is None:
            return None
        (n,) = struct.unpack("<I", hdr)
        return _recv_exact(s, n)
    finally:
        s.close()


def test_native_server_survives_hostile_frames(server):
    """Garbage JSON, unknown ops, truncated frames, oversized length
    headers, and unicode-escape payloads: each is answered or the
    connection dropped — and the server keeps serving well-formed clients
    afterwards."""
    import json
    import struct

    addr = server.address

    def frame(obj) -> bytes:
        body = json.dumps(obj).encode()
        return struct.pack("<I", len(body)) + body

    # unknown op -> structured error
    r = json.loads(_raw(addr, frame({"op": "no_such_op"})))
    assert r["ok"] is False and "unknown op" in r["error"]

    # malformed JSON -> bad-request error, not a crash
    bad = b"this is not json"
    r = json.loads(_raw(addr, struct.pack("<I", len(bad)) + bad))
    assert r["ok"] is False

    # unicode escapes (incl. surrogate pair) round-trip through payloads
    snowman = "sn☃man \U0001F600 q\"uote\\slash"
    r = json.loads(_raw(addr, frame({"op": "set_dataset",
                                     "payloads": [snowman]})))
    assert r["ok"] is True
    r = json.loads(_raw(addr, frame({"op": "get_task"})))
    assert r["ok"] is True and r["task"]["payload"] == snowman

    # oversized length header -> connection dropped, no allocation bomb
    assert _raw(addr, struct.pack("<I", 1 << 30)) is None

    # truncated frame (header promises more bytes than ever arrive, then
    # EOF) -> dropped without a reply
    assert _raw(addr, struct.pack("<I", 100) + b"short",
                half_close=True) is None

    # the server still works for a well-formed client
    c = _client(server)
    c.set_dataset(["after-the-storm"])
    t = c.get_task()
    assert t is not None and t[1] == "after-the-storm"
    c.task_finished(t[0])
