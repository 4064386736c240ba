"""Reader<->chunk bridge + cloud reader end-to-end (the distributed data
plane: dump -> master shards chunks -> consumers stream, with failure
re-dispatch)."""

import numpy as np
import pytest

from paddle_tpu.data.chunks import chunk_reader, cloud_reader, dump_to_chunks
from paddle_tpu.data.dataset import mnist  # noqa: E402
from paddle_tpu.runtime.master_service import MasterClient, MasterServer  # noqa: E402


def test_dump_and_chunk_reader_roundtrip(tmp_path):
    paths = dump_to_chunks(mnist.train(100), str(tmp_path),
                           samples_per_chunk=32)
    assert len(paths) == 4                      # 32+32+32+4
    back = list(chunk_reader(paths)())
    orig = list(mnist.train(100)())
    assert len(back) == 100
    np.testing.assert_allclose(back[0][0], orig[0][0])
    assert back[50][1] == orig[50][1]


def test_cloud_reader_full_pass_and_redispatch(tmp_path):
    paths = dump_to_chunks(mnist.train(64), str(tmp_path),
                           samples_per_chunk=16)
    srv = MasterServer(timeout_s=0.5, failure_max=3, tick_interval=0.1).start()
    try:
        c0 = MasterClient(*srv.address)
        c0.set_dataset(paths)
        # consumer A takes a task and dies
        dead = c0.get_task()
        c0.close()
        # consumer B streams the whole pass, incl. the re-dispatched chunk
        cb = MasterClient(*srv.address)
        samples = list(cloud_reader(cb)())
        assert len(samples) == 64
    finally:
        srv.stop()


def test_cloud_reader_skips_corrupt_chunk(tmp_path):
    paths = dump_to_chunks(mnist.train(48), str(tmp_path),
                           samples_per_chunk=16)
    # corrupt the middle chunk's payload
    raw = bytearray(open(paths[1], "rb").read())
    raw[20] ^= 0xFF
    open(paths[1], "wb").write(bytes(raw))
    srv = MasterServer(timeout_s=5.0, failure_max=2, tick_interval=0.1).start()
    try:
        c = MasterClient(*srv.address)
        c.set_dataset(paths)
        samples = list(cloud_reader(c)())
        # the corrupt chunk is retried then discarded; the rest arrives
        assert len(samples) == 32
        assert c.stats()[3] == 1               # one discarded task
    finally:
        srv.stop()
