"""Host-offloaded embedding path (runtime/host_embedding.py) — the
sparse-remote capability (trainer/RemoteParameterUpdater.h:265,
pserver/ParameterServer2.h:510 getParameterSparse): host-resident master
table, touched-row streaming, sparse row updates, and the exactness of the
overlapped prefetcher. Equivalence oracle: the same model trained with the
table fully on-device."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.runtime import HostEmbeddingTable, HostEmbedPrefetcher

VOCAB, DIM, B, T = 50, 8, 4, 6


def _batches(n, seed=0, vocab=VOCAB):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, vocab, (B, T)) for _ in range(n)]


def _head(seed=1):
    rs = np.random.RandomState(seed)
    return jnp.asarray(rs.standard_normal((DIM,)).astype(np.float32))


def _device_loss(rows, inverse, w):
    """Toy objective over the looked-up embeddings; grads wrt rows are the
    merged SelectedRows gradient."""
    e = HostEmbeddingTable.lookup(rows, inverse)       # [B, T, D]
    return jnp.sum(jnp.tanh(e @ w))


@pytest.mark.parametrize("optimizer", ["sgd", "adagrad"])
def test_offloaded_matches_on_device_table(optimizer):
    """N serial steps through the host table == the same steps with the
    whole table on device (the ShardedEmbedding-style dense path)."""
    lr = 0.1
    rs = np.random.RandomState(3)
    init = rs.standard_normal((VOCAB, DIM)).astype(np.float32) * 0.1
    w = _head()
    batches = _batches(5)

    # --- offloaded path
    table = HostEmbeddingTable(VOCAB, DIM, optimizer=optimizer, lr=lr,
                               capacity=B * T, init=init.copy())
    grad_fn = jax.jit(jax.grad(_device_loss))
    for ids in batches:
        batch = table.prefetch(ids)
        g = grad_fn(batch.rows, batch.inverse, w)
        table.apply_grad(batch, g)

    # --- on-device dense oracle (same optimizer math in numpy/f32)
    dense = init.copy()
    accum = np.zeros_like(dense)
    dgrad = jax.jit(jax.grad(
        lambda t, ids, w: _device_loss(t, ids, w)))
    for ids in batches:
        g = np.asarray(dgrad(jnp.asarray(dense), jnp.asarray(ids), w))
        if optimizer == "sgd":
            dense -= lr * g
        else:
            touched = np.unique(ids)
            accum[touched] += g[touched] ** 2
            denom = np.sqrt(accum[touched]) + 1e-6
            dense[touched] -= lr * g[touched] / denom

    got = table.rows_host(np.arange(VOCAB))
    np.testing.assert_allclose(got, dense, rtol=2e-5, atol=2e-6)


def test_untouched_rows_never_move():
    """Adagrad accumulators and params of rows no batch touches must stay
    bit-identical (the sparse contract; dense offload would decay them)."""
    init = np.ones((VOCAB, DIM), np.float32)
    table = HostEmbeddingTable(VOCAB, DIM, optimizer="adagrad", lr=0.5,
                               capacity=8, init=init.copy())
    ids = np.array([[1, 2, 3, 1]])
    w = _head()
    batch = table.prefetch(ids)
    g = jax.grad(_device_loss)(batch.rows, batch.inverse, w)
    table.apply_grad(batch, g)
    untouched = np.setdiff1d(np.arange(VOCAB), np.unique(ids))
    np.testing.assert_array_equal(table.rows_host(untouched),
                                  init[untouched])
    assert not np.allclose(table.rows_host(np.unique(ids)),
                           init[np.unique(ids)])


def test_capacity_exceeded_raises():
    table = HostEmbeddingTable(VOCAB, DIM, capacity=4)
    with pytest.raises(ValueError, match="capacity"):
        table.prefetch(np.arange(10))


def test_prefetcher_overlap_is_exact():
    """Batches with heavy id overlap: the speculative prefetch of batch i+1
    runs before batch i's update, so without the intersection fix-up the
    read would be stale. Final table must equal the serial path's."""
    lr = 0.2
    rs = np.random.RandomState(7)
    init = rs.standard_normal((VOCAB, DIM)).astype(np.float32) * 0.1
    w = _head()
    # consecutive batches share ~half their ids
    batches = [rs.randint(0, 12, (B, T)) for _ in range(6)]

    serial = HostEmbeddingTable(VOCAB, DIM, lr=lr, capacity=B * T,
                                init=init.copy())
    grad_fn = jax.jit(jax.grad(_device_loss))
    for ids in batches:
        b = serial.prefetch(ids)
        serial.apply_grad(b, grad_fn(b.rows, b.inverse, w))

    overlapped = HostEmbeddingTable(VOCAB, DIM, lr=lr, capacity=B * T,
                                    init=init.copy())
    pf = HostEmbedPrefetcher(overlapped, iter(batches))
    steps = 0
    while True:
        b = pf.next()
        if b is None:
            break
        pf.commit(b, grad_fn(b.rows, b.inverse, w))
        steps += 1
    assert steps == len(batches)
    np.testing.assert_array_equal(
        overlapped.rows_host(np.arange(VOCAB)),
        serial.rows_host(np.arange(VOCAB)))


def test_checkpoint_roundtrip():
    table = HostEmbeddingTable(VOCAB, DIM, optimizer="adagrad", capacity=8)
    ids = np.array([[1, 2, 3, 4]])
    w = _head()
    b = table.prefetch(ids)
    table.apply_grad(b, jax.grad(_device_loss)(b.rows, b.inverse, w))
    blob = table.serialize()

    restored = HostEmbeddingTable(VOCAB, DIM, optimizer="adagrad",
                                  capacity=8)
    restored.deserialize(blob)
    np.testing.assert_array_equal(restored.rows_host(np.arange(VOCAB)),
                                  table.rows_host(np.arange(VOCAB)))
    # post-restore updates continue with the restored accumulators
    b2 = restored.prefetch(ids)
    restored.apply_grad(b2, jax.grad(_device_loss)(b2.rows, b2.inverse, w))
    b3 = table.prefetch(ids)
    table.apply_grad(b3, jax.grad(_device_loss)(b3.rows, b3.inverse, w))
    np.testing.assert_array_equal(restored.rows_host(np.arange(VOCAB)),
                                  table.rows_host(np.arange(VOCAB)))
