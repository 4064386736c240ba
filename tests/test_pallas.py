"""Pallas kernel numerics vs the jnp reference path (interpret mode on CPU) —
the per-op equivalence discipline of the MKLDNN tester (SURVEY.md §8.3)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas_kernels import flash_attention


def _masked_attention(q, k, v, causal, lens=None):
    """Dense f32 reference with the kernels' two masks."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    T, S, D = q.shape[1], k.shape[1], q.shape[-1]
    s = jnp.einsum("bthd,bshd->bhts", q, k) * (D ** -0.5)
    if lens is not None:
        key_ok = (jnp.arange(S)[None, :] < lens[:, None])[:, None, None, :]
        s = jnp.where(key_ok, s, -1e30)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((T, S), bool))[None, None], s, -1e30)
    return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)


def _full_attention(q, k, v, causal=False):
    return _masked_attention(q, k, v, causal)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("T", [32, 48])   # 48 exercises the padded-tail path
def test_flash_attention_matches_reference(causal, T):
    rng = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(rng, 3)
    B, H, D = 2, 2, 16
    q = jax.random.normal(kq, (B, T, H, D))
    k = jax.random.normal(kk, (B, T, H, D))
    v = jax.random.normal(kv, (B, T, H, D))
    ref = _full_attention(q, k, v, causal)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_jits_and_grads():
    rng = jax.random.PRNGKey(1)
    q = jax.random.normal(rng, (1, 32, 2, 16))

    def loss(q):
        return jnp.sum(flash_attention(q, q, q, causal=True, block_q=16,
                                       block_k=16, interpret=True))

    g = jax.jit(jax.grad(loss))(q)
    assert np.isfinite(np.asarray(g)).all()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("T", [32, 48])   # 48 exercises the padded-tail path
def test_flash_backward_kernels_match_reference(causal, T):
    """The Pallas dq / dkv kernels vs autodiff through dense attention —
    the grad-side analog of the MKLDNN equivalence discipline."""
    rng = jax.random.PRNGKey(7)
    kq, kk, kv, kg = jax.random.split(rng, 4)
    B, H, D = 2, 2, 16
    q = jax.random.normal(kq, (B, T, H, D))
    k = jax.random.normal(kk, (B, T, H, D))
    v = jax.random.normal(kv, (B, T, H, D))
    g = jax.random.normal(kg, (B, T, H, D))

    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=16,
                                       block_k=16, interpret=True) * g)

    def f_ref(q, k, v):
        return jnp.sum(_full_attention(q, k, v, causal) * g)

    got = jax.grad(f, (0, 1, 2))(q, k, v)
    want = jax.grad(f_ref, (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_kv_lens_matches_masked_reference(causal):
    """Per-sample kv-length masking (the LoD / padded-source path): output
    AND all grads must match dense attention with an explicit key mask, and
    masked keys' dk/dv must be exactly zero."""
    rng = jax.random.PRNGKey(11)
    kq, kk, kv, kg = jax.random.split(rng, 4)
    B, T, S, H, D = 3, 32, 32, 2, 16
    q = jax.random.normal(kq, (B, T, H, D))
    k = jax.random.normal(kk, (B, S, H, D))
    v = jax.random.normal(kv, (B, S, H, D))
    g = jax.random.normal(kg, (B, T, H, D))
    lens = jnp.array([32, 17, 5], jnp.int32)

    def ref(q, k, v):
        s = jnp.einsum("bthd,bshd->bhts", q, k) * (D ** -0.5)
        key_ok = (jnp.arange(S)[None, :] < lens[:, None])[:, None, None, :]
        s = jnp.where(key_ok, s, -1e30)
        if causal:
            mask = jnp.tril(jnp.ones((T, S), bool))
            s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhts,bshd->bthd", p, v)

    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, kv_lens=lens,
                                       block_q=16, block_k=16,
                                       interpret=True) * g)

    def f_ref(q, k, v):
        return jnp.sum(ref(q, k, v) * g)

    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=causal, kv_lens=lens,
                                   block_q=16, block_k=16, interpret=True)),
        np.asarray(ref(q, k, v)), rtol=2e-4, atol=2e-4)
    got = jax.jit(jax.grad(f, (0, 1, 2)))(q, k, v)
    want = jax.grad(f_ref, (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)
    _, dk, dv = got
    assert np.all(np.asarray(dk)[1, 17:] == 0)      # masked keys: exact zero
    assert np.all(np.asarray(dv)[2, 5:] == 0)


@pytest.mark.parametrize("dense_route", [True, False])
def test_flash_attention_kv_len_zero_sample_is_zeroed(dense_route):
    """A fully-masked sample (kv_lens == 0) must produce exactly-zero output
    rows and exactly-zero grads — not garbage/NaN — on both the short-seq
    dense route and the Pallas route; other samples must be unaffected."""
    rng = jax.random.PRNGKey(13)
    kq, kk, kv = jax.random.split(rng, 3)
    B, T, S, H, D = 3, 32, 32, 2, 16
    q = jax.random.normal(kq, (B, T, H, D))
    k = jax.random.normal(kk, (B, S, H, D))
    v = jax.random.normal(kv, (B, S, H, D))
    lens = jnp.array([32, 0, 5], jnp.int32)
    blocks = {} if dense_route else dict(block_q=16, block_k=16,
                                         interpret=True)

    def f(q, k, v):
        return flash_attention(q, k, v, kv_lens=lens, **blocks)

    out = np.asarray(f(q, k, v))
    assert np.all(np.isfinite(out))
    assert np.all(out[1] == 0)
    # the other samples match a run without the dead sample in the batch
    ref = np.asarray(flash_attention(q[::2], k[::2], v[::2],
                                     kv_lens=lens[::2], **blocks))
    np.testing.assert_allclose(out[::2], ref, rtol=2e-5, atol=2e-5)

    dq, dk, dv = jax.grad(lambda *a: jnp.sum(f(*a)), (0, 1, 2))(q, k, v)
    for garr in (dq, dk, dv):
        garr = np.asarray(garr)
        assert np.all(np.isfinite(garr))
        assert np.all(garr[1] == 0)


def test_flash_cross_attention_shorter_kv():
    """S != T cross-attention shape with kv_lens (the NMT decoder->encoder
    use): matches the dense reference."""
    rng = jax.random.PRNGKey(13)
    kq, kk, kv = jax.random.split(rng, 3)
    B, T, S, H, D = 2, 48, 32, 2, 16
    q = jax.random.normal(kq, (B, T, H, D))
    k = jax.random.normal(kk, (B, S, H, D))
    v = jax.random.normal(kv, (B, S, H, D))
    lens = jnp.array([32, 9], jnp.int32)
    s = jnp.einsum("bthd,bshd->bhts", q, k) * (D ** -0.5)
    key_ok = (jnp.arange(S)[None, :] < lens[:, None])[:, None, None, :]
    p = jax.nn.softmax(jnp.where(key_ok, s, -1e30), axis=-1)
    ref = jnp.einsum("bhts,bshd->bthd", p, v)
    out = flash_attention(q, k, v, kv_lens=lens, block_q=16, block_k=16,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_backward_no_dense_scores_in_jaxpr():
    """The [T, T] score matrix must not materialise in HBM in the backward
    jaxpr (the round-1 fallback recomputed dense attention)."""
    T = 64
    q = jnp.zeros((1, T, 1, 16))

    def loss(q):
        return jnp.sum(flash_attention(q, q, q, block_q=16, block_k=16,
                                       interpret=True))

    jaxpr = jax.make_jaxpr(jax.grad(loss))(q)
    for eqn in jaxpr.jaxpr.eqns:
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            assert not (len(shape) >= 2 and shape[-1] == T and
                        shape[-2] == T), f"dense [T,T] tensor in bwd: {eqn}"


def _qkvg(seed, B, T, S, H, D, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shapes = [(B, T, H, D), (B, S, H, D), (B, S, H, D), (B, T, H, D)]
    return [jax.random.normal(k_, sh).astype(dtype)
            for k_, sh in zip(ks, shapes)]


@pytest.mark.parametrize("with_lens", [False, True], ids=["full", "kv_lens"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("blocks", [(16, 16), (32, 16), (16, 32), (16, 128)],
                         ids=lambda b: f"{b[0]}x{b[1]}")   # 128: one k-block
@pytest.mark.parametrize("T", [80, 75])    # 5 blocks a side; 75: ragged tail
def test_flash_causal_walk_matches_dense(T, blocks, dtype, with_lens):
    """The causal kernels visit only the block pairs at or below the
    diagonal and mask only those it crosses (forward, dq, dk/dv): at >= 3
    blocks a side, with a ragged tail, unequal blocks and per-sample
    lengths, outputs and all three grads still match dense attention."""
    B, H, D = 3, 2, 16
    q, k, v, g = _qkvg(21, B, T, T, H, D, dtype)
    lens = jnp.array([T, 37, 5], jnp.int32) if with_lens else None
    bq, bk = blocks

    def f(q, k, v):
        return flash_attention(q, k, v, causal=True, kv_lens=lens, block_q=bq,
                               block_k=bk, interpret=True)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)
                                       * g.astype(jnp.float32))

    tol = 2e-4 if dtype == "float32" else 3e-2
    ref = functools.partial(_masked_attention, causal=True, lens=lens)
    np.testing.assert_allclose(np.asarray(f(q, k, v), np.float32),
                               np.asarray(ref(q, k, v)), rtol=tol, atol=tol)
    got = jax.jit(jax.grad(loss(f), (0, 1, 2)))(q, k, v)
    want = jax.grad(loss(ref), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol)
    if with_lens:                           # masked keys: exact zero
        assert np.all(np.asarray(got[1], np.float32)[1, 37:] == 0)
        assert np.all(np.asarray(got[2], np.float32)[2, 5:] == 0)


@pytest.mark.parametrize("with_lens", [False, True], ids=["full", "kv_lens"])
@pytest.mark.parametrize("T,S", [(80, 80), (75, 75), (48, 80), (80, 43)],
                         ids=lambda n: str(n))
def test_flash_noncausal_and_cross_match_dense(T, S, with_lens):
    """What the causal walk must not touch: non-causal squares and S != T
    calls at several blocks a side, outputs and grads against dense."""
    B, H, D = 2, 2, 16
    q, k, v, g = _qkvg(23, B, T, S, H, D, jnp.float32)
    lens = jnp.array([S, 19], jnp.int32) if with_lens else None

    def f(q, k, v):
        return flash_attention(q, k, v, kv_lens=lens, block_q=16, block_k=16,
                               interpret=True)

    ref = functools.partial(_masked_attention, causal=False, lens=lens)
    np.testing.assert_allclose(np.asarray(f(q, k, v)),
                               np.asarray(ref(q, k, v)), rtol=2e-4, atol=2e-4)
    got = jax.grad(lambda *a: jnp.sum(f(*a) * g), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(ref(*a) * g), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def _flash_grids(fn, *args):
    """{kernel name: grid} of the pallas_calls in ``fn``'s jaxpr."""
    grids = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids[eqn.params["name"]] = tuple(
                    eqn.params["grid_mapping"].grid)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return grids


@pytest.mark.parametrize("case, causal, T, S, want", [
    # the parent's grids: (B*H, Tp / 512) and (B*H, Sp / min(1024, S))
    ("noncausal", False, 1024, 1024, ((4, 2), (4, 2), (4, 1))),
    ("noncausal-long", False, 2048, 2048, ((4, 4), (4, 4), (4, 2))),
    ("cross", False, 600, 1500, ((4, 2), (4, 2), (4, 2))),
    ("ring-half", False, 1024, 512, ((4, 2), (4, 2), (4, 1))),
    ("one-block", True, 256, 256, ((4, 1), (4, 1), (4, 1))),
    ("causal-train-cell", True, 1023, 1023, ((4, 2), (4, 2), (4, 1))),
    ("causal-long", True, 4096, 4096, ((4, 8), (4, 8), (4, 4))),
])
def test_flash_default_grids(case, causal, T, S, want):
    """Default blocks are 512 / 1024 for every call, so a non-causal call,
    an S != T call, a call with one block a side — and the causal square,
    whose walk narrows the tile on the diagonal inside a program — all
    keep the kernel grids they had before the causal walk (PR 31)."""
    q = jnp.zeros((2, T, 2, 64), jnp.bfloat16)
    kv = jnp.zeros((2, S, 2, 64), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=causal,
                               interpret=True).astype(jnp.float32).sum()

    grids = _flash_grids(jax.grad(loss, (0, 1, 2)), q, kv, kv)
    assert (grids["flash_attention_fwd"], grids["flash_attention_bwd_dq"],
            grids["flash_attention_bwd_dkv"]) == want


@pytest.mark.parametrize("case, causal, T, S, blocks, visited, grid", [
    ("causal", True, 80, 80, (16, 16), 15, 25),          # n (n + 1) / 2
    ("causal-ragged", True, 75, 75, (16, 16), 15, 25),
    ("causal-wide-q", True, 80, 80, (32, 16), 4 + 8 + 12, 36),   # Tp 96
    ("causal-wide-k", True, 80, 80, (16, 32), 21, 36),
    ("causal-one-k-block", True, 75, 75, (16, 128), 15, 24),    # ragged
    ("causal-one-block", True, 16, 16, (16, 16), 1, 1),
    ("noncausal", False, 80, 80, (16, 16), 25, 25),
    ("cross", False, 48, 80, (16, 16), 15, 15),
])
def test_flash_block_pairs_counter(case, causal, T, S, blocks, visited, grid):
    """kernels.flash_block_pairs_total: visited / grid is the share of the
    square a traced call's kernels walk, per kernel, over B * H squares."""
    from paddle_tpu import obs
    B, H, D = 2, 3, 16
    q, k, v, g = _qkvg(29, B, T, S, H, D, jnp.float32)
    r = obs.MetricsRegistry()
    jax.clear_caches()      # counted once a TRACE: a cached one counts nothing
    with obs.ObsSession(registry=r).installed():
        jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, causal=causal, block_q=blocks[0], block_k=blocks[1],
            interpret=True) * g), (0, 1, 2))(q, k, v)
    c = r.counter("kernels.flash_block_pairs_total")
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dq",
                   "flash_attention_bwd_dkv"):
        assert c.get(kernel=kernel, state="visited") == B * H * visited, kernel
        assert c.get(kernel=kernel, state="grid") == B * H * grid, kernel


@pytest.mark.parametrize("block_b,chunk_t", [(2, None), (5, 3)])
def test_lstm_sequence_fused_matches_scan(block_b, chunk_t):
    """The fused whole-sequence LSTM kernel (hl_cuda_lstm.cu analog: u and
    h/c resident in VMEM across all T steps) must match the lax.scan LSTM
    bit-for-bit, including variable-length masking and padded batch tails."""
    from paddle_tpu.ops import rnn as R
    from paddle_tpu.ops.pallas_kernels import lstm_sequence_fused

    rs = np.random.RandomState(3)
    B, T, D, H = 5, 7, 4, 6
    x = jnp.asarray(rs.randn(B, T, D), jnp.float32)
    lens = jnp.asarray(rs.randint(1, T + 1, B), jnp.int32)
    w = jnp.asarray(rs.randn(D, 4 * H) * 0.3, jnp.float32)
    u = jnp.asarray(rs.randn(H, 4 * H) * 0.3, jnp.float32)
    b = jnp.asarray(rs.randn(4 * H) * 0.1, jnp.float32)

    ref_out, ref_state = R.lstm(x, lens, w, u, b, forget_bias=1.0)
    xw = jnp.matmul(x.reshape(B * T, D), w).reshape(B, T, 4 * H)
    out, ht, ct = lstm_sequence_fused(xw, lens, u, b, forget_bias=1.0,
                                      block_b=block_b, chunk_t=chunk_t,
                                      interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ht), np.asarray(ref_state.h),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ct), np.asarray(ref_state.c),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("block_b,chunk_t", [(2, None), (5, 3)])
def test_gru_sequence_fused_matches_scan(block_b, chunk_t):
    """Fused whole-sequence GRU kernel (hl_gpu_gru.cuh analog) vs the
    lax.scan GRU: bit-exact incl. masking and padded batch tails."""
    from paddle_tpu.ops import rnn as R
    from paddle_tpu.ops.pallas_kernels import gru_sequence_fused

    rs = np.random.RandomState(5)
    B, T, D, H = 5, 7, 4, 6
    x = jnp.asarray(rs.randn(B, T, D), jnp.float32)
    lens = jnp.asarray(rs.randint(1, T + 1, B), jnp.int32)
    w = jnp.asarray(rs.randn(D, 3 * H) * 0.3, jnp.float32)
    u = jnp.asarray(rs.randn(H, 3 * H) * 0.3, jnp.float32)
    b = jnp.asarray(rs.randn(3 * H) * 0.1, jnp.float32)

    ref_out, ref_h = R.gru(x, lens, w, u, b)
    xw = jnp.matmul(x.reshape(B * T, D), w).reshape(B, T, 3 * H)
    out, ht = gru_sequence_fused(xw, lens, u, b, block_b=block_b,
                                 chunk_t=chunk_t, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ht), np.asarray(ref_h),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("block_b,chunk_t", [(2, None), (5, 4)])
def test_lstm_fused_backward_kernel_matches_scan_grads(block_b, chunk_t):
    """The hand-written reverse-recurrence LSTM kernel
    (hl_lstm_parallel_backward_data/_weight analog) must produce the same
    dx/dw/du/db/dh0/dc0 as autodiff through the scan, incl. variable
    lengths, nonzero initial state, and padded batch tails."""
    from paddle_tpu.ops import rnn as R

    rs = np.random.RandomState(7)
    B, T, D, H = 5, 7, 4, 6
    x = jnp.asarray(rs.randn(B, T, D), jnp.float32)
    lens = jnp.asarray(rs.randint(1, T + 1, B), jnp.int32)
    w = jnp.asarray(rs.randn(D, 4 * H) * 0.3, jnp.float32)
    u = jnp.asarray(rs.randn(H, 4 * H) * 0.3, jnp.float32)
    b = jnp.asarray(rs.randn(4 * H) * 0.1, jnp.float32)
    h0 = jnp.asarray(rs.randn(B, H) * 0.2, jnp.float32)
    c0 = jnp.asarray(rs.randn(B, H) * 0.2, jnp.float32)
    # weight every output element differently so all grad paths are probed
    wo = jnp.asarray(rs.randn(B, T, H), jnp.float32)
    wh = jnp.asarray(rs.randn(B, H), jnp.float32)
    wc = jnp.asarray(rs.randn(B, H), jnp.float32)

    def loss(fn):
        def inner(x, w, u, b, h0, c0):
            out, state = fn(x, w, u, b, h0, c0)
            return (jnp.sum(out * wo) + jnp.sum(state.h * wh)
                    + jnp.sum(state.c * wc))
        return inner

    def scan_path(x, w, u, b, h0, c0):
        return R.lstm(x, lens, w, u, b, h0=h0, c0=c0, forget_bias=1.0,
                      fused=False)

    def fused_path(x, w, u, b, h0, c0):
        out, ht, ct = R._lstm_fused(x, lens, w, u, b, h0, c0, 1.0, block_b,
                                    chunk_t)
        return out, R.LSTMState(ht, ct)

    g_ref = jax.grad(loss(scan_path), argnums=(0, 1, 2, 3, 4, 5))(
        x, w, u, b, h0, c0)
    g_fused = jax.grad(loss(fused_path), argnums=(0, 1, 2, 3, 4, 5))(
        x, w, u, b, h0, c0)
    for name, a, bb in zip("x w u b h0 c0".split(), g_ref, g_fused):
        np.testing.assert_allclose(np.asarray(bb), np.asarray(a),
                                   rtol=2e-5, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("block_b,chunk_t", [(2, None), (5, 4)])
def test_gru_fused_backward_kernel_matches_scan_grads(block_b, chunk_t):
    """Hand-written whole-sequence GRU backward kernel vs autodiff through
    the scan."""
    from paddle_tpu.ops import rnn as R

    rs = np.random.RandomState(11)
    B, T, D, H = 5, 7, 4, 6
    x = jnp.asarray(rs.randn(B, T, D), jnp.float32)
    lens = jnp.asarray(rs.randint(1, T + 1, B), jnp.int32)
    w = jnp.asarray(rs.randn(D, 3 * H) * 0.3, jnp.float32)
    u = jnp.asarray(rs.randn(H, 3 * H) * 0.3, jnp.float32)
    b = jnp.asarray(rs.randn(3 * H) * 0.1, jnp.float32)
    h0 = jnp.asarray(rs.randn(B, H) * 0.2, jnp.float32)
    wo = jnp.asarray(rs.randn(B, T, H), jnp.float32)
    wh = jnp.asarray(rs.randn(B, H), jnp.float32)

    def loss(fn):
        def inner(x, w, u, b, h0):
            out, ht = fn(x, w, u, b, h0)
            return jnp.sum(out * wo) + jnp.sum(ht * wh)
        return inner

    def scan_path(x, w, u, b, h0):
        return R.gru(x, lens, w, u, b, h0=h0, fused=False)

    def fused_path(x, w, u, b, h0):
        return R._gru_fused(x, lens, w, u, b, h0, block_b, chunk_t)

    g_ref = jax.grad(loss(scan_path), argnums=(0, 1, 2, 3, 4))(x, w, u, b, h0)
    g_fused = jax.grad(loss(fused_path), argnums=(0, 1, 2, 3, 4))(
        x, w, u, b, h0)
    for name, a, bb in zip("x w u b h0".split(), g_ref, g_fused):
        np.testing.assert_allclose(np.asarray(bb), np.asarray(a),
                                   rtol=2e-5, atol=2e-5, err_msg=name)
