"""Distributed-logic tests on the 8-device virtual CPU mesh.

Mirrors the reference's in-process multi-node testing strategy (SURVEY.md §4.3:
pservers on localhost ports, MultiGradientMachine with threads): every sharding
and collective path runs here without hardware.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from paddle_tpu import parallel as pp
from paddle_tpu.nn import Linear, Module, Sequential
from paddle_tpu.optimizer import SGD, Adam


def test_make_mesh_axes_and_wildcard():
    mesh = pp.make_mesh(data=-1)
    assert mesh.shape == {"data": 8}
    mesh = pp.make_mesh(data=4, model=2)
    assert mesh.shape["data"] == 4 and mesh.shape["model"] == 2
    # model axis must be innermost (nearest-neighbour ICI)
    assert mesh.axis_names[-1] == "model"
    with pytest.raises(ValueError):
        pp.make_mesh(data=3, model=3)


def test_collectives_roundtrip():
    mesh = pp.make_mesh(data=8)

    def f(x):
        s = pp.all_reduce(x, "data")
        g = pp.all_gather(x, "data")
        rs = pp.reduce_scatter(g, "data")
        idx = pp.axis_index("data")
        nxt = pp.permute_ring(idx.astype(jnp.float32).reshape(1), "data")
        return s, g, rs, nxt

    x = jnp.arange(8.0)
    fn = jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                       out_specs=(P("data"), P("data"), P("data"), P("data")))
    s, g, rs, nxt = fn(x)
    np.testing.assert_allclose(s, np.full(8, 28.0))          # sum 0..7 bcast
    np.testing.assert_allclose(np.asarray(g)[:8], np.arange(8.0))  # gathered copy
    # each device held a full arange(8) after gather; scatter-sum gives 8*i
    np.testing.assert_allclose(rs, 8.0 * np.arange(8.0))
    # ring: device i receives index of device i-1
    np.testing.assert_allclose(np.sort(np.asarray(nxt)), np.arange(8.0))


def _toy_data(n=64, din=12, classes=3, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, din).astype(np.float32)
    w = rs.randn(din, classes).astype(np.float32)
    y = np.argmax(x @ w, axis=1).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(y)


class _Net(Module):
    def __init__(self):
        super().__init__()
        self.l1 = Linear(12, 32, act=jax.nn.relu)
        self.l2 = Linear(32, 3)

    def __call__(self, params, x, **kw):
        return self.l2(params["l2"], self.l1(params["l1"], x))


def _loss_fn(model):
    def loss(params, x, y):
        logits = model(params, x)
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[:, None], axis=1).mean()
    return loss


def test_data_parallel_matches_single_device():
    """Equivalence test in the spirit of test_CompareSparse.cpp (SURVEY §4.2):
    8-way DP over the mesh must reproduce single-device full-batch training."""
    x, y = _toy_data()
    model = _Net()
    params0 = model.init(jax.random.PRNGKey(1))
    loss = _loss_fn(model)

    # single-device run
    opt = SGD(0.1)
    state = opt.init(params0)
    p_ref = params0
    for _ in range(5):
        _, grads = jax.value_and_grad(loss)(p_ref, x, y)
        p_ref, state = opt.update(grads, state, p_ref)

    # 8-way data parallel
    dp = pp.DataParallel(loss, SGD(0.1), mesh=pp.make_mesh(data=8))
    p, s = dp.init(model.init(jax.random.PRNGKey(1)))
    bx, by = dp.shard_batch((x, y))
    for _ in range(5):
        p, s, l = dp.step(p, s, bx, by)

    for (k1, a), (k2, b) in zip(Module.named_parameters(p_ref),
                                Module.named_parameters(jax.device_get(p))):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5), k1


def test_zero1_matches_plain_dp():
    """TRUE ZeRO-1 (reduce-scatter grads, shard-local optimizer, all-gather
    params) must train identically to plain replicated-optimizer DP."""
    x, y = _toy_data()
    model = _Net()
    loss = _loss_fn(model)
    dp0 = pp.DataParallel(loss, Adam(1e-2), mesh=pp.make_mesh(data=8))
    z = pp.Zero1DataParallel(loss, Adam(1e-2), mesh=pp.make_mesh(data=8))
    pa, sa = dp0.init(model.init(jax.random.PRNGKey(2)))
    zs = z.init(model.init(jax.random.PRNGKey(2)))
    ba = dp0.shard_batch((x, y))
    for _ in range(3):
        pa, sa, _ = dp0.step(pa, sa, *ba)
        zs, _ = z.step(zs, *ba)
    pb = z.params(zs)
    for (_, a), (_, b) in zip(Module.named_parameters(jax.device_get(pa)),
                              Module.named_parameters(pb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_zero1_optimizer_state_is_partitioned():
    """The point of ZeRO-1: every optimizer slot buffer (and the master flat
    param vector) is sharded over the data axis — each device holds 1/n."""
    from jax.sharding import PartitionSpec as P
    x, y = _toy_data()
    model = _Net()
    z = pp.Zero1DataParallel(_loss_fn(model), Adam(1e-2),
                             mesh=pp.make_mesh(data=8))
    zs = z.init(model.init(jax.random.PRNGKey(0)))
    zs, _ = z.step(zs, *z.shard_batch((x, y)))

    def assert_sharded(arr):
        assert arr.sharding.spec == P("data"), arr.sharding
        local = arr.addressable_shards[0].data
        assert local.shape[0] * 8 == arr.shape[0]

    assert_sharded(zs.flat)
    for leaf in jax.tree_util.tree_leaves(zs.opt_state["slots"]):
        assert_sharded(leaf)


def test_tensor_parallel_linear_matches_dense():
    mesh = pp.make_mesh(data=2, model=4)

    class TPNet(Module):
        def __init__(self):
            super().__init__()
            self.up = pp.ColumnParallelLinear(16, 64, act=jax.nn.relu)
            self.down = pp.RowParallelLinear(64, 8)

        def __call__(self, params, x, **kw):
            return self.down(params["down"], self.up(params["up"], x))

    net = TPNet()
    params = net.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    ref = net(params, x)  # no mesh: plain dense math

    rules = pp.ShardingRules([(pat, spec) for pat, spec in
                              pp.tensor_parallel.collect_tp_rules(net)] +
                             [(r".*", P())])
    sp = rules.apply(mesh, params)
    xs = pp.shard_batch(x, mesh, "data")
    with mesh:
        out = jax.jit(net)(sp, xs)
    np.testing.assert_allclose(jax.device_get(out), jax.device_get(ref),
                               rtol=1e-5, atol=1e-5)


def test_sharded_embedding_lookup():
    mesh = pp.make_mesh(model=8)
    emb = pp.ShardedEmbedding(64, 16)
    params = emb.init(jax.random.PRNGKey(0))
    ids = jnp.array([0, 5, 63, 17])
    ref = jnp.take(params["table"], ids, axis=0)
    sp = pp.ShardingRules(pp.tensor_parallel.collect_tp_rules(emb)).apply(mesh, params)
    with mesh:
        out = jax.jit(emb)(sp, ids)
    np.testing.assert_allclose(jax.device_get(out), jax.device_get(ref), rtol=1e-6)


def _full_attention(q, k, v, causal=False):
    B, T, H, D = q.shape
    s = jnp.einsum("bthd,bshd->bhts", q, k) * (D ** -0.5)
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_exact(causal):
    mesh = pp.make_mesh(seq=8)
    rng = jax.random.PRNGKey(3)
    kq, kk, kv = jax.random.split(rng, 3)
    B, T, H, D = 2, 64, 4, 8
    q = jax.random.normal(kq, (B, T, H, D))
    k = jax.random.normal(kk, (B, T, H, D))
    v = jax.random.normal(kv, (B, T, H, D))
    ref = _full_attention(q, k, v, causal)
    out = pp.ring_self_attention(mesh, q, k, v, causal=causal)
    np.testing.assert_allclose(jax.device_get(out), jax.device_get(ref),
                               rtol=2e-4, atol=2e-4)


def test_zigzag_fallback_warns_and_stays_exact():
    """T not divisible by 2*n forces the contiguous causal layout; the
    fallback must be loud (it wastes ~half the FLOPs) and still correct."""
    mesh = pp.make_mesh(seq=8)
    rng = jax.random.PRNGKey(9)
    kq, kk, kv = jax.random.split(rng, 3)
    B, T, H, D = 1, 40, 2, 8          # 40 % 16 != 0 but 40 % 8 == 0
    q = jax.random.normal(kq, (B, T, H, D))
    k = jax.random.normal(kk, (B, T, H, D))
    v = jax.random.normal(kv, (B, T, H, D))
    with pytest.warns(UserWarning, match="CONTIGUOUS causal layout"):
        out = pp.ring_self_attention(mesh, q, k, v, causal=True)
    ref = _full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(jax.device_get(out), jax.device_get(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_attention_matches_full(causal):
    rng = jax.random.PRNGKey(4)
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (2, 48, 2, 8))
    k = jax.random.normal(kk, (2, 48, 2, 8))
    v = jax.random.normal(kv, (2, 48, 2, 8))
    ref = _full_attention(q, k, v, causal)
    out = pp.blockwise_attention(q, k, v, block_size=16, causal=causal)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_ulysses_attention_matches_full():
    mesh = pp.make_mesh(seq=8)
    rng = jax.random.PRNGKey(5)
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (2, 64, 8, 4))
    k = jax.random.normal(kk, (2, 64, 8, 4))
    v = jax.random.normal(kv, (2, 64, 8, 4))
    ref = _full_attention(q, k, v)
    out = pp.ulysses_attention(mesh, q, k, v)
    np.testing.assert_allclose(jax.device_get(out), jax.device_get(ref),
                               rtol=2e-4, atol=2e-4)


def test_pipeline_matches_sequential():
    mesh = pp.make_mesh(pipe=4)
    stage = pp.PipelineStage(lambda: Linear(16, 16, act=jnp.tanh), n_stages=4)
    params = stage.init(jax.random.PRNGKey(6))
    x = jax.random.normal(jax.random.PRNGKey(7), (8, 16))
    ref = stage(params, x)  # sequential scan over stages

    def stage_fn(p, mb):
        return jnp.tanh(mb @ p["w"] + p["b"])

    run = pp.pipeline_spmd(stage_fn, mesh, n_microbatches=4)
    with mesh:
        out = run(params, x)
    np.testing.assert_allclose(jax.device_get(out), jax.device_get(ref),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_trains():
    """Autodiff flows through the ppermute pipeline."""
    mesh = pp.make_mesh(pipe=4)
    stage = pp.PipelineStage(lambda: Linear(8, 8, act=jnp.tanh), n_stages=4)
    params = stage.init(jax.random.PRNGKey(8))
    x = jax.random.normal(jax.random.PRNGKey(9), (8, 8))
    y = jax.random.normal(jax.random.PRNGKey(10), (8, 8))

    def stage_fn(p, mb):
        return jnp.tanh(mb @ p["w"] + p["b"])

    run = pp.pipeline_spmd(stage_fn, mesh, n_microbatches=2)

    def loss(params):
        return jnp.mean((run(params, x) - y) ** 2)

    with mesh:
        l0 = loss(params)
        g = jax.grad(loss)(params)
        params2 = jax.tree_util.tree_map(lambda p, gg: p - 0.5 * gg, params, g)
        l1 = loss(params2)
    assert float(l1) < float(l0)


@pytest.mark.parametrize("n_micro", [4, 6])
def test_pipeline_1f1b_matches_sequential_grads(n_micro):
    """1F1B loss and per-stage grads equal the unpipelined computation.

    Also the schedule-accounting claim: the timetable interleaves so at most
    n_stages microbatch inputs are ever stashed (the buffer IS n_stages
    slots), vs GPipe's all-M stash."""
    S = 4
    mesh = pp.make_mesh(pipe=S)
    stage = pp.PipelineStage(lambda: Linear(8, 8, act=jnp.tanh), n_stages=S)
    params = stage.init(jax.random.PRNGKey(11))
    B = 8 * n_micro // 4
    x = jax.random.normal(jax.random.PRNGKey(12), (B, 8))
    y = jax.random.normal(jax.random.PRNGKey(13), (B, 8))

    def stage_fn(p, mb):
        return jnp.tanh(mb @ p["w"] + p["b"])

    def loss_fn(out, y_mb):
        return jnp.mean((out - y_mb) ** 2)

    def seq_loss(params):
        # mean of per-microbatch losses == 1F1B's accumulation
        mbx = x.reshape(n_micro, B // n_micro, 8)
        mby = y.reshape(n_micro, B // n_micro, 8)
        total = 0.0
        for m in range(n_micro):
            h = mbx[m]
            for si in range(S):
                h = stage_fn(jax.tree_util.tree_map(lambda p: p[si], params),
                             h)
            total = total + loss_fn(h, mby[m])
        return total / n_micro

    ref_loss = seq_loss(params)
    ref_grads = jax.grad(seq_loss)(params)

    step = pp.pipeline_1f1b(stage_fn, loss_fn, mesh, n_microbatches=n_micro)
    with mesh:
        loss, grads = step(params, x, y)
    np.testing.assert_allclose(float(loss), float(ref_loss),
                               rtol=1e-5, atol=1e-6)
    jax.tree_util.tree_map(
        lambda g, r: np.testing.assert_allclose(
            jax.device_get(g), jax.device_get(r), rtol=1e-4, atol=1e-5),
        grads, ref_grads)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_grads_match_full(causal):
    """Ring attention's hand-written backward (Pallas block-gradient kernels
    with dk/dv accumulators riding the ppermute ring) vs autodiff through
    dense attention."""
    mesh = pp.make_mesh(seq=4)
    rng = jax.random.PRNGKey(11)
    kq, kk, kv, kg = jax.random.split(rng, 4)
    B, T, H, D = 2, 32, 2, 8
    q = jax.random.normal(kq, (B, T, H, D))
    k = jax.random.normal(kk, (B, T, H, D))
    v = jax.random.normal(kv, (B, T, H, D))
    g = jax.random.normal(kg, (B, T, H, D))

    def f(q, k, v):
        return jnp.sum(pp.ring_self_attention(mesh, q, k, v, causal=causal) * g)

    def f_ref(q, k, v):
        return jnp.sum(_full_attention(q, k, v, causal) * g)

    got = jax.grad(f, (0, 1, 2))(q, k, v)
    want = jax.grad(f_ref, (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(jax.device_get(a), jax.device_get(b),
                                   rtol=2e-4, atol=2e-4)


def test_ring_attention_contiguous_layout_still_exact():
    """layout="contiguous" keeps the original (discard-future-blocks)
    behavior as an explicit opt-out from zigzag."""
    mesh = pp.make_mesh(seq=8)
    rng = jax.random.PRNGKey(12)
    kq, kk, kv = jax.random.split(rng, 3)
    B, T, H, D = 2, 64, 4, 8
    q = jax.random.normal(kq, (B, T, H, D))
    k = jax.random.normal(kk, (B, T, H, D))
    v = jax.random.normal(kv, (B, T, H, D))
    ref = _full_attention(q, k, v, causal=True)
    out = pp.ring_self_attention(mesh, q, k, v, causal=True,
                                 layout="contiguous")
    np.testing.assert_allclose(jax.device_get(out), jax.device_get(ref),
                               rtol=2e-4, atol=2e-4)


def test_zigzag_order_and_work_balance():
    """The zigzag layout's accounting: the order is a permutation placing
    chunks (d, 2n-1-d) on device d; total attended pairs across all
    devices/steps equal the full causal count (exactness has no slack),
    and per-step work is balanced (max/min < 1.2) — vs the contiguous
    layout where future steps do a full block then discard it (~(n-1)/2n
    of FLOPs wasted)."""
    from paddle_tpu.parallel.ring_attention import (_zigzag_step_pairs,
                                                    zigzag_inverse,
                                                    zigzag_order)

    n, T = 8, 128
    c = T // (2 * n)
    order = np.asarray(zigzag_order(T, n))
    inv = np.asarray(zigzag_inverse(T, n))
    assert sorted(order.tolist()) == list(range(T))        # permutation
    np.testing.assert_array_equal(order[inv], np.arange(T))
    d = 3
    local = order[d * 2 * c:(d + 1) * 2 * c]
    assert local.tolist() == (list(range(d * c, (d + 1) * c)) +
                              list(range((2 * n - 1 - d) * c,
                                         (2 * n - d) * c)))

    diag, off = _zigzag_step_pairs(c)
    # every device does one diagonal step + (n-1) half-block steps
    total = n * (diag + (n - 1) * off)
    full_causal_pairs = T * (T + 1) // 2
    assert total == full_causal_pairs                      # zero waste
    assert max(diag, off) / min(diag, off) < 1.2           # balanced
    # contiguous layout: EVERY ring step runs a full-block kernel and
    # future blocks are discarded after the fact -> n^2 full blocks of
    # kernel FLOPs for T^2/2 useful pairs, ~2x waste
    T_local = T // n
    contiguous_kernel_pairs = n * n * T_local * T_local
    assert contiguous_kernel_pairs > 1.9 * full_causal_pairs
    # zigzag kernel work ~= useful work: only the diagonal step's masked
    # triangle is slack, a 1/(n+...) sliver that vanishes with n (1.12 at
    # n=8) — vs the contiguous layout's constant ~2x
    zz_kernel_pairs = n * (4 * c * c + (n - 1) * 2 * c * c)
    assert zz_kernel_pairs < 1.2 * full_causal_pairs
