"""Fluid (Program IR + Executor) tests — the book-test shapes of
fluid/tests/book/test_recognize_digits_mlp.py and fit_a_line, plus IR
round-trip and executable-cache behavior."""

import os

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.data.dataset import mnist, uci_housing


@pytest.fixture(autouse=True)
def fresh_programs():
    fluid.reset_default_programs()
    # fresh scope per test
    fluid.executor._global_scope = fluid.Scope()
    yield


def _run_startup(exe):
    exe.run(fluid.default_startup_program())


def test_fit_a_line():
    """fluid/tests/book/test_fit_a_line.py analog: linear regression to low loss."""
    x = fluid.layers.data("x", shape=(13,))
    y = fluid.layers.data("y", shape=(1,))
    pred = fluid.layers.fc(x, 1)
    b = fluid.default_main_program().global_block()
    diff = fluid.layers.elementwise_sub(pred, y)
    sq = fluid.layers.elementwise_mul(diff, diff)
    loss = fluid.layers.mean(sq)
    fluid.SGDOptimizer(0.01).minimize(loss)

    exe = fluid.Executor()
    _run_startup(exe)
    data = list(uci_housing.train(256)())
    xs = np.stack([d[0] for d in data])
    ys = np.stack([d[1] for d in data])
    first = None
    for i in range(50):
        out, = exe.run(feed={"x": xs, "y": ys}, fetch_list=[loss])
        if first is None:
            first = float(out)
    assert float(out) < first * 0.5


def test_recognize_digits_mlp():
    """MNIST MLP book test: train to decreasing loss with Adam + accuracy."""
    img = fluid.layers.data("img", shape=(784,))
    label = fluid.layers.data("label", shape=(), dtype="int32")
    h1 = fluid.layers.fc(img, 128, act="relu")
    h2 = fluid.layers.fc(h1, 64, act="relu")
    logits = fluid.layers.fc(h2, 10)
    loss_vec = fluid.layers.softmax_with_cross_entropy(logits, label)
    loss = fluid.layers.mean(loss_vec)
    acc = fluid.layers.accuracy(logits, label)
    fluid.AdamOptimizer(1e-3).minimize(loss)

    exe = fluid.Executor()
    _run_startup(exe)
    data = list(mnist.train(512)())
    xs = np.stack([d[0] for d in data])
    ys = np.array([d[1] for d in data], np.int32)
    costs = []
    for i in range(30):
        c, a = exe.run(feed={"img": xs, "label": ys},
                       fetch_list=[loss, acc])
        costs.append(float(c))
    assert costs[-1] < costs[0] * 0.5
    assert float(a) > 0.5


def test_executable_cache_reused():
    x = fluid.layers.data("x", shape=(4,))
    out = fluid.layers.fc(x, 2)
    exe = fluid.Executor()
    _run_startup(exe)
    exe.run(feed={"x": np.ones((3, 4), np.float32)}, fetch_list=[out])
    n1 = len(exe._cache)
    exe.run(feed={"x": np.zeros((3, 4), np.float32)}, fetch_list=[out])
    assert len(exe._cache) == n1          # same shapes -> cache hit
    exe.run(feed={"x": np.ones((5, 4), np.float32)}, fetch_list=[out])
    assert len(exe._cache) == n1 + 1      # new batch shape -> new executable


def test_program_serialization_roundtrip():
    x = fluid.layers.data("x", shape=(4,))
    h = fluid.layers.fc(x, 8, act="tanh")
    out = fluid.layers.fc(h, 2)
    prog = fluid.default_main_program()
    d = prog.to_dict()
    import json
    d2 = json.loads(json.dumps(d, default=str))
    back = fluid.Program.from_dict(d)
    assert len(back.global_block().ops) == len(prog.global_block().ops)
    assert set(back.global_block().vars) == set(prog.global_block().vars)


def test_prune_drops_dead_ops():
    x = fluid.layers.data("x", shape=(4,))
    used = fluid.layers.fc(x, 2)
    dead = fluid.layers.fc(x, 3)   # never fetched
    prog = fluid.default_main_program()
    pruned = prog.prune([used.name])
    kept_types = [op.type for op in pruned.global_block().ops]
    assert len(pruned.global_block().ops) < len(prog.global_block().ops)
    # the dead fc's mul op must be gone
    dead_inputs = {n for op in prog.global_block().ops
                   if dead.name in op.output_vars() for n in op.input_vars()}
    for op in pruned.global_block().ops:
        assert dead.name not in op.output_vars()


def test_momentum_optimizer_runs():
    x = fluid.layers.data("x", shape=(4,))
    y = fluid.layers.data("y", shape=(1,))
    pred = fluid.layers.fc(x, 1)
    diff = fluid.layers.elementwise_sub(pred, y)
    loss = fluid.layers.mean(fluid.layers.elementwise_mul(diff, diff))
    fluid.MomentumOptimizer(0.01, momentum=0.9).minimize(loss)
    exe = fluid.Executor()
    _run_startup(exe)
    rs = np.random.RandomState(0)
    xs = rs.randn(32, 4).astype(np.float32)
    ys = (xs @ rs.randn(4, 1)).astype(np.float32)
    c0 = float(exe.run(feed={"x": xs, "y": ys}, fetch_list=[loss])[0])
    for _ in range(30):
        c = float(exe.run(feed={"x": xs, "y": ys}, fetch_list=[loss])[0])
    assert c < c0 * 0.5


def test_save_load_persistables(tmp_path):
    x = fluid.layers.data("x", shape=(4,))
    out = fluid.layers.fc(x, 2)
    exe = fluid.Executor()
    _run_startup(exe)
    r1 = exe.run(feed={"x": np.ones((2, 4), np.float32)}, fetch_list=[out])[0]
    fluid.io.save_persistables(exe, str(tmp_path))
    # clobber the scope, reload, same output
    fluid.executor._global_scope = fluid.Scope()
    exe2 = fluid.Executor()
    fluid.io.load_persistables(exe2, str(tmp_path))
    r2 = exe2.run(fluid.default_main_program(),
                  feed={"x": np.ones((2, 4), np.float32)}, fetch_list=[out])[0]
    np.testing.assert_allclose(r1, r2, rtol=1e-6)


# ------------------------------------------------------------ fast path ------
# donation / device-resident scope / shape bucketing / bounded LRU
# (docs/design/executor_perf.md)

def _donation_supported() -> bool:
    """Whether this backend actually invalidates donated buffers (CPU does
    on current jaxlib; if a backend silently ignores donation, correctness
    asserts still hold — only the invalidation assert is skipped)."""
    import jax
    import jax.numpy as jnp
    x = jnp.ones((2,))
    jax.jit(lambda a: a + 1, donate_argnums=0)(x)
    return x.is_deleted()


def _sgd_line_program():
    x = fluid.layers.data("x", shape=(4,))
    y = fluid.layers.data("y", shape=(1,))
    pred = fluid.layers.fc(x, 1)
    diff = fluid.layers.elementwise_sub(pred, y)
    loss = fluid.layers.mean(fluid.layers.elementwise_mul(diff, diff))
    fluid.SGDOptimizer(0.05).minimize(loss)
    rs = np.random.RandomState(0)
    xs = rs.randn(16, 4).astype(np.float32)
    ys = (xs @ rs.randn(4, 1)).astype(np.float32)
    return loss, {"x": xs, "y": ys}


def test_donation_updates_persistables_in_place():
    """3 donating runs with return_numpy=False: updates land in the scope
    (loss keeps falling), the old parameter buffer is invalidated, and a
    same-shape re-run never re-reads a donated buffer."""
    import jax
    loss, feed = _sgd_line_program()
    wname = next(v.name for v in fluid.default_main_program()
                 .global_block().all_parameters())
    exe = fluid.Executor()
    _run_startup(exe)
    costs = []
    old_refs = []
    for _ in range(3):
        old_refs.append(exe.scope.get(wname))
        out, = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
        assert isinstance(out, jax.Array)    # lazy fetch: no host sync
        costs.append(float(np.asarray(out)))
    assert costs[2] < costs[0]               # in-place updates are visible
    # scope stays device-resident between runs
    assert isinstance(exe.scope.get(wname), jax.Array)
    if _donation_supported():
        for ref in old_refs:
            assert ref.is_deleted()          # old buffers are gone for good


def test_donation_opt_outs():
    """A persistable that is fetched in the same run is kept readable, and
    donate=False keeps every old buffer alive."""
    loss, feed = _sgd_line_program()
    wname = next(v.name for v in fluid.default_main_program()
                 .global_block().all_parameters())
    # fetched + written -> automatic opt-out for that persistable
    exe = fluid.Executor()
    _run_startup(exe)
    w_old = np.asarray(exe.scope.get(wname))
    out_w, _ = exe.run(feed=feed, fetch_list=[wname, loss])
    assert not np.allclose(out_w, w_old)       # fetch sees the NEW value
    np.testing.assert_allclose(out_w, np.asarray(exe.scope.get(wname)))
    # donate=False escape hatch: the pre-run reference survives
    ref = exe.scope.get(wname)
    exe.run(feed=feed, fetch_list=[loss], donate=False)
    assert not getattr(ref, "is_deleted", lambda: False)()
    np.asarray(ref)                            # still readable


def test_fed_persistable_overrides_scope_value():
    """Feeding a persistable must use the FED value, not the stale scope
    copy (the scope copy doesn't even ride to the device), and a written
    fed persistable syncs its update back to the scope."""
    loss, feed = _sgd_line_program()
    wname = next(v.name for v in fluid.default_main_program()
                 .global_block().all_parameters())
    exe = fluid.Executor()
    _run_startup(exe)
    c_scope = float(exe.run(feed=feed, fetch_list=[loss], donate=False)[0])
    # re-feed wildly different weights: the loss must reflect THEM
    w_shape = np.asarray(exe.scope.get(wname)).shape
    big = np.full(w_shape, 100.0, np.float32)
    c_fed = float(exe.run(feed={**feed, wname: big},
                          fetch_list=[loss])[0])
    assert c_fed > c_scope * 10                # the fed value was used
    # the optimizer update applied ON TOP of the fed value reached the scope
    w_after = np.asarray(exe.scope.get(wname))
    assert np.abs(w_after).max() > 50          # near 100, not the old scope w


def test_donation_while_subblock_persistable():
    """A persistable written only inside a while sub-block updates
    correctly across 3 donating runs (the loop carry flows back to the
    scope and the old buffer is retired)."""
    from paddle_tpu.fluid import layers
    b = fluid.default_main_program().global_block()
    acc = b.create_var(name="acc", shape=(), dtype="int32",
                       persistable=True, trainable=False)
    i = layers.fill_constant((), "int32", 0)
    n = layers.fill_constant((), "int32", 5)
    cond = layers.less_than(i, n)
    with fluid.While(cond).block():
        sb = fluid.default_main_program().current_block()
        sb.append_op("elementwise_add", {"X": [acc.name], "Y": [i.name]},
                     {"Out": [acc.name]})
        layers.increment(i)
        layers.less_than(i, n, cond=cond)
    exe = fluid.Executor()
    exe.scope.set("acc", np.int32(0))
    vals = []
    refs = []
    for _ in range(3):
        refs.append(exe.scope.get("acc"))
        exe.run(feed={}, fetch_list=[i])
        vals.append(int(np.asarray(exe.scope.get("acc"))))
    assert vals == [10, 20, 30]        # += sum(0..4) per run, in place
    if _donation_supported():
        # run 2's input was run 1's device output: donated, hence retired
        # (run 1's input was the host np scalar seed — never donatable)
        assert refs[1].is_deleted() and refs[2].is_deleted()


def test_bucketing_bounds_recompiles_and_matches_unbucketed():
    """8 distinct lengths under a 2-bucket spec compile exactly twice (the
    jax.compiles_total obs bridge is the witness) and agree element-wise
    with the unbucketed run on the true lengths."""
    from paddle_tpu import obs
    w = fluid.layers.data("w", shape=(-1,))
    sq = fluid.layers.elementwise_mul(w, w)
    exe = fluid.Executor(buckets={"w": (8, 16)})
    lengths = (3, 5, 6, 7, 9, 10, 12, 15)
    feeds = {L: np.arange(2 * L, dtype=np.float32).reshape(2, L)
             for L in lengths}
    # warmup OUTSIDE the counted window: a length in a third bucket (pow-2
    # overflow past 16) warms every eager path (scalar @LEN conversion,
    # device_put, fetch) without touching the two buckets under test
    exe.run(feed={"w": np.ones((2, 20), np.float32)}, fetch_list=[sq])
    r = obs.MetricsRegistry()
    with obs.ObsSession(registry=r).installed():
        bucketed = {L: exe.run(feed={"w": feeds[L]}, fetch_list=[sq])[0]
                    for L in lengths}
    assert r.counter("jax.compiles_total").get() == 2
    assert r.counter("fluid.cache_misses_total").get(bucketed="true") == 2
    assert r.counter("fluid.cache_hits_total").get(bucketed="true") == 6
    import warnings
    exe_plain = fluid.Executor()               # no spec: one compile per length
    with warnings.catch_warnings():
        # this comparison loop churns shapes BY DESIGN — scope its L006
        warnings.simplefilter("ignore", RuntimeWarning)
        for L in lengths:
            out_b = bucketed[L]
            assert out_b.shape[1] in (8, 16)   # padded to the bucket
            out_u, = exe_plain.run(feed={"w": feeds[L]}, fetch_list=[sq])
            np.testing.assert_array_equal(out_b[:, :L], out_u)
            assert np.all(out_b[:, L:] == 0)   # zero pad tail


def test_bucketing_feeds_true_length():
    """The true extent rides along as <name>@LEN so programs can mask."""
    w = fluid.layers.data("w", shape=(-1,))
    ln = fluid.default_main_program().global_block().create_var(
        name="w@LEN", shape=(), dtype="int32", is_data=True)
    total = fluid.layers.elementwise_add(
        fluid.layers.mean(w), fluid.layers.cast(ln, "float32"))
    exe = fluid.Executor(buckets={"w": (8,)})
    out, = exe.run(feed={"w": np.zeros((2, 5), np.float32)},
                   fetch_list=[total])
    assert float(out) == 5.0                   # mean(0-pad)=0 + true len 5


def test_cache_lru_bounded_with_evictions():
    from paddle_tpu import obs
    x = fluid.layers.data("x", shape=(4,))
    out = fluid.layers.fc(x, 2)
    exe = fluid.Executor(cache_capacity=2)
    _run_startup(exe)
    r = obs.MetricsRegistry()
    with obs.ObsSession(registry=r).installed():
        for bs in (1, 2, 3):                   # 3 shapes, capacity 2
            exe.run(feed={"x": np.ones((bs, 4), np.float32)},
                    fetch_list=[out])
        assert len(exe._cache) == 2
        # startup fn + 3 feed shapes through a 2-entry LRU = 2 evictions
        assert r.counter("fluid.cache_evictions_total").get() == 2
        assert r.gauge("fluid.cache_size").get() == 2
        # the evicted shape still runs correctly (rebuild, evicting again)
        res, = exe.run(feed={"x": np.ones((1, 4), np.float32)},
                       fetch_list=[out])
    assert res.shape == (1, 2)
    assert len(exe._cache) == 2


def test_shape_churn_warns_l006():
    import warnings
    x = fluid.layers.data("x", shape=(-1,))
    y = fluid.layers.mean(x)
    exe = fluid.Executor()
    with warnings.catch_warnings(record=True) as got:
        warnings.simplefilter("always")
        for L in range(1, 8):                  # unbucketed shape churn
            exe.run(feed={"x": np.ones((2, L), np.float32)},
                    fetch_list=[y])
    msgs = [str(w.message) for w in got if "L006" in str(w.message)]
    assert len(msgs) == 1                      # warns once, names the lint
    assert "buckets" in msgs[0]
    # a bucketed executor never churns -> never warns
    exe_b = fluid.Executor(buckets={"x": (8,)})
    with warnings.catch_warnings(record=True) as got:
        warnings.simplefilter("always")
        for L in range(1, 8):
            exe_b.run(feed={"x": np.ones((2, L), np.float32)},
                      fetch_list=[y])
    assert not [w for w in got if "L006" in str(w.message)]


def test_shape_churn_warns_when_spec_misses_the_varying_feed():
    """A BucketSpec that doesn't cover the feed that actually varies still
    recompiles per length — L006 must fire and say to extend the spec."""
    import warnings
    x = fluid.layers.data("x", shape=(-1,))
    z = fluid.layers.data("z", shape=(-1,))
    out = fluid.layers.elementwise_add(fluid.layers.mean(x),
                                       fluid.layers.mean(z))
    exe = fluid.Executor(buckets={"x": (8,)})   # z is NOT covered
    with warnings.catch_warnings(record=True) as got:
        warnings.simplefilter("always")
        for L in range(1, 8):                   # z churns unbounded
            exe.run(feed={"x": np.ones((2, 3), np.float32),
                          "z": np.ones((2, L), np.float32)},
                    fetch_list=[out])
    msgs = [str(w.message) for w in got if "L006" in str(w.message)]
    assert len(msgs) == 1 and "extend the BucketSpec" in msgs[0]


def test_covering_spec_warmup_is_not_shape_churn():
    """One compile per bucket during warmup of a fully-covering spec is the
    bounded behavior bucketing promises — L006 must stay quiet even when
    the spec has >= _CHURN_STREAK buckets (the threshold scales with the
    spec's own shape-family size)."""
    import warnings
    x = fluid.layers.data("x", shape=(-1,))
    y = fluid.layers.mean(x)
    exe = fluid.Executor(buckets={"x": (2, 4, 8, 16)})
    with warnings.catch_warnings(record=True) as got:
        warnings.simplefilter("always")
        for L in (2, 3, 7, 12, 20):            # one per bucket + overflow
            exe.run(feed={"x": np.ones((2, L), np.float32)},
                    fetch_list=[y])
    assert not [w for w in got if "L006" in str(w.message)]
    assert len(exe._cache) == 5                # every run was a fresh bucket


def test_lru_eviction_thrash_is_not_shape_churn():
    """Cycling a BOUNDED shape family through a too-small LRU re-pays
    compiles, but bucketing can't help — L006 must stay quiet."""
    import warnings
    x = fluid.layers.data("x", shape=(4,))
    out = fluid.layers.fc(x, 2)
    exe = fluid.Executor(cache_capacity=2)
    _run_startup(exe)
    with warnings.catch_warnings(record=True) as got:
        warnings.simplefilter("always")
        for _ in range(3):                      # 9 runs, all misses
            for bs in (1, 2, 3):
                exe.run(feed={"x": np.ones((bs, 4), np.float32)},
                        fetch_list=[out])
    assert not [w for w in got if "L006" in str(w.message)]


def test_bucketing_static_feed_axis_is_an_error():
    """A spec naming a feed with no dynamic non-batch dim (and no pinned
    axis) must fail loudly at the spec boundary, not pad a feature dim."""
    img = fluid.layers.data("img", shape=(784,))
    out = fluid.layers.fc(img, 2)
    exe = fluid.Executor(buckets={"img": (1024,)})
    with pytest.raises(ValueError, match="cannot infer a bucket axis"):
        exe.run(feed={"img": np.ones((2, 784), np.float32)},
                fetch_list=[out])


def test_compile_cache_wiring(tmp_path, monkeypatch):
    """ONE rule places jax's persistent compilation cache
    (paddle_tpu.enable_compile_cache; paddle_tpu.init goes through it):
    $JAX_COMPILATION_CACHE_DIR wins, and unset means the fixed
    in-checkout path — never a temp name, a pid or a time."""
    import jax

    import paddle_tpu
    prev = jax.config.jax_compilation_cache_dir
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "cc"))
        assert paddle_tpu.enable_compile_cache() == str(tmp_path / "cc")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "cc")
        assert os.path.isdir(tmp_path / "cc")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        flags = paddle_tpu.init()
        assert flags["compile_cache_dir"] == os.path.join(repo, ".jax_cache")
        assert paddle_tpu.DEFAULT_COMPILE_CACHE_DIR == \
            os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(repo, ".jax_cache")
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        # the names the single rule replaced are gone, not aliased
        assert not hasattr(paddle_tpu, "COMPILE_CACHE_ENV")
        with pytest.raises(TypeError):
            paddle_tpu.enable_compile_cache(str(tmp_path / "other"))
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_pruned_program_autodiff_grads_run():
    """Pruning dangling forward ops must not break the autodiff replay
    (regression: num_fwd_ops indexed the ORIGINAL op list, so a pruned
    program recursed forever — the replay now uses the op's own position)."""
    fluid.reset_default_programs()
    x = fluid.layers.data("x", shape=(4,))
    side = fluid.layers.fc(x, 3)              # dangling: not in the cost
    h = fluid.layers.fc(x, 8, act="tanh")
    out = fluid.layers.fc(h, 2)
    loss = fluid.layers.mean(out)
    fluid.SGDOptimizer(0.1).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    params = [v.name
              for v in fluid.default_main_program().global_block()
              .all_parameters()
              if not v.name.startswith("fc_w_1")]   # drop side's params
    grad_names = [p + "@GRAD" for p in params if "fc" in p]
    pruned = fluid.default_main_program().prune(grad_names)
    xs = np.ones((3, 4), np.float32)
    grads = exe.run(pruned, feed={"x": xs}, fetch_list=grad_names)
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)
