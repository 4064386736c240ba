"""TransformerLM (models/transformer.py): the flash-attention kernels'
model-level consumer — causality, reference-math equivalence, training,
tied head, and ring-attention sequence parallelism through the same blocks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import TransformerLM

V, D, H, L, T = 50, 32, 4, 2, 16
B = 4


def _model(max_len=64, **kw):
    m = TransformerLM(V, d_model=D, n_heads=H, n_layers=L, max_len=max_len,
                      **kw)
    return m, m.init(jax.random.PRNGKey(0))


def _ref_logits(model, params, ids):
    """Dense reference attention (softmax over explicit [T, T] scores) run
    through the SAME parameters — validates the flash-kernel model path."""
    B_, T_ = ids.shape
    x = model.embed(params["embed"], ids) + params["pos_embed"][:T_]
    for i in range(len(model.blocks)):
        blk, p = model.blocks[i], params[f"blocks_{i}"]
        h = blk.ln1(p["ln1"], x)
        q, k, v = jnp.split(blk.qkv(p["qkv"], h), 3, axis=-1)
        sh = (B_, T_, blk.n_heads, blk.d_head)
        q, k, v = (a.reshape(sh) for a in (q, k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(blk.d_head)
        mask = jnp.tril(jnp.ones((T_, T_), bool))
        s = jnp.where(mask[None, None], s, -1e30)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        x = x + blk.proj(p["proj"], o.reshape(B_, T_, -1))
        h2 = blk.ln2(p["ln2"], x)
        x = x + blk.mlp_out(p["mlp_out"], blk.mlp_in(p["mlp_in"], h2))
    x = model.ln_f(params["ln_f"], x)
    return x @ params["embed"]["w"].T


def test_matches_dense_reference():
    model, params = _model()
    ids = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, V)
    got = model(params, ids)
    want = _ref_logits(model, params, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_causality():
    """Changing token t must not change logits at positions < t."""
    model, params = _model()
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, T), 0, V)
    base = np.asarray(model(params, ids))
    ids2 = ids.at[0, T // 2].set((int(ids[0, T // 2]) + 1) % V)
    pert = np.asarray(model(params, ids2))
    np.testing.assert_allclose(pert[0, :T // 2], base[0, :T // 2],
                               rtol=1e-5, atol=1e-5)
    assert np.abs(pert[0, T // 2:] - base[0, T // 2:]).max() > 1e-6


def test_trains_next_token():
    """Fit a deterministic cyclic language: loss falls far below the
    uniform floor."""
    from paddle_tpu.optimizer import Adam

    model, params = _model()
    rs = np.random.RandomState(0)
    starts = rs.randint(0, V, (64,))
    ids = jnp.asarray((starts[:, None] + np.arange(T)[None, :]) % V,
                      jnp.int32)
    opt = Adam(3e-3)
    state = opt.init(params)

    @jax.jit
    def step(params, state):
        loss, g = jax.value_and_grad(model.loss)(params, ids)
        params, state = opt.update(g, state, params)
        return params, state, loss

    losses = []
    for _ in range(60):
        params, state, l = step(params, state)
        losses.append(float(l))
    assert losses[-1] < 0.5 and losses[-1] < losses[0] * 0.2


def test_length_masked_loss():
    model, params = _model()
    ids = jax.random.randint(jax.random.PRNGKey(3), (B, T), 0, V)
    lengths = jnp.array([T, T // 2, 3, T], jnp.int32)
    lm = float(model.loss(params, ids, lengths))
    # corrupting tokens past each length must not change the masked loss
    ids2 = ids.at[1, T // 2:].set(0).at[2, 3:].set(0)
    lm2 = float(model.loss(params, ids2, lengths))
    np.testing.assert_allclose(lm, lm2, rtol=1e-6)


# slow: untied-head generate variant; tied-head generate + dense-reference
# equivalence keep the decode path covered in tier-1
@pytest.mark.slow
def test_untied_head_shape_and_generate():
    model, params = _model(tie_head=False)
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 5), 0, V)
    out = model.generate_greedy(params, ids, steps=3)
    assert out.shape == (2, 8)
    assert (np.asarray(out[:, :5]) == np.asarray(ids)).all()


def test_seq_parallel_matches_single_device():
    """The SAME blocks under causal ring attention over a seq mesh axis
    reproduce the single-device forward exactly (contiguous layout; each
    shard feeds its true global positions)."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu import parallel as pp

    n = 8
    if len(jax.devices()) < n:
        pytest.skip("needs 8 virtual devices")
    T_long = 32
    model, params = _model(max_len=T_long)
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, T_long), 0, V)
    positions = jnp.broadcast_to(jnp.arange(T_long), (2, T_long))
    want = np.asarray(model(params, ids))

    mesh = pp.make_mesh(seq=n)

    def fwd(params, ids, positions):
        return model(params, ids, positions=positions, seq_axis="seq")

    sharded = jax.jit(jax.shard_map(
        fwd, mesh=mesh,
        in_specs=(P(), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"), check_vma=False))
    got = np.asarray(sharded(params, ids, positions))
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


# slow: remat-vs-no-remat equivalence is stable niche coverage (56s)
@pytest.mark.slow
def test_remat_matches_no_remat():
    """remat=True (jax.checkpoint per block) must not change values or
    gradients — only the backward's memory/FLOP trade."""
    m1, params = _model()
    m2 = TransformerLM(V, d_model=D, n_heads=H, n_layers=L, max_len=64,
                       remat=True)
    ids = jax.random.randint(jax.random.PRNGKey(6), (B, T), 0, V)
    l1, g1 = jax.value_and_grad(m1.loss)(params, ids)
    l2, g2 = jax.value_and_grad(m2.loss)(params, ids)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    flat1 = jax.tree_util.tree_leaves(g1)
    flat2 = jax.tree_util.tree_leaves(g2)
    for a, b in zip(flat1, flat2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_tensor_parallel_via_sharding_rules():
    """Megatron-style TP on the transformer with ZERO model changes: qkv/
    mlp_in column-parallel, proj/mlp_out row-parallel over a `model` mesh
    axis via ShardingRules; the SPMD partitioner inserts the collectives.
    One jitted dp x tp train step matches the unsharded step exactly."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu import parallel as pp
    from paddle_tpu.optimizer import SGD

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = pp.make_mesh(data=2, model=4)
    model, params = _model()
    ids = jax.random.randint(jax.random.PRNGKey(7), (B, T), 0, V)
    opt = SGD(0.1)

    def step(params, state, ids):
        loss, g = jax.value_and_grad(model.loss)(params, ids)
        params, state = opt.update(g, state, params)
        return params, state, loss

    # unsharded reference
    p_ref, s_ref, l_ref = jax.jit(step)(params, opt.init(params), ids)

    rules = pp.ShardingRules([
        (r".*blocks_\d+/qkv/w$", P(None, "model")),
        (r".*blocks_\d+/mlp_in/w$", P(None, "model")),
        (r".*blocks_\d+/proj/w$", P("model", None)),
        (r".*blocks_\d+/mlp_out/w$", P("model", None)),
        (r".*", P()),
    ])
    sp = rules.apply(mesh, params)
    ss = jax.device_put(opt.init(sp), NamedSharding(mesh, P()))
    ids_sh = jax.device_put(ids, NamedSharding(mesh, P("data", None)))
    with mesh:
        p_tp, s_tp, l_tp = jax.jit(step)(sp, ss, ids_sh)
    np.testing.assert_allclose(float(l_tp), float(l_ref), rtol=1e-5)
    for (ka, a), (kb, b) in zip(
            sorted(jax.tree_util.tree_leaves_with_path(jax.device_get(p_tp)),
                   key=str),
            sorted(jax.tree_util.tree_leaves_with_path(jax.device_get(p_ref)),
                   key=str)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5, err_msg=str(ka))


def test_seq_parallel_shifted_loss_matches_unsharded():
    """The seq-parallel training objective: globally-shifted inputs/targets
    sharded over the seq axis through shifted_loss == the unsharded loss
    exactly; loss(seq_axis=...) is refused (per-shard shifting is wrong)."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu import parallel as pp

    n = 8
    if len(jax.devices()) < n:
        pytest.skip("needs 8 virtual devices")
    T_long = 33                     # odd so the shifted length is 32 = 8*4
    model, params = _model(max_len=T_long)
    ids = jax.random.randint(jax.random.PRNGKey(8), (2, T_long), 0, V)
    want = float(model.loss(params, ids))

    ids_in, targets = ids[:, :-1], ids[:, 1:]
    positions = jnp.broadcast_to(jnp.arange(T_long - 1), ids_in.shape)
    mesh = pp.make_mesh(seq=n)

    def f(params, ids_in, targets, positions):
        return model.shifted_loss(params, ids_in, targets,
                                  positions=positions, seq_axis="seq")

    sharded = jax.jit(jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(), P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(), check_vma=False))
    got = float(sharded(params, ids_in, targets, positions))
    np.testing.assert_allclose(got, want, rtol=2e-5)

    with pytest.raises(ValueError, match="shift"):
        model.loss(params, ids, seq_axis="seq")


# slow: full-reforward equivalence (77s); the bucketed cached-decode test and
# the serving exact-parity suite keep cached decode covered in tier-1
@pytest.mark.slow
def test_cached_decode_matches_full_reforward():
    """KV-cache incremental decode (the serving path) must match the full
    re-forward greedy token-for-token, tied and untied heads."""
    for tie in (True, False):
        model, params = _model(max_len=32, tie_head=tie)
        prompt = jax.random.randint(jax.random.PRNGKey(9), (3, 5), 0, V)
        want = np.asarray(model.generate_greedy(params, prompt, steps=12))
        got = np.asarray(model.generate_cached(params, prompt, steps=12))
        np.testing.assert_array_equal(got, want)


def test_bucketed_cached_decode_matches_unbucketed():
    """Bucketed cache reads (the serving HBM saving) must produce the
    identical token stream, including across bucket boundaries and with the
    overflow guard intact."""
    import pytest

    model, params = _model(max_len=32)
    prompt = jax.random.randint(jax.random.PRNGKey(11), (2, 5), 0, V)
    want = np.asarray(model.generate_cached(params, prompt, steps=20))
    for bucket in (8, 16, 32):   # 5+20=25 crosses several 8-boundaries
        got = np.asarray(model.generate_cached(params, prompt, steps=20,
                                               bucket=bucket))
        np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError, match="max_len"):
        model.generate_cached(params, prompt, steps=30, bucket=8)


def _forward_kv(model, params, ids):
    """The full forward: (logits [B, T, V], every layer's k, v)."""
    x = model.embed(params["embed"], ids)
    x = x + params["pos_embed"][:ids.shape[1]].astype(x.dtype)
    kv = []
    for i in range(len(model.blocks)):
        x, (k, v) = model.blocks[i](params[f"blocks_{i}"], x, return_kv=True)
        kv += [k, v]
    x = model.ln_f(params["ln_f"], x)
    return x @ params["embed"]["w"].T, kv


#: lengths of a [6, 8] ragged batch, two rows a chunk (16 tokens): four
#: live rows walk two whole chunks; three walk two, the second filled up
#: by a dead row; the other dead rows never run
RAGGED = {"chunks": [5, 0, 8, 3, 7, 0], "dead-fill": [5, 0, 8, 0, 3, 0]}


@pytest.mark.parametrize("case", [
    "whole", "none-is-full-lengths",
    *(f"{kv}-{name}" for kv in ("f32", "int8") for name in RAGGED)])
def test_prefill_logits_match_forward(case, monkeypatch):
    """``prefill`` against the full forward: last logits, and on the rows
    that hold a prompt the cache rows too, however the walk chunks them
    (models/paged_lm.py prefill_live_rows); rows of length 0 among
    them keep the cache's fill and are left out of the depth but for
    those that fill up the last live chunk."""
    from paddle_tpu.models import paged_lm, transformer
    from paddle_tpu.ops import pallas_kernels as pk
    model, params = _model(max_len=32)
    if case == "whole":
        prompt = jax.random.randint(jax.random.PRNGKey(10), (2, 7), 0, V)
        _, last = model.prefill(params, prompt)
        full = model(params, prompt)
        np.testing.assert_allclose(np.asarray(last), np.asarray(full[:, -1]),
                                   rtol=1e-5, atol=1e-5)
        return
    monkeypatch.setattr(transformer, "LM_PREFILL_TOKENS", 16)
    # that many tokens of rows a chunk, and never one row alone
    assert [model.prefill_chunk_tokens(w) for w in (4, 8, 16)] == [16, 16, 32]
    prompt = jax.random.randint(jax.random.PRNGKey(10), (6, 8), 0, V)
    if case == "none-is-full-lengths":
        # every row live: three chunks of two rows
        assert paged_lm.live_row_walk(6, 8, 16, 6) == (2, 3)
        cell, last = model.prefill(params, prompt)
        ragged, last_r = model.prefill(params, prompt, jnp.full((6,), 8))
        np.testing.assert_array_equal(last, last_r)
        for nm in cell:
            np.testing.assert_array_equal(cell[nm], ragged[nm])
        np.testing.assert_allclose(last, model(params, prompt)[:, -1],
                                   rtol=1e-5, atol=1e-5)
        return
    kv, name = case.split("-", 1)
    lens = np.asarray(RAGGED[name], np.int32)
    live = lens > 0
    assert paged_lm.live_row_walk(6, 8, 16, int(live.sum())) == (2, 2)
    assert model.prefill_positions(6, 8, int(live.sum())) == 2 * 2 * 8
    cell, last = model.prefill(params, prompt, jnp.asarray(lens),
                               kv_dtype=None if kv == "f32" else "int8",
                               pad_to=16)
    logits, rows = _forward_kv(model, params, prompt)
    np.testing.assert_array_equal(cell["pos"], lens)
    for b in np.flatnonzero(live):
        np.testing.assert_allclose(last[b], logits[b, lens[b] - 1],
                                   rtol=1e-5, atol=1e-5)
    # dead rows the walk never ran hold nothing; those it ran (to fill up
    # its last chunk) hold a pad token's keys
    never = [b for b in np.flatnonzero(~live)
             if not np.asarray(cell["k0"])[b].any()]
    for i in range(L):
        for nm, want in ((f"k{i}", rows[2 * i]), (f"v{i}", rows[2 * i + 1])):
            got = np.asarray(cell[nm])
            assert got.shape == (6, 16, H, D // H)
            # the pad and the rows that never ran: the pool's fill
            assert not got[:, 8:].any() and not got[never].any()
            if kv == "int8":
                q8, scale = pk.quantize_kv(want)
                scales = np.asarray(cell[nm + "_scale"])
                assert scales.shape == (6, 16, H)
                assert (scales[:, 8:] == 1.0).all()
                assert (scales[never] == 1.0).all()
                for b in np.flatnonzero(live):
                    n = lens[b]
                    np.testing.assert_allclose(scales[b, :n], scale[b, :n],
                                               rtol=1e-5)
                    assert np.abs(got[b, :n].astype(np.int32)
                                  - np.asarray(q8[b, :n], np.int32)).max() <= 1
            else:
                for b in np.flatnonzero(live):
                    np.testing.assert_allclose(got[b, :lens[b]],
                                               want[b, :lens[b]],
                                               rtol=1e-5, atol=1e-5)
    ran = int((~live).sum()) - len(never)
    # the dead rows the walk ran are those that filled up its last chunk
    assert ran == 2 * 2 - int(live.sum())
