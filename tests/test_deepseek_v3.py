"""DeepseekV3LM (latent attention + a chip's share of the routed experts)
against the plain reference chipbench/reference/deepseek_v3.py, at a tiny
size on the CPU with seeded weights: the full forward, prefill + decode
through the page pool's latent rows on both routes of the read, absorbed
against expanded attention, the router alone, the share test, and what the
pool refuses for this model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import deepseek_v3 as ref
from paddle_tpu import nn, obs
from paddle_tpu.models import DeepseekV3LM, TransformerLM
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.parallel import expert_share
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.batcher import Request
from paddle_tpu.serving.paged import PagedBatcher, PagePool

ROPE = {"factor": 64, "beta_fast": 32, "beta_slow": 1, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "rope_type": "yarn"}
CFG = dict(num_attention_heads=4, qk_nope_head_dim=8, qk_rope_head_dim=8,
           v_head_dim=16, kv_lora_rank=16, rms_norm_eps=1e-6, rope_theta=1e5,
           rope_scaling=ROPE, router_width=16, experts_held=[0, 1, 2, 3],
           num_experts_per_tok=4, n_group=4, topk_group=2,
           routed_scaling_factor=2.5)
VOCAB, MAX_LEN = 64, 64


def build(held=(0, 1, 2, 3), dtype=jnp.float32, seed=0):
    model = DeepseekV3LM(
        VOCAB, d_model=32, n_heads=4, n_layers=3, n_dense=1, dense_width=64,
        expert_width=16, n_experts=16, experts_held=list(held), top_k=4,
        n_group=4, topk_group=2, q_rank=24, kv_rank=16, d_nope=8, d_rope=8,
        d_v=16, rope_theta=1e5, rope_scaling=ROPE, max_len=MAX_LEN,
        dtype=dtype)
    params = model.init(jax.random.PRNGKey(seed))
    # larger matrices than the 0.02 init so that every path matters at
    # width 32, and a non-zero correction bias
    params = jax.tree_util.tree_map(
        lambda a: (a * 5).astype(a.dtype) if a.ndim >= 2 else a, params)
    for i in (1, 2):
        params[f"blocks_{i}"]["moe"]["e_bias"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(100 + i), (16,))
    return model, params


@pytest.fixture(scope="module")
def tiny():
    return build()


@pytest.fixture(scope="module")
def ids():
    return jax.random.randint(jax.random.PRNGKey(3), (2, 40), 0, VOCAB)


def hp(held=(0, 1, 2, 3)):
    return ref.hparams(dict(CFG, experts_held=list(held)))


def test_full_forward_matches_the_reference(tiny, ids):
    model, params = tiny
    with jax.default_matmul_precision("highest"):
        got = model(params, ids)
        want = ref.forward(params, ids, hp())
    assert got.shape == (2, 40, VOCAB) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-5)


def _paged_logits(model, params, ids, plen, route, bs=8):
    """Prefill ids[:, :plen], then decode the rest a token at a time
    through latent page pools; logits at positions plen-1 .. T-1."""
    B, T = ids.shape
    nb = model.max_len // bs
    cell, last = model.prefill(params, ids[:, :plen])
    tables = 1 + jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    cell = dict({r.name: jnp.concatenate(
        [jnp.zeros((1, bs) + r.shape, r.dtype),
         cell[r.name].reshape((B * nb, bs) + r.shape)])
        for r in model.cache_rows(params)}, pos=cell["pos"])
    out = [last]
    for t in range(plen, T):
        logits, cell = model.decode_step_paged(params, cell, ids[:, t],
                                               tables, attn_route=route)
        out.append(logits)
    return jnp.stack(out, axis=1)


@pytest.mark.parametrize("route", ["dense", "kernel"])
def test_prefill_then_paged_decode_matches_the_reference(tiny, ids, route):
    """The cache holds latent rows; decode absorbs W_UK / W_UV round the
    read. Both routes of the read (dense math, interpreted kernel) against
    the reference's full forward, on logits."""
    model, params = tiny
    with jax.default_matmul_precision("highest"):
        got = _paged_logits(model, params, ids, 9, route)
        want = ref.forward(params, ids, hp())[:, 8:]
    np.testing.assert_allclose(got, want, atol=5e-5)


def test_absorbed_equals_expanded_attention(tiny, ids):
    """One layer's attention alone: the expanded path over the whole
    sequence against the absorbed read of its last position."""
    model, params = tiny
    attn, p = model.blocks[1].attn, params["blocks_1"]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 32))
    pos = jnp.broadcast_to(jnp.arange(24), (2, 24))
    with jax.default_matmul_precision("highest"):
        q_nope, q_rope, lat = attn.project(p, x, pos)
        want = attn.expanded(p, q_nope, q_rope, lat)[:, -1]
        o_lat = pk._dense_latent_attention(
            attn.absorb(p, q_nope[:, -1], q_rope[:, -1]), lat,
            jnp.full((2,), 23), attn.scale, attn.kv_rank)
        got = attn.unabsorb(p, o_lat)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_router_group_limit_bias_and_weights():
    y = jax.random.normal(jax.random.PRNGKey(1), (50, 32))
    w_r = jax.random.normal(jax.random.PRNGKey(2), (32, 16))
    bias = jax.random.normal(jax.random.PRNGKey(4), (16,))
    kw = dict(n_group=4, topk_group=2, top_k=4, routed_scale=2.5)
    with jax.default_matmul_precision("highest"):
        logits = y @ w_r
    experts, w = expert_share.route(logits, bias, **kw)
    s = np.asarray(jax.nn.sigmoid(logits))
    experts, w = np.asarray(experts), np.asarray(w)
    # group limit: the 4 choices lie in at most 2 of the 4 groups
    assert all(len(set(row // 4)) <= 2 for row in experts)
    # weights are the ORIGINAL scores at the chosen experts, renormalised
    # and scaled; the bias is not in them
    picked = np.take_along_axis(s, experts, 1)
    np.testing.assert_allclose(w, picked / picked.sum(1, keepdims=True) * 2.5,
                               rtol=1e-6)
    np.testing.assert_allclose(w.sum(1), 2.5, rtol=1e-6)
    # the bias moves the selection
    plain, _ = expert_share.route(logits, jnp.zeros(16), **kw)
    assert (np.sort(np.asarray(plain), 1) != np.sort(experts, 1)).any()
    # and the reference routes alike
    r_e, r_w = ref.route({"w_router": w_r, "e_bias": bias}, y, hp())
    order = np.argsort(experts, 1)
    r_order = np.argsort(np.asarray(r_e), 1)
    np.testing.assert_array_equal(np.take_along_axis(experts, order, 1),
                                  np.take_along_axis(np.asarray(r_e),
                                                     r_order, 1))
    np.testing.assert_allclose(np.take_along_axis(w, order, 1),
                               np.take_along_axis(np.asarray(r_w), r_order,
                                                  1), rtol=1e-5)


def test_shares_add_up_to_the_uncut_layer():
    """The share test (model-configs guide, section 4): the routed parts of
    all four shares of 4 experts, plus the shared expert once, add up to
    what the reference gives for the whole 16-expert layer."""
    full = expert_share.ExpertShare(
        32, 16, n_experts=16, experts_held=range(16), top_k=4, n_group=4,
        topk_group=2, routed_scale=2.5)
    params = full.init(jax.random.PRNGKey(7))
    params = jax.tree_util.tree_map(lambda a: a * 5, params)
    params["e_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(8), (16,))
    y = jax.random.normal(jax.random.PRNGKey(9), (37, 32))
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(params, y, hp(range(16)))
        total, seen = jnp.zeros_like(want), 0
        for rank in range(4):
            held = list(range(4 * rank, 4 * rank + 4))
            share = expert_share.ExpertShare(
                32, 16, n_experts=16, experts_held=held, top_k=4, n_group=4,
                topk_group=2, routed_scale=2.5, shared=rank == 0)
            p = {k: params[k] for k in ("w_router", "e_bias")}
            for k in ("w_gate", "w_up", "w_down"):
                p[k] = params[k][jnp.asarray(held)]
            if rank == 0:
                p["shared"] = params["shared"]
            out, counts = share(p, y)
            total, seen = total + out, seen + int(counts.sum())
            # and a share alone is what the reference gives for that share
            np.testing.assert_allclose(
                out, ref.expert_layer(p, y, hp(held), shared=rank == 0),
                atol=2e-5)
    assert seen == 37 * 4               # every (token, choice) lands once
    np.testing.assert_allclose(total, want, atol=5e-5)


def test_dead_rows_are_left_out_of_the_experts_and_the_counts(tiny):
    model, params = tiny
    moe, p = model.blocks[1].moe, params["blocks_1"]["moe"]
    y = jax.random.normal(jax.random.PRNGKey(11), (12, 32))
    live = jnp.arange(12) % 3 != 0
    out, counts = moe(p, y, live)
    alone, counts_alone = moe(p, y[live])
    np.testing.assert_array_equal(counts, counts_alone)
    np.testing.assert_allclose(out[live], alone, atol=1e-5)


def test_prefill_runs_only_the_rows_that_hold_a_prompt(tiny, monkeypatch):
    """The pool hands an admission its whole width, length 0 in the slots
    it is not filling: those rows are skipped (zeros come back), the live
    ones read as if they were alone, whatever the chunking."""
    from paddle_tpu.models import paged_lm
    model, params = tiny
    ids = jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, VOCAB)
    lens = jnp.asarray([5, 0, 0, 16, 0, 9, 0, 0], jnp.int32)
    live = np.asarray(lens) > 0
    monkeypatch.setattr(paged_lm, "PREFILL_TOKENS", 32)   # 2 rows a chunk
    cell, last = model.prefill(params, ids, lens, pad_to=16)
    alone, last_alone = model.prefill(params, ids[live], lens[live],
                                      pad_to=16)
    np.testing.assert_allclose(last[live], last_alone, atol=2e-5)
    np.testing.assert_allclose(cell["kv1"][live], alone["kv1"], atol=2e-5)
    # 3 live rows in chunks of 2: one dead row rides in the last chunk,
    # the other four are never computed
    dead = np.abs(np.asarray(cell["kv1"]))[~live].max(axis=(1, 2))
    assert (dead > 0).sum() == 1 and (dead == 0).sum() == 4
    assert int(cell["stats"]["tokens"]) == 5 + 16 + 9
    np.testing.assert_array_equal(cell["stats"]["routed"],
                                  alone["stats"]["routed"])
    np.testing.assert_array_equal(cell["pos"], lens)


def test_chunked_walk_of_held_pairs_equals_one_pass(tiny, monkeypatch):
    """More pairs than CHUNK: the while loop over the sorted held pairs
    computes what one pass computes."""
    model, params = tiny
    moe, p = model.blocks[2].moe, params["blocks_2"]["moe"]
    y = jax.random.normal(jax.random.PRNGKey(12), (40, 32))
    want, c0 = moe(p, y)
    monkeypatch.setattr(expert_share, "CHUNK", 32)
    got, c1 = moe(p, y)
    np.testing.assert_array_equal(c0, c1)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_tile_layout_gives_every_tile_one_group():
    group_of = jnp.asarray([2, 3, 0, 3, 3, 1, 3, 0, 3, 2, 3], jnp.int32)
    src, tile_group, n_tiles, counts = expert_share.tile_layout(group_of, 3,
                                                                2)
    src, tile_group = np.asarray(src), np.asarray(tile_group)
    np.testing.assert_array_equal(counts, [2, 1, 2])
    assert int(n_tiles[0]) == 3 and src.size % 2 == 0
    placed = src[src < group_of.size]
    assert sorted(placed) == [0, 2, 5, 7, 9]        # the rows that are here
    for t in range(int(n_tiles[0])):
        rows = src[2 * t:2 * t + 2]
        rows = rows[rows < group_of.size]
        assert set(np.asarray(group_of)[rows]) == {tile_group[t]}
    assert (src[2 * int(n_tiles[0]):] == group_of.size).all()


@pytest.mark.parametrize("tm, tile_group, n_tiles", [
    (8, [0, 0, 2, 1, 1, 1, 1, 1], 4),
    # tall tiles, the whole matrix held: group 0 owns three adjacent tiles
    # (its matrix is fetched once), group 1 none, group 2 one, group 3 two;
    # the walk stops before the clamped tail
    (128, [0, 0, 0, 2, 3, 3, 3, 3], 6)], ids=["k-split", "resident"])
def test_grouped_matmul_kernel_matches_the_dense_route(tm, tile_group,
                                                       n_tiles):
    K, N = 128, 256
    assert pk.grouped_matmul_blocks(tm, K, N, 4) == (K, N, tm == 128)
    lhs = jax.random.normal(jax.random.PRNGKey(0), (8 * tm, K))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (4, K, N))
    tile_group = jnp.asarray(tile_group, jnp.int32)
    n_tiles = jnp.asarray([n_tiles], jnp.int32)
    kw = dict(tm=tm)
    dense = pk.grouped_matmul(lhs, rhs, tile_group, n_tiles, route="dense",
                              **kw)
    kern = pk.grouped_matmul(lhs, rhs, tile_group, n_tiles, route="kernel",
                             interpret=True, **kw)
    rows = int(n_tiles[0]) * tm
    np.testing.assert_allclose(kern[:rows], dense[:rows], rtol=2e-5,
                               atol=2e-4)
    with pytest.raises(ValueError, match="whole tiles"):
        pk.grouped_matmul(lhs[:-4], rhs, tile_group, n_tiles, **kw)


def test_equal_grouped_products_of_a_program_are_traced_once(monkeypatch):
    """A program calls the kernel with the same shapes a layer; the jitted
    kernel call traces the body once a distinct call, not once a call site
    (set-up time: 36 call sites an admit program)."""
    runs = []
    body = pk._grouped_matmul_kernel

    def counted(*a, **k):
        runs.append(1)
        return body(*a, **k)
    monkeypatch.setattr(pk, "_grouped_matmul_kernel", counted)
    lhs = jnp.ones((24, 384))                 # shapes no other test uses
    rhs = jnp.ones((2, 384, 128))
    group, n = jnp.asarray([0, 1, 1], jnp.int32), jnp.asarray([3], jnp.int32)

    @jax.jit
    def program(x):
        for _ in range(3):
            x = x + pk.grouped_matmul(x, rhs, group, n, tm=8, route="kernel",
                                      interpret=True)[:, :1]
        return x
    program(lhs)
    assert len(runs) == 1


def test_grouped_matmul_resident_plan_walks_no_tile_of_an_empty_layout():
    """No held pair at all (every slot drained): the grid is empty, no
    fetch is started, the call returns."""
    lhs = jnp.ones((256, 128))
    rhs = jnp.ones((2, 128, 256))
    out = pk.grouped_matmul(lhs, rhs, jnp.asarray([1, 1], jnp.int32),
                            jnp.asarray([0], jnp.int32), tm=128,
                            route="kernel", interpret=True)
    assert out.shape == (256, 256)


@pytest.mark.parametrize("n_pairs, chunk", [(96, 8192), (2000, 8192),
                                            (2000, 512)],
                         ids=["decode", "admission", "chunked"])
def test_row_tiles_counts_the_tiles_the_layouts_hold(monkeypatch, n_pairs,
                                                     chunk):
    """row_tiles, from the counts alone, is the number of tiles the
    layer's tile_layout calls lay out — also where the sorted pairs are
    walked CHUNK at a time and a chunk boundary cuts an expert's run."""
    monkeypatch.setattr(expert_share, "CHUNK", chunk)
    n_held = 5
    rng = np.random.default_rng(n_pairs)
    local = rng.choice(n_held + 1, size=n_pairs,
                       p=[.4, .05, .2, 0, .15, .2]).astype(np.int32)
    counts = np.bincount(local, minlength=n_held + 1)[:n_held]
    tm = expert_share.tile_rows(n_pairs)
    assert tm == (16 if n_pairs <= 1024 else 128 if chunk == 8192 else 256)
    # the layer's walk: the held pairs sorted by expert, cut every ``step``
    # pairs, each piece laid out on its own (padding adds no tile)
    held = np.sort(local[local < n_held])
    step = chunk if n_pairs > chunk else n_pairs
    want = sum(int(expert_share.tile_layout(
        jnp.asarray(held[i:i + step]), n_held, tm)[2][0])
        for i in range(0, held.size, step))
    got = int(expert_share.row_tiles(jnp.asarray(counts, jnp.int32), n_pairs))
    assert got == want
    if n_pairs <= chunk:
        assert got == int(np.sum(-(-counts // tm)))
    # a stack of layers' counts sums over the layers
    both = jnp.asarray(np.stack([counts, counts[::-1]]), jnp.int32)
    if n_pairs <= chunk:
        assert int(expert_share.row_tiles(both, n_pairs)) == 2 * got


@pytest.mark.parametrize("program", ["admit", "decode"])
def test_a_program_returns_the_row_tiles_of_its_own_counts(program):
    """``row_tiles`` beside ``routed``: Σ ceil(count / tm) over the
    program's own (layer, held expert) counts — an admission chunk of 512
    tokens (2,048 pairs, tiles of 128: the busiest experts own two) and one
    decode step (tiles of 16, one an expert)."""
    model, params = build()
    if program == "admit":
        ids = jax.random.randint(jax.random.PRNGKey(5), (8, 64), 0, VOCAB)
        lengths = jnp.asarray([64, 64, 64, 64, 64, 64, 64, 3], jnp.int32)
        _, _, stats = model._sequence(params, ids, lengths)
        tm = 128
    else:
        B, bs = 4, 8
        cell = {"pos": jnp.asarray([3, 0, 9, 5], jnp.int32),
                "stats": model.program_stats_zero()}
        for i in range(3):
            cell[f"kv{i}"] = jnp.zeros((1 + B * 2, bs, model.row))
        tables = 1 + jnp.arange(B * 2, dtype=jnp.int32).reshape(B, 2)
        _, cell = model.decode_step_paged(
            params, cell, jnp.asarray([1, 2, 3, 4], jnp.int32), tables,
            live=jnp.asarray([True, False, True, True]))
        stats, tm = cell["stats"], 16
    routed = np.asarray(stats["routed"])
    assert routed.sum() > 0
    assert int(stats["row_tiles"]) == int(np.sum(-(-routed // tm)))
    assert int(stats["row_tiles"]) >= int(stats["touched"])
    if program == "admit":
        assert int(stats["row_tiles"]) > int(stats["touched"])
    else:
        assert int(stats["row_tiles"]) == int(stats["touched"])


def test_latent_read_kernel_matches_the_dense_route():
    B, H, Dk, dv, bs, NB = 3, 4, 24, 16, 8, 4
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, Dk))
    pool = jax.random.normal(jax.random.PRNGKey(1), (1 + B * NB, bs, Dk))
    tables = 1 + jnp.arange(B * NB, dtype=jnp.int32).reshape(B, NB)
    pos = jnp.asarray([0, 13, 31], jnp.int32)
    kw = dict(d_value=dv, scale=0.3)
    dense = pk.paged_latent_attention(q, pool, tables, pos, route="dense",
                                      **kw)
    kern = pk.paged_latent_attention(q, pool, tables, pos, route="kernel",
                                     interpret=True, **kw)
    assert kern.shape == (B, H, dv)
    np.testing.assert_allclose(kern, dense, rtol=1e-5, atol=1e-5)


def test_yarn_frequencies_and_rotation_match_the_reference():
    h = hp()
    np.testing.assert_allclose(
        nn.yarn_inv_freq(8, 1e5, factor=64, original_max_position=4096,
                         beta_fast=32, beta_slow=1), ref.inv_freq(h),
        rtol=1e-6)
    big = dict(h, d_rope=64)
    f = nn.yarn_inv_freq(64, 1e5, factor=64)
    np.testing.assert_allclose(f, ref.inv_freq(big), rtol=1e-6)
    # fast dimensions keep their frequency, slow ones are divided by 64
    base = 1.0 / 1e5 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(f[0], base[0], rtol=1e-6)
    np.testing.assert_allclose(f[-1], base[-1] / 64, rtol=1e-6)
    assert abs(nn.yarn_mscale(64) - 1.4159) < 1e-4
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 10, 4, 8))
    pos = jnp.broadcast_to(jnp.arange(10), (2, 10))
    np.testing.assert_allclose(nn.apply_rope(x, pos, ref.inv_freq(h)),
                               ref._rope(x, h), atol=1e-6)


def test_rms_norm_and_swiglu_follow_their_equations():
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 32))
    norm = nn.RMSNorm(32)
    p = {"gamma": jnp.linspace(0.5, 1.5, 32)}
    want = x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-6) \
        * p["gamma"]
    np.testing.assert_allclose(norm(p, x), want, rtol=1e-5)
    ffn = nn.SwiGLU(32, 64)
    fp = ffn.init(jax.random.PRNGKey(1))
    np.testing.assert_allclose(ffn(fp, x), ref.swiglu(fp, x), atol=1e-6)


def test_bf16_model_keeps_f32_accumulation_and_a_bf16_cache():
    model, params = build(dtype=jnp.bfloat16)
    assert params["blocks_1"]["moe"]["w_gate"].dtype == jnp.bfloat16
    assert params["blocks_1"]["moe"]["e_bias"].dtype == jnp.float32
    rows = model.cache_rows(params)
    assert [(r.name, r.shape) for r in rows] == [
        ("kv0", (24,)), ("kv1", (24,)), ("kv2", (24,))]
    assert all(r.dtype == jnp.bfloat16 for r in rows)
    ids = jax.random.randint(jax.random.PRNGKey(3), (1, 12), 0, VOCAB)
    logits = model(params, ids)
    assert logits.dtype == jnp.float32
    want = ref.forward(params, ids, hp())
    assert float(jnp.max(jnp.abs(logits - want))) < 0.15


# -- through the page pool and the engine ------------------------------------

def _requests(n, seed=0):
    rng = np.random.RandomState(seed)
    return [Request(rid=i,
                    prompt=rng.randint(0, VOCAB, rng.randint(3, 20)).astype(
                        np.int32), max_new=int(rng.randint(2, 14)))
            for i in range(n)]


POOL = dict(slots=3, segment=4, page_block=8, cache_bucket=16,
            prompt_buckets=(16, 32))


def test_paged_batcher_serves_the_reference_greedy_tokens(tiny):
    model, params = tiny
    reqs = _requests(7)
    out = PagedBatcher(model, params, **POOL).serve(reqs)
    with jax.default_matmul_precision("highest"):
        for r in reqs:
            solo = np.asarray(model.generate_cached(
                params, jnp.asarray(r.prompt)[None], r.max_new,
                page_block=8))[0]
            np.testing.assert_array_equal(out[r.rid], solo[r.prompt.size:])
            want = np.argmax(np.asarray(ref.forward(
                params, jnp.asarray(solo)[None], hp()))[0], -1)
            np.testing.assert_array_equal(
                solo[r.prompt.size:], want[r.prompt.size - 1:-1])


def test_pool_allocates_what_the_model_states(tiny):
    model, params = tiny
    pool = PagePool(model, params, **POOL)
    assert sorted(pool.pools) == ["kv0", "kv1", "kv2"]
    assert pool.pools["kv0"].shape == (pool.pages, 8, 24)
    assert pool.page_bytes == 8 * 24 * 4 * 3
    lm = TransformerLM(VOCAB, d_model=32, n_heads=4, n_layers=2,
                       max_len=MAX_LEN)
    lp = lm.init(jax.random.PRNGKey(0))
    gpt = PagePool(lm, lp, **POOL)
    assert sorted(gpt.pools) == ["k0", "k1", "v0", "v1"]
    assert gpt.pools["k0"].shape == (gpt.pages, 8, 4, 8)
    assert gpt.page_bytes == 2.0 * 8 * 4 * 8 * 4 * 2
    q8 = PagePool(lm, lp, kv_dtype="int8", **POOL)
    assert q8.pools["k0"].dtype == jnp.int8
    assert float(q8.pools["v1_scale"].min()) == 1.0
    assert q8.page_bytes == 2.0 * 8 * 4 * (8 + 4) * 2


def test_pool_refuses_what_latent_rows_cannot_do_yet(tiny):
    model, params = tiny
    with pytest.raises(ValueError, match="prefill_paged"):
        PagePool(model, params, prefix_cache=True, **POOL)
    with pytest.raises(ValueError, match="no quantised cache"):
        PagePool(model, params, kv_dtype="int8", **POOL)


def test_latent_pages_ship_between_pools(tiny):
    """export_slot / adopt_slot follow the stated rows: a slot prefilled in
    one pool continues in another with the same tokens."""
    model, params = tiny
    prompt = np.arange(5, 16, dtype=np.int32)
    a, b = PagePool(model, params, **POOL), PagePool(model, params, **POOL)
    first = a.admit([(0, a.plan_admission(prompt, 8))])[0]
    manifest, payload = a.export_slot(0, first)
    from paddle_tpu.serving import ship
    arrays = ship.unpack(manifest, payload)
    assert sorted(arrays) == ["kv0", "kv1", "kv2"]
    b.adopt_slot(1, manifest["plen"], manifest["first"], arrays,
                 b.required_pages(prompt.size, 8))
    np.testing.assert_array_equal(a.run_segment([0])[0],
                                  b.run_segment([1])[1])
    bad = dict(arrays, kv0=arrays["kv0"][..., :-1])
    with pytest.raises(ValueError, match="shape"):
        b.check_shipment(manifest["plen"], bad)


def test_engine_counts_what_the_expert_layer_routed(tiny):
    model, params = tiny
    session = obs.ObsSession().install()
    try:
        eng = ServingEngine(model, params, prefix_cache=False, **POOL)
        rid = eng.submit(np.arange(3, 12, dtype=np.int32), 9)
        while not eng.poll(rid)[1]:
            eng.step()
        assert len(eng.poll(rid)[0]) == 9
        values = {}
        for row in session.registry.collect():
            if row["name"].startswith("moe."):
                values[(row["name"], row["labels"]["program"])] = row["value"]
        spans = [e for e in session.tracer.snapshot()
                 if e.get("name") == "serving.segment"]
        # 9 prompt tokens; then every step a segment RAN for the one live
        # slot (the first re-emits the prefill's token; the last runs the
        # one step the budget still owes), 2 expert layers, top 4
        assert values[("moe.assignments_total", "admit")] == 9 * 4 * 2
        steps = sum(e["args"]["steps"] for e in spans)
        assert steps >= 8
        assert values[("moe.assignments_total", "segment")] == steps * 4 * 2
        here = values[("moe.assignments_here_total", "segment")]
        assert 0 < here <= steps * 4 * 2
        assert values[("moe.experts_touched_total", "segment")] <= here
        assert spans and all(
            {"routed_here", "experts_touched", "row_tiles", "load_max"}
            <= set(e["args"]) for e in spans)
        assert sum(e["args"]["routed_here"] for e in spans) == here
        # a decode step's expert has one short tile; the 9-token admission's
        # 36 pairs an expert layer too
        for prog in ("admit", "segment"):
            assert values[("moe.row_tiles_total", prog)] == \
                values[("moe.experts_touched_total", prog)]
        prefills = [e for e in session.tracer.snapshot()
                    if e.get("name") == "serving.prefill"]
        assert prefills and all("row_tiles" in e["args"] for e in prefills)
        marks = [e for e in session.tracer.snapshot()
                 if e.get("name") == "moe.program"]
        assert {e["args"]["program"] for e in marks} == {"admit", "segment"}
    finally:
        session.uninstall()
