"""KeyeSparseLM (models/keye_vl2.py) against the plain reference
(chipbench/reference/keye_vl2.py) on seeded weights at a small size — the
selection keeps 16 keys, pages of 8, contexts of 40 to 90 positions, so every
request lives far past ``topk`` — and what it brought to shared code: a read
that selects what it reads (five kernels, each against its dense route and
against ``jax.lax.top_k``), a third kind of cached row held wider than
stated, an admission that walks a row a block at a time and writes the
pool's pages in place, a softmax router beside the sigmoid one.

Tolerances. Everything here runs in float32 on the CPU, where the program
and the reference differ only in the ORDER of float32 sums (grouped products
against one expert at a time, a softmax over the selected rows gathered in
ascending order against a masked one over the whole row, rsqrt against
1/sqrt): logits of size ~0.4 agree to a few 1e-7, held to 2e-5 (atol and
rtol). A residual stream rounded to bfloat16 ONCE moves them by 1e-3, a
dropped selection by 0.1 (two tests below show each fail). Exact equalities
(``==``) are between two routes of the SAME arithmetic, or between a
selection and ``jax.lax.top_k``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights_keye_vl2
from chipbench.reference import keye_vl2 as ref
from paddle_tpu.models.paged_lm import CacheRow
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.parallel import expert_share
from paddle_tpu.parallel.expert_share import ExpertShare
from paddle_tpu.serving import ship
from paddle_tpu.serving.paged import PagePool

TOL = dict(atol=2e-5, rtol=2e-5)

#: a small configuration file of the family: 3 layers, 5 of 8 experts held,
#: an indexer of 4 heads of 8 that keeps 16 keys, blocks of 16 positions
CONFIG = {
    "vocab_size": 96, "hidden_size": 32, "head_dim": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 3, "moe_intermediate_size": 16,
    "router_width": 8, "experts_held": [0, 2, 3, 5, 7],
    "num_experts_per_tok": 2, "norm_topk_prob": True, "rope_theta": 1e7,
    "rms_norm_eps": 1e-6, "n_positions": 128, "block_tokens": 16,
    "sa_config": {"indexer_num_heads": 4, "indexer_head_dim": 8, "topk": 16},
}
POOL = dict(slots=4, segment=4, page_block=8, cache_bucket=128,
            prompt_buckets=(32, 64), prefix_cache=False)


def build(**changed):
    """The model the benchmark builds for a configuration file of the
    family (chipbench/weights_keye_vl2.py), at this file's small size, with
    the benchmark's own seeded draw; the router's and the indexer's logits
    widened, so that neither top-k hangs on the last bits of a sum."""
    config = dict(CONFIG, **changed)
    model, shapes = weights_keye_vl2.model_and_shapes(config, jnp.float32)
    params = weights_keye_vl2.make(shapes, 7)
    for i in range(len(model.blocks)):
        p = params[f"blocks_{i}"]
        p["moe"]["w_router"] = 20.0 * p["moe"]["w_router"]
        p["idx"]["w_idx"] = 10.0 * p["idx"]["w_idx"]
    return model, params, config


@pytest.fixture(scope="module")
def lm():
    return build()


def ref_forward(params, ids, config, **hp_changed):
    hp = dict(ref.hparams(config), **hp_changed)
    with jax.default_matmul_precision("highest"):
        return ref.forward(params, jnp.asarray(ids), hp, pairs=True)


def _prompts(lengths, seed=3):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 96, n).astype(np.int32) for n in lengths]


# -- against the reference ---------------------------------------------------

def test_full_forward_matches_the_reference(lm):
    model, params, config = lm
    ids = _prompts([80])[0]             # five times topk
    got = np.asarray(model(params, jnp.asarray(ids)[None]))[0]
    want, _ = ref_forward(params, ids, config)
    np.testing.assert_allclose(got, np.asarray(want), **TOL)


def test_a_dropped_selection_fails_the_tolerance(lm):
    """What the tolerance is FOR: the reference with step 3 taken away, and
    with the 16 most recent keys in the selection's place, leave it by
    three orders of magnitude."""
    model, params, config = lm
    ids = _prompts([80])[0]
    got = np.asarray(model(params, jnp.asarray(ids)[None]))[0]
    for select in ("all", "recent"):
        alt, _ = ref_forward(params, ids, config, select=select)
        assert np.abs(got - np.asarray(alt)).max() > 1000 * TOL["atol"]


def test_a_bfloat16_residual_stream_fails_the_tolerance(lm, monkeypatch):
    model, params, config = lm
    ids = _prompts([80])[0]
    real = type(model.embed).__call__
    monkeypatch.setattr(type(model.embed), "__call__", lambda s, p, i: real(
        s, p, i).astype(jnp.bfloat16).astype(jnp.float32))
    got = np.asarray(model(params, jnp.asarray(ids)[None]))[0]
    want, _ = ref_forward(params, ids, config)
    assert np.abs(got - np.asarray(want)).max() > 50 * TOL["atol"]


def test_the_scores_are_not_degenerate(lm):
    """A row of zeros selects by index, which hides the mechanism: of a
    row's scores after the relu (the reference's), how many are exactly 0 —
    a score is 0 where all the heads' products are negative, 2^-4 of them
    at this file's 4 heads (2^-16 at the published 16)."""
    model, params, config = lm
    hp = ref.hparams(config)
    ids = _prompts([80])[0]
    u = ref._rms(params["embed"]["w"][jnp.asarray(ids)],
                 params["blocks_0"]["input_norm"]["gamma"], hp["eps"])
    qi, ki, w = ref.index_scores(params["blocks_0"]["idx"], u, hp)
    s = jnp.einsum("thd,sd->ths", qi, ki)
    scores = np.asarray(jnp.sum(w[:, :, None] * jnp.maximum(s, 0.0), axis=1))
    zeros = int((scores == 0.0).sum())
    print(f"scores exactly 0 after the relu: {zeros} of {scores.size}")
    assert zeros < 2 * scores.size / 2 ** 4
    assert len(np.unique(scores[-1])) > 70      # a row orders its keys


def _served_logits(model, params, pool, steps):
    """The logits of ``steps`` decode steps of every slot of ``pool``
    (admitted already), through the model's paged step on the pool's own
    arrays; [steps, slots, V], the tokens fed and the stats."""
    for i in range(pool.n_slots):
        pool._ensure(i, int(pool.pos[i]) + steps)
    tables = jnp.asarray(pool.tables)
    step = jax.jit(lambda cell, cur: model.decode_step_paged(
        params, cell, cur, tables,
        live=jnp.asarray(pool.pos > 0)))
    cell = dict(pool.pools, pos=jnp.asarray(pool.pos, jnp.int32),
                stats=model.program_stats_zero())
    cur = jnp.asarray(pool.cur)
    logits, fed = [], []
    for _ in range(steps):
        fed.append(np.asarray(cur))
        lg, cell = step(cell, cur)
        cur = jnp.argmax(lg, -1).astype(cur.dtype)
        logits.append(np.asarray(lg))
    return np.stack(logits), np.stack(fed), cell["stats"]


@pytest.mark.parametrize("lengths, topk, sparse", [
    ((40, 64, 33, 57), 16, True),       # every context past topk
    ((9, 14, 5, 11), 64, False),        # every context within it
], ids=["past-topk", "within-topk"])
def test_prefill_and_paged_decode_match_the_reference(lengths, topk, sparse):
    """Admission through PagePool (the pool's pages written in place, a row
    a block at a time) and paged decode steps, against the reference's full
    forward over prompt + fed tokens: the logits of the prompt's last
    position and of every step."""
    model, params, config = build(sa_config=dict(CONFIG["sa_config"],
                                                 topk=topk))
    pool = PagePool(model, params, **POOL)
    prompts = _prompts(lengths)
    first = pool.admit([(i, pool.plan_admission(p, 8))
                        for i, p in enumerate(prompts)])
    steps = 6
    logits, fed, stats = _served_logits(model, params, pool, steps)
    for i, p in enumerate(prompts):
        ids = np.concatenate([p, fed[:, i]])
        want, pairs = ref_forward(params, ids, config)
        want = np.asarray(want)
        assert first[i] == int(np.argmax(want[len(p) - 1]))
        np.testing.assert_allclose(logits[:, i], want[len(p):], **TOL)
    assert bool(int(stats["sparse_steps"]) > 0) == sparse
    assert bool(int(stats["dense_steps"]) > 0) == (not sparse)
    rows = sum(min(len(p) + s + 1, topk) for p in prompts
               for s in range(steps))
    got = int(stats["selected"][0]) + int(stats["dense_rows"])
    assert got == rows                  # the selection's own count


def test_within_topk_the_read_is_the_dense_kernels():
    """While every context is within ``topk`` the step's read IS
    pk.paged_decode_attention: the same call on the same arrays gives the
    step's attention to the bit."""
    model, params, config = build(sa_config=dict(CONFIG["sa_config"],
                                                 topk=64))
    prompt = jnp.asarray(np.stack(_prompts([12, 12])))
    a = model.generate_cached(params, prompt, 6, page_block=8)
    other, _, _ = build(sa_config=dict(CONFIG["sa_config"], topk=4096))
    b = other.generate_cached(params, prompt, 6, page_block=8)
    assert (np.asarray(a) == np.asarray(b)).all()


def test_a_context_that_crosses_topk_mid_segment(lm):
    """Greedy decoding from a prompt just under ``topk`` to well past it:
    the step switches from the dense read to the selected one on the way,
    and every token is the reference's."""
    model, params, config = lm
    prompt = _prompts([13])[0]
    out = np.asarray(model.generate_cached(
        params, jnp.asarray(prompt)[None], 12, page_block=8))[0]
    want, _ = ref_forward(params, out, config)
    assert (np.argmax(np.asarray(want), -1)[12:-1] == out[13:]).all()


def test_selected_sets_against_the_reference(lm):
    """How many selected sets differ between program and reference at this
    size (float32: none; under bfloat16 boundary swaps between the 16th and
    17th score are expected and harmless)."""
    model, params, config = lm
    ids = _prompts([80])[0]
    _, pairs = ref_forward(params, ids, config)
    _, _, stats = model._sequence(params, jnp.asarray(ids)[None], None)
    assert (np.asarray(stats["selected"]) == np.asarray(pairs)).all()
    assert int(pairs[0]) == sum(min(t + 1, 16) for t in range(80))


# -- the selection -------------------------------------------------------------

@pytest.mark.parametrize("route", ["dense", "kernel"])
def test_select_topk_is_jax_lax_top_k_ties_included(route):
    """Scores on a grid of halves (a third of a row ties at the threshold),
    extents under, at and over ``k``: the mask holds exactly
    ``jax.lax.top_k``'s indices (ties to the lower column)."""
    rs = np.random.RandomState(0)
    R, L, k = 16, 256, 24
    scores = jnp.asarray(np.round(rs.randn(R, L) * 2) / 2, jnp.float32)
    extent = jnp.asarray([1, 5, 23, 24, 25, 40, 100, 256] * 2, jnp.int32)
    bias, cnt = pk.select_topk(scores, extent, k, route=route,
                               interpret=True, rows=8, chunk=128)
    for r in range(R):
        n = int(extent[r])
        _, idx = jax.lax.top_k(scores[r, :n], min(k, n))
        want = np.zeros(L, bool)
        want[np.asarray(idx)] = True
        assert ((np.asarray(bias[r]) == 0.0) == want).all(), r
        assert int(cnt[r]) == min(k, n)


def test_the_kernels_agree_with_their_dense_routes():
    """Each of the selection's kernels, interpreted, against the dense route
    of the same arithmetic (float32: sums in another order)."""
    rs = np.random.RandomState(2)
    f = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)
    Hi, Q, Di, L, q0 = 4, 64, 16, 256, 150
    qi, w, ki = f(Hi, Q, Di), f(Q, Hi), f(L, Di)
    seen = np.arange(L)[None, :] <= q0 + np.arange(Q)[:, None]
    a = pk.index_scores(qi, w, ki, q0, route="dense")
    b = pk.index_scores(qi, w, ki, q0, route="kernel", interpret=True,
                        block_q=32, block_k=64)
    np.testing.assert_allclose(np.where(seen, b, 0), np.where(seen, a, 0),
                               atol=1e-5)
    bias, _ = pk.select_topk(a, jnp.asarray(q0 + np.arange(Q) + 1), 40,
                             route="dense")
    H, Hkv, D = 8, 2, 32
    q, k, v = f(Q, H, D), f(L, Hkv, D), f(L, Hkv, D)
    np.testing.assert_allclose(
        pk.selected_flash_attention(q, k, v, bias, q0, route="kernel",
                                    interpret=True, block_q=32, block_k=64),
        pk.selected_flash_attention(q, k, v, bias, q0, route="dense"),
        atol=1e-5)
    B, NB, bs, P = 3, 8, 16, 40
    tables = jnp.asarray(rs.permutation(np.arange(1, P))[:B * NB].reshape(
        B, NB), jnp.int32)
    pos = jnp.asarray([5, 77, 127], jnp.int32)
    pool, qd, wd = f(P, bs, Di), f(B, Hi, Di), f(B, Hi)
    a = pk.index_scores_paged(qd, wd, pool, tables, pos, route="dense")
    np.testing.assert_allclose(
        pk.index_scores_paged(qd, wd, pool, tables, pos, route="kernel",
                              interpret=True), a, atol=1e-5)
    bias, _ = pk.select_topk(a, pos + 1, 32, route="dense")
    kp, vp, q = f(P, bs, Hkv, D), f(P, bs, Hkv, D), f(B, H, D)
    got, runs = pk.sparse_decode_attention(q, kp, vp, tables, bias, pos,
                                           route="kernel", interpret=True)
    want, same = pk.sparse_decode_attention(q, kp, vp, tables, bias, pos,
                                            route="dense")
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert (np.asarray(runs) == np.asarray(same)).all()
    # ... and the selected read IS the masked read of every row
    kk, vv = pk.gather_pages(kp, tables), pk.gather_pages(vp, tables)
    s = jnp.einsum("bhd,bjhd->bhj", q * D ** -0.5,
                   jnp.repeat(kk, H // Hkv, 2)) + bias[:, None, :]
    np.testing.assert_allclose(got, jnp.einsum(
        "bhj,bjhd->bhd", jax.nn.softmax(s, -1), jnp.repeat(vv, H // Hkv, 2)),
        atol=1e-5)


# -- the pool ------------------------------------------------------------------

def test_the_pool_holds_three_rows_a_layer_the_third_wider_than_stated(lm):
    model, params, _ = lm
    rows = model.cache_rows(params)
    assert [r.name for r in rows[:3]] == ["k0", "v0", "ik0"]
    assert rows[2] == CacheRow("ik0", (8,), jnp.float32, held=(128,))
    pool = PagePool(model, params, **POOL)
    assert pool.pools["ik0"].shape == (pool.pages, 8, 128)
    assert pool.pools["k0"].shape == (pool.pages, 8, 2, 8)
    prompt = _prompts([40])[0]
    pool.admit([(1, pool.plan_admission(prompt, 8))])
    held = np.asarray(pool.pools["ik1"])
    assert (held[:, :, 8:] == 0).all()          # past the stated row: fill
    assert np.abs(held[pool.tables[1, :5], :, :8]).min() > 0
    # what leaves the pool is the stated row
    manifest, payload = pool.export_slot(1, 0)
    arrays = ship.unpack(manifest, payload)
    assert arrays["ik1"].shape == (5, 8, 8)
    other = PagePool(model, params, **POOL)
    other.adopt_slot(2, 40, 0, arrays, pool.required_pages(40, 8))
    np.testing.assert_array_equal(
        np.asarray(other.pools["ik1"])[other.tables[2, :5]],
        held[pool.tables[1, :5]])


def test_an_admission_runs_a_rows_own_blocks_not_its_bucket(lm):
    model, params, _ = lm
    pool = PagePool(model, params, **POOL)
    prompts = _prompts([40, 33])                # bucket 64; blocks of 16
    pool.admit([(i, pool.plan_admission(p, 8))
                for i, p in enumerate(prompts)])
    assert pool.last_stats["positions"] == 48 + 48
    assert pool.last_stats["prompt_tokens"] == 73
    assert pool.last_stats["pairs_causal"] == 3 * (40 * 41 + 33 * 34) // 2
    want = 3 * sum(min(t + 1, 16) for n in (40, 33) for t in range(n))
    assert pool.last_stats["pairs_selected"] == want


# -- the router ----------------------------------------------------------------

def test_the_shares_parts_add_up_to_the_uncut_layer(lm):
    """8 experts in shares of 3, 3 and 2 (softmax router over all 8, no
    shared expert): the parts add up to the reference's uncut layer."""
    model, params, config = lm
    rs = np.random.RandomState(5)
    y = jnp.asarray(rs.randn(24, 32), jnp.float32)
    kw = dict(d_expert=16, n_experts=8, top_k=2, n_group=1, topk_group=1,
              routed_scale=1.0, norm_eps=0.0, shared=False, score="softmax",
              bias=False, dtype=jnp.float32)
    whole = ExpertShare(32, experts_held=list(range(8)), **kw)
    wp = whole.init(jax.random.PRNGKey(1))
    wp["w_router"] = 20.0 * wp["w_router"]
    assert "e_bias" not in wp and "shared" not in wp
    total = jnp.zeros((24, 32), jnp.float32)
    for held in ([0, 1, 2], [3, 4, 5], [6, 7]):
        part = ExpertShare(32, experts_held=held, **kw)
        pp = dict(wp, **{k: wp[k][jnp.asarray(held)]
                         for k in ("w_gate", "w_up", "w_down")})
        out, counts = part(pp, y)
        total = total + out
    hp = dict(ref.hparams(config), experts_held=tuple(range(8)))
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(wp, y, hp)
    np.testing.assert_allclose(total, want, **TOL)
    np.testing.assert_allclose(whole(wp, y)[0], want, **TOL)


def test_route_sigmoid_is_what_it_was():
    """``score="sigmoid"`` (the default the four other classes use) gives
    the experts and weights of the formula written out, with and without
    groups; an unknown score function is refused."""
    rs = np.random.RandomState(6)
    logits = jnp.asarray(rs.randn(12, 16), jnp.float32)
    bias = jnp.asarray(rs.randn(16) * 0.01, jnp.float32)
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits)))
    experts, w = expert_share.route(logits, bias, n_group=1, topk_group=1,
                                    top_k=4, routed_scale=2.5, norm_eps=1e-6)
    want = np.argsort(-(s + np.asarray(bias)), axis=1)[:, :4]
    assert (np.asarray(experts) == want).all()
    ws = np.take_along_axis(s, want, 1)
    np.testing.assert_allclose(w, ws / (ws.sum(1, keepdims=True) + 1e-6)
                               * 2.5, rtol=1e-6)
    again, w2 = expert_share.route(logits, bias, n_group=1, topk_group=1,
                                   top_k=4, routed_scale=2.5, norm_eps=1e-6,
                                   score="sigmoid")
    assert (np.asarray(again) == np.asarray(experts)).all()
    assert (np.asarray(w2) == np.asarray(w)).all()
    grouped, _ = expert_share.route(logits, bias, n_group=4, topk_group=2,
                                    top_k=4, routed_scale=1.0)
    assert grouped.shape == (12, 4)
    with pytest.raises(ValueError, match="score function"):
        expert_share.route(logits, bias, n_group=1, topk_group=1, top_k=4,
                           routed_scale=1.0, score="tanh")


def test_route_softmax_is_over_all_the_experts():
    rs = np.random.RandomState(7)
    logits = jnp.asarray(rs.randn(12, 16), jnp.float32)
    experts, w = expert_share.route(logits, None, n_group=1, topk_group=1,
                                    top_k=4, routed_scale=1.0, norm_eps=0.0,
                                    score="softmax")
    p = np.asarray(jax.nn.softmax(logits, -1))
    want = np.argsort(-p, axis=1)[:, :4]
    assert (np.asarray(experts) == want).all()
    ws = np.take_along_axis(p, want, 1)
    np.testing.assert_allclose(w, ws / ws.sum(1, keepdims=True), rtol=1e-6)


# -- the rotary term -----------------------------------------------------------

def test_three_equal_mrope_ids_are_one_d_rope():
    rs = np.random.RandomState(8)
    x = jnp.asarray(rs.randn(20, 4, 16), jnp.float32)
    ids = jnp.broadcast_to(jnp.arange(20)[None], (3, 20))
    np.testing.assert_allclose(ref.mrope(x, ids, 1e7, [2, 3, 3]),
                               ref._rope(x, 1e7), atol=1e-6)
    other = ids.at[1].add(3)                    # an image's height ids
    assert np.abs(np.asarray(ref.mrope(x, other, 1e7, [2, 3, 3]))
                  - np.asarray(ref._rope(x, 1e7))).max() > 1e-3


def test_a_stated_cache_dtype_is_refused(lm):
    model, params, _ = lm
    with pytest.raises(ValueError, match="no quantised cache"):
        model.cache_rows(params, "int8")
