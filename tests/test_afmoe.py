"""AfmoeLM (models/afmoe.py) against the plain reference
(chipbench/reference/afmoe.py) on seeded weights at a small size — window
16, pages of 8, contexts of 3 to 7 windows, so that a slot's ring of 4 pages
wraps several times — and the mechanisms it brought to shared code: two
kinds of cache in one page pool (pages that grow, a ring a slot), an
admission that writes both in place, the ring shipped between pools.

Tolerances. Everything here runs in float32 on the CPU, where the program
and the reference differ only in the ORDER of float32 sums (grouped products
against one expert at a time, a running softmax over pages in ring order
against a whole one over the plain rows, rsqrt against 1/sqrt): logits of
size ~0.4 agree to a few 1e-7, held to 2e-5 (atol and rtol). A residual
stream rounded to bfloat16 ONCE moves them by 1e-3 (a test below shows it
fail); a forgotten window, gate, norm or embedding scale by 1e-2 and more.
Exact equalities (``==``) are between two routes of the SAME arithmetic, or
between arrays that must not have been touched.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights_afmoe
from chipbench.reference import afmoe as ref
from paddle_tpu.models import AfmoeLM
from paddle_tpu.parallel.expert_share import ExpertShare
from paddle_tpu.serving.paged import PagePool

TOL = dict(atol=2e-5, rtol=2e-5)

#: a small configuration file of the family: two periods of 3 sliding + 1
#: full layer, two dense layers, 5 of 8 experts held
CONFIG = {
    "vocab_size": 96, "hidden_size": 32, "head_dim": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    "num_hidden_layers": 8, "sliding_window": 16, "num_dense_layers": 2,
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "router_width": 8, "experts_held": [0, 2, 3, 5, 7],
    "num_experts_per_tok": 2, "num_shared_experts": 1, "route_scale": 2.826,
    "route_norm": True, "rope_theta": 10000, "mup_enabled": True,
    "rms_norm_eps": 1e-5, "n_positions": 128,
}
POOL = dict(slots=4, segment=4, page_block=8, cache_bucket=128,
            prompt_buckets=(16, 32, 64), prefix_cache=False)


def build(**changed):
    """The model the benchmark builds for a configuration file of the
    family (chipbench/weights_afmoe.py), at this file's small size, with
    the benchmark's own seeded draw (a non-zero router bias)."""
    model, shapes = weights_afmoe.model_and_shapes(dict(CONFIG, **changed),
                                                   jnp.float32)
    params = weights_afmoe.make(shapes, 7)
    for i, blk in enumerate(model.blocks):
        if blk.is_moe:              # wider logits: the top-k is decided
            moe = params[f"blocks_{i}"]["moe"]
            moe["w_router"] = 20.0 * moe["w_router"]
    return model, params


@pytest.fixture(scope="module")
def lm():
    return build()


def ref_logits(params, ids, **changed):
    hp = ref.hparams(dict(CONFIG, **changed))
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(params, jnp.asarray(ids), hp))


def _prompts(lengths, seed=3):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 96, n).astype(np.int32) for n in lengths]


# -- against the reference ---------------------------------------------------

def test_full_forward_matches_the_reference(lm):
    model, params = lm
    ids = _prompts([80])[0]             # five windows
    got = np.asarray(model(params, jnp.asarray(ids)[None]))[0]
    np.testing.assert_allclose(got, ref_logits(params, ids), **TOL)


def test_a_bfloat16_residual_stream_fails_the_tolerance(lm, monkeypatch):
    """What the tolerance is FOR: float32 is stated for the residual
    stream; rounded to bfloat16 once, at the embedding, the logits leave
    it by two orders of magnitude."""
    model, params = lm
    ids = _prompts([80])[0]
    real = AfmoeLM._embed
    monkeypatch.setattr(AfmoeLM, "_embed", lambda self, p, i: real(
        self, p, i).astype(jnp.bfloat16).astype(jnp.float32))
    got = np.asarray(model(params, jnp.asarray(ids)[None]))[0]
    assert np.abs(got - ref_logits(params, ids)).max() > 50 * TOL["atol"]


def _served_logits(model, params, pool, steps):
    """The logits of ``steps`` decode steps of every slot of ``pool``
    (admitted already), through the model's paged step on the pool's own
    arrays and both its tables; [steps, slots, V] and the tokens fed."""
    live = range(pool.n_slots)
    for i in live:
        pool._ensure(i, int(pool.pos[i]) + steps)
    tables = jnp.asarray(pool.tables)
    ring_tables = jnp.asarray(pool.ring_tables)
    step = jax.jit(lambda cell, cur: model.decode_step_paged(
        params, cell, cur, tables, ring_tables=ring_tables))
    cell = dict(pool.pools, pos=jnp.asarray(pool.pos, jnp.int32))
    cur = jnp.asarray(pool.cur)
    logits, fed = [], []
    for _ in range(steps):
        fed.append(np.asarray(cur))
        lg, cell = step(cell, cur)
        cur = jnp.argmax(lg, -1).astype(cur.dtype)
        logits.append(np.asarray(lg))
    return np.stack(logits), np.stack(fed)


def test_prefill_then_decode_through_the_pool_matches_the_reference(lm):
    """Ragged prompts in ONE admission — shorter than the window (5),
    longer (23, 40), a whole bucket on a page edge (64) — then 50 decode
    steps each: contexts to 114 positions, the ring of 4 pages wrapped
    three times. Every step's logits against the reference's full forward
    over the prompt and the tokens fed."""
    model, params = lm
    pool = PagePool(model, params, **POOL)
    assert (pool.window, pool.ring) == (16, 4)
    lengths = [5, 40, 64, 23]
    prompts = _prompts(lengths)
    first = pool.admit([(i, pool.plan_admission(p, 60))
                        for i, p in enumerate(prompts)])
    for i, p in enumerate(prompts):     # the admission's own token
        assert first[i] == int(np.argmax(ref_logits(params, p)[-1]))
    logits, fed = _served_logits(model, params, pool, 50)
    for i, p in enumerate(prompts):
        seq = np.concatenate([p, fed[:, i]])
        want = ref_logits(params, seq)[len(p):]
        np.testing.assert_allclose(logits[:, i], want, **TOL)


def test_served_tokens_through_the_pools_programs_are_the_references(lm):
    """The same through ``PagePool.run_segment`` (the jitted segment
    program, the host's accounting): tokens, and the rows the reads
    covered on the span's account."""
    model, params = lm
    pool = PagePool(model, params, **POOL)
    prompts = _prompts([5, 40, 64, 23])
    pool.admit([(i, pool.plan_admission(p, 60))
                for i, p in enumerate(prompts)])
    pos0 = pool.pos.copy()
    blocks = [pool.run_segment([0, 1, 2, 3]) for _ in range(12)]
    at = pos0[:, None] + 44 + np.arange(4)[None, :]     # the last segment
    assert pool.last_stats["window_rows"] == int(np.minimum(at + 1, 16).sum())
    assert pool.last_stats["full_rows"] == int((at + 1).sum())
    toks = np.concatenate(blocks, axis=1)               # [slots, 48]
    for i, p in enumerate(prompts):
        seq = np.concatenate([p, toks[i]])
        want = np.argmax(ref_logits(params, seq[:-1]), -1)[len(p) - 1:]
        np.testing.assert_array_equal(toks[i], want)


def test_solo_decode_reads_the_window_through_one_table(lm):
    """``generate_cached``: no ring, the sliding layers read their window
    through the sample's one table."""
    model, params = lm
    p = _prompts([21])[0]
    out = np.asarray(model.generate_cached(params, jnp.asarray(p)[None], 40,
                                           page_block=8))[0]
    want = np.argmax(ref_logits(params, out[:-1]), -1)[len(p) - 1:]
    np.testing.assert_array_equal(out[len(p):], want)


# -- the two kinds of layer are really two ------------------------------------

def test_a_sliding_layers_logits_change_with_the_window_and_a_full_ones_do_not(
        lm):
    model, params = lm
    ids = jnp.asarray(_prompts([48])[0])[None]
    base = np.asarray(model(params, ids))
    wide, _ = build(sliding_window=32)
    moved = np.abs(np.asarray(wide(params, ids)) - base)[0]
    assert moved[:16].max() == 0.0          # inside both windows
    assert moved[16:].max() > 1e-3
    np.testing.assert_allclose(np.asarray(wide(params, ids))[0], ref_logits(
        params, ids[0], sliding_window=32), **TOL)
    # all layers full: the window is read by nothing ...
    full = dict(layer_types=["full_attention"] * 8)
    a, pa = build(**full)
    b, _ = build(sliding_window=4, **full)
    np.testing.assert_array_equal(np.asarray(a(pa, ids)),
                                  np.asarray(b(pa, ids)))
    # ... and neither is RoPE's base; a sliding layer reads it
    c, _ = build(rope_theta=500000, **full)
    np.testing.assert_array_equal(np.asarray(a(pa, ids)),
                                  np.asarray(c(pa, ids)))
    d, _ = build(rope_theta=500000)
    assert np.abs(np.asarray(d(params, ids)) - base).max() > 1e-3


def test_cache_rows_state_a_reach_for_the_sliding_layers_alone(lm):
    model, params = lm
    rows = {r.name: r for r in model.cache_rows(params)}
    assert len(rows) == 16
    for i, kind in enumerate(CONFIG["layer_types"]):
        for n in "kv":
            r = rows[f"{n}{i}"]
            assert r.shape == (2, 8) and r.dtype == jnp.float32
            assert r.window == (16 if kind == "sliding_attention" else None)
    assert (model.window_read_layers, model.paged_read_layers) == (6, 2)


# -- the gate, the four norms, the embedding scale, by hand --------------------

def _rms(x, eps=1e-5):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps)


def test_the_gate_multiplies_the_softmaxs_output_by_the_inputs_sigmoid(lm):
    model, params = lm
    attn, p = model.blocks[3].attn, params["blocks_3"]["attn"]
    rs = np.random.RandomState(5)
    x = rs.randn(6, 32).astype(np.float32)
    o = rs.randn(6, 4, 8).astype(np.float32)
    *_, gate = attn.project(p, jnp.asarray(x), jnp.arange(6))
    w = np.asarray(p["w_qkvg"], np.float64)[:, (4 + 2 + 2) * 8:]
    by_hand = 1.0 / (1.0 + np.exp(-(x @ w)))
    np.testing.assert_allclose(np.asarray(gate), by_hand, atol=1e-6)
    out = attn.output(p, jnp.asarray(o), gate)
    np.testing.assert_allclose(
        np.asarray(out),
        (o.reshape(6, 32) * by_hand) @ np.asarray(p["w_o"], np.float64),
        atol=1e-6)


def test_the_four_norms_sandwich_both_halves_of_a_layer():
    """One full layer with a dense FFN, every norm's gain a constant of
    its own: ``h + c2 rms(attn(c1 rms(h)))`` then ``h + c4 rms(ffn(c3
    rms(h)))`` — with c2 = c4 = 0 the layer is the identity."""
    model, params = build(layer_types=["full_attention"],
                          num_hidden_layers=1, num_dense_layers=1)
    blk, p = model.blocks[0], dict(params["blocks_0"])
    gains = dict(input_norm=0.5, post_attn_norm=2.0, pre_mlp_norm=3.0,
                 post_mlp_norm=0.25)
    for nm, c in gains.items():
        p[nm] = {"gamma": jnp.full((32,), c, jnp.float32)}
    rs = np.random.RandomState(9)
    h = jnp.asarray(rs.randn(1, 5, 32), jnp.float32)
    pos = jnp.arange(5)[None]

    def attention(x):
        q, k, v, gate = blk.attn.project(p["attn"], x, pos)
        s = jnp.einsum("bthd,bshd->bhts", q, jnp.repeat(k, 2, 2)) * 8 ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((5, 5), bool)), s, -jnp.inf)
        o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1),
                       jnp.repeat(v, 2, 2))
        return blk.attn.output(p["attn"], o, gate)
    with jax.default_matmul_precision("highest"):
        h1 = h + 2.0 * _rms(np.asarray(attention(0.5 * _rms(np.asarray(h)))))
        h2 = h1 + 0.25 * _rms(np.asarray(blk.ffn(p["ffn"],
                                                 3.0 * _rms(h1))))
    # the layer itself, fed h: put h where the embedding's output goes
    model._embed = lambda params, ids: h
    with jax.default_matmul_precision("highest"):
        got, _, _ = model._sequence(dict(params, blocks_0=p),
                                    jnp.zeros((1, 5), jnp.int32), None)
        np.testing.assert_allclose(np.asarray(got), np.asarray(h2), **TOL)
        for nm in ("post_attn_norm", "post_mlp_norm"):
            p[nm] = {"gamma": jnp.zeros((32,), jnp.float32)}
        same, _, _ = model._sequence(dict(params, blocks_0=p),
                                     jnp.zeros((1, 5), jnp.int32), None)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(h))


def test_the_embedding_is_scaled_by_the_root_of_the_width(lm):
    model, params = lm
    ids = jnp.asarray([[3, 17, 95]])
    w = np.asarray(params["embed"]["w"])
    np.testing.assert_allclose(np.asarray(model._embed(params, ids))[0],
                               w[[3, 17, 95]] * np.sqrt(32.0), rtol=1e-6)
    plain, _ = build(mup_enabled=False)
    np.testing.assert_array_equal(np.asarray(plain._embed(params, ids))[0],
                                  w[[3, 17, 95]])


# -- the share ------------------------------------------------------------------

def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """Four chips of two experts each, the shared expert on the first:
    their parts sum to the reference's layer over all eight experts."""
    kw = dict(n_experts=8, top_k=2, n_group=1, topk_group=1,
              routed_scale=2.826, norm_eps=1e-20, n_shared=1,
              dtype=jnp.float32)
    whole = ExpertShare(32, 16, experts_held=range(8), **kw)
    params = whole.init(jax.random.PRNGKey(4))
    params["e_bias"] = 0.01 * jax.random.normal(jax.random.PRNGKey(5), (8,))
    params["w_router"] = 20.0 * params["w_router"]
    y = jax.random.normal(jax.random.PRNGKey(6), (24, 32), jnp.float32)
    hp = dict(ref.hparams(CONFIG), experts_held=tuple(range(8)))
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(params, y, hp)
        total = jnp.zeros_like(want)
        for s in range(4):
            held = [2 * s, 2 * s + 1]
            share = ExpertShare(32, 16, experts_held=held, shared=s == 0,
                                **kw)
            p = dict(params, **{k: params[k][jnp.asarray(held)]
                                for k in ("w_gate", "w_up", "w_down")})
            if s:
                p.pop("shared")
            part, counts = share(p, y)
            total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), **TOL)


# -- the pool: what ships, what is refused -------------------------------------

def test_a_ring_ships_as_the_contexts_last_pages_under_the_rows_names(lm):
    """``export_slot`` / ``adopt_slot`` of a model with rings: a full
    layer's every page, a sliding layer's last ``ring`` pages (all of them
    where the context has fewer), into another slot's ring of another
    pool — which then decodes on to the same tokens."""
    from paddle_tpu.serving import ship
    model, params = lm
    a, b = PagePool(model, params, **POOL), PagePool(model, params, **POOL)
    for plen, want_ring in ((11, 2), (50, 4)):
        prompt = _prompts([plen], seed=plen)[0]
        plan = a.plan_admission(prompt, 20)
        first = a.admit([(1, plan)])[1]
        arrays = ship.unpack(*a.export_slot(1, first))
        npg = -(-plen // 8)
        assert arrays["k3"].shape == (npg, 8, 2, 8)         # full layer
        assert arrays["k0"].shape == (want_ring, 8, 2, 8)   # sliding layer
        b.check_shipment(plen, arrays)
        with pytest.raises(ValueError, match="k0"):
            b.check_shipment(plen, dict(arrays, k0=arrays["k0"][:1]))
        b.adopt_slot(2, plen, first, arrays, plan.need_pages)
        ta = np.concatenate([a.run_segment([1])[1] for _ in range(5)])
        tb = np.concatenate([b.run_segment([2])[2] for _ in range(5)])
        np.testing.assert_array_equal(ta, tb)
        seq = np.concatenate([prompt, ta])
        np.testing.assert_array_equal(
            ta, np.argmax(ref_logits(params, seq[:-1]), -1)[plen - 1:])
        a.free_slot(1)
        b.free_slot(2)
        assert a.pages_used == b.pages_used == 0


def test_the_pool_counts_growing_pages_alone_and_refuses_a_prefix_index(lm):
    model, params = lm
    pool = PagePool(model, params, **POOL)
    # 2 full layers' k and v grow; 6 sliding layers' live in the ring
    assert pool.page_bytes == 8 * 4 * (2 * 8 * 4)
    assert pool.pools["k3"].shape == (4 * 16 + 1, 8, 2, 8)
    assert pool.pools["k0"].shape == (4 * 4 + 1, 8, 2, 8)
    assert pool.required_pages(100, 20) == -(-(100 + 20 + 3) // 8)
    prompt = _prompts([100])[0]
    pool.admit([(0, pool.plan_admission(prompt, 20))])
    assert pool.pages_used == 13            # ceil(100 / 8): no ring page
    pool.free_slot(0)
    assert pool.pages_used == 0
    with pytest.raises(ValueError, match="prefix_cache"):
        PagePool(model, params, **dict(POOL, prefix_cache=True))
    # one reach a pool: rows of two windows are refused
    from paddle_tpu.models.paged_lm import CacheRow

    class TwoReaches:
        max_len = 128

        def cache_rows(self, params, kv_dtype=None):
            return [CacheRow("a", (2, 8), jnp.float32, window=16),
                    CacheRow("b", (2, 8), jnp.float32, window=32)]
    with pytest.raises(ValueError, match="different windows"):
        PagePool(TwoReaches(), {}, **POOL)
