"""MimoV2LM (models/mimo_v2.py) against the plain reference
(chipbench/reference/mimo_v2.py) on seeded weights at a small size — keys 24
wide and values 16, 2 KV heads in the global layers and 4 in the sliding
ones, window 8, pages of 4, blocks of 16 positions, sink logits drawn N(ln 8,
1)
— and the mechanisms it brought to shared code: attention kernels whose
values are narrower than their keys and whose softmax takes a sink, cache
rows whose k and v differ in shape inside a layer and across layer kinds,
and an admission that walks a row a block at a time and hands a sliding
layer's last pages on, never its row.

Tolerances. Everything here runs in float32 on the CPU, where the program
and the reference differ only in the ORDER of float32 sums (a running
softmax merged over blocks and pages against a whole one, grouped products
against one expert at a time): logits of size ~0.6 agree to a few 1e-7,
held to 2e-5 (atol and rtol). The sink left out moves them by 0.2, a rope
base or a rotated width taken wrong by 1e-2 and more, a residual stream
rounded to bfloat16 ONCE by 1e-3 — each a test below that must FAIL the
tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights_mimo_v2
from chipbench.reference import mimo_v2 as ref
from paddle_tpu import obs
from paddle_tpu.models import MimoV2LM
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.parallel.expert_share import ExpertShare
from paddle_tpu.serving.paged import PagePool

TOL = dict(atol=2e-5, rtol=2e-5)

#: a small configuration file of the family: layer 0 (global, dense), the
#: short first run, a second global layer and a sliding one after it
CONFIG = {
    "vocab_size": 96, "hidden_size": 64, "head_dim": 24, "v_head_dim": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "swa_num_key_value_heads": 4, "partial_rotary_factor": 0.334,
    "rope_theta": 5000000, "swa_rope_theta": 10000, "sliding_window": 8,
    "attention_value_scale": 0.707, "layernorm_epsilon": 1e-5,
    "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1, 1], "num_hidden_layers": 7,
    "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True, "intermediate_size": 96,
    "moe_intermediate_size": 32, "router_width": 8,
    "experts_held": [0, 2, 3, 5, 7], "num_experts_per_tok": 2,
    "n_positions": 128, "block_tokens": 16,
}
POOL = dict(slots=4, segment=4, page_block=4, cache_bucket=128,
            prompt_buckets=(64,), prefix_cache=False)


def build(**changed):
    """The model the benchmark builds for a configuration file of the
    family (chipbench/weights_mimo_v2.py), at this file's small size, with
    the benchmark's own seeded draw (sinks of N(ln window, 1), a non-zero router
    bias)."""
    model, shapes = weights_mimo_v2.model_and_shapes(dict(CONFIG, **changed),
                                                     jnp.float32)
    params = weights_mimo_v2.make(shapes, 7,
                                  weights_mimo_v2.sink_mean(CONFIG))
    for i, blk in enumerate(model.blocks):
        if blk.is_moe:              # wider logits: the top-k is decided
            moe = params[f"blocks_{i}"]["moe"]
            moe["w_router"] = 20.0 * moe["w_router"]
    return model, params


@pytest.fixture(scope="module")
def lm():
    return build()


def ref_logits(params, ids, **changed):
    hp = dict(ref.hparams(CONFIG), **changed)
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(params, jnp.asarray(ids), hp))


def _prompts(lengths, seed=3):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 96, n).astype(np.int32) for n in lengths]


# -- against the reference ---------------------------------------------------

def test_full_forward_matches_the_reference(lm):
    """Five blocks of 16 positions, ten windows: every layer of both kinds
    reads across block boundaries."""
    model, params = lm
    ids = _prompts([80])[0]
    got = np.asarray(model(params, jnp.asarray(ids)[None]))[0]
    np.testing.assert_allclose(got, ref_logits(params, ids), **TOL)


def _served_logits(model, params, pool, steps):
    """The logits of ``steps`` decode steps of every slot of ``pool``
    (admitted already), through the model's paged step on the pool's own
    arrays and both its tables; [steps, slots, V] and the tokens fed."""
    for i in range(pool.n_slots):
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


def _admit_and_decode(model, params, lengths, steps):
    pool = PagePool(model, params, **POOL)
    prompts = _prompts(lengths)
    first = pool.admit([(i, pool.plan_admission(p, 60))
                        for i, p in enumerate(prompts)])
    logits, fed = _served_logits(model, params, pool, steps)
    return pool, prompts, first, logits, fed


def test_prefill_then_decode_through_the_pool_matches_the_reference(lm):
    """Ragged prompts in ONE admission — shorter than the window (5), past
    it and across two block boundaries (40), a whole bucket on a page and a
    block edge (64), inside a second block (23) — then 50 decode steps
    each: contexts to 114 positions, the ring of 4 pages of 4 wrapped three
    times. Every step's logits against the reference's full forward over
    the prompt and the tokens fed."""
    model, params = lm
    pool, prompts, first, logits, fed = _admit_and_decode(
        model, params, [5, 40, 64, 23], 50)
    assert (pool.window, pool.ring) == (8, 4)
    for i, p in enumerate(prompts):
        assert first[i] == int(np.argmax(ref_logits(params, p)[-1]))
        seq = np.concatenate([p, fed[:, i]])
        want = ref_logits(params, seq)[len(p):]
        np.testing.assert_allclose(logits[:, i], want, **TOL)


@pytest.mark.parametrize("what", ["no_sink", "whole_head_rotated",
                                  "one_rope_base", "bfloat16_residual"])
def test_a_lost_mechanism_fails_the_tolerance(lm, what, monkeypatch):
    """What the tolerance is FOR. The same comparison with the sink left
    out of the program's reads, with the whole head rotated where a third
    is stated, with the global layers' rope base in the sliding ones, or
    with the residual stream rounded to bfloat16 once where float32 is
    stated, leaves the tolerance by orders of magnitude. (Values taken as
    wide as keys are another SHAPE — ``cache_rows`` and the kernels'
    outputs are held to 16 beside 24 below.)"""
    _, params = lm
    changed = {}
    if what == "no_sink":
        flash = pk.flash_attention_with_lse
        monkeypatch.setattr(pk, "flash_attention_with_lse", lambda *a, **kw:
                            flash(*a, **dict(kw, sink=None)))
    elif what == "whole_head_rotated":
        changed = dict(partial_rotary_factor=1.0)
    elif what == "one_rope_base":
        changed = dict(swa_rope_theta=CONFIG["rope_theta"])
    else:
        real = MimoV2LM._embed
        monkeypatch.setattr(MimoV2LM, "_embed", lambda self, p, i: real(
            self, p, i).astype(jnp.bfloat16).astype(jnp.float32))
    ids = _prompts([80])[0]
    fresh = build(**changed)[0]         # no program traced before the patch
    got = np.asarray(fresh(params, jnp.asarray(ids)[None]))[0]
    assert np.abs(got - ref_logits(params, ids)).max() > 50 * TOL["atol"]


def test_the_reference_without_the_sink_is_another_model(lm):
    """The control the reference child runs on the chip
    (``hp["sink"] = (False, False)``): by how much a forgotten sink moves
    the logits — 0.2 of 0.6 here — against the tolerance's 2e-5."""
    _, params = lm
    ids = _prompts([80])[0]
    gap = np.abs(ref_logits(params, ids)
                 - ref_logits(params, ids, sink=(False, False))).max()
    assert gap > 1000 * TOL["atol"]


def test_a_program_that_is_handed_no_sink_counts_none():
    """``attention.sink_rows_total`` counts where the reads are CALLED, the
    operand they are handed: the same class over parameters that hold no
    sink reads 0 in both programs, whatever the constructor was told."""
    model, params = build()
    for blk in params.values():
        if isinstance(blk, dict):
            blk.get("attn", {}).pop("sink", None)
    reg = obs.MetricsRegistry()
    with obs.ObsSession(registry=reg).installed():
        pool = PagePool(model, params, **POOL)
        pool.admit([(0, pool.plan_admission(_prompts([23])[0], 8))])
        assert pool.last_stats["sink_rows"] == 0
        pool.run_segment([0])
        assert pool.last_stats["sink_rows"] == 0
        assert pool.last_stats["window_rows"] > 0
    assert {m["labels"]["program"]: m["value"] for m in reg.collect()
            if m["name"] == "attention.sink_rows_total"} \
        == {"admit": 0, "segment": 0}


def test_served_tokens_through_the_pools_programs_are_the_references(lm):
    """The same through ``PagePool.run_segment`` (the jitted segment
    program, the host's accounting): tokens, the rows the reads covered on
    the span's account, and the sink's counter."""
    model, params = lm
    reg = obs.MetricsRegistry()
    with obs.ObsSession(registry=reg).installed():
        pool = PagePool(model, params, **POOL)
        prompts = _prompts([5, 40, 64, 23])
        pool.admit([(i, pool.plan_admission(p, 60))
                    for i, p in enumerate(prompts)])
        admitted = dict(pool.last_stats)
        pos0 = pool.pos.copy()
        blocks = [pool.run_segment([0, 1, 2, 3]) for _ in range(12)]
    n = np.array([5, 40, 64, 23])
    assert admitted["sink_rows"] == int(n.sum()) * 5     # 5 sliding layers
    assert admitted["pairs_causal"] == int((n * (n + 1) // 2).sum())
    assert admitted["pairs_band"] == int(
        (np.minimum(n, 8) * (np.minimum(n, 8) + 1) // 2
         + np.maximum(n - 8, 0) * 8).sum())
    # each row's own blocks of 16, not its bucket of 64
    assert admitted["positions"] == 16 + 48 + 64 + 32
    at = pos0[:, None] + 44 + np.arange(4)[None, :]     # the last segment
    assert pool.last_stats["window_rows"] == int(np.minimum(at + 1, 8).sum())
    assert pool.last_stats["full_rows"] == int((at + 1).sum())
    assert pool.last_stats["sink_rows"] == 4 * 4 * 5
    sinks = {m["labels"]["program"]: m["value"] for m in reg.collect()
             if m["name"] == "attention.sink_rows_total"}
    assert sinks == {"admit": 132 * 5, "segment": 12 * 4 * 4 * 5}
    toks = np.concatenate(blocks, axis=1)               # [slots, 48]
    for i, p in enumerate(prompts):
        seq = np.concatenate([p, toks[i]])
        want = np.argmax(ref_logits(params, seq[:-1]), -1)[len(p) - 1:]
        np.testing.assert_array_equal(toks[i], want)


def test_solo_decode_reads_the_window_through_one_table(lm):
    """``generate_cached``: no ring, no tail — a sliding layer's whole row
    comes back from the walk and its window is read through the sample's
    one table."""
    model, params = lm
    p = _prompts([21])[0]
    out = np.asarray(model.generate_cached(params, jnp.asarray(p)[None], 40,
                                           page_block=4))[0]
    want = np.argmax(ref_logits(params, out[:-1]), -1)[len(p) - 1:]
    np.testing.assert_array_equal(out[len(p):], want)


def test_through_the_engine_and_the_daemons_loop(lm):
    """``ServingEngine`` (admission waves, segments cut to what is owed,
    slots freed and filled again): every request's tokens are the
    reference's greedy ones."""
    from paddle_tpu.serving import ServingEngine
    model, params = lm
    eng = ServingEngine(model, params, slots=2, segment=4, page_block=4,
                        cache_bucket=128, prompt_buckets=(64,),
                        prefix_cache=False)
    prompts = _prompts([30, 7, 52], seed=11)
    rids = [eng.submit(p, n) for p, n in zip(prompts, (9, 14, 6))]
    for _ in range(80):
        if all(eng.poll(r)[1] for r in rids):
            break
        eng.step()
    for p, r in zip(prompts, rids):
        toks, done, _ = eng.poll(r)
        assert done
        seq = np.concatenate([p, toks])
        want = np.argmax(ref_logits(params, seq[:-1]), -1)[len(p) - 1:]
        np.testing.assert_array_equal(np.asarray(toks), want)


# -- the kernels: values narrower than keys, a sink ---------------------------

def _qkv(seed, B, T, H, Hkv, Dk, Dv):
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(B, T, H, Dk), jnp.float32),
            jnp.asarray(rs.randn(B, T, Hkv, Dk), jnp.float32),
            jnp.asarray(rs.randn(B, T, Hkv, Dv), jnp.float32),
            jnp.asarray(rs.randn(H), jnp.float32))


@pytest.mark.parametrize("sink", [False, True])
@pytest.mark.parametrize("causal, window", [(True, None), (True, 8),
                                            (True, 40), (False, None)])
def test_flash_kernel_matches_its_dense_route(window, sink, causal):
    """The forward kernel in interpret mode against the dense route: q, k
    24 wide, v, o 16; a sink a head in the denominator; the band; with the
    log-sum-exp a merge needs (the sink inside it)."""
    q, k, v, b = _qkv(1, 2, 80, 4, 2, 24, 16)
    kw = dict(causal=causal, window=window, sink=b if sink else None)
    o1, l1 = pk.flash_attention_with_lse(q, k, v, short_dense=True, **kw)
    o2, l2 = pk.flash_attention_with_lse(q, k, v, block_q=16, block_k=32,
                                         interpret=True, **kw)
    assert o1.shape == (2, 80, 4, 16) and l1.shape == (2, 80, 4)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), **TOL)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), **TOL)
    # flash_attention returns the same o, and o alone, by either route
    for blocks in ({}, dict(block_q=16, block_k=32, interpret=True)):
        o = pk.flash_attention(q, k, v, **kw, **blocks)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o1), **TOL)
    if sink:
        plain = pk.flash_attention(q, k, v, causal=causal, window=window)
        assert np.abs(np.asarray(plain) - np.asarray(o1)).max() > 0.01


@pytest.mark.parametrize("window", [None, 8])
@pytest.mark.parametrize("sink", [False, True])
def test_paged_read_kernel_matches_its_dense_route(window, sink):
    """The grouped paged read in interpret mode against the dense route,
    over pools whose value rows are narrower than their key rows, through
    a table and through a ring."""
    rs = np.random.RandomState(2)
    q, _, _, b = _qkv(3, 2, 1, 4, 2, 24, 16)
    q = q[:, 0]
    kp = jnp.asarray(rs.randn(13, 8, 2, 24), jnp.float32)
    vp = jnp.asarray(rs.randn(13, 8, 2, 16), jnp.float32)
    NB = 6 if window is None else 3
    tables = jnp.asarray(1 + np.arange(2 * NB).reshape(2, NB), jnp.int32)
    pos = jnp.asarray([13, 47 if window is None else 22], jnp.int32)
    kw = dict(window=window, sink=b if sink else None)
    dense = pk.paged_decode_attention(q, kp, vp, tables, pos, route="dense",
                                      **kw)
    kern = pk.paged_decode_attention(q, kp, vp, tables, pos, route="kernel",
                                     interpret=True, **kw)
    assert dense.shape == (2, 4, 16)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(kern), **TOL)


def test_a_sink_or_split_widths_are_the_grouped_bodys():
    q, _, _, b = _qkv(3, 2, 1, 4, 4, 24, 16)
    kp, vp = jnp.zeros((5, 8, 4, 24)), jnp.zeros((5, 8, 4, 16))
    with pytest.raises(ValueError, match="grouped body"):
        pk.paged_decode_attention(
            q[:, 0], kp, vp, jnp.ones((2, 2), jnp.int32),
            jnp.zeros((2,), jnp.int32), route="kernel", interpret=True)


# -- what the pool holds and what it charges -----------------------------------

def test_cache_rows_state_each_kinds_heads_and_k_and_v_apart(lm):
    model, params = lm
    rows = {r.name: r for r in model.cache_rows(params)}
    assert (rows["k0"].shape, rows["v0"].shape) == ((2, 24), (2, 16))
    assert (rows["k1"].shape, rows["v1"].shape) == ((4, 24), (4, 16))
    assert rows["k0"].window is None and rows["k1"].window == 8
    assert rows["k0"].held == (2, 128) and rows["v0"].held is None
    pool = PagePool(model, params, **POOL)
    assert pool.pools["v0"].shape == (4 * 32 + 1, 4, 2, 16)
    assert pool.pools["v1"].shape == (4 * 4 + 1, 4, 4, 16)
    # the growing rows' page: 2 global layers x 2 heads x (24 + 16) x 4 B
    assert pool.page_bytes == 4 * 2 * 2 * (24 + 16) * 4


def _bytes(reg):
    return {m["labels"]["kernel"]: m["value"] for m in reg.collect()
            if m["name"] == "kernels.bytes_total"}


def test_reads_are_charged_from_each_kinds_own_geometry(lm):
    """``kernels.bytes_total``: a global layer's pages at 2 heads x (24 +
    16) values, a sliding layer's ring pages at 4 heads, the banded flash
    at q, o of 4 heads and k, v of 4 KV heads with the widths apart."""
    model, params = lm
    reg = obs.MetricsRegistry()
    with obs.ObsSession(registry=reg).installed():
        pool = PagePool(model, params, **POOL)
        pool.admit([(0, pool.plan_admission(_prompts([23])[0], 20))])
        admit = _bytes(reg)
        pool.run_segment([0], 1)
    got = _bytes(reg)
    assert admit["flash_window_attention_fwd"] == 32 * 5 * (
        4 * (24 + 16) + 4 * (24 + 16)) * 4
    # one step: slot 0 at position 23 walks 6 pages of 4 (the others one),
    # 2 global layers; its window of 8 lies in 2 ring pages, 5 layers
    assert got["paged_decode_attention"] == (6 + 3) * 2 * 4 * 2 * 40 * 4
    assert got["paged_window_attention"] == (2 + 3) * 5 * 4 * 4 * 40 * 4


@pytest.mark.parametrize("family", ["afmoe", "keye_vl2"])
def test_one_width_models_are_charged_what_they_were(family):
    """The cost models took a layer kind's k and v widths apart; a model
    of ONE width and one head count is charged the bytes it was."""
    from paddle_tpu.obs import roofline
    import test_afmoe
    import test_keye_vl2
    model, params = (test_afmoe.build() if family == "afmoe"
                     else test_keye_vl2.build()[:2])
    geom = model.paged_read_geometry(params)
    assert geom == model.paged_read_geometry(params, kind="window")
    assert "d_value" not in geom
    row = geom["kv_heads"] * geom["d_head"] * geom["itemsize"]
    for kernel in ("paged_decode_attention", "paged_window_attention"):
        assert roofline.kernel_cost(kernel, pages=7, page_block=8, **geom) \
            == 2.0 * 7 * 8 * row
    geom.pop("kv_dtype")
    assert roofline.kernel_cost(
        "flash_window_attention_fwd", positions=96, **geom) == 2.0 * 96 * (
            geom["n_heads"] + geom["kv_heads"]) * geom["d_head"] \
        * geom["itemsize"]


def test_the_ring_is_handed_its_last_pages_and_never_the_row(lm):
    """What the admit program's walk hands the pool's write of a sliding
    layer: ``ring`` pages (16 positions here), whatever the bucket — the
    jaxpr of the 64-token admission holds no [.., 64, 4, 24] array of a
    sliding layer's keys beside the pools."""
    model, params = lm
    pool = PagePool(model, params, **POOL)
    state = (pool.pools, pool.slot_state)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)     # noqa: E731
    text = str(jax.make_jaxpr(pool._admit_fn(64, 16)._jitted)(
        params, state, i32(4, 64), i32(4), i32(4, 16), i32(4, 4)))
    assert "f32[64,4,24]" not in text and "f32[1,64,4,24]" not in text
    assert "f32[64,2,24]" in text            # a global layer's row so far
    assert "f32[32,4,24]" in text            # 16 kept + a block of 16


def test_a_ring_ships_as_the_contexts_last_pages(lm):
    """``export_slot`` / ``adopt_slot``: a global layer's every page, a
    sliding layer's last ``ring`` pages under the rows' names and stated
    shapes (the held 128-wide key comes out 24 wide), into another pool —
    which decodes on to the same tokens."""
    from paddle_tpu.serving import ship
    model, params = lm
    a, b = PagePool(model, params, **POOL), PagePool(model, params, **POOL)
    prompt = _prompts([50], seed=50)[0]
    plan = a.plan_admission(prompt, 20)
    first = a.admit([(1, plan)])[1]
    arrays = ship.unpack(*a.export_slot(1, first))
    assert arrays["k0"].shape == (13, 4, 2, 24)
    assert arrays["v0"].shape == (13, 4, 2, 16)
    assert arrays["k1"].shape == (4, 4, 4, 24)
    b.adopt_slot(2, 50, first, arrays, plan.need_pages)
    ta = np.concatenate([a.run_segment([1])[1] for _ in range(5)])
    tb = np.concatenate([b.run_segment([2])[2] for _ in range(5)])
    np.testing.assert_array_equal(ta, tb)
    seq = np.concatenate([prompt, ta])
    np.testing.assert_array_equal(
        ta, np.argmax(ref_logits(params, seq[:-1]), -1)[49:])


# -- the share ------------------------------------------------------------------

def test_the_shares_routed_parts_add_up_to_the_uncut_layer():
    """Four chips of two experts each (no shared expert): their parts sum
    to the reference's layer over all eight experts."""
    kw = dict(n_experts=8, top_k=2, n_group=1, topk_group=1,
              routed_scale=1.0, norm_eps=1e-20, shared=False, bias=True,
              score="sigmoid", dtype=jnp.float32)
    whole = ExpertShare(64, 32, experts_held=range(8), **kw)
    params = whole.init(jax.random.PRNGKey(4))
    params["e_bias"] = 0.01 * jax.random.normal(jax.random.PRNGKey(5), (8,))
    params["w_router"] = 20.0 * params["w_router"]
    y = jax.random.normal(jax.random.PRNGKey(6), (24, 64), jnp.float32)
    hp = dict(ref.hparams(CONFIG), experts_held=tuple(range(8)))
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(params, y, hp)
        total = jnp.zeros_like(want)
        for s in range(4):
            held = [2 * s, 2 * s + 1]
            share = ExpertShare(64, 32, experts_held=held, **kw)
            p = dict(params, **{k: params[k][jnp.asarray(held)]
                                for k in ("w_gate", "w_up", "w_down")})
            part, counts = share(p, y)
            total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), **TOL)


# -- the layer's parts, each against a hand ------------------------------------

def test_rotary_turns_the_leading_part_of_a_head_alone(lm):
    model, params = lm
    attn = model.blocks[1].attn
    assert attn.rotary == 8 and attn.inv_freq.shape == (4,)
    x = jnp.asarray(np.random.RandomState(0).randn(5, 4, 24), jnp.float32)
    pos = jnp.arange(5)
    got = np.asarray(attn._rotate(x, pos))
    np.testing.assert_array_equal(got[..., 8:], np.asarray(x)[..., 8:])
    np.testing.assert_allclose(
        got, np.asarray(ref._rope_part(x, pos, 10000.0, 8)), **TOL)
    # the two kinds turn at their own bases
    assert model.blocks[0].attn.inv_freq[1] != attn.inv_freq[1]


def test_values_are_cached_scaled(lm):
    model, params = lm
    blk, p = model.blocks[0], params["blocks_0"]
    x = jnp.asarray(np.random.RandomState(1).randn(3, 64), jnp.float32)
    _, _, v = blk.attn.project(p["attn"], x, jnp.arange(3))
    w = np.asarray(p["attn"]["w_qkv"])[:, (4 + 2) * 24:]
    np.testing.assert_allclose(
        np.asarray(v).reshape(3, -1), 0.707 * (np.asarray(x) @ w), **TOL)


def test_global_layers_have_no_sink_and_other_heads(lm):
    model, params = lm
    assert "sink" not in params["blocks_0"]["attn"]
    assert params["blocks_1"]["attn"]["sink"].shape == (4,)
    assert [b.attn.kv_heads for b in model.blocks] == [2, 4, 4, 4, 4, 2, 4]
    assert (model.paged_read_layers, model.window_read_layers) == (2, 5)
    with pytest.raises(ValueError, match="global layer"):
        build(hybrid_layer_pattern=[1] * 7)
