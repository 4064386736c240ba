"""Lfm2MoeLM (models/lfm2.py) against the plain reference
(chipbench/reference/lfm2.py) on seeded weights at a small size, the
mechanisms it brought to shared code (per-slot rows in the page pool,
grouped-query heads in the paged decode read and in the flash forward, the
router without groups, half-split RoPE, the short convolution), and the
promise to the models that were there: at ``kv_heads == heads`` and with no
slot rows stated the programs are the ones they were.

Tolerances. Everything here runs in float32 on the CPU, where the program
and the reference differ only in the ORDER of float32 sums (grouped
products against one expert at a time, the kernel's running softmax against
a whole one, rsqrt against 1/sqrt): logits of size ~1 agree to a few 1e-5,
held to 2e-4 (atol, rtol 2e-4). A wrong tap, head group, rotation layout or
router epsilon moves logits by 1e-2 and more. Exact equalities (``==``) are
between two routes of the SAME arithmetic.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights_lfm2
from chipbench.reference import lfm2 as ref
from paddle_tpu import nn
from paddle_tpu.models import DeepseekV3LM, Lfm2MoeLM, TransformerLM
from paddle_tpu.models.paged_lm import CacheRow, SlotRow
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.parallel import expert_share
from paddle_tpu.serving.paged import PagePool

TOL = dict(atol=2e-4, rtol=2e-4)

#: a small configuration file of the family: published layers 1..7 of an
#: 8-layer pattern (conv, attention, conv, conv, conv, attention, conv)
CONFIG = {
    "vocab_size": 96, "hidden_size": 32, "intermediate_size": 48,
    "moe_intermediate_size": 16, "num_hidden_layers": 7,
    "num_dense_layers": 1, "first_layer": 1,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "num_experts": 8,
    "router_width": 8, "experts_held": list(range(8)),
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "conv_L_cache": 3, "norm_eps": 1e-5,
    "rope_theta": 1000000, "n_positions": 64,
}


def build(dtype=jnp.float32):
    """The model the benchmark builds for a configuration file of the
    family (chipbench/weights_lfm2.py), at this file's small size."""
    return weights_lfm2.model_and_shapes(CONFIG, dtype)[0]


@pytest.fixture(scope="module")
def lm():
    model = build()
    params = model.init(jax.random.PRNGKey(7))
    # a router bias that is not zero: selection (s + bias) and weights (s)
    # can then be told apart; wider logits so the top-k is decided
    for i in range(len(model.blocks)):
        p = params[f"blocks_{i}"]
        if "moe" in p:
            key = jax.random.PRNGKey(100 + i)
            p["moe"]["e_bias"] = 0.05 * jax.random.normal(key, (8,))
            p["moe"]["w_router"] = 20.0 * p["moe"]["w_router"]
    return model, params


def ref_logits(params, ids):
    hp = ref.hparams(CONFIG)
    with jax.default_matmul_precision("highest"):
        return np.stack([np.asarray(ref.forward(params, jnp.asarray(row), hp))
                         for row in np.asarray(ids)])


def test_full_forward_equals_the_reference(lm):
    model, params = lm
    assert [b.kind for b in model.blocks] == [
        "conv", "full_attention", "conv", "conv", "conv", "full_attention",
        "conv"]
    ids = np.random.RandomState(0).randint(0, 96, (2, 40)).astype(np.int32)
    got = np.asarray(model(params, jnp.asarray(ids)))
    np.testing.assert_allclose(got, ref_logits(params, ids), **TOL)


def test_pool_admission_and_segments_equal_solo_decode(lm):
    """Through PagePool: admission at each row's own length (padded to a
    prompt bucket, three slots at once), then decode segments that write
    pages, roll slot rows and read through the work list — the tokens are
    those of the model's solo paged decode, whose logits the next test
    holds to the reference's full forward."""
    model, params = lm
    pool = PagePool(model, params, slots=3, segment=4, page_block=8,
                    cache_bucket=16, prompt_buckets=(16, 32))
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, 96, n).astype(np.int32) for n in (5, 13, 22)]
    first = pool.admit([(s, pool.plan_admission(p, 12))
                        for s, p in enumerate(prompts)])
    blocks = [pool.run_segment([0, 1, 2]) for _ in range(3)]
    for s, prompt in enumerate(prompts):
        toks = np.concatenate([b[s] for b in blocks])
        assert toks[0] == first[s]      # a segment re-emits the current one
        solo = np.asarray(model.generate_cached(
            params, jnp.asarray(prompt)[None], 12, page_block=8))[0]
        np.testing.assert_array_equal(solo[prompt.size:], toks)


def test_paged_decode_logits_equal_the_reference(lm):
    """Logits, not tokens: prefill a prompt, then feed the reference's own
    continuation through decode_step_paged and compare every step."""
    model, params = lm
    rs = np.random.RandomState(2)
    seq = rs.randint(0, 96, 30).astype(np.int32)
    want = ref_logits(params, seq[None])[0]
    for plen in (1, 2, 9, 17):
        cell, last = model.prefill(params, jnp.asarray(seq[None, :plen]))
        np.testing.assert_allclose(np.asarray(last)[0], want[plen - 1],
                                   **TOL)
        nb, bs = 8, 8
        tables = 1 + jnp.arange(nb, dtype=jnp.int32)[None]
        state = {"pos": cell["pos"]}
        for r in model.cache_rows(params):
            if isinstance(r, SlotRow):
                state[r.name] = cell[r.name]
            else:
                rows = cell[r.name].reshape((nb, bs) + r.shape)
                state[r.name] = jnp.concatenate(
                    [jnp.zeros((1, bs) + r.shape, r.dtype), rows])
        for t in range(plen, seq.size):
            logits, state = model.decode_step_paged(
                params, state, jnp.asarray(seq[t:t + 1]), tables)
            np.testing.assert_allclose(np.asarray(logits)[0], want[t], **TOL)


# -- the short convolution -----------------------------------------------

def test_conv_decode_roll_equals_prefill_at_every_position():
    conv = nn.ShortConv(16, 3)
    p = conv.init(jax.random.PRNGKey(3))
    u = jax.random.normal(jax.random.PRNGKey(4), (2, 11, 16))
    y, tail = conv(p, u)
    state = jnp.zeros((2, 2, 16))
    for t in range(11):
        yt, state = conv.step(p, u[:, t], state)
        np.testing.assert_allclose(np.asarray(yt), np.asarray(y[:, t]),
                                   atol=1e-6, rtol=1e-6)
        # the state after t + 1 positions is a prefill's tail at length t+1
        _, tail_t = conv(p, u, None, jnp.full((2,), t + 1))
        np.testing.assert_array_equal(np.asarray(state), np.asarray(tail_t))
    np.testing.assert_array_equal(np.asarray(state), np.asarray(tail))


def test_conv_prefill_in_two_pieces_carrying_the_tail_equals_one():
    conv = nn.ShortConv(16, 3)
    p = conv.init(jax.random.PRNGKey(5))
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 12, 16))
    y, tail = conv(p, u)
    for cut in (1, 2, 7):
        ya, ta = conv(p, u[:, :cut])
        yb, tb = conv(p, u[:, cut:], ta)
        np.testing.assert_allclose(
            np.asarray(jnp.concatenate([ya, yb], 1)), np.asarray(y),
            atol=1e-6, rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(tb), np.asarray(tail))
    # rows of different lengths: each tail at its own length, zeros before
    # the sequence's start
    _, t = conv(p, u, None, jnp.asarray([1, 5]))
    z, _ = conv._gates(p, u)
    np.testing.assert_array_equal(np.asarray(t[0, 0]), np.zeros(16))
    np.testing.assert_array_equal(np.asarray(t[0, 1]), np.asarray(z[0, 0]))
    np.testing.assert_array_equal(np.asarray(t[1]), np.asarray(z[1, 3:5]))


# -- rotary, router ----------------------------------------------------------

def test_half_split_rope_is_rotate_half():
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 5, 3, 8))
    pos = jnp.broadcast_to(jnp.arange(5), (2, 5))
    inv = nn.yarn_inv_freq(8, 1e6)
    got = nn.apply_rope(x, pos, inv, layout="half")
    want = jnp.stack([ref._rope(x[b], 1e6) for b in range(2)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    # and it is NOT the interleaved layout
    assert not np.allclose(np.asarray(got),
                           np.asarray(nn.apply_rope(x, pos, inv)), atol=1e-3)
    with pytest.raises(ValueError, match="layout"):
        nn.apply_rope(x, pos, inv, layout="pairs")


def test_route_without_groups_is_the_published_router():
    """n_group 1: top-k of s + bias over all experts, weights s / (sum +
    1e-6) * scale — against the reference's own, incl. the epsilon (scores
    small enough that 1e-6 shows)."""
    logits = jax.random.normal(jax.random.PRNGKey(9), (64, 32)) * 3.0 - 12.0
    bias = 1e-6 * jax.random.normal(jax.random.PRNGKey(10), (32,))
    experts, w = expert_share.route(logits, bias, n_group=1, topk_group=1,
                                    top_k=4, routed_scale=1.0, norm_eps=1e-6)
    hp = {"top_k": 4, "norm_topk_prob": True, "routed_scale": 1.0}
    eye = {"w_router": jnp.eye(32), "e_bias": bias}
    chosen, want = ref.route(eye, logits, hp)
    np.testing.assert_array_equal(np.sort(np.asarray(experts), 1),
                                  np.sort(np.asarray(chosen), 1))
    np.testing.assert_allclose(np.sort(np.asarray(w), 1),
                               np.sort(np.asarray(want), 1), rtol=1e-5)
    # the epsilon is a parameter: DeepSeek-V3's 1e-20 gives other weights
    _, w20 = expert_share.route(logits, bias, n_group=1, topk_group=1,
                                top_k=4, routed_scale=1.0)
    assert float(jnp.max(jnp.abs(w20.sum(1) - 1.0))) < 1e-5
    assert float(jnp.max(jnp.abs(w.sum(1) - 1.0))) > 1e-3
    # n_group 1 through the grouped code is the same selection
    e8, w8 = expert_share.route(logits, bias, n_group=2, topk_group=2,
                                top_k=4, routed_scale=1.0, norm_eps=1e-6)
    np.testing.assert_array_equal(np.asarray(e8), np.asarray(experts))


# -- grouped-query heads in the shared kernels ------------------------------

def _paged_case(Hq, Hkv, seed=11, B=3, NB=4, bs=8, D=16):
    rs = np.random.RandomState(seed)
    P = 1 + B * NB
    k_pool = jnp.asarray(rs.randn(P, bs, Hkv, D), jnp.float32)
    v_pool = jnp.asarray(rs.randn(P, bs, Hkv, D), jnp.float32)
    tables = jnp.asarray(1 + np.arange(B * NB).reshape(B, NB), jnp.int32)
    pos = jnp.asarray([3, 17, 31][:B], jnp.int32)
    q = jnp.asarray(rs.randn(B, Hq, D), jnp.float32)
    return q, k_pool, v_pool, tables, pos


def test_grouped_paged_decode_kernel_equals_dense_route():
    q, kp, vp, tables, pos = _paged_case(8, 2)
    dense = pk.paged_decode_attention(q, kp, vp, tables, pos, route="dense")
    kern = pk.paged_decode_attention(q, kp, vp, tables, pos, route="kernel",
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense),
                               atol=1e-5, rtol=1e-5)
    # a KV head serves ITS group: against every head's own copy of it
    rep = pk.paged_decode_attention(q, jnp.repeat(kp, 4, 2),
                                    jnp.repeat(vp, 4, 2), tables, pos,
                                    route="kernel", interpret=True)
    # (the group's body multiplies on the MXU, a head of its own on the
    # VPU: the same numbers in another order of summation)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(rep), atol=1e-6)
    with pytest.raises(ValueError, match="whole groups"):
        pk.paged_decode_attention(q[:, :7], kp, vp, tables, pos)


@pytest.mark.parametrize("given_work", [False, True], ids=["own-list", "work"])
@pytest.mark.parametrize("kv", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("Hq, Hkv, D", [(8, 2, 16), (32, 8, 64),
                                        (32, 2, 128)])
def test_grouped_paged_decode_body_equals_dense_route(Hq, Hkv, D, kv,
                                                      given_work):
    """The grouped body (one product of all query heads against the page's
    [rows * Hkv, D] matrix, the other groups' columns masked) against the
    dense route, at a toy group, rag's and chatburst's: ragged lengths, an
    idle slot on the null page, a slot whose last live row ends a page and
    one whose last row starts one; float pools (bf16 goes to the product as
    it is, q and the weights as hi + lo halves) and int8 with scales; the
    work list built here or handed in, as a decode step hands it."""
    rs = np.random.RandomState(Hq + Hkv + D)
    B, NB, bs = 4, 4, 8
    P = 1 + B * NB
    G = Hq // Hkv
    kp = jnp.asarray(rs.randn(P, bs, Hkv, D), jnp.float32)
    vp = jnp.asarray(rs.randn(P, bs, Hkv, D), jnp.float32)
    tables = jnp.asarray(1 + np.arange(B * NB).reshape(B, NB), jnp.int32)
    tables = tables.at[1].set(0)                  # idle: the null page
    pos = jnp.asarray([13, 0, 15, 24], jnp.int32)
    q = jnp.asarray(rs.randn(B, Hq, D), jnp.float32)
    kw = {}
    if kv == "int8":
        kp, kw["k_scale"] = pk.quantize_kv(kp)
        vp, kw["v_scale"] = pk.quantize_kv(vp)
    elif kv == "bf16":
        kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    dense = pk.paged_decode_attention(q, kp, vp, tables, pos, route="dense",
                                      **kw)
    work = pk.paged_work_list(tables, pos, bs) if given_work else None
    kern = pk.paged_decode_attention(q, kp, vp, tables, pos, route="kernel",
                                     interpret=True, work=work, **kw)
    assert kern.shape == (B, Hq, D) and kern.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)
    # a KV head serves ITS group: against every head's own copy of it,
    # which runs the body of a group of one
    rep = {n: jnp.repeat(a, G, 2) for n, a in kw.items()}
    own = pk.paged_decode_attention(q, jnp.repeat(kp, G, 2),
                                    jnp.repeat(vp, G, 2), tables, pos,
                                    route="kernel", interpret=True, **rep)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(own),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("Hq, Hkv, plan", [(4, 4, "head_vpu"),
                                           (8, 2, "group_mxu"),
                                           (32, 2, "group_mxu")])
def test_paged_decode_plan_follows_the_group(Hq, Hkv, plan):
    """kernels.paged_decode_plan_total: counted once a TRACE of a kernel-
    route call, the body its programs run and the group's size; the shapes
    alone choose (a group of one keeps the VPU body), and the dense route
    counts nothing."""
    from paddle_tpu import obs
    q, kp, vp, tables, pos = _paged_case(Hq, Hkv)
    r = obs.MetricsRegistry()
    with obs.ObsSession(registry=r).installed():
        pk.paged_decode_attention(q, kp, vp, tables, pos, route="dense")
        pk.paged_decode_attention(q, kp, vp, tables, pos, route="kernel",
                                  interpret=True)
    c = r.counter("kernels.paged_decode_plan_total")
    other = {"head_vpu": "group_mxu", "group_mxu": "head_vpu"}[plan]
    assert c.get(plan=plan, group=str(Hq // Hkv)) == 1
    assert c.get(plan=other, group=str(Hq // Hkv)) == 0


def test_paged_decode_at_equal_heads_is_the_program_it_was(monkeypatch):
    """kv_heads == heads: the pallas_call gets the grid, block shapes,
    scratch and operands the parent's wrapper built (q [B, H, D], one
    [H, 1] / [H, D] scratch set, pools as they are), and no swapaxes."""
    q, kp, vp, tables, pos = _paged_case(4, 4)
    seen = {}
    real = pk._decode_attn_call

    def spy(prefetch, q_, k, v, ks, vs, qo, kv, sc, **kw):
        seen.update(q=q_.shape, k=k.shape, qo=qo.block_shape,
                    kv=kv.block_shape, grid_rank=len(kw["grid"]),
                    chunk=kw["chunk"], name=kw["name"])
        return real(prefetch, q_, k, v, ks, vs, qo, kv, sc, **kw)
    monkeypatch.setattr(pk, "_decode_attn_call", spy)
    pk.paged_decode_attention(q, kp, vp, tables, pos, route="kernel",
                              interpret=True)
    assert seen == {"q": (3, 4, 16), "k": (13, 8, 4, 16),
                    "qo": (1, 4, 16), "kv": (1, 8, 4, 16), "grid_rank": 1,
                    "chunk": 8, "name": "paged_decode_attention"}
    q, kp, vp, tables, pos = _paged_case(8, 2)
    pk.paged_decode_attention(q, kp, vp, tables, pos, route="kernel",
                              interpret=True)
    assert seen["q"] == (3, 8, 16) and seen["qo"] == (1, 8, 16)
    assert seen["kv"] == (1, 8, 2, 16)


def test_grouped_flash_forward_equals_repeated_heads():
    rs = np.random.RandomState(12)
    q = jnp.asarray(rs.randn(2, 96, 8, 16), jnp.float32)
    k = jnp.asarray(rs.randn(2, 96, 2, 16), jnp.float32)
    v = jnp.asarray(rs.randn(2, 96, 2, 16), jnp.float32)
    want = pk.flash_attention(q, jnp.repeat(k, 4, 2), jnp.repeat(v, 4, 2),
                              causal=True, block_q=32, block_k=64,
                              interpret=True)
    got = pk.flash_attention(q, k, v, causal=True, block_q=32, block_k=64,
                             interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    short = pk.flash_attention(q, k, v, causal=True)        # dense route
    np.testing.assert_allclose(np.asarray(short), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    with pytest.raises(ValueError, match="whole groups"):
        pk.flash_attention(q, k[:, :, :1].repeat(3, 2), v[:, :, :1].repeat(
            3, 2), causal=True, block_q=32, block_k=64, interpret=True)


def test_cost_model_charges_the_kv_heads():
    from paddle_tpu.obs import roofline
    full = roofline.kernel_cost("paged_decode_attention", pages=10,
                                page_block=64, n_heads=32, d_head=64,
                                itemsize=2)
    grouped = roofline.kernel_cost("paged_decode_attention", pages=10,
                                   page_block=64, n_heads=32, kv_heads=8,
                                   d_head=64, itemsize=2)
    assert grouped == full / 4 == 2 * 10 * 64 * 8 * 64 * 2


# -- per-slot rows in the page pool ------------------------------------------

def test_pool_allocates_slot_rows_beside_pages(lm):
    model, params = lm
    rows = model.cache_rows(params)
    assert [type(r).__name__ + ":" + r.name for r in rows] == [
        "SlotRow:conv0", "CacheRow:k1", "CacheRow:v1", "SlotRow:conv2",
        "SlotRow:conv3", "SlotRow:conv4", "CacheRow:k5", "CacheRow:v5",
        "SlotRow:conv6"]
    pool = PagePool(model, params, slots=3, segment=4, page_block=8,
                    cache_bucket=16, prompt_buckets=(16, 32))
    assert sorted(pool.pools) == ["k1", "k5", "v1", "v5"]
    assert pool.pools["k1"].shape == (pool.pages, 8, 2, 8)
    assert sorted(pool.slot_state) == ["conv0", "conv2", "conv3", "conv4",
                                       "conv6"]
    assert pool.slot_state["conv0"].shape == (3, 2, 32)
    assert pool.slot_state_bytes == 5 * 2 * 32 * 4
    assert pool.page_bytes == 8 * 4 * 2 * 8 * 4       # pages only
    assert pool._read_layers == 2
    with pytest.raises(ValueError, match="prefix_cache"):
        PagePool(model, params, slots=2, page_block=8, cache_bucket=16,
                 prefix_cache=True)
    with pytest.raises(ValueError, match="kv_dtype"):
        PagePool(model, params, slots=2, page_block=8, cache_bucket=16,
                 kv_dtype="int8")


def test_models_without_slot_rows_get_the_pool_they_had():
    """TransformerLM and DeepseekV3LM state no SlotRow: no slot state is
    allocated, pool shapes are what they were, and a shipment names the
    pools alone."""
    gpt = TransformerLM(64, d_model=32, n_heads=4, n_layers=2, max_len=64)
    gp = gpt.init(jax.random.PRNGKey(0))
    pool = PagePool(gpt, gp, slots=2, segment=4, page_block=8,
                    cache_bucket=16)
    assert pool.slot_state == {} and pool.slot_state_bytes == 0
    assert all(isinstance(r, CacheRow) for r in gpt.cache_rows(gp))
    assert pool.pools["k0"].shape == (pool.pages, 8, 4, 8)
    assert pool._read_layers == 2
    plan = pool.plan_admission(np.arange(5, dtype=np.int32), 6)
    pool.admit([(0, plan)])
    manifest, _ = pool.export_slot(0, 1)
    assert [e["name"] for e in manifest["entries"]] == ["k0", "k1", "v0",
                                                        "v1"]
    pool.free_slot(0)
    v3 = DeepseekV3LM(64, d_model=32, n_heads=2, n_layers=2, n_dense=1,
                      dense_width=48, expert_width=16, n_experts=4, top_k=2,
                      n_group=2, topk_group=1, q_rank=16, kv_rank=16,
                      d_nope=8, d_rope=8, d_v=16, max_len=64,
                      dtype=jnp.float32)
    vp = v3.init(jax.random.PRNGKey(1))
    assert PagePool(v3, vp, slots=2, page_block=8,
                    cache_bucket=16).slot_state == {}


def test_slot_rows_cleared_by_free_and_round_tripped_by_shipping(lm):
    model, params = lm
    kw = dict(slots=3, segment=4, page_block=8, cache_bucket=16,
              prompt_buckets=(16, 32))
    a, b = PagePool(model, params, **kw), PagePool(model, params, **kw)
    rs = np.random.RandomState(13)
    prompt = rs.randint(0, 96, 11).astype(np.int32)
    plan = a.plan_admission(prompt, 9)
    first = a.admit([(1, plan)])[1]
    tail = np.asarray(a.slot_state["conv0"][1])
    assert np.abs(tail).max() > 0
    # only the admitted slot was written
    assert float(jnp.abs(a.slot_state["conv0"][0]).max()) == 0.0
    assert float(jnp.abs(a.slot_state["conv0"][2]).max()) == 0.0

    manifest, payload = a.export_slot(1, first)
    from paddle_tpu.serving import ship
    arrays = ship.unpack(manifest, payload)
    assert arrays["conv0"].shape == (2, 32)
    assert arrays["k1"].shape == (2, 8, 2, 8)
    b.check_shipment(11, arrays)
    bad = dict(arrays, conv0=arrays["conv0"][:1])
    with pytest.raises(ValueError, match="conv0"):
        b.check_shipment(11, bad)
    with pytest.raises(ValueError, match="missing"):
        b.check_shipment(11, {k: v for k, v in arrays.items()
                              if k != "conv2"})
    b.adopt_slot(2, 11, first, arrays, plan.need_pages)
    np.testing.assert_array_equal(np.asarray(b.slot_state["conv0"][2]), tail)

    # both pools continue to the same tokens, equal to solo decode
    ta = a.run_segment([1])[1]
    tb = b.run_segment([2])[2]
    np.testing.assert_array_equal(ta, tb)
    solo = np.asarray(model.generate_cached(
        params, jnp.asarray(prompt)[None], 4, page_block=8))[0, 11:]
    np.testing.assert_array_equal(ta, solo)

    # a freed slot's rows are back at their fill after the next segment
    # (the segment program keeps live slots' rows only; no program of its
    # own per freed slot), and an admission into it starts from its own
    # prefill: same tokens as a fresh pool
    a.free_slot(1)
    a.run_segment([])
    for nm, st in a.slot_state.items():
        assert float(jnp.abs(st[1]).max()) == 0.0, nm
    first2 = a.admit([(1, a.plan_admission(prompt, 9))])[1]
    assert first2 == first
    np.testing.assert_array_equal(a.run_segment([1])[1], ta)


def test_bf16_model_keeps_state_in_bf16_and_tracks_the_reference():
    """The configuration's own precision: bfloat16 parameters, pages and
    slot rows, f32 accumulation. Against the f32 reference over the same
    (bf16-valued) weights the logits differ by bf16 rounding of operands —
    a few 1e-3 at logits of size ~0.5 — and are held to 3e-2."""
    model = build(dtype=jnp.bfloat16)
    params = model.init(jax.random.PRNGKey(14))
    rows = model.cache_rows(params)
    assert {jnp.dtype(r.dtype) for r in rows} == {jnp.dtype(jnp.bfloat16)}
    ids = np.random.RandomState(15).randint(0, 96, (1, 24)).astype(np.int32)
    got = np.asarray(model(params, jnp.asarray(ids)))
    want = ref_logits(params, ids)
    assert np.abs(got - want).max() < 3e-2
    cell, _ = model.prefill(params, jnp.asarray(ids))
    assert cell["conv0"].dtype == jnp.bfloat16
    assert cell["k1"].dtype == jnp.bfloat16
