"""NemotronHLM (models/nemotron_h.py) against the plain reference
(chipbench/reference/nemotron_h.py) on seeded weights at a small size, the
mechanisms it brought to shared code (a recurrent carry among the page
pool's per-slot rows, written in place; the chunked scan and the in-place
state update; an expert's stated form and a matrix held transposed in the
grouped products; groups of 16 and heads of 128 in both attention kernels),
and the promise to the models that were there: a gated expert layer, the
plan chooser at their shapes and a pool without in-place rows are what they
were.

Tolerances. Everything here runs in float32 on the CPU, where the program
and the reference differ only in the ORDER of float32 sums (the chunked
scan's matrix products against one state update a position, grouped
products against one expert at a time, the kernel's running softmax against
a whole one, rsqrt against 1/sqrt): logits of size ~0.5 agree to a few
1e-6, held to 2e-4 (atol, rtol 2e-4). A wrong decay, group, tap, head pair
or router epsilon moves logits by 1e-2 and more. The scan and the update
are compared with a float64 loop over positions, held to 2e-5 at values of
size ~3. Exact equalities (``==``) are between two routes of the SAME
arithmetic, or between arrays that must not have been touched.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights_nemotron_h
from chipbench.reference import nemotron_h as ref
from paddle_tpu import nn
from paddle_tpu.models import Lfm2MoeLM, NemotronHLM
from paddle_tpu.models.paged_lm import SlotRow
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.parallel import expert_share
from paddle_tpu.serving.paged import PagePool

TOL = dict(atol=2e-4, rtol=2e-4)

#: a small configuration file of the family: every kind of layer, 5 of 8
#: experts held, chunks of 8 positions
CONFIG = {
    "vocab_size": 96, "hidden_size": 32,
    "hybrid_override_pattern": "MEM*EME", "layer_norm_epsilon": 1e-5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "moe_intermediate_size": 24, "moe_shared_expert_intermediate_size": 40,
    "router_width": 8, "experts_held": [0, 2, 3, 5, 7],
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 1e-4, "n_positions": 64,
}


def build(dtype=jnp.float32, **kw):
    """The model the benchmark builds for a configuration file of the
    family (chipbench/weights_nemotron_h.py), at this file's small size."""
    return weights_nemotron_h.model_and_shapes(CONFIG, dtype, **kw)


@pytest.fixture(scope="module")
def lm():
    model, shapes = build()
    # the benchmark's own seeded draw (a non-zero router bias, log-uniform
    # steps, the convolution's bias), with steps ~30x the published range
    # so that a prompt of 20 positions decays its state visibly
    params = weights_nemotron_h.make(
        shapes, 7, dict(CONFIG, time_step_min=0.03, time_step_max=1.0))
    for i, blk in enumerate(model.blocks):
        if blk.kind == "moe":       # wider logits: the top-k is decided
            moe = params[f"blocks_{i}"]["moe"]
            moe["w_router"] = 20.0 * moe["w_router"]
    return model, params


def ref_logits(params, ids):
    hp = ref.hparams(CONFIG)
    with jax.default_matmul_precision("highest"):
        return np.stack([np.asarray(ref.forward(params, jnp.asarray(row), hp))
                         for row in np.asarray(ids)])


def loop_scan(x, dt, a, bm, cm, lengths):
    """The recurrence one position at a time in float64: (y [R, T, H, P]
    up to each row's length, the state [R, H, P, N] AT that length)."""
    x, dt, a, bm, cm = (np.asarray(v, np.float64) for v in (x, dt, a, bm, cm))
    R, T, H, P = x.shape
    G, N = bm.shape[2:]
    y, s = np.zeros((R, T, H, P)), np.zeros((R, H, P, N))
    for r in range(R):
        for t in range(int(lengths[r])):
            for h in range(H):
                g = h // (H // G)
                s[r, h] = np.exp(dt[r, t, h] * a[h]) * s[r, h] \
                    + dt[r, t, h] * np.outer(x[r, t, h], bm[r, t, g])
                y[r, t, h] = s[r, h] @ cm[r, t, g]
    return y, s


def scan_case(seed, R=3, T=20, H=4, P=8, G=2, N=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (R, T, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (R, T, H)) - 1.0),
            -jnp.exp(0.5 * jax.random.normal(k[2], (H,))),
            jax.random.normal(k[3], (R, T, G, N)),
            jax.random.normal(k[4], (R, T, G, N)))


# -- the model against the reference ------------------------------------------

def test_full_forward_equals_the_reference(lm):
    model, params = lm
    assert [b.kind for b in model.blocks] == [
        "mamba", "moe", "mamba", "attention", "moe", "mamba", "moe"]
    ids = np.random.RandomState(0).randint(0, 96, (2, 37)).astype(np.int32)
    got = np.asarray(model(params, jnp.asarray(ids)))
    np.testing.assert_allclose(got, ref_logits(params, ids), **TOL)


def test_pool_admission_and_segments_equal_solo_decode(lm):
    """Through PagePool: admission at each row's own length (padded to a
    prompt bucket, three slots at once, the carry written in place), then
    decode segments that write pages, roll the tails, update the carry in
    place and read through the work list — the tokens are those of the
    model's solo paged decode, whose logits the next test holds to the
    reference's full forward."""
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


@pytest.mark.parametrize("plen", [1, 3, 8, 9, 17])
def test_paged_decode_logits_equal_the_reference(lm, plen):
    """Logits, not tokens: prefill a prompt (shorter than a chunk, a whole
    chunk, past one), then feed the reference's own continuation through
    decode_step_paged and compare every step: a prompt prefilled then
    decoded is the same tokens prefilled whole."""
    model, params = lm
    seq = np.random.RandomState(2).randint(0, 96, 30).astype(np.int32)
    want = ref_logits(params, seq[None])[0]
    cell, last = model.prefill(params, jnp.asarray(seq[None, :plen]))
    np.testing.assert_allclose(np.asarray(last)[0], want[plen - 1], **TOL)
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
    # ... and the carry after the decode steps is a whole prefill's
    whole, _ = model.prefill(params, jnp.asarray(seq[None]))
    for i in model.mamba_layers:
        for nm in (f"ssm{i}", f"conv{i}"):
            np.testing.assert_allclose(np.asarray(state[nm]),
                                       np.asarray(whole[nm]), **TOL)


def test_bf16_model_keeps_a_float32_carry_and_tracks_the_reference():
    """The configuration's own precision: bfloat16 parameters, pages and
    conv tails, a float32 carry, f32 accumulation. Against the f32
    reference over the same (bf16-valued) weights the logits differ by
    bf16 rounding of operands — a few 1e-3 at logits of size ~0.5 — and
    are held to 3e-2."""
    model, shapes = build(dtype=jnp.bfloat16)
    params = weights_nemotron_h.make(shapes, 14, CONFIG)
    kinds = {r.name[:3]: jnp.dtype(r.dtype) for r in model.cache_rows(params)}
    assert kinds["ssm"] == jnp.float32 and kinds["con"] == jnp.bfloat16
    ids = np.random.RandomState(15).randint(0, 96, (1, 24)).astype(np.int32)
    got = np.asarray(model(params, jnp.asarray(ids)))
    assert np.abs(got - ref_logits(params, ids)).max() < 3e-2
    cell, _ = model.prefill(params, jnp.asarray(ids))
    assert cell["ssm0"].dtype == jnp.float32
    assert cell["conv0"].dtype == jnp.bfloat16
    assert cell["k3"].dtype == jnp.bfloat16


def test_a_wide_row_runs_alone_in_its_chunk_and_gives_the_same_cell(
        lm, monkeypatch):
    """``SOLO_ROW_TOKENS``: rows at or past it are admitted one a chunk
    (two rows of 1,024 a chunk never return on the chip). Rows are
    independent of one another, so the cell and the logits are what rows
    filling a chunk give — to the order of float32 sums inside a chunk's
    grouped products (TOL), and ``==`` where nothing is summed."""
    from paddle_tpu.models import paged_lm as mod
    model, params = lm
    assert mod.SOLO_ROW_TOKENS == 1024 == mod.PREFILL_TOKENS // 2
    ids = np.random.RandomState(21).randint(0, 96, (4, 16)).astype(np.int32)
    lens = jnp.asarray([16, 5, 0, 11], jnp.int32)
    monkeypatch.setattr(mod, "PREFILL_TOKENS", 32)      # 2 rows of 16 a chunk
    monkeypatch.setattr(mod, "SOLO_ROW_TOKENS", 10 ** 9)
    filled, want = model.prefill(params, jnp.asarray(ids), lens)
    monkeypatch.setattr(mod, "SOLO_ROW_TOKENS", 16)     # ... or one
    solo, got = model.prefill(params, jnp.asarray(ids), lens)
    live = np.asarray(lens) > 0      # nobody reads an empty row's logits
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               **TOL)
    # both walks ran the three rows that hold a prompt: the same real
    # positions a Mamba layer, two chunks of two rows against three of one
    n_m = len(model.mamba_layers)
    assert int(solo["stats"]["scan_real"]) \
        == int(filled["stats"]["scan_real"]) == 32 * n_m
    assert int(filled["stats"]["scan_padded"]) == (2 * 2 * 16 - 32) * n_m
    assert int(solo["stats"]["scan_padded"]) == (3 * 1 * 16 - 32) * n_m
    for nm in ("ssm0", "conv0", "k3", "v3"):
        np.testing.assert_allclose(np.asarray(solo[nm], np.float32)[live],
                                   np.asarray(filled[nm], np.float32)[live],
                                   **TOL)
    for cell in (solo, filled):      # the empty row's carry is not written
        assert np.all(np.asarray(cell["ssm0"][2]) == 0)


def test_unknown_layer_letter_is_refused():
    with pytest.raises(ValueError, match="unknown layer letters"):
        build_kw = dict(CONFIG, hybrid_override_pattern="ME-*")
        weights_nemotron_h.model_and_shapes(build_kw, jnp.float32)
    with pytest.raises(ValueError, match="at least one attention"):
        weights_nemotron_h.model_and_shapes(
            dict(CONFIG, hybrid_override_pattern="MEME"), jnp.float32)


# -- the two state-space kernels ------------------------------------------------

@pytest.mark.parametrize("route", ["dense", "kernel"])
@pytest.mark.parametrize("lengths", [(20, 13, 5), (8, 16, 1), (20, 20, 20)],
                         ids=["ragged", "chunk-edges", "full"])
def test_chunked_scan_equals_the_recurrence_at_each_rows_length(route,
                                                                lengths):
    """Lengths that are no chunk multiples (chunks of 8 over T = 20, padded
    to 24 inside), rows of DIFFERENT lengths in one call: every row's
    outputs up to its own length and its state AT that length are the
    position-by-position recurrence's, whatever follows in the row."""
    x, dt, a, bm, cm = scan_case(3)
    want_y, want_s = loop_scan(x, dt, a, bm, cm, lengths)
    y, packed = pk.ssd_chunk_scan(x, dt, a, bm, cm, jnp.asarray(lengths),
                                  chunk=8, route=route, interpret=True)
    state = np.asarray(pk.ssm_unpack(packed))
    for r, n in enumerate(lengths):
        np.testing.assert_allclose(np.asarray(y)[r, :n], want_y[r, :n],
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(state[r], want_s[r], atol=2e-5,
                                   rtol=2e-5)


def test_chunked_scan_kernel_route_equals_dense_route():
    x, dt, a, bm, cm = scan_case(4, T=24)
    lengths = jnp.asarray([24, 9, 17])
    dense = pk.ssd_chunk_scan(x, dt, a, bm, cm, lengths, chunk=8,
                              route="dense")
    kern = pk.ssd_chunk_scan(x, dt, a, bm, cm, lengths, chunk=8,
                             route="kernel", interpret=True)
    np.testing.assert_allclose(np.asarray(kern[1]), np.asarray(dense[1]),
                               atol=1e-5, rtol=1e-5)
    for r, n in enumerate((24, 9, 17)):     # y past a row's length is free
        np.testing.assert_allclose(np.asarray(kern[0])[r, :n],
                                   np.asarray(dense[0])[r, :n], atol=1e-5,
                                   rtol=1e-5)
    # a chunk wholly past its row's length is skipped, and writes zeros
    assert float(jnp.abs(kern[0][1, 16:]).max()) == 0.0
    with pytest.raises(ValueError, match="whole pairs"):
        pk.ssd_chunk_scan(x[:, :, :3], dt[:, :, :3], a[:3], bm[:, :, :1],
                          cm[:, :, :1], chunk=8)
    with pytest.raises(ValueError, match="unknown ssd_chunk_scan route"):
        pk.ssd_chunk_scan(x, dt, a, bm, cm, chunk=8, route="fast")


def test_state_update_kernel_route_equals_dense_route_and_the_recurrence():
    x, dt, a, bm, cm = scan_case(5, T=6)
    _, state0 = loop_scan(x, dt, a, bm, cm, (5, 5, 5))
    packed = pk.ssm_pack(jnp.asarray(state0, jnp.float32))
    np.testing.assert_array_equal(np.asarray(pk.ssm_unpack(packed)),
                                  np.asarray(state0, np.float32))
    live = jnp.asarray([True, False, True])
    args = (packed, x[:, 5], dt[:, 5], a, bm[:, 5], cm[:, 5], live)
    yd, sd = pk.ssm_state_update(*args, route="dense")
    yk, sk = pk.ssm_state_update(*args, route="kernel", interpret=True)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yd), atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sd), atol=1e-6,
                               rtol=1e-6)
    want_y, want_s = loop_scan(x, dt, a, bm, cm, (6, 6, 6))
    for r in (0, 2):
        np.testing.assert_allclose(np.asarray(yk)[r], want_y[r, 5],
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(pk.ssm_unpack(sk))[r],
                                   want_s[r], atol=2e-5, rtol=2e-5)
    # a slot that is not live keeps its state bit for bit; its y is zero
    for got in (sd, sk):
        np.testing.assert_array_equal(np.asarray(got)[1],
                                      np.asarray(packed)[1])
    assert float(jnp.abs(yk[1]).max()) == 0.0 == float(jnp.abs(yd[1]).max())
    # no slot live at all: nothing moves
    yn, sn = pk.ssm_state_update(*args[:-1], jnp.zeros((3,), bool),
                                 route="kernel", interpret=True)
    np.testing.assert_array_equal(np.asarray(sn), np.asarray(packed))
    assert float(jnp.abs(yn).max()) == 0.0


def test_mixer_step_from_a_prefix_equals_the_whole_sequence():
    mixer = nn.Mamba2Mixer(32, heads=4, head_dim=8, groups=2, state=16,
                           chunk=8)
    p = mixer.init(jax.random.PRNGKey(6))
    u = jax.random.normal(jax.random.PRNGKey(7), (2, 13, 32))
    y, state, tail = mixer(p, u, jnp.asarray([13, 7]))
    assert state.shape == (2, 2, 16, 16) and tail.shape == (2, 3, 96)
    ys, s1, t1 = mixer(p, u[:, :7])
    # row 1 stopped at 7: its state and tail are the 7-position prefix's
    np.testing.assert_array_equal(np.asarray(s1[1]), np.asarray(state[1]))
    np.testing.assert_array_equal(np.asarray(t1[1]), np.asarray(tail[1]))
    for t in range(7, 13):
        yt, s1, t1 = mixer.step(p, u[:, t], s1, t1)
        np.testing.assert_allclose(np.asarray(yt[0]), np.asarray(y[0, t]),
                                   atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(s1[0]), np.asarray(state[0]),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(t1[0]), np.asarray(tail[0]))


def test_cost_models_of_the_state_space_kernels():
    from paddle_tpu.obs import roofline
    shape = dict(heads=64, head_dim=64, state=128, groups=8)
    one = 2 * 64 * 64 * 128 * 4 + (2 * 64 * 64 + 2 * 8 * 128 + 64) * 4
    assert roofline.kernel_cost("ssm_state_update", updates=3,
                                **shape) == 3 * one
    assert roofline.kernel_cost(
        "ssd_chunk_scan", tokens=10, itemsize=2, **shape) \
        == 10 * ((64 * 64 + 2 * 8 * 128) * 2 + (64 + 64 * 64) * 4)


# -- the expert layer: a stated form, a share of it ---------------------------------

def _relu2_layer(held, shared=True, **kw):
    return expert_share.ExpertShare(
        32, 24, n_experts=8, experts_held=held, top_k=2, n_group=1,
        topk_group=1, routed_scale=2.5, norm_eps=1e-20, gated=False,
        shared_width=40, shared=shared, **kw)


@pytest.mark.parametrize("transposed", [True, False],
                         ids=["up-held-f-by-d", "up-held-d-by-f"])
def test_ungated_expert_share_is_the_published_expert(transposed):
    layer = _relu2_layer([0, 2, 3, 5, 7], up_transposed=transposed)
    p = layer.init(jax.random.PRNGKey(8))
    assert "w_gate" not in p and set(p["shared"]) == {"w_up", "w_down"}
    assert p["w_up"].shape == ((5, 24, 32) if transposed else (5, 32, 24))
    assert p["shared"]["w_up"].shape == (32, 40)
    p["w_router"] = 20.0 * p["w_router"]
    p["e_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(9), (8,))
    y = jax.random.normal(jax.random.PRNGKey(10), (19, 32))
    out, counts = layer(p, y)
    hp = dict(ref.hparams(CONFIG), experts_held=(0, 2, 3, 5, 7))
    rp = p if transposed else dict(p, w_up=jnp.swapaxes(p["w_up"], 1, 2))
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(rp, y, hp)
        chosen, _ = ref.route(p, y, hp)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)
    np.testing.assert_array_equal(
        np.asarray(counts),
        [(np.asarray(chosen) == e).sum() for e in (0, 2, 3, 5, 7)])


def test_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The guide's share test: the routed parts the 8 chips' shares give (2
    shares of 4 experts here) plus the shared expert counted ONCE equal
    the layer that holds every expert."""
    whole = _relu2_layer(list(range(8)), up_transposed=True)
    p = whole.init(jax.random.PRNGKey(11))
    p["w_router"] = 20.0 * p["w_router"]
    y = jax.random.normal(jax.random.PRNGKey(12), (23, 32))
    want, _ = whole(p, y)
    total = None
    for n, held in enumerate(([0, 1, 2, 3], [4, 5, 6, 7])):
        part = _relu2_layer(held, shared=n == 0, up_transposed=True)
        pp = {k: (v[jnp.asarray(held)] if k in ("w_up", "w_down") else v)
              for k, v in p.items() if k != "shared" or n == 0}
        out, _ = part(pp, y)
        total = out if total is None else total + out
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), **TOL)


def test_gated_expert_share_keeps_its_parameters_and_products():
    """``gated=True`` (the default): the three matrices in the order they
    had, a SwiGLU shared expert of n_shared x d_expert, no transposed
    matrix at widths that are multiples of 128 or at small ones."""
    layer = expert_share.ExpertShare(
        32, 16, n_experts=8, experts_held=[1, 4], top_k=2, n_group=1,
        topk_group=1, routed_scale=1.0, n_shared=2)
    p = layer.init(jax.random.PRNGKey(13))
    assert list(p) == ["w_router", "e_bias", "w_gate", "w_up", "w_down",
                       "shared"]
    assert p["w_gate"].shape == (2, 32, 16) == p["w_up"].shape
    assert p["shared"]["w_gate"].shape == (32, 32)
    assert not layer.up_transposed
    wide = expert_share.ExpertShare(
        2048, 1792, n_experts=32, experts_held=[0], top_k=4, n_group=1,
        topk_group=1, routed_scale=1.0, n_shared=0)
    assert not wide.up_transposed and wide.shared is None
    # held transposed only where the model says so (NemotronHLM: 1856 =
    # 29 x 64 under 2688 = 21 x 128, held with 2688 minor)
    assert not _relu2_layer([0]).up_transposed
    model, _ = build()
    assert all(b.moe.up_transposed for b in model.blocks if b.kind == "moe")


def test_route_is_the_published_router():
    """One group, top-6 of s + bias over 128, weights s / (sum + 1e-20) x
    2.5 — against the reference's own."""
    logits = jax.random.normal(jax.random.PRNGKey(14), (64, 128)) * 3.0
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(15), (128,))
    experts, w = expert_share.route(logits, bias, n_group=1, topk_group=1,
                                    top_k=6, routed_scale=2.5,
                                    norm_eps=1e-20)
    hp = {"top_k": 6, "norm_topk_prob": True, "routed_scale": 2.5}
    chosen, want = ref.route({"w_router": jnp.eye(128), "e_bias": bias},
                             logits, hp)
    np.testing.assert_array_equal(np.sort(np.asarray(experts), 1),
                                  np.sort(np.asarray(chosen), 1))
    np.testing.assert_allclose(np.sort(np.asarray(w), 1),
                               np.sort(np.asarray(want), 1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w.sum(1)), 2.5, rtol=1e-5)


# -- the grouped product: a matrix held transposed, the plan chooser --------------------

@pytest.mark.parametrize("tm, K, N", [(8, 48, 40), (128, 256, 128)],
                         ids=["k-split", "resident"])
def test_transposed_grouped_matmul_kernel_equals_dense(tm, K, N):
    rs = np.random.RandomState(16)
    G, tiles = 3, 5
    lhs = jnp.asarray(rs.randn(tiles * tm, K), jnp.float32)
    rhs = jnp.asarray(rs.randn(G, K, N), jnp.float32)
    group = jnp.asarray([0, 0, 1, 2, 2], jnp.int32)
    n = jnp.asarray([4], jnp.int32)
    assert pk.grouped_matmul_blocks(tm, K, N, 4)[2] == (tm == 128)
    want = pk.grouped_matmul(lhs, rhs, group, n, tm=tm, route="dense")
    rhs_t = jnp.swapaxes(rhs, 1, 2)
    dense = pk.grouped_matmul(lhs, rhs_t, group, n, tm=tm, transposed=True,
                              route="dense")
    kern = pk.grouped_matmul(lhs, rhs_t, group, n, tm=tm, transposed=True,
                             route="kernel", interpret=True)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(want),
                               atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(kern)[:4 * tm],
                               np.asarray(want)[:4 * tm], atol=1e-4,
                               rtol=1e-5)


@pytest.mark.parametrize("tm, K, N, blocks", [
    (16, 7168, 2048, (512, 2048, False)),
    (16, 2048, 7168, (256, 3584, False)),
    (256, 7168, 2048, (512, 2048, False)),
    (256, 2048, 7168, (256, 3584, False)),
    (16, 2048, 1792, (512, 1792, False)),
    (16, 1792, 2048, (256, 2048, False)),
    (128, 2048, 1792, (2048, 1792, True)),
    (128, 1792, 2048, (1792, 2048, True)),
    (256, 2048, 1792, (2048, 1792, True)),
    (256, 1792, 2048, (1792, 2048, True)),
    (16, 2688, 1856, (384, 1856, False)),
    (16, 1856, 2688, (1856, 896, False)),
    (128, 2688, 1856, (2688, 1856, True)),
    (256, 1856, 2688, (1856, 2688, True))],
    ids=["longout-decode-gate", "longout-decode-down", "longout-admit-gate",
         "longout-admit-down", "rag-decode-gate", "rag-decode-down",
         "rag-admit-gate", "rag-admit-down", "rag-chunk-gate",
         "rag-chunk-down", "nemotron-decode-up", "nemotron-decode-down",
         "nemotron-admit-up", "nemotron-chunk-down"])
def test_grouped_matmul_blocks_are_the_parents_at_the_cells_shapes(
        tm, K, N, blocks):
    """The plan chooser was widened for K / N = 2688 / 1856 (1856 = 29 x
    64 has no divisor that is a multiple of 128: today's rule would cut
    ``down`` into 21 strips of 256-byte rows). At every shape longout and
    rag call it with it gives EXACTLY the parent's tuple; Nemotron's get a
    sound one: up in 7 blocks of 384 x 1856, down whole-K in 3 strips of
    896, the admissions resident."""
    assert pk.grouped_matmul_blocks(tm, K, N, 2) == blocks


# -- groups of 16, heads of 128 in the shared attention kernels -------------------------

def test_group_16_head_128_paged_decode_kernel_equals_dense_route():
    rs = np.random.RandomState(17)
    B, NB, bs, Hq, Hkv, D = 2, 3, 8, 32, 2, 128
    P = 1 + B * NB
    kp = jnp.asarray(rs.randn(P, bs, Hkv, D), jnp.float32)
    vp = jnp.asarray(rs.randn(P, bs, Hkv, D), jnp.float32)
    tables = jnp.asarray(1 + np.arange(B * NB).reshape(B, NB), jnp.int32)
    pos = jnp.asarray([5, 20], jnp.int32)
    q = jnp.asarray(rs.randn(B, Hq, D), jnp.float32)
    kw = dict(scale=D ** -0.5)
    dense = pk.paged_decode_attention(q, kp, vp, tables, pos, route="dense",
                                      **kw)
    kern = pk.paged_decode_attention(q, kp, vp, tables, pos, route="kernel",
                                     interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense),
                               atol=1e-5, rtol=1e-5)
    rep = pk.paged_decode_attention(q, jnp.repeat(kp, 16, 2),
                                    jnp.repeat(vp, 16, 2), tables, pos,
                                    route="kernel", interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(rep), atol=1e-6)


def test_group_16_head_128_flash_forward_equals_dense_route():
    rs = np.random.RandomState(18)
    q = jnp.asarray(rs.randn(1, 96, 32, 128), jnp.float32)
    k = jnp.asarray(rs.randn(1, 96, 2, 128), jnp.float32)
    v = jnp.asarray(rs.randn(1, 96, 2, 128), jnp.float32)
    got = pk.flash_attention(q, k, v, causal=True, block_q=32, block_k=64,
                             interpret=True)
    dense = pk.flash_attention(q, k, v, causal=True)        # dense route
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


# -- the carry in the page pool -----------------------------------------------------

def test_pool_states_the_carry_beside_pages_and_keeps_it_in_place(lm):
    model, params = lm
    rows = model.cache_rows(params)
    assert [type(r).__name__ + ":" + r.name for r in rows] == [
        "SlotRow:ssm0", "SlotRow:conv0", "SlotRow:ssm2", "SlotRow:conv2",
        "CacheRow:k3", "CacheRow:v3", "SlotRow:ssm5", "SlotRow:conv5"]
    pool = PagePool(model, params, slots=3, segment=4, page_block=8,
                    cache_bucket=16, prompt_buckets=(16, 32))
    assert pool._in_place and sorted(pool.pools) == ["k3", "v3"]
    assert pool.slot_state["ssm0"].shape == (3, 2, 16, 16)
    assert pool.slot_state["conv0"].shape == (3, 3, 96)
    assert pool.slot_state_bytes == 3 * (2 * 16 * 16 + 3 * 96) * 4
    assert pool._read_layers == 1
    with pytest.raises(ValueError, match="prefix_cache"):
        PagePool(model, params, slots=2, page_block=8, cache_bucket=16,
                 prefix_cache=True)
    with pytest.raises(ValueError, match="kv_dtype"):
        PagePool(model, params, slots=2, page_block=8, cache_bucket=16,
                 kv_dtype="int8")
    # a model with small slot rows keeps the blended write it had
    lfm = Lfm2MoeLM(64, d_model=32, n_heads=4, kv_heads=2,
                    layer_types=["conv", "full_attention"], n_dense=1,
                    dense_width=48, expert_width=16, n_experts=4, top_k=2,
                    max_len=64, dtype=jnp.float32)
    assert not PagePool(lfm, lfm.init(jax.random.PRNGKey(0)), slots=2,
                        page_block=8, cache_bucket=16)._in_place


def test_an_admission_writes_its_own_slots_and_no_other(lm):
    """In place: slot 1 is live and mid-decode when slots 0 and 2 are
    admitted (two rows in a chunk of eight: the chunk's other six rows are
    slots of length 0, slot 1 among them). Slot 1's carry and tail are
    untouched bit for bit, and its stream goes on as if alone."""
    model, params = lm
    pool = PagePool(model, params, slots=3, segment=4, page_block=8,
                    cache_bucket=16, prompt_buckets=(16, 32))
    rs = np.random.RandomState(19)
    mid, a, b = (rs.randint(0, 96, n).astype(np.int32) for n in (9, 4, 14))
    pool.admit([(1, pool.plan_admission(mid, 13))])
    toks = [pool.run_segment([1])[1]]
    before = {k: np.asarray(v[1]) for k, v in pool.slot_state.items()}
    assert np.abs(before["ssm0"]).max() > 0
    pool.admit([(0, pool.plan_admission(a, 6)),
                (2, pool.plan_admission(b, 6))])
    for k, v in pool.slot_state.items():
        np.testing.assert_array_equal(np.asarray(v[1]), before[k], k)
        assert float(jnp.abs(v[0]).max()) > 0 and \
            float(jnp.abs(v[2]).max()) > 0, k
    toks += [pool.run_segment([0, 1, 2])[1] for _ in range(2)]
    solo = np.asarray(model.generate_cached(
        params, jnp.asarray(mid)[None], 12, page_block=8))[0, 9:]
    np.testing.assert_array_equal(np.concatenate(toks), solo)


def test_carry_reset_once_a_slot_is_not_live_and_round_tripped_by_shipping(
        lm):
    model, params = lm
    kw = dict(slots=3, segment=4, page_block=8, cache_bucket=16,
              prompt_buckets=(16, 32))
    a, b = PagePool(model, params, **kw), PagePool(model, params, **kw)
    prompt = np.random.RandomState(20).randint(0, 96, 11).astype(np.int32)
    plan = a.plan_admission(prompt, 9)
    first = a.admit([(1, plan)])[1]
    carry = np.asarray(a.slot_state["ssm0"][1])
    assert np.abs(carry).max() > 0
    # only the admitted slot was written
    for s in (0, 2):
        assert float(jnp.abs(a.slot_state["ssm0"][s]).max()) == 0.0
        assert float(jnp.abs(a.slot_state["conv0"][s]).max()) == 0.0

    manifest, payload = a.export_slot(1, first)
    from paddle_tpu.serving import ship
    arrays = ship.unpack(manifest, payload)
    assert arrays["ssm0"].shape == (2, 16, 16)
    assert arrays["conv0"].shape == (3, 96)
    assert arrays["k3"].shape == (2, 8, 2, 8)
    b.check_shipment(11, arrays)
    with pytest.raises(ValueError, match="ssm0"):
        b.check_shipment(11, dict(arrays, ssm0=arrays["ssm0"][:1]))
    with pytest.raises(ValueError, match="missing"):
        b.check_shipment(11, {k: v for k, v in arrays.items()
                              if k != "ssm2"})
    b.adopt_slot(2, 11, first, arrays, plan.need_pages)
    np.testing.assert_array_equal(np.asarray(b.slot_state["ssm0"][2]), carry)

    # both pools continue to the same tokens, equal to solo decode
    ta = a.run_segment([1])[1]
    tb = b.run_segment([2])[2]
    np.testing.assert_array_equal(ta, tb)
    solo = np.asarray(model.generate_cached(
        params, jnp.asarray(prompt)[None], 4, page_block=8))[0, 11:]
    np.testing.assert_array_equal(ta, solo)

    # a freed slot's rows are back at their fill after the next segment
    # (a scatter at the dead slots alone), the live slot's are kept, and an
    # admission into the freed slot starts from its own prefill
    b.admit([(0, b.plan_admission(prompt[:6], 9))])
    keep = np.asarray(b.slot_state["ssm5"][0])
    b.free_slot(2)
    b.run_segment([0])
    for nm, st in b.slot_state.items():
        assert float(jnp.abs(st[2]).max()) == 0.0, nm
    assert np.abs(np.asarray(b.slot_state["ssm5"][0]) - keep).max() > 0
    a.free_slot(1)
    a.run_segment([])
    for nm, st in a.slot_state.items():
        assert float(jnp.abs(st).max()) == 0.0, nm
    assert a.admit([(1, a.plan_admission(prompt, 9))])[1] == first
    np.testing.assert_array_equal(a.run_segment([1])[1], ta)


def test_programs_count_the_state_space_work(lm):
    """What a program returns beside its tokens: live (slot, Mamba layer)
    updates of a segment; real and padded (position, layer) pairs, rows
    and prompt tokens of an admission — and the spans carry them."""
    from paddle_tpu import obs
    model, params = lm
    pool = PagePool(model, params, slots=3, segment=4, page_block=8,
                    cache_bucket=16, prompt_buckets=(16, 32))
    prompts = [np.arange(n, dtype=np.int32) for n in (5, 13)]
    session = obs.ObsSession().install()
    try:
        pool.admit([(s, pool.plan_admission(p, 8))
                    for s, p in enumerate(prompts)])
        assert pool.last_stats["prompt_tokens"] == 18
        assert pool.last_stats["rows"] == 2
        assert {"routed_here", "experts_touched", "row_tiles",
                "load_max"} <= set(pool.last_stats)
        pool.run_segment([0, 1])
        assert "prompt_tokens" not in pool.last_stats
        got = {(m["name"], tuple(sorted(m["labels"].items()))): m["value"]
               for m in session.registry.collect()
               if m["name"].startswith("ssm.")}
        kernels = {m["labels"]["kernel"]: m["value"]
                   for m in session.registry.collect()
                   if m["name"] == "kernels.bytes_total"}
    finally:
        session.uninstall()
    assert kernels["ssm_state_update"] > 0 and kernels["ssd_chunk_scan"] > 0
    n_m = len(model.mamba_layers)
    assert got[("ssm.state_updates_total", (("program", "segment"),))] \
        == 2 * 4 * n_m
    assert got[("ssm.scan_tokens_total", (("state", "real"),))] == 18 * n_m
    # one chunk of the pool's 3 rows x 16 positions ran: the rest of it is
    # padding
    assert got[("ssm.scan_tokens_total", (("state", "padded"),))] \
        == (3 * 16 - 18) * n_m
