"""What PR 39 promised the code it threaded a window through: called with NO
window, ``pk.paged_work_list``, ``pk.paged_decode_attention`` and
``pk.flash_attention`` give the parent's outputs bit for bit and count the
parent's ``kernels.flash_block_pairs_total`` / plan counters;
``pk.grouped_matmul_blocks`` returns the parent's tuple at every (tm, K, N)
the three expert cells call it with; and a page pool over a model that
states no window builds the parent's segment program (the jaxpr's text; the
admit program's fingerprint is PR 40's, whose write is a page at a time,
printed the same way on that PR's tree; the segment program's is PR 43's,
whose loop takes its step count as an argument). Beside them the new reach itself: the windowed read and the banded
flash against their dense routes over ragged positions and lengths.

``GOLDEN`` was printed by this file run as a script on the parent commit
(6876e59: ``PYTHONPATH=<parent checkout> python tests/test_window_guards.py``
— every fingerprint below uses only what that tree has). A digest is of the
outputs' bytes, or of a jaxpr's text with addresses taken out; it moves
with the JAX version as well as with the code, so a failure after an
upgrade is answered by printing them again on a tree known to be sound.
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import obs
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.serving.paged import PagePool

#: (tm, K, N) of the grouped products the three expert cells trace: decode
#: (16), admission (128) and the CHUNK walk (256) x (d_model, d_expert) both
#: ways, for gigachat (7168, 2048), lfm2 (2048, 1792), nemotron (2688, 1856)
EXPERT_SHAPES = [(tm, K, N) for tm in (16, 128, 256)
                 for d, f in ((7168, 2048), (2048, 1792), (2688, 1856))
                 for K, N in ((d, f), (f, d))]


def _digest(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(str((a.shape, a.dtype)).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def _pool_inputs(groups, seed=5):
    """q [B, H, D], pools [P, bs, Hkv, D], a table whose rows own disjoint
    pages, and ragged ``pos`` (0, a page edge, mid-page, the last row)."""
    rs = np.random.RandomState(seed)
    B, Hkv, D, bs, NB = 4, 2, 8, 8, 6
    q = jnp.asarray(rs.randn(B, Hkv * groups, D), jnp.float32)
    k = jnp.asarray(rs.randn(B * NB + 1, bs, Hkv, D), jnp.float32)
    v = jnp.asarray(rs.randn(B * NB + 1, bs, Hkv, D), jnp.float32)
    tables = jnp.asarray(1 + np.arange(B * NB).reshape(B, NB), jnp.int32)
    pos = jnp.asarray([0, 15, 21, NB * bs - 1], jnp.int32)
    return q, k, v, tables, pos


def _qkv(seed, B, T, H, Hkv, D):
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(B, T, H, D), jnp.float32),
            jnp.asarray(rs.randn(B, T, Hkv, D), jnp.float32),
            jnp.asarray(rs.randn(B, T, Hkv, D), jnp.float32))


def fp_work_list():
    _, _, _, tables, pos = _pool_inputs(1)
    return _digest(*pk.paged_work_list(tables, pos, 8))


def fp_paged_read(route, groups):
    q, k, v, tables, pos = _pool_inputs(groups)
    r = obs.MetricsRegistry()
    jax.clear_caches()
    with obs.ObsSession(registry=r).installed():
        out = pk.paged_decode_attention(q, k, v, tables, pos, route=route,
                                        interpret=True)
    plan = "group_mxu" if groups > 1 else "head_vpu"
    return (_digest(out),
            r.counter("kernels.routes_total").get(
                kernel="paged_decode_attention", route=route),
            r.counter("kernels.paged_decode_plan_total").get(
                plan=plan, group=str(groups)))


def fp_flash(case):
    """(digest of the output, visited, grid) of one forward call."""
    kw, heads = {
        "causal": (dict(causal=True, block_q=16, block_k=32), (3, 3)),
        "causal-lens": (dict(causal=True, block_q=16, block_k=16,
                             kv_lens=jnp.asarray([80, 37])), (3, 3)),
        "gqa": (dict(causal=True, block_q=32, block_k=16), (4, 2)),
        "noncausal": (dict(block_q=16, block_k=16), (3, 3)),
        "dense-route": (dict(causal=True), (4, 2)),
    }[case]
    q, k, v = _qkv(29, 2, 80, heads[0], heads[1], 16)
    r = obs.MetricsRegistry()
    jax.clear_caches()
    with obs.ObsSession(registry=r).installed():
        out = pk.flash_attention(q, k, v, interpret=True, **kw)
    c = r.counter("kernels.flash_block_pairs_total")
    return (_digest(out),
            c.get(kernel="flash_attention_fwd", state="visited"),
            c.get(kernel="flash_attention_fwd", state="grid"))


def _models():
    """name -> model: one of every kind of pool the parent built — pages alone, a latent row, slot rows blended, slot rows in
    place."""
    from paddle_tpu.models import (DeepseekV3LM, Lfm2MoeLM, NemotronHLM,
                                   TransformerLM)
    f32 = jnp.float32
    return {
        "gpt2": TransformerLM(97, d_model=32, n_heads=4, n_layers=2,
                              max_len=64),
        "deepseek_v3": DeepseekV3LM(
            96, d_model=32, n_heads=4, n_layers=2, n_dense=1,
            dense_width=48, expert_width=16, n_experts=8,
            experts_held=[0, 1, 2, 3], top_k=2, n_group=2, topk_group=1,
            q_rank=16, kv_rank=16, d_nope=8, d_rope=4, d_v=12, max_len=64,
            dtype=f32),
        "lfm2": Lfm2MoeLM(
            96, d_model=32, n_heads=4, kv_heads=2,
            layer_types=["conv", "full_attention", "conv"], n_dense=1,
            dense_width=48, expert_width=16, n_experts=8,
            experts_held=[0, 1, 2, 3], top_k=2, max_len=64, dtype=f32),
        "nemotron_h": NemotronHLM(
            96, d_model=32, pattern="ME*M", n_heads=4, kv_heads=2, d_head=8,
            mamba_heads=4, mamba_head_dim=8, ssm_groups=2, ssm_state=16,
            expert_width=24, shared_width=40, n_experts=8,
            experts_held=[0, 1, 2, 3], top_k=2, chunk=8, max_len=64,
            dtype=f32),
    }


def fp_programs(name):
    """Digests of the texts of the admit and the segment program a pool
    over model ``name`` builds (4 slots, pages of 8, a 16-token bucket)."""
    model = _models()[name]
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pool = PagePool(model, params, slots=4, segment=4, page_block=8,
                    cache_bucket=64, prompt_buckets=(16, 32),
                    prefix_cache=False)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)   # noqa: E731
    state = (pool.pools, pool.slot_state)

    def text(fn, *args):
        t = str(jax.make_jaxpr(fn._jitted)(params, state, *args))
        return hashlib.sha256(re.sub(r" at 0x[0-9a-f]+", "", t).encode()
                              ).hexdigest()[:16]
    return (text(pool._admit_fn(16, 2), i32(4, 16), i32(4), i32(4, 2)),
            text(pool._seg_fn(8), i32(4, 8), i32(4), i32(4),
                 jax.ShapeDtypeStruct((4,), bool), i32()))


GOLDEN = {'blocks/128/1792/2048': [1792, 2048, True],
 'blocks/128/1856/2688': [1856, 2688, True],
 'blocks/128/2048/1792': [2048, 1792, True],
 'blocks/128/2048/7168': [256, 3584, False],
 'blocks/128/2688/1856': [2688, 1856, True],
 'blocks/128/7168/2048': [512, 2048, False],
 'blocks/16/1792/2048': [256, 2048, False],
 'blocks/16/1856/2688': [1856, 896, False],
 'blocks/16/2048/1792': [512, 1792, False],
 'blocks/16/2048/7168': [256, 3584, False],
 'blocks/16/2688/1856': [384, 1856, False],
 'blocks/16/7168/2048': [512, 2048, False],
 'blocks/256/1792/2048': [1792, 2048, True],
 'blocks/256/1856/2688': [1856, 2688, True],
 'blocks/256/2048/1792': [2048, 1792, True],
 'blocks/256/2048/7168': [256, 3584, False],
 'blocks/256/2688/1856': [2688, 1856, True],
 'blocks/256/7168/2048': [512, 2048, False],
 'flash/causal': ['903aba5f32bfbd0c', 126.0, 216.0],
 'flash/causal-lens': ['bc49087377c09e34', 90.0, 150.0],
 'flash/dense-route': ['c1f691608876ab3c', 0.0, 0.0],
 'flash/gqa': ['deef7c9e42019175', 192.0, 288.0],
 'flash/noncausal': ['6711924b8047a21c', 150.0, 150.0],
 'paged_read/dense/1': ['804d078fdbccda4d', 1.0, 0.0],
 'paged_read/dense/2': ['5667f40498527815', 1.0, 0.0],
 'paged_read/kernel/1': ['864d414c69b2d71c', 1.0, 1.0],
 'paged_read/kernel/2': ['9fd1e8c231df8bf6', 1.0, 1.0],
 'programs/deepseek_v3': ['4310c24713a0c211', 'bbe72cd4baf9003d'],
 'programs/gpt2': ['b345b8ed8fb7b5d0', '03052607977a64ee'],
 'programs/lfm2': ['31820185837e5f50', '260e49e985d13d61'],
 'programs/nemotron_h': ['d1066b4e2546ae0e', '69023f72181ebc31'],
 'work_list': '2792a21f73e1639a'}


# -- the guards ---------------------------------------------------------------

def test_work_list_without_a_window_is_the_parents():
    assert fp_work_list() == GOLDEN["work_list"]


@pytest.mark.parametrize("route", ["dense", "kernel"])
@pytest.mark.parametrize("groups", [1, 2])
def test_paged_read_without_a_window_is_the_parents(route, groups):
    assert list(fp_paged_read(route, groups)) == \
        GOLDEN[f"paged_read/{route}/{groups}"]


@pytest.mark.parametrize("case", ["causal", "causal-lens", "gqa",
                                  "noncausal", "dense-route"])
def test_flash_without_a_window_is_the_parents(case):
    assert list(fp_flash(case)) == GOLDEN[f"flash/{case}"]


@pytest.mark.parametrize("tm, K, N", EXPERT_SHAPES)
def test_grouped_matmul_blocks_are_the_parents(tm, K, N):
    assert list(pk.grouped_matmul_blocks(tm, K, N)) == \
        GOLDEN[f"blocks/{tm}/{K}/{N}"]


@pytest.mark.parametrize("name", ["gpt2", "deepseek_v3", "lfm2",
                                  "nemotron_h"])
def test_pool_programs_of_a_model_without_a_window_are_the_parents(name):
    """No ring, no ring table among the arguments. The admit program's
    text is PR 40's (a page written at a time where PR 39's was one
    scatter) and PR 43 left it so; the SEGMENT program's was PR 39's until
    PR 43 gave it its step count as an argument (a ``fori_loop`` under a
    traced bound where the scan ran 32 steps; printed again on that
    tree)."""
    assert list(fp_programs(name)) == GOLDEN[f"programs/{name}"]


# -- the new reach against its dense route ------------------------------------

def _ring_case(pos, window, bs, ring, groups=2, seed=3):
    """A cache of ``pos + 1`` rows a slot written into a ring as a decode
    would have left it, and the window's softmax over the plain rows."""
    rs = np.random.RandomState(seed)
    pos = np.asarray(pos)
    B, Hkv, D = len(pos), 2, 8
    H = Hkv * groups
    L = int(pos.max()) + 1
    kf, vf = rs.randn(B, L, Hkv, D), rs.randn(B, L, Hkv, D)
    q = rs.randn(B, H, D)
    tables = 1 + np.arange(B * ring).reshape(B, ring)
    kp = rs.randn(B * ring + 1, bs, Hkv, D)         # stale rows: noise
    vp = rs.randn(B * ring + 1, bs, Hkv, D)
    want = np.zeros((B, H, D))
    for b in range(B):
        for p in range(pos[b] + 1):                 # later pages overwrite
            page = tables[b, (p // bs) % ring]
            kp[page, p % bs], vp[page, p % bs] = kf[b, p], vf[b, p]
        lo = max(0, pos[b] - window + 1)
        kk = np.repeat(kf[b, lo:pos[b] + 1], groups, axis=1)
        vv = np.repeat(vf[b, lo:pos[b] + 1], groups, axis=1)
        s = np.einsum("hd,jhd->hj", q[b] * D ** -0.5, kk)
        w = np.exp(s - s.max(-1, keepdims=True))
        want[b] = np.einsum("hj,jhd->hd", w / w.sum(-1, keepdims=True), vv)
    f32 = lambda a: jnp.asarray(a, jnp.float32)     # noqa: E731
    return (f32(q), f32(kp), f32(vp), jnp.asarray(tables, jnp.int32),
            jnp.asarray(pos, jnp.int32)), want


@pytest.mark.parametrize("route", ["dense", "kernel"])
@pytest.mark.parametrize("pos", [
    (0, 5, 15),            # pos < window: nothing has slid out
    (16, 23, 24),          # the window's first full turn; a page edge
    (63, 64, 95),          # a ring that has wrapped, on and off an edge
    (40, 7, 199),          # short and long side by side; several wraps
])
def test_windowed_paged_read_matches_the_plain_window(route, pos):
    args, want = _ring_case(pos, window=16, bs=8, ring=4)
    r = obs.MetricsRegistry()
    jax.clear_caches()
    with obs.ObsSession(registry=r).installed():
        got = pk.paged_decode_attention(*args, window=16, route=route,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6, rtol=2e-6)
    # counted under its own name, and nothing under the full read's
    routes = r.counter("kernels.routes_total")
    assert routes.get(kernel="paged_window_attention", route=route) == 1
    assert routes.get(kernel="paged_decode_attention", route=route) == 0


def test_windowed_work_list_walks_the_windows_pages_alone():
    """Slot b's items are the ring entries of pages ``(pos - window) // bs
    .. pos // bs``, ordinals ABSOLUTE, oldest first, last flagged."""
    tables = jnp.asarray(1 + np.arange(12).reshape(3, 4), jnp.int32)
    pos = jnp.asarray([5, 24, 95], jnp.int32)
    slot, page, ordinal, last, n = (np.asarray(a) for a in
                                    pk.paged_work_list(tables, pos, 8, 16))
    # pos 5: rows 0..5, page 0; pos 24: rows 9..24, pages 1..3; pos 95:
    # rows 80..95, pages 10 and 11 — in ring entries 2 and 3 of slot 2
    assert int(n[0]) == 6
    assert slot[:6].tolist() == [0, 1, 1, 1, 2, 2]
    assert ordinal[:6].tolist() == [0, 1, 2, 3, 10, 11]
    assert page[:6].tolist() == [1, 6, 7, 8, 11, 12]
    assert last[:6].tolist() == [1, 0, 0, 1, 0, 1]


def test_a_ring_too_small_for_its_window_is_refused():
    tables = jnp.zeros((2, 2), jnp.int32)
    with pytest.raises(ValueError, match="cannot hold"):
        pk.paged_work_list(tables, jnp.zeros((2,), jnp.int32), 8, 16)


def test_windowed_read_of_one_kv_head_a_query_head_is_refused():
    args, _ = _ring_case((5, 9), window=16, bs=8, ring=4, groups=1)
    with pytest.raises(ValueError, match="grouped"):
        pk.paged_decode_attention(*args, window=16, route="kernel",
                                  interpret=True)


@pytest.mark.parametrize("T, window, blocks, visited, grid", [
    (64, 16, (8, 16), None, None),      # the diagonal tile masked on the left
    (96, 16, (16, 8), None, None),      # q-blocks taller than k-blocks
    (96, 40, (8, 16), None, None),      # clear blocks between edge and diagonal
    (40, 16, (8, 64), None, None),      # one k-block
    (64, 100, (8, 16), None, None),     # a window wider than the sequence
    (80, 32, (16, 16), 1 + 2 + 3 * 3, 25),  # rows of 1, 2, 3, 3, 3 blocks
    (300, 64, (None, None), None, None),    # the default blocks
])
def test_banded_flash_matches_the_dense_band(T, window, blocks, visited,
                                             grid):
    """Ragged lengths too: a row shorter than the window beside a longer
    one (``kv_lens``), read up to each row's own length."""
    B, H, Hkv, D = 2, 4, 2, 8
    q, k, v = _qkv(11, B, T, H, Hkv, D)
    r = obs.MetricsRegistry()
    jax.clear_caches()
    kw = dict(causal=True, window=window, block_q=blocks[0],
              block_k=blocks[1], interpret=True)
    with obs.ObsSession(registry=r).installed():
        got = pk.flash_attention(q, k, v, **kw)
    want = pk._dense_attention(q, k, v, True, D ** -0.5, None, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6,
                               rtol=2e-6)
    c = r.counter("kernels.flash_block_pairs_total")
    if visited is not None:
        assert c.get(kernel="flash_window_attention_fwd",
                     state="visited") == B * H * visited
        assert c.get(kernel="flash_window_attention_fwd",
                     state="grid") == B * H * grid
    assert c.get(kernel="flash_attention_fwd", state="visited") == 0
    lens = jnp.asarray([T - 5, window // 2 - 1])
    got = pk.flash_attention(q, k, v, kv_lens=lens, **kw)
    want = pk._dense_attention(q, k, v, True, D ** -0.5, lens, window)
    for b, n in enumerate(np.asarray(lens)):
        np.testing.assert_allclose(np.asarray(got[b, :n]),
                                   np.asarray(want[b, :n]), atol=2e-6,
                                   rtol=2e-6)


def test_banded_flash_is_the_causal_square_where_the_window_covers_it():
    q, k, v = _qkv(13, 1, 48, 4, 2, 8)
    kw = dict(causal=True, block_q=8, block_k=16, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(pk.flash_attention(q, k, v, window=48, **kw)),
        np.asarray(pk.flash_attention(q, k, v, **kw)))


def test_a_window_is_forward_only_and_causal():
    q, k, v = _qkv(17, 1, 32, 2, 2, 8)
    with pytest.raises(ValueError, match="causal"):
        pk.flash_attention(q, k, v, window=8)
    for blocks in ({}, dict(block_q=8, block_k=8, interpret=True)):
        with pytest.raises(NotImplementedError, match="forward-only"):
            jax.grad(lambda q: jnp.sum(pk.flash_attention(
                q, k, v, causal=True, window=8, **blocks)))(q)


if __name__ == "__main__":
    golden = {"work_list": fp_work_list()}
    for route in ("dense", "kernel"):
        for groups in (1, 2):
            golden[f"paged_read/{route}/{groups}"] = list(
                fp_paged_read(route, groups))
    for case in ("causal", "causal-lens", "gqa", "noncausal", "dense-route"):
        golden[f"flash/{case}"] = list(fp_flash(case))
    for tm, K, N in EXPERT_SHAPES:
        golden[f"blocks/{tm}/{K}/{N}"] = list(
            pk.grouped_matmul_blocks(tm, K, N))
    for name in ("gpt2", "deepseek_v3", "lfm2", "nemotron_h"):
        golden[f"programs/{name}"] = list(fp_programs(name))
    import pprint
    pprint.pprint(golden, width=78)
