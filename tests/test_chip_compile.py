"""Compile the main-path kernels for a DESCRIBED TPU v5e, at GPT-2-small
widths, without a chip — the one file in the repo that describes a chip.

The TPU's compiler is installed beside the CPU backend and compiles for a
topology that is described, not attached (on-chip-measurement guide §2).
Interpret-mode tests cannot see what Mosaic refuses: a dot_general whose
dimension numbers it cannot parse, a block that overflows VMEM, a slice
that is not tile-aligned. These cases do — every Pallas kernel
``chip_smoke.py``'s train and serve phases can reach, plus the fused
RNN forwards and the sharded GPT-2-small step on a four-device mesh.

Rules this file keeps (and why it is ONE file): only one process at a time
may load the TPU's library, the process that did keeps it until it exits,
and xdist workers each import every test file. So the topology is
described inside a module-scoped fixture, never at import, in a ``skipif``
or in ``parametrize``; every compile runs in the test's own process; and
the persistent compilation cache is off round the compiles (an entry
written for a described device cannot be read back without one).
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import pallas_kernels as pk

# GPT-2-small heads (d_model 768 = 12 x 64) and the smoke's serving batch
B, H, D = 8, 12, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def S(one_chip):
    """Shape on the described chip: S(shape, dtype)."""
    return lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)


def _compile(fn, *avals):
    """Lower + compile ``fn`` for the avals' (described) devices; returns
    the optimized text, where a Pallas kernel shows as tpu_custom_call."""
    text = jax.jit(fn).lower(*avals).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("T", [1024, 4096])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_attention_compiles(S, T, grad):
    qkv = S((2, T, H, D), jnp.bfloat16)

    def fwd(q, k, v):
        return pk.flash_attention(q, k, v, causal=True, interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    _compile(fn, qkv, qkv, qkv)


@pytest.mark.parametrize("cell, shape, dtype, grad, calls", [
    ("gpt2m-train-1k", (8, 1023, 16, 64), jnp.bfloat16, False, 1),
    ("gpt2m-train-1k", (8, 1023, 16, 64), jnp.bfloat16, True, 3),
    ("gpt2l-serve", (16, 512, 20, 64), jnp.float32, False, 1),
], ids=["train-fwd", "train-fwd_bwd", "serve-512-fwd"])
def test_flash_attention_compiles_at_the_cells_calls(S, cell, shape, dtype,
                                                     grad, calls):
    """The benchmark cells' own causal calls: the train step's 8 rows x 1023
    tokens x 16 heads in bf16 (not a block multiple: Tp 1024), forward and
    forward + both backward kernels, and the GPT-2 serve cells' widest
    prompt bucket in f32. Every kernel must keep an operand or result of
    ``[rows * heads, T | T + 1, d_head]``: the benchmark's reader,
    ``chipbench/metrics/flash_attention_roofline.py``, tells the kernels in
    a trace by that shape, and a layout it cannot find makes the metric
    vanish and the result line malformed."""
    import re
    qkv = S(shape, dtype)
    rows, T, heads, d_head = shape

    def fwd(q, k, v):
        return pk.flash_attention(q, k, v, causal=True, interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd,
                    qkv, qkv, qkv)
    kernels = [ln for ln in text.splitlines()
               if " custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(kernels) == calls
    shape_re = re.compile(rf"\[{rows * heads},({T}|{T + 1}),{d_head}\]")
    for ln in kernels:
        assert shape_re.search(ln), ln[:300]


@pytest.mark.parametrize("L", [256, 512, 1024])
@pytest.mark.parametrize("kv", ["bf16", "f32", "int8"])
def test_decode_attention_compiles(S, L, kv):
    """f32 is the cache ``generate_cached`` holds for f32 params — the
    reference child of chip_smoke.py's serve phase."""
    q, pos = S((B, H, D), jnp.bfloat16), S((B,), jnp.int32)
    if kv == "int8":
        cache, sc = S((B, L, H, D), jnp.int8), S((B, L, H), jnp.float32)
        _compile(lambda q, k, ks, v, vs, pos: pk.decode_attention(
            q, k, v, pos, k_scale=ks, v_scale=vs, route="kernel",
            interpret=False), q, cache, sc, cache, sc, pos)
    else:
        cache = S((B, L, H, D),
                  jnp.bfloat16 if kv == "bf16" else jnp.float32)
        _compile(lambda q, k, v, pos: pk.decode_attention(
            q, k, v, pos, route="kernel", interpret=False),
            q, cache, cache, pos)


@pytest.mark.parametrize("kv", ["bf16", "f32", "int8"])
@pytest.mark.parametrize("slots, heads, NB, pages", [
    (B, H, 8, 8 * 8 + 1), (16, 20, 16, 105)], ids=["gpt2-small", "gpt2-large"])
def test_paged_decode_attention_compiles(S, slots, heads, NB, pages, kv):
    """gpt2-small: page 64 x 8 pages = the daemon's cache_bucket 512 read
    (benchmarks/serving_daemon.py, chip_smoke.py serve phase). gpt2-large:
    the benchmark's serve cells — 16 slots, 20 heads, the full table of 16
    over a 105-page pool; a (20, 64) tile pads to (24, 128) where (12, 64)
    pads to (16, 128), and only the compiler sees the difference. f32 is
    what ``paddle_tpu serve`` holds (no dtype flag: pools follow the
    params). The grid is the work list's traced length either way."""
    bs = 64
    q, pos = S((slots, heads, D), jnp.float32), S((slots,), jnp.int32)
    tables = S((slots, NB), jnp.int32)
    if kv == "int8":
        pool = S((pages, bs, heads, D), jnp.int8)
        sc = S((pages, bs, heads), jnp.float32)
        _compile(lambda q, k, ks, v, vs, t, pos: pk.paged_decode_attention(
            q, k, v, t, pos, k_scale=ks, v_scale=vs, route="kernel",
            interpret=False), q, pool, sc, pool, sc, tables, pos)
    else:
        pool = S((pages, bs, heads, D),
                 jnp.bfloat16 if kv == "bf16" else jnp.float32)
        _compile(lambda q, k, v, t, pos: pk.paged_decode_attention(
            q, k, v, t, pos, route="kernel", interpret=False),
            q, pool, pool, tables, pos)


def _grouped_paged_read(S, pool, q, NB):
    """One grouped-query paged read compiled for the described chip: the
    kernel keeps its own name in the program (the benchmark's readers,
    chipbench/metrics/gqa_decode_roofline.py and gqa_head_dim_decode_
    roofline.py, find it by that), takes the pools as they are — no copy
    of one in the program — and q in the caller's head order: the grouped
    body (one MXU product of a page by all its query heads) transposes
    nothing outside the kernel."""
    text = _compile(lambda q, k, v, t, pos: pk.paged_decode_attention(
        q, k, v, t, pos, route="kernel", interpret=False),
        S(q, jnp.float32), pool, pool, S((q[0], NB), jnp.int32),
        S((q[0],), jnp.int32))
    call = next(ln for ln in text.splitlines() if " custom-call(" in ln
                and "tpu_custom_call" in ln)
    assert "paged_decode_attention" in call.split(" = ")[0]
    dims = ",".join(map(str, pool.shape))
    assert f"bf16[{dims}]" in call
    assert "f32[%d,%d,%d]" % q in call.split(" custom-call(")[0]
    assert not [ln for ln in text.splitlines() if " copy(" in ln
                and f"bf16[{dims}]" in ln.split(" copy(")[0]]


def test_grouped_query_paged_decode_attention_compiles(S, one_chip):
    """The lfm2-serve-rag cell's read: 32 slots x 32 query heads over bf16
    pools of 8 KV heads (an (8, 64) tile: half a bf16 sublane tile, which
    no other pool showed the compiler), the whole table of 72 over a
    2305-page pool [pages, 64, 8, 64]. The pools are given in the row-
    major order a program that carries them holds them in (left to itself
    the compiler lays a lone ENTRY parameter of 64-wide rows out pages-
    minor and copies it): against such a pool the kernel asks for no
    copy."""
    from jax.experimental.layout import Format, Layout
    pool = jax.ShapeDtypeStruct(
        (2305, 64, 8, D), jnp.bfloat16,
        sharding=Format(Layout(major_to_minor=(0, 1, 2, 3)), one_chip))
    _grouped_paged_read(S, pool, (32, 32, D), 72)


@pytest.mark.parametrize("rows, T", [(4, 512), (1, 4096)])
def test_grouped_query_flash_forward_compiles(S, rows, T):
    """The cell's prefill chunks: 32 query heads over 8 KV heads through
    the forward kernel's index map (no repeated K / V in HBM: the kernel's
    K and V operands keep 8 heads' rows), at the shortest and the longest
    prompt bucket. Its result is ``[rows x 32, T, 64]`` under the kernel's
    own name: what chipbench/metrics/flash_prefill_roofline.py reads."""
    import re
    bf = jnp.bfloat16
    text = _compile(lambda q, k, v: pk.flash_attention(
        q, k, v, causal=True, interpret=False),
        S((rows, T, 32, D), bf), S((rows, T, 8, D), bf),
        S((rows, T, 8, D), bf))
    call = next(ln for ln in text.splitlines() if " custom-call(" in ln
                and "tpu_custom_call" in ln)
    assert "flash_attention_fwd" in call.split(" = ")[0]
    m = re.search(r"\[(\d+),(\d+),(\d+)\]", call)
    assert tuple(int(g) for g in m.groups()) == (rows * 32, T, D)
    assert f"bf16[{rows * 8},{T},{D}]" in call


@pytest.mark.parametrize("NB", [4, 32], ids=["cache-256", "cache-2048"])
def test_paged_latent_attention_compiles(S, NB):
    """The absorbed latent read at the gigachat-ep16 cell's real shapes: 32
    slots x 64 query heads of width 576 (512 latent + 64 rotary: 4.5 lane
    tiles) over ONE bf16 row a token, values its first 512; a 1025-page
    pool of 64-row pages under the narrowest and the widest table."""
    bf = jnp.bfloat16
    text = _compile(lambda q, pool, t, pos: pk.paged_latent_attention(
        q, pool, t, pos, d_value=512, scale=0.145, route="kernel",
        interpret=False),
        S((32, 64, 576), bf), S((1025, 64, 576), bf), S((32, NB), jnp.int32),
        S((32,), jnp.int32))
    assert "paged_latent_attention" in text


@pytest.mark.parametrize("G, rows, tm, K, N", [
    (16, 512, 16, 7168, 2048), (16, 512, 16, 2048, 7168),
    (16, 12288, 256, 7168, 2048), (16, 12288, 256, 2048, 7168),
    (32, 640, 16, 2048, 1792), (32, 12288, 128, 2048, 1792),
    (32, 12288, 128, 1792, 2048), (32, 16384, 256, 2048, 1792),
    (32, 16384, 256, 1792, 2048)],
    ids=["decode-gate", "decode-down", "prefill-gate", "prefill-down",
         "rag-decode-gate", "rag-admit-gate", "rag-admit-down",
         "rag-chunk-gate", "rag-chunk-down"])
def test_expert_grouped_matmul_compiles(S, G, rows, tm, K, N):
    """The held experts' grouped products at the cells' real shapes, bf16.
    longout: 16 experts of 7168 x 2048 (gate, up) and 2048 x 7168 (down); a
    decode step's 256 pairs in tiles of 16 rows, an admission chunk's 8192
    in tiles of 256 (parallel/expert_share.py's layouts). rag: 32 experts
    of 2048 x 1792 and 1792 x 2048; a decode step's 128 pairs in tiles of
    16, an admission's <= 8192 in tiles of 128, a 4,096-token admission's
    chunks of 8192 in tiles of 256 — the admission shapes take the
    RESIDENT plan (a whole matrix twice in VMEM, its own VMEM limit, a
    hand-started fetch): Mosaic's verdict on it is this test."""
    bf = jnp.bfloat16
    text = _compile(lambda lhs, rhs, group, n: pk.grouped_matmul(
        lhs, rhs, group, n, tm=tm, route="kernel", interpret=False),
        S((rows, K), bf), S((G, K, N), bf), S((rows // tm,), jnp.int32),
        S((1,), jnp.int32))
    assert "expert_grouped_matmul" in text


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
    (256, 1792, 2048, (1792, 2048, True))],
    ids=["longout-decode-gate", "longout-decode-down", "longout-admit-gate",
         "longout-admit-down", "rag-decode-gate", "rag-decode-down",
         "rag-admit-gate", "rag-admit-down", "rag-chunk-gate",
         "rag-chunk-down"])
def test_grouped_matmul_blocks_by_shape(tm, K, N, blocks):
    """Which plan runs where (no chip, no compile): every ``tm`` 16 shape of
    both expert cells and longout's admissions (a 7168-deep matrix does not
    fit VMEM twice) keep the K-split blocks they had before the resident
    plan existed; rag's admissions hold the whole matrix."""
    assert pk.grouped_matmul_blocks(tm, K, N, 2) == blocks


# -- NemotronHLM's kernels at the nemotron3-ep8-serve-chatburst cell's shapes

def test_ssm_state_update_compiles_in_place(S):
    """The decode step's state update at the cell's real shapes: 32 slots x
    64 heads of 64 x 128 in float32, packed [32, 128, 128] a slot (2 MB in
    and 2 MB out a grid step, its own VMEM limit), the live slots a
    scalar-prefetched grid. The state is donated and the kernel aliases
    it: the compiled program holds NO second copy of the 64 MB (alias
    bytes = the state's; temporaries a rounding error beside it)."""
    H, P, G, N, B = 64, 64, 8, 128, 32
    compiled = jax.jit(
        lambda s, x, dt, a, b, c, live: pk.ssm_state_update(
            s, x, dt, a, b, c, live, route="kernel", interpret=False),
        donate_argnums=(0,)).lower(
        S((B, H // 2, N, 2 * P)), S((B, H, P)), S((B, H)), S((H,)),
        S((B, G, N)), S((B, G, N)), S((B,), jnp.bool_)).compile()
    text = compiled.as_text()
    call = next(ln for ln in text.splitlines() if " custom-call(" in ln
                and "tpu_custom_call" in ln)
    assert "ssm_state_update" in call.split(" = ")[0]
    mem = compiled.memory_analysis()
    state = B * H * P * N * 4
    assert mem.alias_size_in_bytes == state
    assert mem.temp_size_in_bytes < state // 16


@pytest.mark.parametrize("rows, T", [(8, 256), (1, 2048)])
def test_ssd_chunk_scan_compiles(S, rows, T):
    """The admission's chunked scan at the cell's real shapes: a prefill
    chunk of 8 rows x 256 or 1 x 2,048 positions, 64 heads of 64 over 8
    groups of state 128, chunks of 128, bf16 operands: matrix products
    with a transposed left side, column and row forms of the running
    decay, the group's packed state resident across a row's chunks."""
    H, P, G, N = 64, 64, 8, 128
    text = _compile(lambda x, dt, a, b, c, n: pk.ssd_chunk_scan(
        x, dt, a, b, c, n, dtype=jnp.bfloat16, route="kernel",
        interpret=False),
        S((rows, T, H, P)), S((rows, T, H)), S((H,)), S((rows, T, G, N)),
        S((rows, T, G, N)), S((rows,), jnp.int32))
    call = next(ln for ln in text.splitlines() if " custom-call(" in ln
                and "tpu_custom_call" in ln)
    assert "ssd_chunk_scan" in call.split(" = ")[0]
    assert f"f32[{rows},32,128,128]" in call       # the packed final state


@pytest.mark.parametrize("rows, tm, K, N, transposed", [
    (448, 16, 2688, 1856, True), (448, 16, 1856, 2688, False),
    (12288, 256, 2688, 1856, True), (12288, 256, 1856, 2688, False)],
    ids=["decode-up", "decode-down", "chunk-up", "chunk-down"])
def test_relu2_expert_grouped_matmul_compiles(S, rows, tm, K, N, transposed):
    """The 16 held relu2 experts' two grouped products at the cell's real
    shapes, bf16: ``up`` held [16, 1856, 2688] (as published, the multiple
    of 128 minor) and contracted over both last dimensions, ``down``
    [16, 1856, 2688]; a decode step's 192 pairs in tiles of 16 (K-split:
    7 blocks of 384 x 1856; whole-K strips of 896), an admission chunk's
    8,192 in tiles of 256 (resident). No operand is re-laid out: the
    weights reach the kernel in the layout the device stores them in."""
    bf = jnp.bfloat16
    w = (16, N, K) if transposed else (16, K, N)
    text = _compile(lambda lhs, rhs, group, n: pk.grouped_matmul(
        lhs, rhs, group, n, tm=tm, transposed=transposed, route="kernel",
        interpret=False),
        S((rows, K), bf), S(w, bf), S((rows // tm,), jnp.int32),
        S((1,), jnp.int32))
    assert "expert_grouped_matmul" in text
    copies = [ln for ln in text.splitlines() if " copy(" in ln
              and f"bf16[{w[0]},{w[1]},{w[2]}]" in ln.split(" copy(")[0]]
    assert not copies


def test_group_16_head_128_paged_decode_attention_compiles(S):
    """The cell's paged read: 32 slots x 32 query heads of 128 over bf16
    pools of 2 KV heads (a group of 16), the whole table of 44 over a
    1409-page pool, pools as they are, [pages, 64, 2, 128] (two bf16 rows
    a packed sublane: the page collapses to a [128, 128] matrix in place)."""
    _grouped_paged_read(S, S((1409, 64, 2, 128), jnp.bfloat16),
                        (32, 32, 128), 44)


@pytest.mark.parametrize("rows, T", [(8, 256), (1, 2048)])
def test_group_16_head_128_flash_forward_compiles(S, rows, T):
    """The cell's prefill chunks: 32 query heads of 128 over 2 KV heads
    through the forward kernel's index map, at the shortest and the
    longest prompt bucket."""
    bf = jnp.bfloat16
    text = _compile(lambda q, k, v: pk.flash_attention(
        q, k, v, causal=True, scale=128 ** -0.5, interpret=False),
        S((rows, T, 32, 128), bf), S((rows, T, 2, 128), bf),
        S((rows, T, 2, 128), bf))
    call = next(ln for ln in text.splitlines() if " custom-call(" in ln
                and "tpu_custom_call" in ln)
    assert "flash_attention_fwd" in call.split(" = ")[0]
    assert f"bf16[{rows * 2},{T},128]" in call


# -- AfmoeLM's two reads at the trinity-ep8-serve-mixedlen cell's shapes ----

@pytest.mark.parametrize("window, NB, pages, name", [
    (2048, 34, 12 * 34 + 1, "paged_window_attention"),
    (None, 140, 1681, "paged_decode_attention")])
def test_group_8_paged_reads_of_both_kinds_compile(S, window, NB, pages,
                                                   name):
    """12 slots x 32 query heads of 128 over bf16 pools of 4 KV heads (a
    group of 8): a sliding layer's read through its ring of 34 pages, under
    its own name (what chipbench/metrics/window_decode_roofline.py finds it
    by), and a full layer's over the whole table of 140."""
    text = _compile(lambda q, k, v, t, pos: pk.paged_decode_attention(
        q, k, v, t, pos, window=window, route="kernel", interpret=False),
        S((12, 32, 128)), S((pages, 64, 4, 128), jnp.bfloat16),
        S((pages, 64, 4, 128), jnp.bfloat16), S((12, NB), jnp.int32),
        S((12,), jnp.int32))
    call = next(ln for ln in text.splitlines() if " custom-call(" in ln
                and "tpu_custom_call" in ln)
    assert call.split(" = ")[0].strip().lstrip("ROOT %").startswith(name)


@pytest.mark.parametrize("rows, T", [(4, 512), (1, 8192)])
@pytest.mark.parametrize("window", [2048, None], ids=["band", "square"])
def test_group_8_head_128_flash_forwards_compile(S, rows, T, window):
    """The cell's prefill chunks at the shortest and the longest prompt
    bucket (K and V of 8,192 rows of 128 resident a program): the banded
    walk under ``flash_window_attention_fwd`` with the result's shape the
    reader takes rows and length from, the causal one as it was."""
    bf = jnp.bfloat16
    text = _compile(lambda q, k, v: pk.flash_attention(
        q, k, v, causal=True, window=window, interpret=False),
        S((rows, T, 32, 128), bf), S((rows, T, 4, 128), bf),
        S((rows, T, 4, 128), bf))
    call = next(ln for ln in text.splitlines() if " custom-call(" in ln
                and "tpu_custom_call" in ln)
    name = "flash_attention_fwd" if window is None \
        else "flash_window_attention_fwd"
    assert call.split(" = ")[0].strip().lstrip("ROOT %").startswith(name)
    assert f"bf16[{rows * 32},{T},128]" in call
    assert f"bf16[{rows * 4},{T},128]" in call


# -- KeyeSparseLM's selection at the keye-ep8-serve-longctx cell's shapes ----

KEYE = dict(Q=2048, L=32768, B=8, NB=528, P=4225, K=2048)


def _keye_cases():
    bf, i32 = jnp.bfloat16, jnp.int32
    Q, L, B, NB, P, K = (KEYE[k] for k in ("Q", "L", "B", "NB", "P", "K"))
    kernel = dict(route="kernel", interpret=False)
    return {
        "index_scores": (
            lambda q, w, k, q0: pk.index_scores(q, w, k, q0, **kernel),
            [((16, Q, 64), bf), ((Q, 16),), ((L, 64), bf), ((), i32)]),
        "index_scores_paged": (
            lambda q, w, ik, t, p: pk.index_scores_paged(q, w, ik, t, p,
                                                         **kernel),
            [((B, 16, 64), bf), ((B, 16),), ((P, 64, 128), bf),
             ((B, NB), i32), ((B,), i32)]),
        "select_topk": (
            lambda s, e: pk.select_topk(s, e, K, **kernel),
            [((Q, L),), ((Q,), i32)]),
        "select_topk-decode": (
            lambda s, e: pk.select_topk(s, e, K, **kernel),
            [((B, NB * 64),), ((B,), i32)]),
        "sparse_decode_attention": (
            lambda q, k, v, t, b, p: pk.sparse_decode_attention(
                q, k, v, t, b, p, **kernel)[0],
            [((B, 32, 128),), ((P, 64, 4, 128), bf), ((P, 64, 4, 128), bf),
             ((B, NB), i32), ((B, NB * 64),), ((B,), i32)]),
        "selected_flash_attention": (
            lambda q, k, v, b, q0: pk.selected_flash_attention(
                q, k, v, b, q0, **kernel),
            [((Q, 32, 128), bf), ((L, 4, 128), bf), ((L, 4, 128), bf),
             ((Q, L),), ((), i32)]),
    }


@pytest.mark.parametrize("name", [
    "index_scores", "index_scores_paged", "select_topk",
    "select_topk-decode", "sparse_decode_attention",
    "selected_flash_attention"])
def test_the_selections_kernels_compile_at_the_cells_shapes(S, name):
    """A block of 2,048 queries against a 32,768-token row, and a decode
    step of 8 slots over tables of 528 pages of 64: each of the five
    kernels under its own name (what chipbench/metrics/
    _keye_vl2_common.py finds it by). The indexer's pool is held 128 wide:
    a page of 64-wide rows cannot be cut out of its tiles by a DMA."""
    fn, avals = _keye_cases()[name]
    text = _compile(fn, *(S(*a) for a in avals))
    calls = [ln.split(" = ")[0].strip().lstrip("ROOT %")
             for ln in text.splitlines()
             if " custom-call(" in ln and "tpu_custom_call" in ln]
    assert calls and all(c.startswith(name.split("-")[0]) for c in calls)


def test_a_page_of_64_wide_rows_is_no_dma(S):
    """Why ``CacheRow.held``: the scores' kernel over a pool as narrow as
    the indexer's keys is refused by the chip's compiler, so the wrapper
    widens such a pool (by a copy) and the serving pool holds it wide."""
    fn, avals = _keye_cases()["index_scores_paged"]
    narrow = [a if a[0] != (KEYE["P"], 64, 128) else ((KEYE["P"], 64, 64),
                                                      jnp.bfloat16)
              for a in avals]
    text = _compile(fn, *(S(*a) for a in narrow))
    assert "bf16[4225,64,128]" in text          # the widened copy


@pytest.mark.parametrize("program", ["admit", "segment"])
def test_keye_programs_keep_the_pools_in_place(one_chip, S, program,
                                               monkeypatch):
    """The cell's admit program at its one prompt bucket (32,768) and its
    segment program (its step count a traced argument), whole, at 2 of the
    12 layers: no pool array is copied (three kinds of row, the third held
    wider than stated), every pool is aliased, the segment's steps are one
    loop, and what a 32,768-token row expands is a BLOCK's: under 1 GiB
    of temporaries (the same walk over 8,192-token rows a layer at a time
    was 4.2 GB, PERF.md section 6, PR 39). All 12 layers, off this suite:
    9.274 + 2.008 GiB (admit) and 9.273 + 0.029 GiB (segment)."""
    import json
    from chipbench import weights_keye_vl2
    from paddle_tpu.serving.paged import PagePool
    monkeypatch.setattr(pk, "_interpret", lambda interpret: False)
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "chipbench/configs/keye-vl2-30b-ep8-12l.json")) as f:
        cfg = dict(json.load(f), num_hidden_layers=2)
    model, shapes = weights_keye_vl2.model_and_shapes(cfg)
    params = jax.tree_util.tree_map(lambda s: S(s.shape, s.dtype), shapes)
    pool = PagePool.__new__(PagePool)
    pool.model, pool.kv_dtype, pool.bs, pool.segment = model, None, 64, 32
    pool.ring, pool._ring_names, pool._in_place = 0, set(), False
    pool._slot_rows, pool._fns = [], {}
    pools = {r.name: S((4225, 64) + tuple(r.held or r.shape), r.dtype)
             for r in model.cache_rows({"embed": {"w": jnp.zeros(
                 (1,), jnp.bfloat16)}})}
    i32 = jnp.int32
    if program == "admit":
        compiled = pool._admit_fn(32768, 512)._jitted.lower(
            params, (pools, {}), S((8, 32768), i32), S((8,), i32),
            S((8, 512), i32)).compile()
    else:
        compiled = pool._seg_fn(528)._jitted.lower(
            params, (pools, {}), S((8, 528), i32), S((8,), i32),
            S((8,), i32), S((8,), jnp.bool_), S((), i32)).compile()
    text = compiled.as_text()
    assert not _pool_copies(text, pools, [(4, 128), (64,)])
    if program == "segment":
        # the steps are ONE loop under the traced count (the reads below
        # ``index_topk`` and the selected ones are a ``conditional`` a
        # layer inside it, no loop of their own)
        assert text.count(" while(") == 1
    mem = compiled.memory_analysis()
    held = sum(int(np.prod(a.shape)) * 2 for a in pools.values())
    assert mem.alias_size_in_bytes == held
    assert mem.temp_size_in_bytes < 2 ** 30


# -- MimoV2LM at the mimo-ep16-serve-agentctx cell's shapes ------------------

def _mimo_programs(S, program, layers):
    """The cell's admit program at its one prompt bucket (49,152) or its
    segment program, lowered for the described chip over the first
    ``layers`` layers of the configuration: (compiled, the pools' avals)."""
    import json
    from chipbench import weights_mimo_v2
    from paddle_tpu.serving.paged import PagePool
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "chipbench/configs/mimo-v2-flash-ep16-11l.json")) as f:
        cfg = dict(json.load(f), num_hidden_layers=layers)
    model, shapes = weights_mimo_v2.model_and_shapes(cfg)
    params = jax.tree_util.tree_map(lambda s: S(s.shape, s.dtype), shapes)
    pool = PagePool.__new__(PagePool)
    pool.model, pool.kv_dtype, pool.bs, pool.segment = model, None, 64, 32
    pool.window, pool.ring, pool._in_place = 128, 4, False
    pool._slot_rows, pool._fns = [], {}
    rows = model.cache_rows({"embed": {"w": jnp.zeros((1,), jnp.bfloat16)}})
    pool._ring_names = {r.name for r in rows if r.window}
    pools = {r.name: S(((33 if r.window else 6273), 64)
                       + tuple(r.held or r.shape), r.dtype) for r in rows}
    i32 = jnp.int32
    if program == "admit":
        compiled = pool._admit_fn(49152, 768)._jitted.lower(
            params, (pools, {}), S((8, 49152), i32), S((8,), i32),
            S((8, 768), i32), S((8, 4), i32)).compile()
    else:
        compiled = pool._seg_fn(784)._jitted.lower(
            params, (pools, {}), S((8, 784), i32), S((8,), i32),
            S((8,), i32), S((8,), jnp.bool_), S((), i32),
            S((8, 4), i32)).compile()
    return compiled, pools


@pytest.mark.parametrize("program", ["admit", "segment"])
def test_mimo_programs_keep_the_pools_in_place(S, program, monkeypatch):
    """The cell's admit program at its one prompt bucket (49,152: a row
    walked 2,048 positions at a time, a sliding layer's last pages alone
    handed on) and its segment program, whole, at layers 0-5 of 11 (both
    kinds of layer, the dense FFN and the experts): no pool array is copied
    — keys stated 192 wide and held 256, values 128, 4 or 8 heads by kind,
    pages and rings —, every pool is aliased, the segment's steps are one
    loop, and what a 49,152-token row expands is a block's. All 11 layers,
    off this suite (PERF.md section 6, PR 47)."""
    monkeypatch.setattr(pk, "_interpret", lambda interpret: False)
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    compiled, pools = _mimo_programs(S, program, 6)
    text = compiled.as_text()
    # the pages that grow (the global layers') are copied nowhere; a RING
    # array is 33 pages (8.6 MB a key array), and the compiler stages so
    # small an array through VMEM inside the step loop — into memory space
    # 1 and back, never into another order
    grown = {nm: a for nm, a in pools.items() if a.shape[0] == 6273}
    assert len(grown) == 4
    assert not _pool_copies(text, grown, [(4, 192)])
    for copy in _pool_copies(text, pools, [(8, 192)]):
        assert "S(1)" in copy and "{3,2,1,0:" in copy, copy
    for name in (("flash_attention_fwd", "flash_window_attention_fwd")
                 if program == "admit" else
                 ("paged_decode_attention", "paged_window_attention")):
        assert re.search(rf"%{name}[.\d]* = ", text), name
    if program == "segment":
        assert text.count(" while(") == 1
    mem = compiled.memory_analysis()
    held = sum(int(np.prod(a.shape)) * 2 for a in pools.values())
    assert mem.alias_size_in_bytes == held
    # (1.56 GiB here, 2.22 at all 11 layers; a whole 49,152-token row of
    # ONE layer's q alone would be 1.2 GB)
    assert mem.temp_size_in_bytes < 2 * 2 ** 30


def test_flash_attention_compiles_at_latent_head_width(S):
    """Prefill of the latent-attention model expands k and v and runs the
    flash kernel at head width 192 (128 + 64 rotary; v is 192 as well): 1.5
    lane tiles, which GPT-2's 64 never showed the compiler. One prefill
    chunk of the cell: 8 rows x 512 x 64 heads."""
    qkv = S((8, 512, 64, 192), jnp.bfloat16)
    _compile(lambda q, k, v: pk.flash_attention(
        q, k, v, causal=True, scale=0.145, interpret=False), qkv, qkv, qkv)


@pytest.mark.parametrize("cell", ["lstm", "gru"])
def test_fused_rnn_forward_compiles(S, cell):
    """The flagship recurrent shape: bs 64, T 100, hidden 256."""
    bs, T, h = 64, 100, 256
    gates = 4 if cell == "lstm" else 3
    fused = (pk.lstm_sequence_fused if cell == "lstm"
             else pk.gru_sequence_fused)
    _compile(lambda xw, lens, u: fused(xw, lens, u, interpret=False),
             S((bs, T, gates * h)), S((bs,), jnp.int32), S((h, gates * h)))


def test_sharded_gpt2_small_step_lowers_on_four_chips(topo, monkeypatch):
    """The step ``chip_smoke.py --chips 4`` runs — Trainer(mesh, layout)'s
    DataParallel step with the mesh and layout benchmarks/sharded_gpt2.py
    builds (tp 2 x fsdp 2, pos_embed pinned replicated) — compiled for the
    four described chips. Depth is cut to 2 layers so it stays in seconds;
    widths are GPT-2-small's."""
    # the model asks the backend whether to interpret its kernels and here
    # the backend is the CPU: steer it to the compiled kernel, as on a chip
    monkeypatch.setattr(pk, "_interpret", lambda interpret: False)
    from paddle_tpu import parallel as pp
    from paddle_tpu.models import TransformerLM
    from paddle_tpu.optimizer import Adam
    from paddle_tpu.parallel.data_parallel import DataParallel

    mesh = Mesh(np.array(topo.devices).reshape(1, 2, 2),
                ("data", "fsdp", "tp"))
    layout = pp.SpecLayout(rules=[(r"pos_embed$", P())])
    model = TransformerLM(32768, d_model=768, n_heads=H, n_layers=2,
                          max_len=1024)
    opt = Adam(3e-4)

    def loss_fn(params, ids):
        p16 = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16)
            if a.dtype == jnp.float32 else a, params)
        return model.loss(p16, ids)

    def described(tree):
        shapes = jax.eval_shape(lambda: tree())
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            shapes, layout.shardings(mesh, shapes))

    params = described(lambda: model.init(jax.random.PRNGKey(0)))
    state = described(lambda: opt.init(model.init(jax.random.PRNGKey(0))))
    ids = jax.ShapeDtypeStruct(
        (8, 1024), jnp.int32,
        sharding=NamedSharding(mesh, P("data", None)))
    dp = DataParallel(loss_fn, opt, mesh=mesh, param_rules=layout)
    with pp.use_mesh(mesh):                  # as DataParallel.step does
        compiled = dp._build_step(params, state)._jitted.lower(
            params, state, ids).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text          # flash attention is the kernel
    assert "all-reduce" in text or "all-gather" in text or \
        "reduce-scatter" in text             # and the step really is SPMD
    # every described chip holds less than the whole of the parameters
    w = params["blocks_0"]["mlp_in"]["w"]
    assert len({s for s in w.sharding.devices_indices_map(w.shape).values()
                }) == 4


# -- pools held where they lie: no program copies one in or out (PR 40) -----

def _default_format(S, shape, dtype):
    """The format the described chip's runtime gives an array of this shape
    when nothing is said: what a program's argument of it arrives in."""
    return jax.jit(lambda p: p + 1).lower(S(shape, dtype)).compile() \
        .input_formats[0][0]


def _default_layout(S, shape, dtype):
    return _default_format(S, shape, dtype).layout


def _held(S, shape, dtype):
    """``shape`` as the pool would hold it on the described chip: its own
    rule (``paged._held_shape``) over the chip's default layout."""
    import types
    from paddle_tpu.serving import paged
    held = paged._held_shape(types.SimpleNamespace(
        format=_default_format(S, shape, dtype), shape=tuple(shape),
        ndim=len(shape)))
    # ... which the same runtime lays out row-major
    assert tuple(_default_layout(S, held, dtype).major_to_minor) \
        == tuple(range(len(held)))
    return held


#: a ``copy`` (or the start of an asynchronous one, whose result is a tuple
#: that begins with the copy's) in optimized HLO text, group 1 its result
#: ``dtype[dims]``; and HLO's names for jnp's dtypes
_HLO_COPY = re.compile(r"= \(?(\w+\[[\d,]*\])[^\n=]*? copy(?:-start)?\(")
_HLO_DTYPE = {"float32": "f32", "bfloat16": "bf16", "float16": "f16",
              "int8": "s8", "int32": "s32"}


def _pool_copies(text, pools, rows=()):
    """``copy`` instructions of an optimized program that copy a whole
    pool array, as held or as its rows are stated: what re-lays a pool
    out."""
    def hlo_type(a, shape):
        name = jnp.dtype(a.dtype).name
        return f"{_HLO_DTYPE.get(name, name)}[{','.join(map(str, shape))}]"
    shapes = {hlo_type(a, shape) for a in pools.values()
              for shape in [a.shape] + [a.shape[:2] + tuple(r) for r in rows]}
    return [m.group(0) for m in _HLO_COPY.finditer(text)
            if m.group(1) in shapes]


def test_pool_copies_rule_finds_whole_pool_copies_alone():
    """The rule the cases below hold the compiled programs to, on a text
    with two copies of a pool array (one asynchronous), a copy of
    something smaller, a ``copy(`` inside metadata and a consumer."""
    text = ("  %copy.1 = f32[17,8,4,8]{0,3,2,1:T(8,128)} "
            "copy(%param.3), metadata={}\n"
            "  %copy-start.2 = (f32[17,8,4,8]{3,2,1,0:T(8,128)}, "
            "f32[17,8,4,8]{0,3,2,1:T(8,128)S(1)}, u32[]{:S(2)}) "
            "copy-start(%x)\n"
            "  %copy-done.2 = f32[17,8,4,8]{3,2,1,0:T(8,128)} "
            "copy-done(%copy-start.2)\n"
            "  %copy.9 = f32[2,8,4,8]{3,2,1,0} copy(%y), "
            "metadata={op_name=\"f32[17,8,4,8] copy(\"}\n"
            "  %f = f32[17,8,4,8]{3,2,1,0} fusion(%copy.1)\n")
    pools = {"k": jax.ShapeDtypeStruct((17, 8, 4, 8), jnp.float32)}
    assert len(_pool_copies(text, pools)) == 2
    assert len(_pool_copies(text, pools, [(4, 4)])) == 2
    assert not _pool_copies(text, {"k": jax.ShapeDtypeStruct(
        (17, 8, 4, 8), jnp.bfloat16)})


def _gpt2_large_pool(S):
    """gpt2-large's pool as both GPT-2 serve cells hold it — 16 slots, 105
    pages of 64 rows, f32, 72 arrays stated ``[105, 64, 20, 64]`` —
    described on the chip the way the pool holds it there: the runtime
    lays the stated shape out pages-MINOR (``{0,3,2,1}``), so the rows are
    held ``(24, 128)`` wide (two pages are held here)."""
    from paddle_tpu.models import TransformerLM
    from paddle_tpu.serving.paged import PagePool
    model = TransformerLM(50257, d_model=1280, n_heads=20, n_layers=36,
                          max_len=1024)
    params = jax.tree_util.tree_map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))))
    pool = PagePool(model, params, slots=16, pages=2, page_block=64)
    stated = (105, 64, 20, 64)
    assert tuple(_default_layout(S, stated, jnp.float32).major_to_minor) \
        == (1, 2, 3, 0)
    held = _held(S, stated, jnp.float32)
    assert held == (105, 64, 24, 128)
    pool.pools = {nm: S(held, a.dtype) for nm, a in pool.pools.items()}
    return pool, params


#: 72 arrays held f32[105, 64, 24, 128]: 82.6 MB each against 34.4 MB of
#: stated rows — the bytes a row-major f32[105, 64, 20, 64] pads to there
GPT2L_POOL_DEVICE_BYTES = 72 * 105 * 64 * 24 * 128 * 4


@pytest.mark.parametrize("nb", [4, 16], ids=["cache-256", "cache-1024"])
def test_gpt2_large_segment_program_keeps_the_pools_in_place(one_chip, S, nb,
                                                             monkeypatch):
    """The segment program both GPT-2 serve cells run (``PagePool._seg_fn``
    : as many decode steps as its traced argument says, 32 at most, over 16
    slots, all 36 layers, the paged read on the kernel route) compiled
    whole for the described chip under the
    narrowest and the widest table: the pools go in and come out as the
    pool holds them, in the runtime's own order — NO copy of a pool array
    in the optimized program (the parent held 144: every pool re-laid out
    from the runtime's pages-minor default for the kernel, and back),
    every pool aliased at its size on the device, the kernel reading the
    stated rows out of the held ones by a bitcast — and it fits beside
    the weights."""
    monkeypatch.setattr(pk, "_interpret", lambda interpret: False)
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    pool, params = _gpt2_large_pool(S)
    compiled = pool._seg_fn(nb)._jitted.lower(
        params, (pool.pools, {}), S((16, nb), jnp.int32), S((16,), jnp.int32),
        S((16,), jnp.int32), S((16,), jnp.bool_), S((), jnp.int32)).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if " custom-call(" in ln
             and "tpu_custom_call" in ln]
    # the read keeps its name and its pool-shaped operands: what
    # chipbench/metrics/paged_decode_roofline.py finds it by
    assert calls and all("paged_decode_attention" in ln.split(" = ")[0]
                         and "f32[105,64,20,64]" in ln for ln in calls)
    assert not _pool_copies(text, pool.pools, [(20, 64)])
    # a step's rows go in by ONE scatter an array, whole rows of the held
    # width; a scatter into the rows' leading corner was expanded into a
    # loop over the slots (73 loops, and twice the step on the chip);
    # the one loop is the steps', its trip count the program's argument,
    # and the block of tokens it fills a step is no loop either
    assert text.count(" while(") == 1
    assert len(re.findall(r"= f32\[105,64,24,128\]\S* scatter\(", text)) == 72
    # the model's word to the compiler reached the program (``TransformerLM.
    # decode_compiler_options``): a step's weights come into fast memory
    # WHOLE, not in quarters — no ``slice-start`` in the loop's body and
    # 820 asynchronous starts and waits where the compiler's own choice
    # was 1,364 of a step's 2,085 operations, each an event the profiler
    # must write (PERF.md section 6, PR 40)
    start = text.index(
        "\n%" + re.search(r"body=%?([\w.\-]+)", text).group(1) + " (")
    body = text[start:text.index("\n}", start)]
    waits = len(re.findall(r" (?:slice|copy)-(?:start|done)\(", body))
    assert " slice-start(" not in body and waits <= 900, waits
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == GPT2L_POOL_DEVICE_BYTES
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 14 * 2 ** 30)
    for fmt in jax.tree_util.tree_leaves(
            (compiled.input_formats[0][1][0], compiled.output_formats[0][0])):
        assert fmt.layout.major_to_minor == (0, 1, 2, 3)


def test_page_writes_keep_rag_shaped_pools_in_place(S):
    """The admission's page write and the pool's held shape at the
    lfm2-serve-rag cell's — 6 arrays stated ``bf16[2305, 64, 8, 64]``,
    which the runtime too lays out pages-minor, so held ``(8, 128)`` wide;
    32 slots, a 512-token bucket: pages written one
    ``dynamic_update_slice`` each into the leading corner of pools that
    are donated and never copied, and a reader's view of the stated rows
    (``pk.pool_rows``) is no copy either."""
    from paddle_tpu.serving import paged
    bf = jnp.bfloat16
    stated = (2305, 64, 8, D)
    assert tuple(_default_layout(S, stated, bf).major_to_minor) \
        != (0, 1, 2, 3)
    held = _held(S, stated, bf)
    assert held == (2305, 64, 8, 128)
    pools = {f"{kv}{i}": S(held, bf) for kv in "kv" for i in (1, 5, 9)}
    cells = {nm: S((32, 512, 8, D), bf) for nm in pools}

    def write(pools, cells, src, dst, n, tables):
        pools = paged._write_pages(pools, cells, src, dst, n)
        return pools, pk.gather_pages(
            pk.pool_rows(pools["k1"], (8, D)), tables)
    compiled = jax.jit(write, donate_argnums=(0,)).lower(
        pools, cells, S((256, 2), jnp.int32), S((256,), jnp.int32),
        S((), jnp.int32), S((32, 8), jnp.int32)).compile()
    text = compiled.as_text()
    assert "dynamic-update-slice" in text
    assert not _pool_copies(text, pools, [(8, D)])
    assert compiled.memory_analysis().alias_size_in_bytes \
        == 6 * 2305 * 64 * 8 * 128 * 2
    for fmt in compiled.output_formats[0].values():
        assert fmt.layout.major_to_minor == (0, 1, 2, 3)


def test_gpt2_large_prefix_hit_program_keeps_the_pools_in_place(S):
    """The prefix-hit program of the GPT-2 serve cells (``PagePool._hit_fn``
    : copy-on-write of the matched partial pages, then
    ``TransformerLM.prefill_paged`` over the suffixes) at the widest
    bucket: the copy is a page at a time where the pages lie, so no pool
    array is copied here either (a scatter of whole pages re-laid all 72
    out and back, as the admission's did), every pool aliased."""
    pool, params = _gpt2_large_pool(S)
    compiled = pool._hit_fn(512, 8)._jitted.lower(
        params, pool.pools, S((16, 512), jnp.int32), S((16,), jnp.int32),
        S((16,), jnp.int32), S((16, 8), jnp.int32), S((16,), jnp.int32),
        S((16,), jnp.int32), S((), jnp.int32)).compile()
    assert not _pool_copies(compiled.as_text(), pool.pools, [(20, 64)])
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == GPT2L_POOL_DEVICE_BYTES
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 14 * 2 ** 30)


@pytest.mark.parametrize("bucket", [128, 256, 512])
def test_gpt2_large_admit_program_compiles(one_chip, S, bucket, monkeypatch):
    """The admit program both GPT-2 serve cells run (``PagePool._admit_fn``
    over ``TransformerLM.prefill``'s walk of the live rows: gpt2-large, 16
    slots, 105 pages of 64 rows, f32) compiled whole for the described
    chip at the cells' three widest prompt buckets: it fits beside the
    weights, the flash kernel is in it (from ``SHORT_SEQ_DENSE`` rows
    on), and the pools are written where they lie: donated, aliased at
    their size on the device, NO copy of a pool array in the program (the
    parent's scatter re-laid all 72 out and back) and none of a whole
    array of the cell either (the pages are cut out of it first)."""
    monkeypatch.setattr(pk, "_interpret", lambda interpret: False)
    pool, params = _gpt2_large_pool(S)
    nbp = bucket // 64
    compiled = pool._admit_fn(bucket, nbp)._jitted.lower(
        params, (pool.pools, {}), S((16, bucket), jnp.int32),
        S((16,), jnp.int32), S((16, nbp), jnp.int32)).compile()
    text = compiled.as_text()
    assert (("tpu_custom_call" in text) == (bucket >= pk.SHORT_SEQ_DENSE))
    assert not _pool_copies(text, pool.pools, [(20, 64)])
    assert not [ln for ln in text.splitlines() if " copy(" in ln
                and f"f32[16,{bucket},20,64]" in ln.split(" copy(")[0]]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == GPT2L_POOL_DEVICE_BYTES
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 14 * 2 ** 30)
