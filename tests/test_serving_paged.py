"""Paged KV-cache serving (paddle_tpu/serving/paged.py + engine.py): the
cache is a shared page pool + per-request block tables instead of per-slot
max_len rows. Contracts under test:

* EXACTNESS — greedy tokens through the paged pool are bit-equal to solo
  decode (generate_cached / generate_fused at the same kv_dtype), mixed
  lengths, incl. int8 KV;
* RECLAMATION — finished/cancelled/timed-out requests return their pages
  immediately and the freed slot re-admits queued work;
* the paged read's kernel and dense routes share one formulation
  (ops/pallas_kernels.paged_decode_attention);
* validation hardening — malformed requests die structured at submit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import obs
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.serving import (ContinuousBatcher, Overloaded, PagedBatcher,
                                Request, ServingEngine)

VOCAB, D, H, L, MAX_LEN = 97, 32, 4, 2, 128


@pytest.fixture(scope="module")
def model_and_params(paged_model_and_params):
    """The session-shared model (conftest.py): pools built over the same
    instance share traced admission/segment executables per shape family
    instead of re-tracing per test (ROADMAP item 5)."""
    return paged_model_and_params


def _solo(model, params, prompt, steps, _bucket=12):
    """Solo-decode reference, steps padded onto shared scan compiles
    (greedy is prefix-stable; same trick as test_serving.py)."""
    padded = min(-(-steps // _bucket) * _bucket,
                 model.max_len - len(prompt))
    out = model.generate_cached(params, jnp.asarray(prompt[None]),
                                steps=padded)
    return np.asarray(out)[0, len(prompt):len(prompt) + steps]


@pytest.mark.parametrize("page_block", [8, 16, 32])
def test_paged_matches_solo_decode(model_and_params, page_block):
    """The tentpole contract: mixed prompt/gen lengths through the paged
    pool, every request's greedy continuation token-for-token equal to
    decoding it alone, whatever the page size — and every page back in
    the free list after."""
    model, params = model_and_params
    rs = np.random.RandomState(3)
    reqs = []
    for rid in range(9):          # more requests than slots -> churn
        plen = int(rs.randint(3, 40))
        gen = int(rs.randint(1, 37))
        reqs.append(Request(rid, rs.randint(0, VOCAB, plen), gen))
    b = PagedBatcher(model, params, slots=4, segment=8,
                     page_block=page_block, cache_bucket=32)
    got = b.serve(reqs)
    assert sorted(got) == [r.rid for r in reqs]
    for r in reqs:
        want = _solo(model, params, r.prompt, r.max_new)
        np.testing.assert_array_equal(
            got[r.rid], want,
            err_msg=f"request {r.rid} (prompt {len(r.prompt)}, gen "
                    f"{r.max_new}) diverged under the paged cache")
    assert b.pool.pages_used == 0 and b.pool.reserved == 0
    assert 0 < b.pool.peak_pages_used <= b.pool.capacity_pages


@pytest.mark.parametrize("fills", [1, 2, 4], ids=["one", "two", "all"])
def test_admission_walk_matches_paged_greedy(fills, monkeypatch):
    """ONE admission that fills 1, 2 and all of 4 slots, two rows a chunk
    of the walk (``prefill_live_rows``: a chunk filled up by a dead row,
    one whole chunk, two chunks): first tokens, pages and the segments
    after them are the model's solo paged decode."""
    from paddle_tpu.models import TransformerLM, paged_lm, transformer
    from paddle_tpu.serving.paged import PagePool
    monkeypatch.setattr(transformer, "LM_PREFILL_TOKENS", 32)
    # a model of its own: the programs are traced here, at this chunk
    model = TransformerLM(VOCAB, d_model=D, n_heads=H, n_layers=L,
                          max_len=MAX_LEN)
    params = model.init(jax.random.PRNGKey(0))
    pool = PagePool(model, params, slots=4, segment=4, page_block=8,
                    cache_bucket=32, prompt_buckets=(16, 32))
    rs = np.random.RandomState(7)
    prompts = [rs.randint(0, VOCAB, n).astype(np.int32)
               for n in (5, 13, 16, 9)[:fills]]
    slots = [2, 0, 3, 1][:fills]             # not the first rows of the pool
    first = pool.admit([(s, pool.plan_admission(p, 12))
                        for s, p in zip(slots, prompts)])
    assert pool.last_stats["positions"] == -(-fills // 2) * 2 * 16
    blocks = [pool.run_segment(slots) for _ in range(3)]
    for s, prompt in zip(slots, prompts):
        toks = np.concatenate([b[s] for b in blocks])
        assert toks[0] == first[s]      # a segment re-emits the current one
        solo = np.asarray(paged_lm.paged_greedy(
            model, params, jnp.asarray(prompt)[None], 12, 8))[0]
        np.testing.assert_array_equal(solo[prompt.size:], toks)


# -- pools held where they lie, and the admission's page writes (PR 40) ------

def _scatter_pages(pools, cells, src, dst, n):
    """What ``_write_pages`` replaced — ONE scatter over the whole bucket,
    every (row, page) pair of it, what holds no prompt sent wherever its
    table entry points (the null page)."""
    out = {}
    for nm, pool in pools.items():
        bs = pool.shape[1]
        rows = cells[nm].reshape((cells[nm].shape[0], -1, bs)
                                 + cells[nm].shape[2:])
        corner = tuple(slice(0, k) for k in rows.shape[2:])
        out[nm] = pool.at[(dst,) + corner].set(
            rows[src[:, 0], src[:, 1]].astype(pool.dtype))
    return out


def _pages_minor_runtime(a):
    """``_held_shape`` of an array the runtime lays out pages-minor under
    an (8, 128) tile, as a TPU does a row narrower than its tile
    (``f32[105, 64, 20, 64]``): the CPU lays everything out row-major, so
    the answer is forced here."""
    if a.ndim < 4:
        return tuple(a.shape)
    return tuple(a.shape[:-2]) + tuple(
        -(-k // t) * t for k, t in zip(a.shape[-2:], (8, 128)))


@pytest.fixture(params=["stated", "padded"])
def held(request, monkeypatch):
    """Pools held at the shape the model states (what the CPU's runtime
    asks for) and held padded to the tile (what a TPU's does)."""
    from paddle_tpu.serving import paged
    if request.param == "padded":
        monkeypatch.setattr(paged, "_held_shape", _pages_minor_runtime)
    return request.param


def test_pool_keeps_the_stated_arrays_where_padding_buys_nothing(
        model_and_params, monkeypatch):
    """The held shape is a rule read off one runtime: where the padded
    array does not come out row-major either, the pool holds the arrays
    as the model states them (the parent's programs, nothing padded) and
    says so once."""
    from paddle_tpu.serving import paged
    model, params = model_and_params
    monkeypatch.setattr(paged, "_held_shape", _pages_minor_runtime)
    monkeypatch.setattr(paged, "_row_major", lambda a: False)
    with pytest.warns(UserWarning, match="held as stated"):
        pool = paged.PagePool(model, params, slots=2, segment=4, page_block=8,
                              cache_bucket=32)
    for nm, a in pool.pools.items():
        assert a.shape == (pool.pages, pool.bs) + pool._row_shapes[nm], nm


def test_held_shape_refuses_a_tile_it_cannot_pad_to():
    """``_held_shape`` pads a row's last two dims to a 2-D first tile and
    names any other tiling instead of guessing."""
    import types
    from paddle_tpu.serving import paged
    lay = types.SimpleNamespace(major_to_minor=(1, 2, 3, 0),
                                tiling=((1024,),))
    a = types.SimpleNamespace(shape=(9, 8, 4, 8), ndim=4,
                              format=types.SimpleNamespace(layout=lay))
    with pytest.raises(ValueError, match="first tile"):
        paged._held_shape(a)
    lay.tiling = ((8, 128), (2, 1))
    assert paged._held_shape(a) == (9, 8, 8, 128)


def _marked_pool(model, params, kv_dtype, **kw):
    """A pool whose every page holds values no admission writes, so that a
    write where none belongs shows."""
    from paddle_tpu.serving.paged import PagePool
    pool = PagePool(model, params, slots=4, segment=4, page_block=8,
                    cache_bucket=32, prompt_buckets=(16, 32),
                    kv_dtype=kv_dtype, **kw)
    rs = np.random.RandomState(5)
    pool.pools = {nm: jnp.asarray(rs.randint(-100, 100, a.shape), a.dtype)
                  for nm, a in pool.pools.items()}
    return pool


def _held_where_it_lay(pool, held):
    """Every array is still the shape the pool built it in (the stated
    rows ``[4, 8]``, or those padded to ``[8, 128]``), row-major."""
    for nm, a in pool.pools.items():
        want = pool._row_shapes[nm]
        if held == "padded" and len(want) == 2:
            want = (8, 128)
        assert a.shape == (pool.pages, pool.bs) + want, nm
        assert a.format.layout.major_to_minor == tuple(range(a.ndim)), nm


def _stated(pool):
    """The pool's arrays as numpy, their rows as the model states them."""
    return {nm: np.asarray(pk.pool_rows(a, pool._row_shapes[nm]))
            for nm, a in pool.pools.items()}


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["f32", "int8"])
@pytest.mark.parametrize("lens", [(5,), (16, 9), (13, 1, 8, 24)],
                         ids=["one", "two", "all"])
def test_admission_writes_only_the_pages_it_admitted(model_and_params,
                                                     monkeypatch, kv_dtype,
                                                     lens, held):
    """After an admission every page that holds no admitted prompt — the
    null page, the free pages, the pages of slots admitted EARLIER — is
    byte for byte what it was (the padding of a page held wider than its
    rows too), and the pages it did write hold what the scatter over the
    bucket put there."""
    from paddle_tpu.serving import paged
    model, params = model_and_params
    rs = np.random.RandomState(9)
    pool = _marked_pool(model, params, kv_dtype)
    early = pool.admit([(1, pool.plan_admission(
        rs.randint(0, VOCAB, 11).astype(np.int32), 6))])
    assert list(early) == [1]
    before = {nm: np.asarray(a) for nm, a in pool.pools.items()}
    slots = [3, 0, 2, 1][:len(lens)]
    if len(lens) == 4:              # all four: the early one goes first
        pool.free_slot(1)
    prompts = [rs.randint(0, VOCAB, n).astype(np.int32) for n in lens]
    pool.admit([(s, pool.plan_admission(p, 6))
                for s, p in zip(slots, prompts)])
    wrote = {int(pg) for s, p in zip(slots, prompts)
             for pg in pool.tables[s, :-(-p.size // 8)]}
    assert 0 not in wrote and len(wrote) == sum(-(-n // 8) for n in lens)
    kept = np.asarray([pg for pg in range(pool.pages) if pg not in wrote])
    after = {nm: np.asarray(a) for nm, a in pool.pools.items()}
    for nm in before:
        np.testing.assert_array_equal(after[nm][kept], before[nm][kept],
                                      err_msg=nm)
    # ... and the written pages: what the scatter's programs put there
    monkeypatch.setattr(paged, "_write_pages", _scatter_pages)
    monkeypatch.setattr(paged, "_shared_fn_cache", lambda model: {})
    ref = _marked_pool(model, params, kv_dtype)
    rs = np.random.RandomState(9)
    ref.admit([(1, ref.plan_admission(
        rs.randint(0, VOCAB, 11).astype(np.int32), 6))])
    if len(lens) == 4:
        ref.free_slot(1)
    ref.admit([(s, ref.plan_admission(p, 6))
               for s, p in zip(slots, prompts)])
    np.testing.assert_array_equal(ref.tables, pool.tables)
    for nm, a in ref.pools.items():
        np.testing.assert_array_equal(after[nm][1:], np.asarray(a)[1:],
                                      err_msg=nm)
    _held_where_it_lay(pool, held)


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["f32", "int8"])
@pytest.mark.parametrize("page_block", [8, 16, 32])
def test_page_writes_serve_the_scatter_paths_tokens(model_and_params,
                                                    monkeypatch, kv_dtype,
                                                    page_block, held):
    """The parity cases' traffic through the page-at-a-time write into
    pools held as stated or padded, and through the scatter it replaced
    into pools as stated (programs of its own, traced under the patch):
    the same tokens, request for request."""
    from paddle_tpu.serving import paged
    model, params = model_and_params
    rs = np.random.RandomState(3)
    reqs = [Request(rid, rs.randint(0, VOCAB, int(rs.randint(3, 40))),
                    int(rs.randint(1, 37))) for rid in range(9)]

    def serve():
        b = PagedBatcher(model, params, slots=4, segment=8,
                         page_block=page_block, cache_bucket=32,
                         kv_dtype=kv_dtype)
        return b, b.serve(reqs)
    b, got = serve()
    assert all(a.shape[2:] == ((8, 128) if held == "padded" and a.ndim == 4
                               else b.pool._row_shapes[nm])
               for nm, a in b.pool.pools.items())
    monkeypatch.undo()
    monkeypatch.setattr(paged, "_write_pages", _scatter_pages)
    monkeypatch.setattr(paged, "_shared_fn_cache", lambda model: {})
    _, want = serve()
    assert sorted(got) == sorted(want) == [r.rid for r in reqs]
    for r in reqs:
        np.testing.assert_array_equal(got[r.rid], want[r.rid])


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["f32", "int8"])
@pytest.mark.parametrize("ends", ["same", "padded-to-stated",
                                  "stated-to-padded"])
def test_shipped_slot_lands_where_the_pages_lie(model_and_params,
                                                monkeypatch, kv_dtype, ends):
    """A slot ships as the rows the model states, whatever either pool
    holds them in: ``export_slot`` cuts them out of a padded pool,
    ``adopt_slot`` writes them — through a program that writes pages
    where they lie, not an eager scatter over the pool — into the
    adopted slot's pages and no other, and the segments after it are the
    shipping pool's own."""
    from paddle_tpu.serving import paged, ship
    model, params = model_and_params
    rs = np.random.RandomState(21)
    prompt = rs.randint(0, VOCAB, 19).astype(np.int32)
    if ends == "padded-to-stated":
        monkeypatch.setattr(paged, "_held_shape", _pages_minor_runtime)
    src = _marked_pool(model, params, kv_dtype)
    first = src.admit([(2, src.plan_admission(prompt, 8))])[2]
    manifest, payload = src.export_slot(2, first)
    arrays = ship.unpack(manifest, payload)
    assert all(arrays[nm].shape == (3, 8) + src._row_shapes[nm]
               for nm in src.pools)
    monkeypatch.undo()
    if ends == "stated-to-padded":
        monkeypatch.setattr(paged, "_held_shape", _pages_minor_runtime)
    dst = _marked_pool(model, params, kv_dtype)
    before = {nm: np.asarray(a) for nm, a in dst.pools.items()}
    dst.adopt_slot(1, manifest["plen"], manifest["first"], arrays,
                   dst.required_pages(prompt.size, 8))
    _held_where_it_lay(dst, "padded" if ends == "stated-to-padded"
                       else "stated")
    wrote = [int(pg) for pg in dst.tables[1, :3]]
    kept = np.asarray([pg for pg in range(dst.pages) if pg not in wrote])
    for nm, a in dst.pools.items():
        np.testing.assert_array_equal(np.asarray(a)[kept], before[nm][kept])
    for nm, a in _stated(dst).items():
        np.testing.assert_array_equal(a[wrote], arrays[nm])
    np.testing.assert_array_equal(dst.run_segment([1])[1],
                                  src.run_segment([2])[2])


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["f32", "int8"])
def test_prefix_hit_copies_and_writes_only_its_own_pages(model_and_params,
                                                         kv_dtype, held):
    """The prefix-hit program carries the pools where they lie too: its
    copy-on-write is a page copied where it lies (``_copy_pages``), not a
    scatter over the pool. After a hit that diverges mid-block the pages
    the hit slot does not OWN — the matched full blocks, the stored
    partial page it copied from, every free page — are byte for byte what
    they were (the null page takes the padded rows ``prefill_paged``
    drains there), and its copy starts with the stored rows."""
    model, params = model_and_params
    rs = np.random.RandomState(5)
    pool = _marked_pool(model, params, kv_dtype, prefix_cache=True)
    shared = rs.randint(0, VOCAB, 21).astype(np.int32)   # 2 blocks + 5
    pool.admit([(0, pool.plan_admission(shared, 6))])
    before = {nm: np.asarray(a) for nm, a in pool.pools.items()}
    # 19 shared tokens: two full-block hits, 3 rows into the stored tail
    prompt = np.concatenate([shared[:19],
                             rs.randint(0, VOCAB, 6).astype(np.int32)])
    plan = pool.plan_admission(prompt, 6)
    src = plan.match.partial.page
    pool.admit([(1, plan)])
    assert pool.cow_copies_total == 1
    owned = [int(pg) for pg in pool.tables[1, 2:4]]      # positions 16..24
    assert src not in owned and 0 not in owned
    kept = np.asarray([pg for pg in range(1, pool.pages)
                       if pg not in owned])
    for nm, a in pool.pools.items():
        a = np.asarray(a)
        np.testing.assert_array_equal(a[kept], before[nm][kept], err_msg=nm)
        np.testing.assert_array_equal(a[owned[0], :3], before[nm][src, :3],
                                      err_msg=nm)
    _held_where_it_lay(pool, held)
    # ... and the tokens behind it are the solo decode's
    solo = np.asarray(model.generate_cached(
        params, jnp.asarray(prompt)[None], steps=6))[0, prompt.size:] \
        if kv_dtype is None else None
    toks = pool.run_segment([1])[1]
    if solo is not None:
        np.testing.assert_array_equal(toks[:4], solo[1:5])


@pytest.mark.parametrize("case", ["defaults", "refusals"])
def test_pool_defaults_and_grid_refusals(model_and_params, case):
    """The pool's geometry comes from its signature: page_block 64,
    cache_bucket 256, prompt buckets 32..512 with nothing passed, and the
    batcher and the engine hand the same values on. A geometry off the
    page grid is refused with the ValueError that ``serve`` prints as
    ``serve: <message>`` before it exits 2 (page_block against max_len
    through the CLI: test_cli_serve_bad_flags_structured_error) — the
    defaults too, on a model whose max_len they do not divide."""
    import inspect
    from paddle_tpu.models import TransformerLM
    from paddle_tpu.serving.paged import PagePool
    model, params = model_and_params
    grid = (64, 256, (32, 64, 128, 256, 512))
    if case == "defaults":
        pool = PagePool(model, params, slots=2)
        assert (pool.bs, pool.cache_bucket,
                tuple(pool.prompt_buckets)) == grid
        for owner in (PagePool, PagedBatcher, ServingEngine):
            sig = inspect.signature(owner.__init__).parameters
            assert tuple(sig[k].default for k in (
                "page_block", "cache_bucket", "prompt_buckets")) == grid
        return
    with pytest.raises(ValueError, match="cache_bucket 40 must be a "
                                         "multiple of page_block 16"):
        ServingEngine(model, params, slots=2, page_block=16,
                      cache_bucket=40)
    short = TransformerLM(VOCAB, d_model=D, n_heads=H, n_layers=1,
                          max_len=96)
    with pytest.raises(ValueError, match="page_block 64 must divide "
                                         "max_len 96"):
        PagePool(short, None, slots=2)


def test_paged_matches_pinned_batcher(model_and_params):
    """Paged and pinned pools run the same masked-softmax read: identical
    outputs on an identical workload (the memory manager is invisible)."""
    model, params = model_and_params
    rs = np.random.RandomState(9)
    reqs = [Request(i, rs.randint(0, VOCAB, int(rs.randint(3, 30))),
                    int(rs.randint(1, 25))) for i in range(5)]
    pinned = ContinuousBatcher(model, params, slots=3, segment=8,
                               cache_bucket=32, schedule="fifo").serve(
        [Request(r.rid, r.prompt.copy(), r.max_new) for r in reqs])
    paged = PagedBatcher(model, params, slots=3, segment=8, page_block=8,
                         cache_bucket=32, schedule="fifo").serve(
        [Request(r.rid, r.prompt.copy(), r.max_new) for r in reqs])
    for r in reqs:
        np.testing.assert_array_equal(paged[r.rid], pinned[r.rid])


def test_paged_int8_matches_solo_int8(model_and_params):
    """Quantized-KV exactness carries over: int8 paged tokens equal SOLO
    decode at the same kv_dtype (batching and paging add no error)."""
    model, params = model_and_params
    rs = np.random.RandomState(13)
    reqs = [Request(rid, rs.randint(0, VOCAB, int(rs.randint(3, 30))),
                    int(rs.randint(1, 25))) for rid in range(3)]
    b = PagedBatcher(model, params, slots=2, segment=8, page_block=8,
                     cache_bucket=32, kv_dtype="int8")
    got = b.serve(reqs)
    for r in reqs:
        want = np.asarray(model.generate_fused(
            params, jnp.asarray(r.prompt[None]), steps=r.max_new,
            kv_dtype="int8"))[0, len(r.prompt):]
        np.testing.assert_array_equal(got[r.rid], want,
                                      err_msg=f"request {r.rid}")


def test_paged_eos_and_small_pool_queueing(model_and_params):
    """EOS truncation works through pages, and a pool too small for every
    request at once queues the tail (admission control) without changing
    anyone's tokens."""
    model, params = model_and_params
    rs = np.random.RandomState(5)
    prompt = rs.randint(0, VOCAB, 9)
    full = _solo(model, params, prompt, 24)
    eos = int(full[7])
    # pool sized so ~one request fits at a time: (9 + 24 + 8 - 1) / 8 -> 5
    # pages; 8 usable pages hold one live request + change
    b = PagedBatcher(model, params, slots=3, segment=8, page_block=8,
                     pages=9, cache_bucket=32)
    reqs = [Request(0, prompt, 24, eos_id=eos),
            Request(1, rs.randint(0, VOCAB, 7), 11),
            Request(2, rs.randint(0, VOCAB, 5), 9)]
    got = b.serve(reqs)
    first_hit = int(np.nonzero(full == eos)[0][0])
    np.testing.assert_array_equal(got[0], full[:first_hit])
    for r in reqs[1:]:
        np.testing.assert_array_equal(
            got[r.rid], _solo(model, params, r.prompt, r.max_new))
    assert b.pool.pages_used == 0


def test_admission_wave_cannot_overcommit_pool(model_and_params):
    """Regression: fits() must count pages the SAME admission wave already
    claimed. Two free slots + two requests each reserving 5 pages against
    an 8-page pool used to both pass fits(5) (pool.reserved only updates
    inside pool.admit), then exhaust the free list mid-decode with
    'page pool exhausted past its reservations'. Now the second queues,
    both finish exactly, and the reservation invariant holds throughout."""
    model, params = model_and_params
    rs = np.random.RandomState(41)
    reqs = [Request(0, rs.randint(0, VOCAB, 8), 25),
            Request(1, rs.randint(0, VOCAB, 8), 25)]   # 5 pages each
    b = PagedBatcher(model, params, slots=2, segment=8, page_block=8,
                     pages=9, cache_bucket=32)         # capacity 8 < 2*5
    got = b.serve(reqs)
    for r in reqs:
        np.testing.assert_array_equal(
            got[r.rid], _solo(model, params, r.prompt, r.max_new))
    assert b.pool.pages_used == 0
    assert b.pool.peak_pages_used <= b.pool.capacity_pages
    # engine path shares the fix
    eng = ServingEngine(model, params, slots=2, segment=8, page_block=8,
                        pages=9, cache_bucket=32, queue_cap=4)
    rids = [eng.submit(r.prompt, r.max_new) for r in reqs]
    eng.step()
    assert eng.pool.reserved <= eng.pool.capacity_pages
    while not all(eng.poll(r)[1] for r in rids):
        eng.step()
        assert eng.pool.reserved <= eng.pool.capacity_pages
    assert eng.pool.pages_used == 0


def test_paged_attention_routes_agree(model_and_params):
    """paged_decode_attention: the scalar-prefetch kernel (pages assembled
    in VMEM) vs the dense gather route — same formulation, f32/int8 —
    and the dense route is bit-equal to the dense-ROW decode_attention on
    the gathered cache (the pinned-parity building block)."""
    del model_and_params
    B, Hh, Dh, bs, NB, P = 3, 4, 16, 8, 4, 14
    rs = np.random.RandomState(0)
    k_pool = jnp.asarray(rs.randn(P, bs, Hh, Dh), jnp.float32)
    v_pool = jnp.asarray(rs.randn(P, bs, Hh, Dh), jnp.float32)
    tables = jnp.asarray(np.stack(
        [rs.choice(np.arange(1, P), NB, replace=False) for _ in range(B)]),
        jnp.int32)
    q = jnp.asarray(rs.randn(B, Hh, Dh), jnp.float32)
    pos = jnp.asarray([3, 17, 30], jnp.int32)
    dense = pk.paged_decode_attention(q, k_pool, v_pool, tables, pos,
                                      route="dense")
    kern = pk.paged_decode_attention(q, k_pool, v_pool, tables, pos,
                                     route="kernel", interpret=True)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense),
                               rtol=2e-6, atol=2e-6)
    row = pk.decode_attention(q, pk.gather_pages(k_pool, tables),
                              pk.gather_pages(v_pool, tables), pos,
                              route="dense")
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(row))
    k8, ks = pk.quantize_kv(k_pool)
    v8, vs = pk.quantize_kv(v_pool)
    d8 = pk.paged_decode_attention(q, k8, v8, tables, pos, k_scale=ks,
                                   v_scale=vs, route="dense")
    k8o = pk.paged_decode_attention(q, k8, v8, tables, pos, k_scale=ks,
                                    v_scale=vs, route="kernel",
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(k8o), np.asarray(d8),
                               rtol=2e-6, atol=2e-6)


def _ragged_case(name, NB, bs):
    """(tables [B, NB], pos [B]) of one ragged block table; page 0 is the
    null page, entries past a slot's live pages point at it."""
    def table(pages):
        return pages + [0] * (NB - len(pages))
    if name == "empty_slot":          # pos 0 under an all-null table
        return [table([]), table([3, 4]), table([5])], [0, bs + 36, 5]
    if name == "page_boundary":       # last row of page 0, first of page 1
        return ([table([1]), table([2, 3]), table([4, 5])],
                [bs - 1, bs, bs + 1])
    if name == "whole_table_beside_one_page":
        return ([table([1]), table(list(range(2, 2 + NB))), table([20])],
                [7, NB * bs - 1, bs - 2])
    if name == "shared_prefix":       # two slots read the same two pages
        return ([table([1, 2, 3]), table([1, 2, 4]), table([5])],
                [2 * bs + 22, 2 * bs + 42, 0])
    if name == "every_slot_full":     # the whole table is live
        return ([table(list(range(1 + b * NB, 1 + (b + 1) * NB)))
                 for b in range(2)], [NB * bs - 1] * 2)
    raise AssertionError(name)


@pytest.mark.parametrize("kv", ["f32", "int8"])
@pytest.mark.parametrize("NB", [4, 16])
@pytest.mark.parametrize("case", ["empty_slot", "page_boundary",
                                  "whole_table_beside_one_page",
                                  "shared_prefix", "every_slot_full"])
def test_paged_kernel_walks_ragged_tables(case, NB, kv):
    """The kernel route's grid is the work list of live (slot, page) pairs:
    on ragged tables it walks exactly the started pages (one item for an
    empty slot), in slot order, and agrees with the dense gather route."""
    Hh, Dh, bs, P = 4, 16, 64, 34
    tables, pos = _ragged_case(case, NB, bs)
    tables, pos = np.asarray(tables, np.int32), np.asarray(pos, np.int32)
    B = len(pos)
    rs = np.random.RandomState(NB)
    k_pool = jnp.asarray(rs.randn(P, bs, Hh, Dh), jnp.float32)
    v_pool = jnp.asarray(rs.randn(P, bs, Hh, Dh), jnp.float32)
    q = jnp.asarray(rs.randn(B, Hh, Dh), jnp.float32)

    slot, page, ordinal, last, n_work = map(
        np.asarray, pk.paged_work_list(jnp.asarray(tables),
                                       jnp.asarray(pos), bs))
    n = int(n_work[0])
    want = [(b, j) for b in range(B) for j in range(pos[b] // bs + 1)]
    assert n == len(want) <= B * NB == slot.size
    assert list(zip(slot[:n], ordinal[:n])) == want
    np.testing.assert_array_equal(page[:n], [tables[b, j] for b, j in want])
    np.testing.assert_array_equal(
        last[:n], [int(j == pos[b] // bs) for b, j in want])

    scales = {}
    if kv == "int8":
        k_pool, scales["k_scale"] = pk.quantize_kv(k_pool)
        v_pool, scales["v_scale"] = pk.quantize_kv(v_pool)
    args = (q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(pos))
    dense = pk.paged_decode_attention(*args, route="dense", **scales)
    kern = pk.paged_decode_attention(*args, route="kernel", interpret=True,
                                     **scales)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense),
                               rtol=2e-6, atol=2e-6)


def test_paged_batcher_kernel_route_tokens_equal_dense(model_and_params,
                                                       monkeypatch):
    """A mixed-length batch decoded with the paged read on the kernel route
    (the interpreter, with ``pk.decode_route`` patched) emits the dense
    route's greedy tokens: idle slots, slots finishing mid-segment and
    tables of different widths all go through the work list."""
    from paddle_tpu.models import TransformerLM
    model, params = model_and_params
    rs = np.random.RandomState(21)
    reqs = [Request(rid, rs.randint(0, VOCAB, plen), gen)
            for rid, (plen, gen) in enumerate(
                [(3, 30), (37, 9), (8, 1), (20, 26), (5, 12), (33, 17)])]
    kw = dict(slots=4, segment=8, page_block=8, cache_bucket=32)
    want = PagedBatcher(model, params, **kw).serve(reqs)
    # the route is chosen while a program is traced: a model of its own
    # keeps these programs out of the session model's shared cache
    fresh = TransformerLM(VOCAB, d_model=D, n_heads=H, n_layers=L,
                          max_len=MAX_LEN)
    monkeypatch.setattr(pk, "decode_route",
                        lambda L, route=None: route or "kernel")
    r = obs.MetricsRegistry()
    with obs.ObsSession(registry=r).installed():
        got = PagedBatcher(fresh, params, **kw).serve(reqs)
    assert r.counter("kernels.routes_total").get(
        kernel="paged_decode_attention", route="kernel") > 0
    assert r.counter("kernels.routes_total").get(
        kernel="paged_decode_attention", route="dense") == 0
    for req in reqs:
        np.testing.assert_array_equal(got[req.rid], want[req.rid])


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_decode_pages_walked_counters_and_bytes(model_and_params, kv_dtype):
    """run_segment counts the paged read's programs (walked) beside the
    table's cells, from the host's pos: walked <= table, equal when every
    slot fills the table, and the modeled kernel bytes of a segment are
    exactly the pages walked x one layer's (k + v) page."""
    from paddle_tpu.serving.paged import PagePool
    model, params = model_and_params
    layers = len(model.blocks)

    def segment(pool, plens):
        group = [(slot, pool.plan_admission(np.arange(plen) % VOCAB, 4))
                 for slot, plen in enumerate(plens)]
        pool.admit(group)
        r = obs.MetricsRegistry()
        with obs.ObsSession(registry=r).installed():
            pool.run_segment([slot for slot, _ in group])
        walked = r.counter("serving.decode_pages_walked_total").get()
        table = r.counter("serving.decode_pages_table_total").get()
        read = r.counter("kernels.bytes_total").get(
            kernel="paged_decode_attention")
        assert read == pool.read_bytes_total
        assert read == walked * pool.page_bytes / layers
        return walked, table

    kw = dict(slots=3, page_block=8, cache_bucket=32, kv_dtype=kv_dtype)
    # ragged, a table 4 wide, 8 steps: pos 11 reads 2 pages for 5 steps and
    # 3 from pos 16 on; pos 3 reads 1, then 2 from pos 8 on; the idle slot 1
    walked, table = segment(PagePool(model, params, segment=8, **kw), [11, 3])
    assert table == layers * 8 * 3 * 4
    assert walked == layers * ((5 * 2 + 3 * 3) + (5 * 1 + 3 * 2) + 8) < table
    # every slot on the table's last page: the whole grid is live
    walked, table = segment(PagePool(model, params, segment=1, **kw),
                            [30, 27, 25])
    assert walked == table == layers * 3 * 4


def test_validation_hardening(model_and_params):
    """Malformed requests die AT SUBMIT with precise errors (not as shape
    errors deep in prefill): max_new <= 0, empty prompt, prompt past the
    page budget — for both batchers and the engine."""
    model, params = model_and_params
    b = PagedBatcher(model, params, slots=2, segment=8, page_block=8,
                     cache_bucket=32)
    with pytest.raises(ValueError, match="max_new"):
        b.serve([Request(0, np.array([3, 5], np.int32), 0)])
    with pytest.raises(ValueError, match="empty prompt"):
        b.serve([Request(0, np.zeros((0,), np.int32), 4)])
    pinned = ContinuousBatcher(model, params, slots=2, segment=8,
                               cache_bucket=32)
    with pytest.raises(ValueError, match="max_new"):
        pinned.serve([Request(1, np.array([3], np.int32), -2)])
    # page budget: a 6-usable-page pool (48 positions) cannot ever hold
    # prompt 60 — rejected structured at submit, nothing queued
    tiny = PagedBatcher(model, params, slots=2, segment=8, page_block=8,
                        pages=7, cache_bucket=32)
    with pytest.raises(ValueError, match="pages"):
        tiny.serve([Request(2, np.arange(60, dtype=np.int32) % VOCAB, 4)])
    eng = ServingEngine(model, params, slots=2, segment=8, page_block=8,
                        cache_bucket=32, queue_cap=2)
    with pytest.raises(ValueError, match="max_new"):
        eng.submit(np.array([3], np.int32), 0)


def test_engine_cancel_frees_pages_and_readmits(model_and_params):
    """Mid-flight cancel: the request finalizes with reason=cancelled, its
    pages return at the next segment boundary, and the freed slot admits
    queued work — driven deterministically via engine.step()."""
    model, params = model_and_params
    rs = np.random.RandomState(21)
    eng = ServingEngine(model, params, slots=1, segment=8, page_block=8,
                        cache_bucket=32, queue_cap=4)
    long_rid = eng.submit(rs.randint(0, VOCAB, 9), 100)
    short_prompt = rs.randint(0, VOCAB, 7)
    short_rid = eng.submit(short_prompt, 9)
    eng.step()                       # admit long (slot 0) + one segment
    toks, done, _ = eng.poll(long_rid)
    assert toks and not done
    used_live = eng.pool.pages_used
    assert used_live > 0
    assert eng.poll(short_rid)[0] == []          # still queued (1 slot)
    assert eng.cancel(long_rid) is True
    eng.step()                       # reap: free pages, admit the short
    toks, done, reason = eng.poll(long_rid)
    assert done and reason == "cancelled"
    eng.step()
    while not eng.poll(short_rid)[1]:
        eng.step()
    toks, done, reason = eng.poll(short_rid)
    assert done and reason == "length"
    np.testing.assert_array_equal(
        np.asarray(toks, np.int32), _solo(model, params, short_prompt, 9))
    assert eng.pool.pages_used == 0 and eng.pool.reserved == 0
    # cancel of a finished request is a no-op, not an error
    assert eng.cancel(short_rid) is False


def test_engine_timeout_frees_pages(model_and_params):
    """Deadlines: a queued request times out without touching the pool; a
    LIVE request's timeout frees slot + pages (fake clock, no sleeps)."""
    model, params = model_and_params
    rs = np.random.RandomState(23)
    t = [0.0]
    eng = ServingEngine(model, params, slots=1, segment=8, page_block=8,
                        cache_bucket=32, queue_cap=4, clock=lambda: t[0])
    live = eng.submit(rs.randint(0, VOCAB, 9), 100, timeout_s=50.0)
    queued = eng.submit(rs.randint(0, VOCAB, 5), 10, timeout_s=10.0)
    eng.step()                                   # live admitted
    assert eng.pool.pages_used > 0
    t[0] = 20.0                                  # queued deadline passes
    eng.step()
    assert eng.poll(queued)[1:] == (True, "timeout")
    t[0] = 60.0                                  # live deadline passes
    eng.step()
    assert eng.poll(live)[1:] == (True, "timeout")
    assert eng.pool.pages_used == 0 and eng.pool.reserved == 0


def test_engine_backpressure_structured(model_and_params):
    """Queue-cap admission control raises the STRUCTURED Overloaded (with
    a retry hint) — and the engine keeps serving afterwards."""
    model, params = model_and_params
    rs = np.random.RandomState(29)
    eng = ServingEngine(model, params, slots=1, segment=8, page_block=8,
                        cache_bucket=32, queue_cap=1)
    first = eng.submit(rs.randint(0, VOCAB, 5), 3)   # fills the 1-deep queue
    with pytest.raises(Overloaded) as ei:
        eng.submit(rs.randint(0, VOCAB, 5), 3)
    assert ei.value.retry_after_s > 0
    while not eng.poll(first)[1]:                    # still serving after
        eng.step()
    second = eng.submit(rs.randint(0, VOCAB, 5), 3)  # queue drained: admits
    while not eng.poll(second)[1]:
        eng.step()
    assert eng.pool.pages_used == 0


def test_engine_dispatch_failure_fails_loudly(model_and_params):
    """A dispatch blowing up must not leave a daemon that LOOKS alive:
    outstanding requests finalize with reason=error (pollers see done, not
    an infinite hang) and new submissions carry the cause."""
    import time as _time
    model, params = model_and_params
    rs = np.random.RandomState(37)
    eng = ServingEngine(model, params, slots=1, segment=8, page_block=8,
                        cache_bucket=32, queue_cap=4)

    def boom(live, steps=None):
        raise RuntimeError("synthetic device failure")
    eng.pool.run_segment = boom
    eng.start()
    try:
        rid = eng.submit(rs.randint(0, VOCAB, 5), 10)
        deadline = _time.monotonic() + 30
        while _time.monotonic() < deadline and not eng.poll(rid)[1]:
            _time.sleep(0.02)
        toks, done, reason = eng.poll(rid)
        assert done and reason == "error"
        with pytest.raises(RuntimeError, match="synthetic device failure"):
            eng.submit(rs.randint(0, VOCAB, 5), 10)
    finally:
        eng.stop()


def test_engine_slo_metrics_and_gauges(model_and_params):
    """TTFT/TPOT histograms and the queue/page gauges land in the metric
    registry (the obs summary the acceptance criterion names)."""
    model, params = model_and_params
    rs = np.random.RandomState(31)
    reg = obs.MetricsRegistry()
    with obs.ObsSession(registry=reg).installed():
        eng = ServingEngine(model, params, slots=2, segment=8, page_block=8,
                            cache_bucket=32, queue_cap=8)
        rids = [eng.submit(rs.randint(0, VOCAB, int(rs.randint(3, 20))),
                           int(rs.randint(2, 20))) for _ in range(4)]
        while not all(eng.poll(r)[1] for r in rids):
            eng.step()
    samples = reg.collect()
    names = {s["name"] for s in samples}
    assert "serving.ttft_seconds" in names
    assert "serving.tpot_seconds" in names
    assert "serving.page_occupancy" in names
    done = [s for s in samples if s["name"] == "serving.requests_total"]
    assert sum(s["value"] for s in done) == len(rids)
    occ = [s["value"] for s in samples
           if s["name"] == "serving.page_occupancy"]
    assert all(0.0 <= v <= 1.0 for v in occ)
