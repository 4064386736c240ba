"""The selected decode read (pk.sparse_decode_attention, ISSUE 45): the unit
it fetches is an aligned RUN of one page's rows — ``pk.sparse_run(page_block)
= gcd(64, page_block)``, the whole page in the serving pool — one descriptor
for k and one for v where the run holds a selected row, none where it holds
none, and the softmax is over the selected rows alone.

The kernel route runs interpreted here; every scene is held against the
dense route (a masked softmax over the gathered context) and against a plain
softmax over the selected rows fetched by index in float64. Float32 pools
throughout but for one bfloat16 case, so the three differ in the ORDER of
float32 sums alone: 1e-5. What a run that is NOT fetched would have brought
is planted with NaN, which a fetch would carry into ``o``.
"""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import obs
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.serving import ServingEngine

import test_keye_vl2

H, HKV, D, TOPK = 8, 2, 32, 64
#: positions a table covers: three chunks of ``pk.SPARSE_ROWS`` with the
#: last one part padding, whole pages of 64, 16 and 48 rows
CONTEXT = 2304

#: scene -> each slot's ``pos`` (the step's own key lies there, written)
SCENES = {
    "one-long-slot": [2303],
    "eight-full-slots": [2303] * 8,
    "short-and-long": [70, 2303, 5, 1100],
    "an-idle-slot-at-0": [0, 900, 0],
    "a-partial-last-run": [1029],
}


def _scene(pos, bs, *, seed=0, only_pages=None, dtype=jnp.float32):
    """(q, k_pool, v_pool, tables, bias, pos) of slots at ``pos`` over pages
    of ``bs`` rows; the selection is ``select_topk``'s over seeded scores
    (``only_pages``: the fraction of a slot's pages the best keys lie in)."""
    rs = np.random.RandomState(seed)
    B, NB = len(pos), CONTEXT // bs
    P = B * NB + 1
    tables = rs.permutation(np.arange(1, P)).reshape(B, NB).astype(np.int32)
    scores = rs.randn(B, NB * bs).astype(np.float32)
    if only_pages is not None:
        scores += 8.0 * np.repeat(rs.rand(B, NB) < only_pages, bs, axis=1)
    pos = jnp.asarray(pos, jnp.int32)
    bias, _ = pk.select_topk(jnp.asarray(scores), pos + 1, TOPK,
                             route="dense")
    f = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)
    return (f(B, H, D), f(P, bs, HKV, D).astype(dtype),
            f(P, bs, HKV, D).astype(dtype), jnp.asarray(tables), bias, pos)


def _plain(q, kp, vp, tables, bias):
    """A softmax over each slot's selected rows and no others, the rows
    fetched by index, float64."""
    q, kp, vp = (np.asarray(a, np.float64) for a in (q, kp, vp))
    bs = kp.shape[1]
    out = np.zeros(q.shape)
    for b in range(q.shape[0]):
        at = np.nonzero(np.asarray(bias[b]) == 0.0)[0]
        page, row = np.asarray(tables)[b, at // bs], at % bs
        k = np.repeat(kp[page, row], H // HKV, axis=1)      # [n, H, D]
        v = np.repeat(vp[page, row], H // HKV, axis=1)
        s = np.einsum("hd,nhd->hn", q[b] * D ** -0.5, k)
        w = np.exp(s - s.max(axis=1, keepdims=True))
        out[b] = np.einsum("hn,nhd->hd", w / w.sum(axis=1, keepdims=True), v)
    return out


def _runs(bias, bs):
    """Runs of ``sparse_run(bs)`` rows that hold a selected row, a slot."""
    hit = np.asarray(bias) == 0.0
    return hit.reshape(hit.shape[0], -1, pk.sparse_run(bs)).any(-1).sum(-1)


def _read(scene, route):
    return pk.sparse_decode_attention(*scene, route=route, interpret=True)


@pytest.mark.parametrize("bs", [64, 16, 48])
def test_a_run_is_the_gcd_of_64_and_the_page(bs):
    assert pk.sparse_run(bs) == math.gcd(64, bs) == {64: 64, 16: 16,
                                                     48: 16}[bs]
    assert bs % pk.sparse_run(bs) == 0 and pk.SPARSE_ROWS % 64 == 0


@pytest.mark.parametrize("bs", [64, 16, 48])
@pytest.mark.parametrize("name", list(SCENES))
def test_the_read_is_the_softmax_over_the_selected_rows(name, bs):
    """Kernel = dense route = plain softmax over the selected rows, and both
    routes count the same runs: the mask's own."""
    scene = _scene(SCENES[name], bs, seed=len(name) + bs)
    got, runs = _read(scene, "kernel")
    dense, same = _read(scene, "dense")
    np.testing.assert_allclose(got, dense, atol=1e-5)
    np.testing.assert_allclose(got, _plain(*scene[:5]), atol=1e-5)
    want = _runs(scene[4], bs)
    assert (np.asarray(runs) == want).all() and (np.asarray(same) == want).all()
    # an idle slot at position 0 selects its one key: ONE run, two
    # descriptors, whatever its table holds
    assert all(int(r) == 1 for r, p in zip(runs, SCENES[name]) if p == 0)


@pytest.mark.parametrize("bs", [64, 16, 48])
def test_a_run_without_a_selected_row_is_not_fetched(bs):
    """A selection that lies in a tenth of the pages: ``runs`` counts the
    runs that hold a selected row — far fewer than the context's — and the
    others are NOT fetched: NaN planted in every row (k and v) of every run
    that holds no selected row, and of every page no table names, does not
    reach ``o``."""
    q, kp, vp, tables, bias, pos = _scene([2303, 1500, 700], bs, seed=bs,
                                          only_pages=0.1)
    run = pk.sparse_run(bs)
    held = (np.asarray(bias) == 0.0).reshape(3, -1, run).any(-1)
    keep = np.zeros((kp.shape[0] * bs // run,), bool)
    first = np.asarray(tables)[:, :, None] * bs + np.arange(0, bs, run)
    keep[first.reshape(3, -1)[held] // run] = True
    nan = np.where(np.repeat(keep, run), 0.0, np.nan).reshape(
        kp.shape[0], bs, 1, 1).astype(np.float32)
    got, runs = _read((q, kp + nan, vp + nan, tables, bias, pos), "kernel")
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, _plain(q, kp, vp, tables, bias),
                               atol=1e-5)
    assert (np.asarray(runs) == held.sum(-1)).all()
    context = (np.asarray(pos) // run + 1)
    # whole pages are left out: most of the longest slot's
    assert (np.asarray(runs) < context).all() and 4 * int(runs[0]) < context[0]


@pytest.mark.parametrize("bs", [64, 16])
def test_unselected_rows_of_a_fetched_run_do_not_reach_o(bs):
    """A fetched run brings its unselected rows along: their keys may be
    anything (NaN planted: a masked score), their values meet a weight of
    exactly 0 (garbage of 1e30 planted; a pool's rows are finite, as for
    the dense paged read's rows past ``pos``)."""
    q, kp, vp, tables, bias, pos = _scene([2303, 40, 1029], bs, seed=7)
    hit = np.zeros((kp.shape[0] * bs,), bool)
    at = np.nonzero(np.asarray(bias) == 0.0)
    hit[np.asarray(tables)[at[0], at[1] // bs] * bs + at[1] % bs] = True
    hit = hit.reshape(kp.shape[0], bs, 1, 1)
    bad_k = jnp.where(hit, kp, jnp.nan)
    bad_v = jnp.where(hit, vp, 1e30)
    got, _ = _read((q, bad_k, bad_v, tables, bias, pos), "kernel")
    np.testing.assert_allclose(got, _plain(q, kp, vp, tables, bias),
                               atol=1e-5)


def test_bfloat16_pools_take_the_hi_lo_products():
    """The serving pool's dtype: the rows go to the MXU as they are and q
    and the weights as hi + lo halves (~16 bits): 2e-4 of the dense route,
    which multiplies the same rows in float32."""
    scene = _scene(SCENES["short-and-long"], 64, seed=5, dtype=jnp.bfloat16)
    got, runs = _read(scene, "kernel")
    np.testing.assert_allclose(got, _read(scene, "dense")[0], atol=2e-4)
    assert (np.asarray(runs) == _runs(scene[4], 64)).all()


def test_a_mask_that_does_not_fit_the_tables_is_refused():
    q, kp, vp, tables, bias, pos = _scene([100], 64)
    with pytest.raises(ValueError, match="mask of"):
        pk.sparse_decode_attention(q, kp, vp, tables[:, :-1], bias, pos)


# -- what the program says it fetched ------------------------------------------

@pytest.fixture(scope="module")
def served():
    """One engine run of the small KeyeSparseLM (pages of 8: a run is the
    page) under an obs session, every context past ``topk``."""
    model, params, _ = test_keye_vl2.build()
    rs = np.random.RandomState(4)
    reg = obs.MetricsRegistry()
    with obs.ObsSession(registry=reg).installed() as s:
        eng = ServingEngine(model, params, slots=4, segment=8, page_block=8,
                            cache_bucket=128, prompt_buckets=(32, 64),
                            prefix_cache=False)
        rids = [eng.submit(rs.randint(0, 90, n), 12) for n in (40, 57, 33)]
        for _ in range(40):
            if all(eng.poll(r)[1] for r in rids):
                break
            eng.step()
        events = s.tracer.snapshot()
    spans = [e["args"] for e in events
             if e["name"] == "serving.segment" and e.get("kind") == "span"]
    return model, spans, reg.collect()


def test_the_segment_span_carries_rows_and_descriptors(served):
    """``read_descriptors`` = 2 a fetched run, ``rows_fetched`` = its rows:
    a run a selected row at least (``keys_selected`` rows in runs of 8) and
    a page of the context at most; the counters total the spans."""
    model, spans, metrics = served
    assert spans and all(a["sparse_steps"] > 0 for a in spans)
    run = pk.sparse_run(8)
    for a in spans:
        runs = a["rows_fetched"] // run
        assert a["rows_fetched"] == runs * run
        assert a["read_descriptors"] == 2 * runs
        assert a["keys_selected"] / run <= runs <= a["keys_selected"]
        assert a["rows_fetched"] <= a["keys_scored"] + (run - 1) * len(
            model.blocks) * a["sparse_steps"]
    total = {m["name"]: m["value"] for m in metrics
             if m["labels"].get("program") == "segment"}
    assert total["sparse.rows_fetched_total"] \
        == sum(a["rows_fetched"] for a in spans)
    assert total["sparse.read_descriptors_total"] \
        == sum(a["read_descriptors"] for a in spans)


def test_descriptors_are_two_a_run_of_the_live_slots(monkeypatch):
    """The account, against a read that reports 3 runs a slot whatever it
    fetched: 2 x 3 descriptors and 3 runs' rows for every LIVE slot-step
    through the selection and every layer — an idle slot's read is not
    counted."""
    real = pk.sparse_decode_attention

    def three(*a, **kw):
        o, runs = real(*a, **kw)
        return o, jnp.full_like(runs, 3)
    monkeypatch.setattr(pk, "sparse_decode_attention", three)
    model, params, _ = test_keye_vl2.build()
    from paddle_tpu.serving.paged import PagePool
    pool = PagePool(model, params, **test_keye_vl2.POOL)
    prompts = test_keye_vl2._prompts([40, 57])          # two of four slots
    pool.admit([(i, pool.plan_admission(p, 8))
                for i, p in enumerate(prompts)])
    _, _, stats = test_keye_vl2._served_logits(model, params, pool, 5)
    layers, steps = len(model.blocks), int(stats["sparse_steps"])
    assert steps == 2 * 5
    assert [int(n) for n in stats["read"]] == [3 * 8 * steps * layers,
                                               2 * 3 * steps * layers]
