"""The contract between the page pool and a served model, held where it is
written down (``paddle_tpu/models/paged_lm.py`` ``PagedLM``) — and the price
of a new model: ``ToyLM`` below is everything a seventh class has to write
(blocks, ``cache_rows``, ``_sequence``, ``_decode_layer``) to be served
through ``PagePool`` with ``generate_cached``'s tokens.
"""

import inspect
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import nn
from paddle_tpu.models.paged_lm import CacheRow, PagedLM, SlotRow
from paddle_tpu.nn.initializer import normal
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.serving import paged
from paddle_tpu.serving.batcher import Request

from test_segment_steps import MODELS


@pytest.fixture(scope="module", params=list(MODELS))
def served(request):
    return MODELS[request.param]()


def _pool_reads():
    """Every attribute ``serving/paged.py`` reads off its model."""
    return set(re.findall(r"\bmodel\.([A-Za-z_]\w*)",
                          inspect.getsource(paged)))


def test_the_pool_probes_for_nothing():
    src = inspect.getsource(paged)
    assert not re.findall(r"(?:getattr|hasattr)\(\s*(?:self\.)?model\b", src)
    assert {"cache_rows", "prefill", "decode_step_paged", "prefill_paged",
            "admits_in_place", "slot_rows_in_place", "paged_read_layers",
            "decode_compiler_options", "admitted_positions",
            "program_stats_zero"} <= _pool_reads()


def test_every_attribute_the_pool_reads_is_declared_on_the_base(served):
    """... on ``PagedLM`` itself — as a default, an abstract method or (what
    a class's ``__init__`` holds: ``max_len``, ``blocks``) an annotation —
    and a served model answers each by plain attribute access."""
    model, _ = served
    assert isinstance(model, PagedLM)
    declared = set(dir(PagedLM)) | set(PagedLM.__annotations__)
    assert _pool_reads() <= declared, _pool_reads() - declared
    for name in _pool_reads():
        getattr(model, name)


def test_prefill_has_one_signature(served):
    def shape(fn):
        return [(p.name, p.kind, p.default)
                for p in inspect.signature(fn).parameters.values()]
    model, _ = served
    assert shape(type(model).prefill) == shape(PagedLM.prefill)


def test_prefills_cell_holds_the_stated_rows(served):
    """``pos``, ``stats`` (the base's ``prefill``) and exactly the names of
    ``cache_rows``: a ``CacheRow`` ``[B, max_len, *shape]``, a ``SlotRow``
    ``[B, *shape]``, each in its stated dtype."""
    model, params = served
    prompt = jnp.asarray(np.random.RandomState(0).randint(0, 60, (2, 16)))
    cell, last = model.prefill(params, prompt)
    rows = model.cache_rows(params)
    own = type(model).prefill is not PagedLM.prefill     # TransformerLM's
    assert set(cell) == {r.name for r in rows} | {"pos"} | (
        set() if own else {"stats"})
    assert last.shape[0] == 2 and cell["pos"].tolist() == [16, 16]
    for r in rows:
        lead = (2,) if isinstance(r, SlotRow) else (2, model.max_len)
        assert cell[r.name].shape == lead + tuple(r.shape), r.name
        assert cell[r.name].dtype == r.dtype, r.name


# -- a seventh model, whole ----------------------------------------------------

class ToyBlock(nn.Module):
    def __init__(self, d, kind):
        super().__init__()
        self.kind = kind
        self.norm = nn.RMSNorm(d, 1e-5)
        if kind == "conv":
            self.conv = nn.ShortConv(d, 2, w_init=normal(0.0, 0.3))
        else:
            self.param("w_qkv", (d, 3 * d), normal(0.0, 0.3))


class ToyLM(PagedLM):
    """Two kinds of layer: a short convolution whose tail lives PER SLOT,
    and two-head attention over pages; the head tied to the embedding."""
    n_heads = kv_heads = 2

    def __init__(self, vocab=61, d=16, kinds=("conv", "attn") * 2,
                 max_len=64):
        super().__init__()
        self.max_len, self.d, self.d_head = max_len, d, d // 2
        self.embed = nn.Embedding(vocab, d, w_init=normal(0.0, 1.0))
        self.blocks = [ToyBlock(d, k) for k in kinds]
        self.norm_f = nn.RMSNorm(d, 1e-5)

    def cache_rows(self, params, kv_dtype=None):
        self._no_kv_dtype(kv_dtype)
        dt = self._compute_dtype(params)
        return [row for i, b in enumerate(self.blocks) for row in (
            [SlotRow(f"conv{i}", (1, self.d), dt)] if b.kind == "conv" else
            [CacheRow(f"{n}{i}", (2, self.d_head), dt) for n in "kv"])]

    def _qkv(self, p, x):
        return [t.reshape(x.shape[:-1] + (2, self.d_head))
                for t in jnp.split(x @ p["w_qkv"], 3, axis=-1)]

    def _sequence(self, params, ids, lengths):
        h, state = self._embed(params, ids), {}
        for i, blk in enumerate(self.blocks):
            p = params[f"blocks_{i}"]
            x = blk.norm(p["norm"], h)
            if blk.kind == "conv":
                y, state[f"conv{i}"] = blk.conv(p["conv"], x, None, lengths)
            else:
                q, k, v = self._qkv(p, x)
                y = pk.flash_attention(q, k, v, causal=True).reshape(h.shape)
                state[f"k{i}"], state[f"v{i}"] = k, v
            h = h + y
        return h, state, {}

    def _decode_layer(self, i, blk, p, h, cell, step):
        x = blk.norm(p["norm"], h)
        if blk.kind == "conv":
            y, tail = blk.conv.step(p["conv"], x, cell[f"conv{i}"])
            return h + y, {f"conv{i}": tail}, None
        q, k, v = self._qkv(p, x)
        o, kp, vp = step.full.write_and_attend(
            q, k, v, cell[f"k{i}"], cell[f"v{i}"], scale=self.d_head ** -0.5)
        return h + o.reshape(h.shape), {f"k{i}": kp, f"v{i}": vp}, None


def test_a_seventh_model_is_served_with_its_solo_decodes_tokens():
    model = ToyLM()
    params = model.init(jax.random.PRNGKey(7))
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, 61, n).astype(np.int32) for n in (5, 13, 9, 16)]
    budgets = (7, 3, 12, 5)
    batcher = paged.PagedBatcher(model, params, slots=2, segment=4,
                                 page_block=8, cache_bucket=32,
                                 prompt_buckets=(16,))
    got = batcher.serve([Request(i, p, n) for i, (p, n)
                         in enumerate(zip(prompts, budgets))])
    for i, (p, n) in enumerate(zip(prompts, budgets)):
        solo = model.generate_cached(params, jnp.asarray(p)[None], n,
                                     page_block=8)
        np.testing.assert_array_equal(got[i], np.asarray(solo)[0, p.size:])
    # per-slot rows went through the pool beside the pages, and the full
    # forward agrees with the admission
    assert set(batcher.pool.slot_state) == {"conv0", "conv2"}
    ids = jnp.asarray(prompts[3])[None]
    _, last = model.prefill(params, ids)
    np.testing.assert_allclose(np.asarray(model(params, ids))[:, -1],
                               np.asarray(last), rtol=1e-5, atol=1e-5)
