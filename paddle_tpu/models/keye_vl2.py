"""Keye-VL-2.0's language model as a served stack: a pre-norm block of
grouped-query attention that SELECTS what it reads, and a routed-expert
layer with a softmax router and no shared expert (parallel/expert_share.py,
``score="softmax"``). The sixth model class behind ``serve --config``, a
``PagedLM`` (models/paged_lm.py).

The selection is DeepSeek Sparse Attention's lightning indexer. Beside q, k
and v a layer projects, from the same normed input, ``index_heads`` small
queries ``qI`` and ONE small key ``kI`` of ``index_dim``, and a weight a
head ``w``; key s scores ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] .
kI[s])`` for query t, the ``index_topk`` best keys ``s <= t`` are kept
(every key while there are no more than that; ties to the lower index),
and the softmax of the layer's 32 heads runs over those keys alone — one
set a query token, the same for every head. ``kI`` is LayerNormed (unit
gain, no bias) and ``qI``, ``kI`` carry the layer's half-split RoPE over
their own width.

So there is a THIRD kind of cached row (``cache_rows``): beside a layer's
``k{i}`` / ``v{i}`` pages the pool holds ``ik{i}``, the indexer's key — a
row that exists only to be scored. It is stated ``index_dim`` wide and
HELD 128 wide (``CacheRow.held``): the chip keeps a 64-wide row in 128
lanes anyway, and only a page of whole lanes can be fetched by a DMA.

A decode step, a layer: write k, v, kI at ``pos``; while every slot's
context is within ``index_topk`` the read is pk.paged_decode_attention as
it stands (the selection would keep every key). Past it: score the slot's
``pos + 1`` indexer keys (pk.index_scores_paged), select
(pk.select_topk: exact, no sort) and read the selected rows of k and v
under that mask (pk.sparse_decode_attention): the read fetches a page's
aligned RUN of rows (pk.sparse_run: the whole page, in the serving pool)
with one descriptor where the run holds a selected row and skips it where
it holds none, and the softmax is over the selected rows alone — what the
value read streams follows the pages the selection touches, not the
context. The program counts what it fetched (``read``:
``program_stats_zero``).

An admission runs ONE row at a time and, inside the row, a BLOCK of
``block_tokens`` positions at a time through the whole depth
(``_sequence``): a block's keys go into the row's buffers, its queries
score the keys so far (pk.index_scores), select, and attend under the
selection (pk.selected_flash_attention: dense flash tiles under the
per-query mask). What a row expands — the float32 residual, q, the
experts' gathers, the [block, keys] scores — is a block's, whatever the
row's length, so a 32,768-token prompt is admitted by the program an
8,192-token one is; and the walk stops at the row's last block, so a row
pays for its own length, not for its bucket's.

Precision: parameters and pages in ``dtype`` (bfloat16 as published), every
product with operands in that dtype and float32 accumulation; the residual
stream, the norms, RoPE, the softmax, the indexer's relu and sum, the
selection and the router in float32.

``mrope_section`` (the published rotary term gives frequency pairs to a
temporal, a height and a width position id) is served as 1-D RoPE: a text
token's three ids are equal, and the vision tower is not part of this
class.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.initializer import normal
from ..ops import pallas_kernels as pk
from ..parallel.expert_share import ExpertShare, ffn_or_experts
from .paged_lm import CacheRow, PagedLM, _dot

#: the lane width the indexer's row is held at (module docstring)
LANES = 128


class GroupedAttention(nn.Module):
    """``n_heads`` query heads over ``kv_heads`` key/value heads of
    ``d_head``, q and k RMS-normed per head, half-split RoPE on both.
    ``w_qkv`` holds the published q, k and v projections side by side."""

    def __init__(self, d_model, n_heads, kv_heads, d_head, *, inv_freq, eps,
                 dtype, init_std):
        super().__init__()
        if n_heads % kv_heads:
            raise ValueError(f"{n_heads} query heads are not whole groups "
                             f"over {kv_heads} KV heads")
        self.n_heads, self.kv_heads, self.d_head = n_heads, kv_heads, d_head
        self.inv_freq, self.scale = inv_freq, d_head ** -0.5
        init = normal(0.0, init_std)
        self.param("w_qkv", (d_model, (n_heads + 2 * kv_heads) * d_head),
                   init, dtype=dtype)
        self.q_norm = nn.RMSNorm(d_head, eps, dtype=dtype)
        self.k_norm = nn.RMSNorm(d_head, eps, dtype=dtype)
        self.param("w_o", (n_heads * d_head, d_model), init, dtype=dtype)

    def project(self, params, x, positions):
        """x [..., d] (normed) at ``positions`` [...] -> (q [..., H, D] f32,
        k [..., Hkv, D], v [..., Hkv, D]; k and v in the cache dtype)."""
        dt = params["w_qkv"].dtype
        H, K, D = self.n_heads, self.kv_heads, self.d_head
        y = _dot(x, params["w_qkv"])
        lead = x.shape[:-1]
        q = self.q_norm(params["q_norm"], y[..., :H * D].reshape(
            lead + (H, D)))
        k = self.k_norm(params["k_norm"], y[..., H * D:(H + K) * D].reshape(
            lead + (K, D)))
        v = y[..., (H + K) * D:].reshape(lead + (K, D))
        q = nn.apply_rope(q, positions, self.inv_freq, layout="half")
        k = nn.apply_rope(k, positions, self.inv_freq, layout="half")
        return q, k.astype(dt), v.astype(dt)

    def output(self, params, o):
        """o [..., H, D] -> [..., d]."""
        return _dot(o.reshape(o.shape[:-2] + (-1,)), params["w_o"])


class LightningIndexer(nn.Module):
    """``heads`` queries and ONE key of ``dim`` a token, and a weight a
    head. ``w_idx`` holds the three projections side by side: qI (heads x
    dim), kI (dim), w (heads)."""

    def __init__(self, d_model, heads, dim, *, inv_freq, eps, dtype,
                 init_std):
        super().__init__()
        self.heads, self.dim, self.inv_freq, self.eps = heads, dim, \
            inv_freq, eps
        self.param("w_idx", (d_model, heads * dim + dim + heads),
                   normal(0.0, init_std), dtype=dtype)
        self.k_norm = nn.RMSNorm(dim, eps, dtype=dtype)   # its gain alone

    def project(self, params, x, positions):
        """x [..., d] (normed) at ``positions`` [...] -> (qI [..., heads,
        dim] and kI [..., dim] in the cache dtype, w [..., heads] f32)."""
        dt = params["w_idx"].dtype
        Hi, Di = self.heads, self.dim
        y = _dot(x, params["w_idx"])
        qi = y[..., :Hi * Di].reshape(x.shape[:-1] + (Hi, Di))
        ki = y[..., Hi * Di:Hi * Di + Di]
        # LayerNorm, unit gain and no bias at initialisation
        mu = jnp.mean(ki, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(ki - mu), axis=-1, keepdims=True)
        ki = (ki - mu) * jax.lax.rsqrt(var + self.eps) \
            * params["k_norm"]["gamma"].astype(jnp.float32)
        qi = nn.apply_rope(qi, positions, self.inv_freq, layout="half")
        ki = nn.apply_rope(ki, positions, self.inv_freq, layout="half")
        return qi.astype(dt), ki.astype(dt), y[..., Hi * Di + Di:]


class KeyeBlock(nn.Module):
    """One layer: ``h += attn(norm(h))`` under the indexer's selection;
    ``h += experts(norm(h))``."""

    is_moe = True

    def __init__(self, d_model, *, attn_kw, index_kw, moe_kw, eps, dtype,
                 init_std):
        super().__init__()
        kw = dict(eps=eps, dtype=dtype, init_std=init_std)
        self.input_norm = nn.RMSNorm(d_model, eps, dtype=dtype)
        self.attn = GroupedAttention(d_model, **attn_kw, **kw)
        self.idx = LightningIndexer(d_model, **index_kw, **kw)
        self.ffn_norm = nn.RMSNorm(d_model, eps, dtype=dtype)
        self.moe = ExpertShare(d_model, dtype=dtype, init_std=init_std,
                               **moe_kw)


class KeyeSparseLM(PagedLM):
    """``vocab`` rows of embedding and of an untied head, ``n_layers``
    blocks, each an expert layer over ``experts_held`` of ``n_experts``."""

    #: rows of up to 32,768 positions, three arrays a layer: no [slots,
    #: prompt bucket] copy of them beside the pools
    admits_in_place = True
    #: ``paged_read_kernel`` is the read's cost model while a context is
    #: within ``index_topk``; past it the model's own counters speak
    #: (``note_program_stats``), so the pool counts no page walked
    paged_read_layers = 0

    def __init__(self, vocab: int, *, d_model: int, n_heads: int,
                 kv_heads: int, d_head: int, n_layers: int,
                 expert_width: int, n_experts: int,
                 experts_held: Optional[Sequence[int]] = None,
                 top_k: int = 8, index_heads: int = 16, index_dim: int = 64,
                 index_topk: int = 2048, rope_theta: float = 1e7,
                 eps: float = 1e-6, max_len: int = 4096,
                 block_tokens: int = 2048, dtype=jnp.bfloat16,
                 init_std: float = 0.02):
        super().__init__()
        self.vocab, self.max_len, self.dtype = vocab, max_len, dtype
        held = list(range(n_experts)) if experts_held is None \
            else list(experts_held)
        self.n_heads, self.kv_heads, self.d_head = n_heads, kv_heads, d_head
        self.d_model, self.block_tokens = d_model, block_tokens
        self.index_heads, self.index_dim = index_heads, index_dim
        self.index_topk = index_topk
        self.n_moe, self.n_held, self.top_k = n_layers, len(held), top_k
        self.embed = nn.Embedding(vocab, d_model, dtype=dtype,
                                  w_init=normal(0.0, init_std))
        # every layer is ONE constructor call repeated: ``_decode_layer``
        # (below) computes any layer with ``blocks[0]``'s modules and that
        # layer's parameters, which holds because no argument differs
        layer = dict(
            eps=eps, dtype=dtype, init_std=init_std,
            attn_kw=dict(n_heads=n_heads, kv_heads=kv_heads, d_head=d_head,
                         inv_freq=nn.yarn_inv_freq(d_head, rope_theta)),
            index_kw=dict(heads=index_heads, dim=index_dim,
                          inv_freq=nn.yarn_inv_freq(index_dim, rope_theta)),
            moe_kw=dict(d_expert=expert_width, n_experts=n_experts,
                        experts_held=held, top_k=top_k, n_group=1,
                        topk_group=1, routed_scale=1.0, norm_eps=0.0,
                        shared=False, score="softmax", bias=False))
        self.blocks = [KeyeBlock(d_model, **layer) for _ in range(n_layers)]
        self.norm_f = nn.RMSNorm(d_model, eps, dtype=dtype)
        self.head = nn.Embedding(vocab, d_model, dtype=dtype,
                                 w_init=normal(0.0, init_std))
        self._layer = jax.jit(self._decode_layer_impl,
                              static_argnames=("attn_route",))

    # -- what the page pool asks -------------------------------------------
    def cache_rows(self, params, kv_dtype: Optional[str] = None):
        """THREE rows a layer: ``k{i}`` / ``v{i}`` of ``kv_heads`` heads and
        ``ik{i}``, the indexer's key, stated ``index_dim`` wide and held at
        the lane width (module docstring)."""
        self._no_kv_dtype(kv_dtype)
        dt = self._compute_dtype(params)
        rows = []
        for i in range(len(self.blocks)):
            rows += [CacheRow(f"{n}{i}", (self.kv_heads, self.d_head), dt)
                     for n in "kv"]
            rows.append(CacheRow(
                f"ik{i}", (self.index_dim,), dt,
                held=(-(-self.index_dim // LANES) * LANES,)))
        return rows

    def prefill_chunk_tokens(self, width: int) -> int:
        """ONE row a chunk, whatever its width: the row walks its own
        blocks (``_sequence``)."""
        return width

    def admitted_positions(self, lengths, width: int) -> int:
        """Positions an admission of rows of ``lengths`` runs through the
        depth: each row's own blocks, not its bucket."""
        q = min(self.block_tokens, width)
        return int(sum(-(-int(n) // q) * q for n in lengths if n > 0))

    # -- what a program returns beside its tokens ---------------------------
    def program_stats_zero(self):
        """ProgramStats' tree (the expert layers') and the selection's
        account. ``selected`` [layers]: the keys the selected reads took,
        as pk.select_topk COUNTED them on the device (a decode step's live
        slots; an admission's real queries). ``scored``: the indexer keys
        one layer scored for a decode step's live slots. ``dense_rows``:
        the rows one layer read through the dense kernel, in the steps
        whose contexts were all within ``index_topk``. ``sparse_steps`` /
        ``dense_steps``: the live slot-steps that read through the
        selection / through the dense kernel. ``read`` [2]: what the live
        slots' selected reads FETCHED, all layers — rows of k (as many of
        v), and descriptors (one a run of k, one of v:
        pk.sparse_decode_attention's ``runs``). ``pairs_causal``: the
        (query, key) pairs of an admission's causal triangles, one layer's
        (float32: a share's denominator, not an account)."""
        zero = jnp.zeros((), jnp.int32)
        return dict(super().program_stats_zero(),
                    selected=jnp.zeros((len(self.blocks),), jnp.int32),
                    scored=zero, dense_rows=zero, sparse_steps=zero,
                    dense_steps=zero, read=jnp.zeros((2,), jnp.int32),
                    pairs_causal=jnp.zeros((), jnp.float32))

    def note_program_stats(self, stats, program: str):
        from .. import obs
        attrs = super().note_program_stats(stats, program)
        layers = len(self.blocks)
        selected = int(stats["selected"].sum())
        # what each kernel's cost model is told: the work, and the row it
        # is done on
        kv = dict(kv_heads=self.kv_heads, d_head=self.d_head,
                  itemsize=jnp.dtype(self.dtype).itemsize)
        if program == "segment":
            scored = int(stats["scored"]) * layers
            dense = int(stats["dense_rows"]) * layers
            rows_fetched, descriptors = (int(n) for n in stats["read"])
            attrs.update(keys_scored=scored, keys_selected=selected + dense,
                         dense_rows=dense,
                         sparse_steps=int(stats["sparse_steps"]),
                         dense_steps=int(stats["dense_steps"]),
                         rows_fetched=rows_fetched,
                         read_descriptors=descriptors)
            obs.count("sparse.rows_fetched_total", rows_fetched,
                      program=program)
            obs.count("sparse.read_descriptors_total", descriptors,
                      program=program)
            work = {"index_scores_paged": dict(
                        keys=scored, index_dim=self.index_dim,
                        itemsize=kv["itemsize"]),
                    "select_topk": dict(keys=scored),
                    "sparse_decode_attention": dict(rows=selected, **kv),
                    "paged_decode_attention": dict(
                        pages=dense, page_block=1, n_heads=self.n_heads,
                        **kv)}
        else:
            scored = int(float(stats["pairs_causal"]) * layers)
            attrs.update(pairs_selected=selected, pairs_causal=scored)
            work = {"index_scores": dict(pairs=scored),
                    "select_topk": dict(keys=scored),
                    "selected_flash_attention": dict(
                        pairs=scored, kv_heads=self.kv_heads)}
        obs.count("sparse.keys_scored_total", scored, program=program)
        obs.count("sparse.keys_selected_total", selected, program=program)
        for kernel, kw in work.items():
            obs.count("kernels.bytes_total",
                      obs.roofline.kernel_cost(kernel, **kw) or 0.0,
                      kernel=kernel)
        return attrs

    # -- whole sequences ---------------------------------------------------
    def _block_step(self, blk, p, h, q0, bufs, live):
        """One layer over a block of queries: h [Q, d] f32 at positions
        ``q0 ..``; ``bufs`` = the row's (k [L, Hkv, D], v, kI [L, Di]) so
        far -> (h, bufs with the block's rows written, the experts'
        counts, the keys selected for the ``live`` [Q] queries)."""
        Q = h.shape[0]
        pos = q0 + jnp.arange(Q, dtype=jnp.int32)
        x = blk.input_norm(p["input_norm"], h)
        q, k, v = blk.attn.project(p["attn"], x, pos)
        qi, ki, w = blk.idx.project(p["idx"], x, pos)
        kb, vb, ib = (jax.lax.dynamic_update_slice(
            buf, new, (q0,) + (0,) * (new.ndim - 1))
            for buf, new in zip(bufs, (k, v, ki)))
        scores = pk.index_scores(jnp.moveaxis(qi, 1, 0), w, ib, q0)
        bias, cnt = pk.select_topk(scores, pos + 1, self.index_topk)
        o = pk.selected_flash_attention(q.astype(kb.dtype), kb, vb, bias, q0,
                                        scale=blk.attn.scale)
        h = h + blk.attn.output(p["attn"], o)
        h, counts = ffn_or_experts(blk, p, h, live)
        return h, (kb, vb, ib), counts, \
            jnp.sum(jnp.where(live, cnt, 0), dtype=jnp.int32)

    def _sequence(self, params, ids, lengths):
        """ids [1, T] (T whole blocks of ``min(block_tokens, T)``), lengths
        [1] or None -> (the hidden state at the row's last position [1, d]
        f32 — or, ``lengths`` None, every position's [1, T, d] —, state:
        ``k{i}`` / ``v{i}`` / ``ik{i}`` [1, T, ...] of every layer, stats).
        The row's blocks run one after another through the whole depth,
        for as long as they hold a token of the row."""
        R, T = ids.shape
        if R != 1:
            raise ValueError(f"a chunk of {R} rows: KeyeSparseLM admits one "
                             "row at a time (prefill_chunk_tokens)")
        Q = min(self.block_tokens, T)
        if T % Q:
            raise ValueError(f"a row of {T} positions is not whole blocks "
                             f"of {Q}")
        n = jnp.int32(T) if lengths is None \
            else jnp.asarray(lengths, jnp.int32)[0]
        dt = self._compute_dtype(params)
        layers = range(len(self.blocks))
        bufs0 = [(jnp.zeros((T, self.kv_heads, self.d_head), dt),) * 2
                 + (jnp.zeros((T, self.index_dim), dt),) for _ in layers]
        whole = lengths is None
        last0 = jnp.zeros((T if whole else 1, self.d_model), jnp.float32)

        def block(carry):
            j, last, bufs, stats = carry
            q0 = j * Q
            live = q0 + jnp.arange(Q, dtype=jnp.int32) < n
            h = self.embed(params["embed"], jax.lax.dynamic_slice(
                ids[0], (q0,), (Q,))).astype(jnp.float32)
            new, counts, selected = [], [], []
            for i, blk in enumerate(self.blocks):
                h, b, c, s = self._block_step(blk, params[f"blocks_{i}"], h,
                                              q0, bufs[i], live)
                new.append(b), counts.append(c), selected.append(s)
            if whole:
                last = jax.lax.dynamic_update_slice(last, h, (q0, 0))
            else:
                at = jnp.clip(n - 1 - q0, 0, Q - 1)
                last = jnp.where((n - 1 >= q0) & (n - 1 < q0 + Q),
                                 jax.lax.dynamic_slice(h, (at, 0),
                                                       (1, h.shape[1])), last)
            hi = jnp.minimum(n, q0 + Q).astype(jnp.float32)
            lo = q0.astype(jnp.float32)
            stats = self._add_stats(
                stats, counts, live, Q, selected=jnp.stack(selected),
                pairs_causal=(hi * (hi + 1) - lo * (lo + 1)) / 2)
            return j + 1, last, new, stats
        _, last, bufs, stats = jax.lax.while_loop(
            lambda c: c[0] * Q < n, block,
            (jnp.int32(0), last0, bufs0, self.program_stats_zero()))
        state = {}
        for i, (kb, vb, ib) in enumerate(bufs):
            state[f"k{i}"], state[f"v{i}"], state[f"ik{i}"] = \
                kb[None], vb[None], ib[None]
        return last[None] if whole else last, state, stats

    def _blocks_of(self, width: int):
        """(block, padded width) of a row ``width`` wide."""
        q = min(self.block_tokens, -(-width // 8) * 8)
        return q, -(-width // q) * q

    def __call__(self, params, ids, **kw):
        """ids [B, T] -> logits [B, T, V] f32, a row at a time."""
        T = ids.shape[1]
        ids = jnp.pad(ids, ((0, 0), (0, self._blocks_of(T)[1] - T)))
        return jnp.stack([
            self.logits(params, self._sequence(params, row[None], None)[0]
                        [0, :T]) for row in ids])

    # -- one token against the paged cache ---------------------------------
    def _decode_layer_impl(self, p, h, kc, vc, ic, rd, past, live, alive, *,
                           attn_route):
        """One layer of a decode step: the step's rows written into the
        layer's three pools, the read, the experts -> (h, the pools, the
        experts' counts, the keys the live slots' reads took, the runs
        their selected reads fetched).
        A step calls this as ONE jitted function (``_layer``) a layer, so a
        program traces and lowers the layer — its ``cond``, both reads,
        four kernels — once and not once a layer; XLA inlines the calls
        and compiles what it compiled. ``blocks[0]``'s modules stand for
        any layer's: ``__init__`` builds every block from the same
        arguments, and ``p`` is the layer's own parameters. A model whose
        layers differ would hand the layer's kind over as a static
        argument. (Why this model alone: PERF.md section 6, PR 43.)"""
        blk = self.blocks[0]
        pos, tables = rd.pos, rd.tables
        x = blk.input_norm(p["input_norm"], h)
        q, k, v = blk.attn.project(p["attn"], x, pos)
        qi, ki, w = blk.idx.project(p["idx"], x, pos)
        kp, k_rows = rd.put(kc, k)
        vp, v_rows = rd.put(vc, v)
        ip, _ = rd.put(ic, ki)

        def dense(_):
            return rd.attend(q, k_rows, v_rows, scale=blk.attn.scale,
                             route=attn_route), pos + 1, jnp.zeros_like(pos)

        def sparse(_):
            bias, cnt = pk.select_topk(
                pk.index_scores_paged(qi, w, ip, tables, pos), pos + 1,
                self.index_topk)
            o, runs = pk.sparse_decode_attention(
                q, k_rows, v_rows, tables, bias, pos, scale=blk.attn.scale)
            return o, cnt, runs
        o, cnt, runs = jax.lax.cond(past, sparse, dense, None)
        h = h + blk.attn.output(p["attn"], o)
        h, c = ffn_or_experts(blk, p, h, live)
        return (h, kp, vp, ip, c) + tuple(
            jnp.sum(jnp.where(alive, n, 0), dtype=jnp.int32)
            for n in (cnt, runs))

    def _step_extra(self, pos, live):
        """(whether the longest context is past ``index_topk``: one
        ``cond`` a step, so every layer reads the same way; the slots that
        count)."""
        return (jnp.max(pos) >= self.index_topk,
                jnp.ones(pos.shape, bool) if live is None else live)

    def _decode_layer(self, i, blk, p, h, cell, step):
        """Every layer writes the step's k, v and kI at ``tables[b, pos //
        bs]`` and reads as the module docstring says: the dense paged read
        while EVERY slot's context is within ``index_topk``, else scores ->
        selection -> the selected rows."""
        past, alive = step.extra
        h, kp, vp, ip, c, cnt, runs = self._layer(
            p, h, cell[f"k{i}"], cell[f"v{i}"], cell[f"ik{i}"], step.full,
            past, step.live, alive, attn_route=step.attn_route)
        return h, {f"k{i}": kp, f"v{i}": vp, f"ik{i}": ip}, c, (cnt, runs)

    def _step_stats(self, step, notes):
        past, alive = step.extra
        pos, bs = step.full.pos, step.page_block
        selected, runs = zip(*notes)
        steps = jnp.sum(alive, dtype=jnp.int32)
        keys = jnp.sum(jnp.where(alive, pos + 1, 0), dtype=jnp.int32)
        return dict(
            selected=jnp.where(past, jnp.stack(selected), 0),
            read=sum(runs) * jnp.array([pk.sparse_run(bs), 2], jnp.int32),
            scored=jnp.where(past, keys, 0),
            dense_rows=jnp.where(past, 0, keys),
            sparse_steps=jnp.where(past, steps, 0),
            dense_steps=jnp.where(past, 0, steps))
