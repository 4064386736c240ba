"""LFM2's block (``lfm2_moe``) as a served language model: layers whose
operator is either a GATED SHORT CONVOLUTION (nn.ShortConv) or grouped-query
attention with per-head RMS norms on q and k and half-split RoPE, each
followed by a SwiGLU FFN — dense in the leading layers, a routed-expert
layer (parallel/expert_share.py: sigmoid scores, top-k of score + bias over
all experts, no groups, no shared expert) after them; RMSNorm throughout, a
final norm, the head tied to the embedding. The third model class behind
``serve --config``: it offers the paged pool the entry points
``TransformerLM`` and ``DeepseekV3LM`` offer.

Two kinds of state live side by side. An attention layer keeps keys and
values in PAGES, ``kv_heads`` heads a row (fewer than the query heads: a
KV head serves its group through ops/pallas_kernels.paged_decode_attention
and, in prefill, through the flash kernel's index map — no repeated copy).
A convolution layer keeps the last ``taps - 1`` rows of its gated input,
PER SLOT and fixed in size whatever the context (``SlotRow``): the pool
holds ``[slots, taps - 1, d_model]`` beside the pages, prefill returns each
row's tail at its own length, a decode step rolls it.

Precision: parameters, pages and slot state in ``dtype`` (bfloat16 as
published), every product with operands in that dtype and float32
accumulation; the residual stream, the norms, RoPE, the softmax, the
convolution's taps and the router's scores and top-k in float32.

A chip's stage of a pipeline is built by passing that stage's
``layer_types`` and ``n_dense``; a share of the experts by ``experts_held``.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.initializer import normal
from ..ops import pallas_kernels as pk
from ..parallel.expert_share import (ExpertShare, ProgramStats,
                                     ffn_or_experts)
from .transformer import (PREFILL_TOKENS, CacheRow, LiveRowPrefill, SlotRow,
                          paged_greedy, prefill_live_rows)


def _dot(x, w):
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


class GroupedQueryAttention(nn.Module):
    """``n_heads`` query heads over ``kv_heads`` key/value heads (query head
    h reads KV head ``h // (n_heads // kv_heads)``), q and k RMS-normed per
    head before half-split RoPE. ``w_qkv`` holds the published q, k and v
    projections side by side."""

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
        """x [..., d] (normed) at ``positions`` [...] -> (q [..., H, D] f32
        normed and rotated, k [..., Hkv, D] normed and rotated, v [..., Hkv,
        D]; k and v in the cache dtype)."""
        dt = params["w_qkv"].dtype
        H, K, D = self.n_heads, self.kv_heads, self.d_head
        qkv = _dot(x, params["w_qkv"])
        lead = x.shape[:-1]
        q = qkv[..., :H * D].reshape(lead + (H, D))
        k = qkv[..., H * D:(H + K) * D].reshape(lead + (K, D))
        v = qkv[..., (H + K) * D:].reshape(lead + (K, D))
        q = nn.apply_rope(self.q_norm(params["q_norm"], q), positions,
                          self.inv_freq, layout="half")
        k = nn.apply_rope(self.k_norm(params["k_norm"], k), positions,
                          self.inv_freq, layout="half")
        return q, k.astype(dt), v.astype(dt)


class Lfm2Block(nn.Module):
    """``h += operator(norm(h)); h += ffn(norm(h))`` with ``kind`` the
    operator: "conv" or "full_attention"."""

    def __init__(self, d_model, kind, *, attn_kw, taps, dense_width=None,
                 moe_kw=None, eps, dtype, init_std):
        super().__init__()
        self.kind = kind
        self.op_norm = nn.RMSNorm(d_model, eps, dtype=dtype)
        if kind == "conv":
            self.conv = nn.ShortConv(d_model, taps,
                                     w_init=normal(0.0, init_std),
                                     dtype=dtype)
        elif kind == "full_attention":
            self.attn = GroupedQueryAttention(d_model, eps=eps, dtype=dtype,
                                              init_std=init_std, **attn_kw)
        else:
            raise ValueError(f"unknown layer type {kind!r}")
        self.ffn_norm = nn.RMSNorm(d_model, eps, dtype=dtype)
        self.is_moe = moe_kw is not None
        if self.is_moe:
            self.moe = ExpertShare(d_model, dtype=dtype, init_std=init_std,
                                   **moe_kw)
        else:
            self.ffn = nn.SwiGLU(d_model, dense_width,
                                 w_init=normal(0.0, init_std), dtype=dtype)


class Lfm2MoeLM(ProgramStats, LiveRowPrefill, nn.Module):
    """``vocab`` rows of embedding (and tied head), one block per entry of
    ``layer_types``; the first ``n_dense`` carry the dense FFN, the rest
    the expert layer over ``experts_held`` of ``n_experts``."""

    def __init__(self, vocab: int, *, d_model: int, n_heads: int,
                 kv_heads: int, layer_types: Sequence[str], n_dense: int,
                 dense_width: int, expert_width: int, n_experts: int,
                 experts_held: Optional[Sequence[int]] = None,
                 top_k: int = 4, routed_scale: float = 1.0,
                 conv_taps: int = 3, d_head: Optional[int] = None,
                 rope_theta: float = 1e6, eps: float = 1e-5,
                 max_len: int = 4096, dtype=jnp.bfloat16,
                 init_std: float = 0.02):
        super().__init__()
        self.vocab, self.max_len, self.dtype = vocab, max_len, dtype
        d_head = d_head or d_model // n_heads
        attn_kw = dict(n_heads=n_heads, kv_heads=kv_heads, d_head=d_head,
                       inv_freq=nn.yarn_inv_freq(d_head, rope_theta))
        held = list(range(n_experts)) if experts_held is None \
            else list(experts_held)
        moe_kw = dict(d_expert=expert_width, n_experts=n_experts,
                      experts_held=held, top_k=top_k, n_group=1,
                      topk_group=1, routed_scale=routed_scale,
                      norm_eps=1e-6, n_shared=0)     # 1e-6: as published
        self.n_heads, self.kv_heads, self.d_head = n_heads, kv_heads, d_head
        self.d_model, self.taps = d_model, conv_taps
        self.n_moe, self.n_held = len(layer_types) - n_dense, len(held)
        self.top_k = top_k
        self.embed = nn.Embedding(vocab, d_model, dtype=dtype,
                                  w_init=normal(0.0, init_std))
        self.blocks = [
            Lfm2Block(d_model, kind, attn_kw=attn_kw, taps=conv_taps,
                      eps=eps, dtype=dtype, init_std=init_std,
                      **(dict(dense_width=dense_width) if i < n_dense
                         else dict(moe_kw=moe_kw)))
            for i, kind in enumerate(layer_types)]
        self.attn_layers = [i for i, b in enumerate(self.blocks)
                            if b.kind == "full_attention"]
        if not self.attn_layers:
            raise ValueError("the paged engine needs at least one attention "
                             "layer (its pages carry the positions)")
        self.norm_f = nn.RMSNorm(d_model, eps, dtype=dtype)

    # -- what the page pool asks -------------------------------------------
    def cache_rows(self, params, kv_dtype: Optional[str] = None):
        """Pages for the attention layers only — ``k{i}`` / ``v{i}`` rows of
        ``kv_heads`` heads — and a per-slot row ``conv{i}`` (the last
        ``taps - 1`` gated inputs) for every convolution layer."""
        self._no_kv_dtype(kv_dtype)
        dt = self._compute_dtype(params)
        rows = []
        for i, blk in enumerate(self.blocks):
            if blk.kind == "conv":
                rows.append(SlotRow(f"conv{i}",
                                    (self.taps - 1, self.d_model), dt))
            else:
                rows += [CacheRow(f"{n}{i}", (self.kv_heads, self.d_head),
                                  dt) for n in "kv"]
        return rows

    @staticmethod
    def _no_kv_dtype(kv_dtype):
        if kv_dtype is not None:
            raise ValueError(f"kv_dtype {kv_dtype!r}: pages and slot state "
                             "are kept in the parameters' dtype; there is "
                             "no quantised cache for this model")

    def prefill_chunk_tokens(self, width: int) -> int:
        return PREFILL_TOKENS

    #: the decode read's registered cost model (obs/roofline.kernel_cost)
    paged_read_kernel = "paged_decode_attention"

    @property
    def paged_read_layers(self):
        """Layers of a decode step that read the pages."""
        return len(self.attn_layers)

    def paged_read_geometry(self, params, kv_dtype=None):
        return {"n_heads": self.n_heads, "kv_heads": self.kv_heads,
                "d_head": self.d_head, "kv_dtype": None,
                "itemsize": jnp.dtype(self._compute_dtype(params)).itemsize}

    def _compute_dtype(self, params):
        return params["embed"]["w"].dtype

    # -- whole sequences ---------------------------------------------------
    def _sequence(self, params, ids, lengths):
        """ids [B, T] -> (h [B, T, d] f32, state: ``k{i}`` / ``v{i}`` [B,
        T, Hkv, D] and ``conv{i}`` [B, taps - 1, d] at each row's length,
        stats)."""
        B, T = ids.shape
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        live = None if lengths is None else \
            positions < jnp.asarray(lengths, jnp.int32)[:, None]
        h = self.embed(params["embed"], ids).astype(jnp.float32)
        state, counts = {}, []
        for i, blk in enumerate(self.blocks):
            p = params[f"blocks_{i}"]
            x = blk.op_norm(p["op_norm"], h)
            if blk.kind == "conv":
                y, state[f"conv{i}"] = blk.conv(p["conv"], x, None, lengths)
                h = h + y
            else:
                q, k, v = blk.attn.project(p["attn"], x, positions)
                o = pk.flash_attention(q.astype(k.dtype), k, v, causal=True,
                                       scale=blk.attn.scale)
                h = h + _dot(o.reshape(B, T, -1), p["attn"]["w_o"])
                state[f"k{i}"], state[f"v{i}"] = k, v
            h, c = ffn_or_experts(blk, p, h, live)
            if c is not None:
                counts.append(c)
        stats = self._add_stats(self.program_stats_zero(), counts, live,
                                B * T)
        return h, state, stats

    def logits(self, params, h):
        x = self.norm_f(params["norm_f"], h)
        w = params["embed"]["w"]                # the head is the embedding
        return jax.lax.dot_general(x.astype(w.dtype), w,
                                   (((x.ndim - 1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def __call__(self, params, ids, **kw):
        """ids [B, T] -> logits [B, T, V] f32."""
        h, _, _ = self._sequence(params, ids, None)
        return self.logits(params, h)

    def prefill(self, params, prompt, lengths=None, *,
                kv_dtype: Optional[str] = None,
                pad_to: Optional[int] = None):
        """TransformerLM.prefill's contract: (cell, last logits [B, V]);
        the cell holds ``pos``, ``k{i}`` / ``v{i}`` [B, pad_to, Hkv, D] for
        the attention layers, ``conv{i}`` [B, taps - 1, d] (each row's tail
        at its own length) for the convolution layers, and ``stats``. Only
        the rows that HOLD a prompt run (``prefill_live_rows``:
        ``PREFILL_TOKENS`` at a time, live rows first); the others' cell
        entries come back zero and the pool reads none of them. Only each
        row's last position reaches the head."""
        self._no_kv_dtype(kv_dtype)
        B, T0 = prompt.shape
        limit = self.max_len if pad_to is None else min(pad_to, self.max_len)
        if limit < T0:
            raise ValueError(f"prefill cache limit {limit} (pad_to/max_len) "
                             f"is narrower than the prompt ({T0})")
        pos = (jnp.full((B,), T0, jnp.int32) if lengths is None
               else jnp.asarray(lengths, jnp.int32))
        rows = self.cache_rows(params)
        per_slot = {r.name for r in rows if isinstance(r, SlotRow)}
        state0 = {r.name: jnp.zeros(
            (B,) + (() if r.name in per_slot else (T0,)) + r.shape, r.dtype)
            for r in rows}
        last, state, stats = prefill_live_rows(
            lambda ids, n: self._sequence(params, ids, n), prompt, pos,
            self.d_model, state0, self.program_stats_zero(),
            self.prefill_chunk_tokens(T0))
        cell = {"pos": pos, "stats": stats}
        for nm, buf in state.items():
            cell[nm] = buf if nm in per_slot else jnp.pad(
                buf, ((0, 0), (0, limit - T0), (0, 0), (0, 0)))
        return cell, self.logits(params, last)

    # -- one token against the paged cache ---------------------------------
    def decode_step_paged(self, params, cell, tokens, tables, *,
                          live=None, attn_route: Optional[str] = None):
        """TransformerLM.decode_step_paged's contract. Attention layers
        write the step's k, v (after the norms and RoPE) into their pools
        ``k{i}`` / ``v{i}`` [P, bs, Hkv, D] at page ``tables[b, pos // bs]``
        and read through pk.paged_decode_attention, a KV head serving its
        group of query heads, on one work list for all of them; convolution
        layers roll the slot's tail ``conv{i}`` [B, taps - 1, d]. ``live``
        [B] marks the slots whose tokens count (and whose experts run);
        ``cell["stats"]``, when present, accumulates
        :meth:`program_stats_zero`'s tree."""
        pos = cell["pos"]
        bs = cell[f"k{self.attn_layers[0]}"].shape[1]
        work = pk.paged_work_list(tables, pos, bs)
        page = jnp.take_along_axis(tables, (pos // bs)[:, None],
                                   axis=1)[:, 0]
        row = pos % bs
        B = tokens.shape[0]
        h = self.embed(params["embed"], tokens).astype(jnp.float32)
        new_cell = {"pos": pos + 1}
        counts = []
        for i, blk in enumerate(self.blocks):
            p = params[f"blocks_{i}"]
            x = blk.op_norm(p["op_norm"], h)
            if blk.kind == "conv":
                y, new_cell[f"conv{i}"] = blk.conv.step(
                    p["conv"], x, cell[f"conv{i}"])
                h = h + y
            else:
                q, k, v = blk.attn.project(p["attn"], x, pos)
                kp, k_rows = pk.put_rows(cell[f"k{i}"], page, row, k)
                vp, v_rows = pk.put_rows(cell[f"v{i}"], page, row, v)
                new_cell[f"k{i}"], new_cell[f"v{i}"] = kp, vp
                o = pk.paged_decode_attention(
                    q, k_rows, v_rows, tables, pos, scale=blk.attn.scale,
                    work=work, route=attn_route)
                h = h + _dot(o.reshape(B, -1), p["attn"]["w_o"])
            h, c = ffn_or_experts(blk, p, h, live)
            if c is not None:
                counts.append(c)
        if "stats" in cell:
            new_cell["stats"] = self._add_stats(cell["stats"], counts, live,
                                                B)
        return self.logits(params, h), new_cell

    def generate_cached(self, params, prompt, steps: int, *,
                        page_block: int = 64):
        """Greedy continuation through prefill + the paged decode step
        (one private table a sample): prompt [B, T0] -> [B, T0 + steps].
        The solo decode a served stream is compared with."""
        return paged_greedy(self, params, prompt, steps, page_block)
