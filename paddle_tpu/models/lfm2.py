"""LFM2's block (``lfm2_moe``) as a served language model: layers whose
operator is either a GATED SHORT CONVOLUTION (nn.ShortConv) or grouped-query
attention with per-head RMS norms on q and k and half-split RoPE, each
followed by a SwiGLU FFN — dense in the leading layers, a routed-expert
layer (parallel/expert_share.py: sigmoid scores, top-k of score + bias over
all experts, no groups, no shared expert) after them; RMSNorm throughout, a
final norm, the head tied to the embedding. The third model class behind
``serve --config``, a ``PagedLM`` (models/paged_lm.py).

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

import jax.numpy as jnp

from .. import nn
from ..nn.initializer import normal
from ..ops import pallas_kernels as pk
from ..parallel.expert_share import ExpertShare, ffn_or_experts
from .paged_lm import CacheRow, PagedLM, SlotRow, _dot


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


class Lfm2MoeLM(PagedLM):
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

    @property
    def paged_read_layers(self):
        """Layers of a decode step that read the pages."""
        return len(self.attn_layers)

    # -- whole sequences ---------------------------------------------------
    def _sequence(self, params, ids, lengths):
        """ids [B, T] -> (h [B, T, d] f32, state: ``k{i}`` / ``v{i}`` [B,
        T, Hkv, D] and ``conv{i}`` [B, taps - 1, d] at each row's length,
        stats)."""
        B, T = ids.shape
        positions, live = self._positions_live(ids, lengths)
        h = self._embed(params, ids)
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

    # -- one token against the paged cache ---------------------------------
    def _decode_layer(self, i, blk, p, h, cell, step):
        """An attention layer writes the step's k, v (after the norms and
        RoPE) into its pools ``k{i}`` / ``v{i}`` [P, bs, Hkv, D] and reads
        them back, a KV head serving its group of query heads; a
        convolution layer rolls the slot's tail ``conv{i}`` [B, taps - 1,
        d]."""
        x = blk.op_norm(p["op_norm"], h)
        if blk.kind == "conv":
            y, tail = blk.conv.step(p["conv"], x, cell[f"conv{i}"])
            h, rows = h + y, {f"conv{i}": tail}
        else:
            q, k, v = blk.attn.project(p["attn"], x, step.full.pos)
            o, kp, vp = step.full.write_and_attend(
                q, k, v, cell[f"k{i}"], cell[f"v{i}"], scale=blk.attn.scale,
                route=step.attn_route)
            h = h + _dot(o.reshape(h.shape[0], -1), p["attn"]["w_o"])
            rows = {f"k{i}": kp, f"v{i}": vp}
        h, c = ffn_or_experts(blk, p, h, step.live)
        return h, rows, c
