"""Arcee's ``afmoe`` stack (Trinity-Mini) as a served language model: every
layer is grouped-query attention whose OUTPUT IS GATED by the layer's input
(``o * sigmoid(x W_g)``) followed by a SwiGLU FFN — dense in the leading
layers, a routed-expert layer with a shared expert after them
(parallel/expert_share.py: sigmoid scores, top-k of score + bias, no
groups) — under SANDWICH norms: ``h += post_attn_norm(attn(input_norm(h)))``;
``h += post_mlp_norm(mlp(pre_mlp_norm(h)))``. With ``embed_scale`` the
embedding's output is multiplied by it (muP: ``sqrt(d_model)``). A final
RMSNorm, an untied head. The fifth model class behind ``serve --config``,
a ``PagedLM`` (models/paged_lm.py).

``layer_types`` makes two kinds of attention layer, and they differ in two
things. ``sliding_attention``: half-split RoPE on q and k, and the query at
position p sees the keys ``p - window < j <= p``. ``full_attention``: NO
positional term, and every key ``j <= p``. Both RMS-norm q and k per head.

So there are two kinds of CACHE, and the model states both
(``cache_rows``): a full layer's ``k{i}`` / ``v{i}`` are pages that grow
with the context; a sliding layer's state a reach (``CacheRow(window=)``),
and the pool keeps them in a RING of the slot's own that stops growing at
``window`` positions (serving/paged.py). A decode step writes ``k``, ``v``
at the layer's own table and reads through the kernel of its kind —
pk.paged_decode_attention over one work list for the full layers, the same
call with ``window`` (``paged_window_attention`` in a trace) over another
for the sliding ones. Prefill runs the banded flash kernel
(``flash_attention(window=)``) in sliding layers, the causal one in full
layers, and writes the pool's pages in place a chunk at a time.

Precision: parameters and pages in ``dtype`` (bfloat16 as published), every
product with operands in that dtype and float32 accumulation; the residual
stream, the norms, RoPE, the softmax, the gate's sigmoid and the router in
float32.

A chip's share of a wide deployment is built by passing ``experts_held``
(and a sliced ``vocab``).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.initializer import normal
from ..ops import pallas_kernels as pk
from ..parallel.expert_share import ExpertShare
from .paged_lm import CacheRow, PagedLM, _dot


class GatedGroupedAttention(nn.Module):
    """``n_heads`` query heads over ``kv_heads`` key/value heads of
    ``d_head``, q and k RMS-normed per head, the output gated by the input.
    ``w_qkvg`` holds the published q, k, v and gate projections side by
    side. ``inv_freq``: half-split RoPE on q and k after the norms; None:
    no positional term."""

    def __init__(self, d_model, n_heads, kv_heads, d_head, *, inv_freq, eps,
                 dtype, init_std):
        super().__init__()
        if n_heads % kv_heads:
            raise ValueError(f"{n_heads} query heads are not whole groups "
                             f"over {kv_heads} KV heads")
        self.n_heads, self.kv_heads, self.d_head = n_heads, kv_heads, d_head
        self.inv_freq, self.scale = inv_freq, d_head ** -0.5
        init = normal(0.0, init_std)
        self.param("w_qkvg", (d_model, 2 * (n_heads + kv_heads) * d_head),
                   init, dtype=dtype)
        self.q_norm = nn.RMSNorm(d_head, eps, dtype=dtype)
        self.k_norm = nn.RMSNorm(d_head, eps, dtype=dtype)
        self.param("w_o", (n_heads * d_head, d_model), init, dtype=dtype)

    def project(self, params, x, positions):
        """x [..., d] (normed) at ``positions`` [...] -> (q [..., H, D] f32
        normed (and rotated), k [..., Hkv, D] likewise, v [..., Hkv, D]; k
        and v in the cache dtype; gate [..., H * D] f32, ``sigmoid(x
        W_g)``)."""
        dt = params["w_qkvg"].dtype
        H, K, D = self.n_heads, self.kv_heads, self.d_head
        y = _dot(x, params["w_qkvg"])
        lead = x.shape[:-1]
        q = self.q_norm(params["q_norm"], y[..., :H * D].reshape(
            lead + (H, D)))
        k = self.k_norm(params["k_norm"], y[..., H * D:(H + K) * D].reshape(
            lead + (K, D)))
        v = y[..., (H + K) * D:(H + 2 * K) * D].reshape(lead + (K, D))
        if self.inv_freq is not None:
            q = nn.apply_rope(q, positions, self.inv_freq, layout="half")
            k = nn.apply_rope(k, positions, self.inv_freq, layout="half")
        return (q, k.astype(dt), v.astype(dt),
                jax.nn.sigmoid(y[..., (H + 2 * K) * D:]))

    def output(self, params, o, gate):
        """o [..., H, D] (the softmax's), gate [..., H * D] -> [..., d]."""
        o = o.reshape(gate.shape).astype(jnp.float32) * gate
        return _dot(o, params["w_o"])


class AfmoeBlock(nn.Module):
    """One layer under its four norms; ``kind`` "sliding_attention" or
    "full_attention"; a dense ``ffn`` or a routed ``moe``."""

    def __init__(self, d_model, kind, *, attn_kw, inv_freq, dense_width=None,
                 moe_kw=None, eps, dtype, init_std):
        super().__init__()
        if kind not in ("sliding_attention", "full_attention"):
            raise ValueError(f"unknown layer type {kind!r}")
        self.kind = kind
        self.sliding = kind == "sliding_attention"
        for nm in ("input_norm", "post_attn_norm", "pre_mlp_norm",
                   "post_mlp_norm"):
            setattr(self, nm, nn.RMSNorm(d_model, eps, dtype=dtype))
        self.attn = GatedGroupedAttention(
            d_model, eps=eps, dtype=dtype, init_std=init_std,
            inv_freq=inv_freq if self.sliding else None, **attn_kw)
        self.is_moe = moe_kw is not None
        if self.is_moe:
            self.moe = ExpertShare(d_model, dtype=dtype, init_std=init_std,
                                   **moe_kw)
        else:
            self.ffn = nn.SwiGLU(d_model, dense_width,
                                 w_init=normal(0.0, init_std), dtype=dtype)

    def mlp(self, params, h, live):
        """h [..., d] f32 -> (h + post_mlp_norm(mlp(pre_mlp_norm(h))),
        counts or None)."""
        y = self.pre_mlp_norm(params["pre_mlp_norm"], h)
        if self.is_moe:
            out, counts = self.moe(params["moe"], y.reshape(-1, y.shape[-1]),
                                   None if live is None else live.reshape(-1))
            out = out.reshape(h.shape)
        else:
            out, counts = self.ffn(params["ffn"], y), None
        return h + self.post_mlp_norm(params["post_mlp_norm"], out), counts


class AfmoeLM(PagedLM):
    """``vocab`` rows of embedding and of an untied head, one block per
    entry of ``layer_types``; the first ``n_dense`` carry the dense FFN, the
    rest the expert layer over ``experts_held`` of ``n_experts``."""

    def __init__(self, vocab: int, *, d_model: int, n_heads: int,
                 kv_heads: int, d_head: int, layer_types: Sequence[str],
                 window: int, n_dense: int, dense_width: int,
                 expert_width: int, n_experts: int,
                 experts_held: Optional[Sequence[int]] = None,
                 top_k: int = 8, n_shared: int = 1,
                 routed_scale: float = 1.0, rope_theta: float = 10000.0,
                 embed_scale: float = 1.0, eps: float = 1e-5,
                 max_len: int = 4096, dtype=jnp.bfloat16,
                 init_std: float = 0.02):
        super().__init__()
        self.vocab, self.max_len, self.dtype = vocab, max_len, dtype
        held = list(range(n_experts)) if experts_held is None \
            else list(experts_held)
        attn_kw = dict(n_heads=n_heads, kv_heads=kv_heads, d_head=d_head)
        moe_kw = dict(d_expert=expert_width, n_experts=n_experts,
                      experts_held=held, top_k=top_k, n_group=1,
                      topk_group=1, routed_scale=routed_scale,
                      norm_eps=1e-20, n_shared=n_shared)
        self.n_heads, self.kv_heads, self.d_head = n_heads, kv_heads, d_head
        self.d_model, self.window = d_model, window
        self.embed_scale = embed_scale
        self.n_moe, self.n_held = len(layer_types) - n_dense, len(held)
        self.top_k = top_k
        self.embed = nn.Embedding(vocab, d_model, dtype=dtype,
                                  w_init=normal(0.0, init_std))
        inv_freq = nn.yarn_inv_freq(d_head, rope_theta)
        self.blocks = [
            AfmoeBlock(d_model, kind, attn_kw=attn_kw, inv_freq=inv_freq,
                       eps=eps, dtype=dtype, init_std=init_std,
                       **(dict(dense_width=dense_width) if i < n_dense
                          else dict(moe_kw=moe_kw)))
            for i, kind in enumerate(layer_types)]
        self.window_read_layers = sum(b.sliding for b in self.blocks)
        if not self.paged_read_layers:
            raise ValueError("the paged engine needs at least one "
                             "full_attention layer (its pages carry the "
                             "positions the pool counts)")
        self.norm_f = nn.RMSNorm(d_model, eps, dtype=dtype)
        self.head = nn.Embedding(vocab, d_model, dtype=dtype,
                                 w_init=normal(0.0, init_std))

    # -- what the page pool asks -------------------------------------------
    def cache_rows(self, params, kv_dtype: Optional[str] = None):
        """``k{i}`` / ``v{i}`` rows of ``kv_heads`` heads for every layer;
        a sliding layer's state their reach, ``window``."""
        self._no_kv_dtype(kv_dtype)
        dt = self._compute_dtype(params)
        return [CacheRow(f"{n}{i}", (self.kv_heads, self.d_head), dt,
                         window=self.window if blk.sliding else None)
                for i, blk in enumerate(self.blocks) for n in "kv"]

    prefill_chunk_tokens = PagedLM.solo_row_chunk_tokens

    @property
    def paged_read_layers(self):
        """The full layers: their decode read's registered cost model is
        ``paged_read_kernel``, the sliding layers' (``window_read_layers``)
        ``paged_window_attention``, over the same geometry."""
        return len(self.blocks) - self.window_read_layers

    # -- what a program returns beside its tokens ---------------------------
    def program_stats_zero(self):
        """ProgramStats' tree (the expert layers') and ``band_positions``:
        the (position, sliding layer) pairs the banded flash kernel ran."""
        return dict(super().program_stats_zero(),
                    band_positions=jnp.zeros((), jnp.int32))

    def note_program_stats(self, stats, program: str):
        from .. import obs
        attrs = super().note_program_stats(stats, program)
        band = int(stats["band_positions"])
        if band:
            obs.count("kernels.bytes_total", obs.roofline.kernel_cost(
                "flash_window_attention_fwd", positions=band,
                n_heads=self.n_heads, kv_heads=self.kv_heads,
                d_head=self.d_head,
                itemsize=jnp.dtype(self.dtype).itemsize) or 0.0,
                kernel="flash_window_attention_fwd")
        return attrs

    # -- whole sequences ---------------------------------------------------
    def _embed(self, params, ids):
        h = super()._embed(params, ids)
        return h * self.embed_scale if self.embed_scale != 1.0 else h

    def _sequence(self, params, ids, lengths):
        """ids [B, T] -> (h [B, T, d] f32, state: ``k{i}`` / ``v{i}`` [B,
        T, Hkv, D] of every layer, stats)."""
        B, T = ids.shape
        positions, live = self._positions_live(ids, lengths)
        h = self._embed(params, ids)
        state, counts = {}, []
        for i, blk in enumerate(self.blocks):
            p = params[f"blocks_{i}"]
            x = blk.input_norm(p["input_norm"], h)
            q, k, v, gate = blk.attn.project(p["attn"], x, positions)
            o = pk.flash_attention(
                q.astype(k.dtype), k, v, causal=True, scale=blk.attn.scale,
                window=self.window if blk.sliding else None)
            h = h + blk.post_attn_norm(
                p["post_attn_norm"], blk.attn.output(p["attn"], o, gate))
            state[f"k{i}"], state[f"v{i}"] = k, v
            h, c = blk.mlp(p, h, live)
            if c is not None:
                counts.append(c)
        stats = self._add_stats(
            self.program_stats_zero(), counts, live, B * T,
            band_positions=B * T * self.window_read_layers)
        return h, state, stats

    # -- one token against the paged cache ---------------------------------
    def _decode_layer(self, i, blk, p, h, cell, step):
        """The step's k, v (after the norms, and RoPE in a sliding layer)
        written into the layer's pools ``k{i}`` / ``v{i}`` [P, bs, Hkv, D]
        and read back through the read of its kind: a full layer's at
        ``tables`` and every row to ``pos``, a sliding layer's at the ring
        and the window's rows."""
        rd = step.ringed if blk.sliding else step.full
        x = blk.input_norm(p["input_norm"], h)
        q, k, v, gate = blk.attn.project(p["attn"], x, rd.pos)
        o, kp, vp = rd.write_and_attend(
            q, k, v, cell[f"k{i}"], cell[f"v{i}"], scale=blk.attn.scale,
            route=step.attn_route)
        h = h + blk.post_attn_norm(
            p["post_attn_norm"], blk.attn.output(p["attn"], o, gate))
        h, c = blk.mlp(p, h, step.live)
        return h, {f"k{i}": kp, f"v{i}": vp}, c
