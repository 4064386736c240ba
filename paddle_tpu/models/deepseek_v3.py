"""DeepSeek-V3's block as a served language model: multi-head LATENT
attention with a YaRN rotary slice, RMSNorm, a SwiGLU dense FFN in the
leading layers and a routed-expert layer (parallel/expert_share.py) after
them, an untied head. The second model class behind ``serve --config``: a
``PagedLM`` (models/paged_lm.py), which the pool runs with the code it runs
GPT-2 with.

What the cache holds is the point of latent attention: ONE row a token a
layer — the normed latent ``c_kv`` (``kv_rank`` wide) followed by the
rotary key ``k_rope`` (after RoPE, one head shared by all) — not a key and
a value per head. Prefill and decode take different paths through the same
weights:

* prefill expands ``k_nope`` and ``v`` from the latent rows it has just
  made (``W_UKV``) and runs the flash kernel at head width
  ``d_nope + d_rope``;
* decode ABSORBS ``W_UK`` into the query (``q_abs = [q_nope W_UK^T |
  q_rope]``), reads the latent rows once for all heads
  (ops/pallas_kernels.paged_latent_attention) and applies ``W_UV`` to the
  ``kv_rank``-wide result — the same scores and values, reassociated.

Precision: parameters and cache rows in ``dtype`` (bfloat16 as published),
every product with operands in that dtype and float32 accumulation; the
residual stream, the norms, RoPE, the softmax, and the router's scores and
top-k in float32.

Left out: the multi-token-prediction module (``num_nextn_predict_layers``),
a draft head past the last layer whose weights inference drops; the main
model's logits do not depend on it. A chip's share of a wide deployment is
built by passing ``experts_held`` (and a sliced ``vocab``).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax.numpy as jnp

from .. import nn
from ..nn.initializer import normal
from ..ops import pallas_kernels as pk
from ..parallel.expert_share import ExpertShare, ffn_or_experts
from .paged_lm import CacheRow, PagedLM, _dot


class LatentAttention(nn.Module):
    def __init__(self, d_model, n_heads, *, q_rank, kv_rank, d_nope, d_rope,
                 d_v, inv_freq, scale, eps, dtype, init_std):
        super().__init__()
        self.n_heads, self.kv_rank = n_heads, kv_rank
        self.d_nope, self.d_rope, self.d_v = d_nope, d_rope, d_v
        self.inv_freq, self.scale = inv_freq, scale
        init = normal(0.0, init_std)
        self.param("w_dq", (d_model, q_rank), init, dtype=dtype)
        self.q_norm = nn.RMSNorm(q_rank, eps, dtype=dtype)
        self.param("w_uq", (q_rank, n_heads * (d_nope + d_rope)), init,
                   dtype=dtype)
        self.param("w_dkv", (d_model, kv_rank + d_rope), init, dtype=dtype)
        self.kv_norm = nn.RMSNorm(kv_rank, eps, dtype=dtype)
        self.param("w_ukv", (kv_rank, n_heads * (d_nope + d_v)), init,
                   dtype=dtype)
        self.param("w_o", (n_heads * d_v, d_model), init, dtype=dtype)

    def project(self, params, x, positions):
        """x [..., d] (normed) at ``positions`` [...] -> (q_nope [..., H,
        d_nope] f32, q_rope [..., H, d_rope] f32 rotated, latent [...,
        kv_rank + d_rope] in the cache dtype: normed c_kv | rotated
        k_rope)."""
        dt = params["w_dq"].dtype
        c_q = self.q_norm(params["q_norm"], _dot(x, params["w_dq"]))
        q = _dot(c_q, params["w_uq"]).reshape(
            x.shape[:-1] + (self.n_heads, self.d_nope + self.d_rope))
        q_nope, q_rope = q[..., :self.d_nope], q[..., self.d_nope:]
        q_rope = nn.apply_rope(q_rope, positions, self.inv_freq)
        ckr = _dot(x, params["w_dkv"])
        c_kv = self.kv_norm(params["kv_norm"], ckr[..., :self.kv_rank])
        k_rope = nn.apply_rope(ckr[..., self.kv_rank:], positions,
                               self.inv_freq)
        latent = jnp.concatenate([c_kv, k_rope], axis=-1).astype(dt)
        return q_nope, q_rope, latent

    def _w_ukv(self, params):
        return params["w_ukv"].reshape(self.kv_rank, self.n_heads,
                                       self.d_nope + self.d_v)

    def expanded(self, params, q_nope, q_rope, latent):
        """Causal attention over a whole sequence with k_nope and v
        expanded from the latent rows: [B, T, ...] -> o [B, T, H * d_v]."""
        dt = latent.dtype
        B, T = latent.shape[:2]
        kv = jnp.einsum("btc,chx->bthx", latent[..., :self.kv_rank],
                        self._w_ukv(params),
                        preferred_element_type=jnp.float32)
        k_rope = jnp.broadcast_to(
            latent[..., None, self.kv_rank:],
            (B, T, self.n_heads, self.d_rope))
        q = jnp.concatenate([q_nope, q_rope], axis=-1).astype(dt)
        k = jnp.concatenate([kv[..., :self.d_nope].astype(dt), k_rope],
                            axis=-1)
        v = kv[..., self.d_nope:].astype(dt)
        if v.shape[-1] != q.shape[-1]:
            raise NotImplementedError(
                "the flash kernel takes one head width: d_v must equal "
                "d_nope + d_rope")
        o = pk.flash_attention(q, k, v, causal=True, scale=self.scale)
        return o.reshape(B, T, self.n_heads * self.d_v)

    def absorb(self, params, q_nope, q_rope):
        """[q_nope W_UK^T | q_rope]: [B, H, kv_rank + d_rope] in the cache
        dtype."""
        w_uk = self._w_ukv(params)[..., :self.d_nope]          # [c, H, n]
        q_lat = jnp.einsum("bhn,chn->bhc", q_nope.astype(w_uk.dtype), w_uk,
                           preferred_element_type=jnp.float32)
        return jnp.concatenate([q_lat, q_rope], axis=-1).astype(w_uk.dtype)

    def unabsorb(self, params, o_lat):
        """W_UV applied to the latent-space result: [B, H, kv_rank] ->
        [B, H * d_v] f32."""
        w_uv = self._w_ukv(params)[..., self.d_nope:]          # [c, H, v]
        o = jnp.einsum("bhc,chv->bhv", o_lat.astype(w_uv.dtype), w_uv,
                       preferred_element_type=jnp.float32)
        return o.reshape(o.shape[0], -1)


class DeepseekV3Block(nn.Module):
    def __init__(self, d_model, attn_kw, *, dense_width=None, moe_kw=None,
                 eps, dtype, init_std):
        super().__init__()
        self.attn_norm = nn.RMSNorm(d_model, eps, dtype=dtype)
        self.attn = LatentAttention(d_model, eps=eps, dtype=dtype,
                                    init_std=init_std, **attn_kw)
        self.ffn_norm = nn.RMSNorm(d_model, eps, dtype=dtype)
        self.is_moe = moe_kw is not None
        if self.is_moe:
            self.moe = ExpertShare(d_model, dtype=dtype, init_std=init_std,
                                   **moe_kw)
        else:
            self.ffn = nn.SwiGLU(d_model, dense_width,
                                 w_init=normal(0.0, init_std), dtype=dtype)

    def feed_forward(self, params, h, live):
        """h [..., d] f32 -> (h + FFN(norm(h)), counts or None)."""
        return ffn_or_experts(self, params, h, live)


class DeepseekV3LM(PagedLM):
    """``vocab`` rows of embedding and (untied) head, ``n_layers`` blocks of
    which the first ``n_dense`` carry the dense FFN and the rest the expert
    layer over ``experts_held`` of ``n_experts``."""

    def __init__(self, vocab: int, *, d_model: int, n_heads: int,
                 n_layers: int, n_dense: int, dense_width: int,
                 expert_width: int, n_experts: int,
                 experts_held: Optional[Sequence[int]] = None,
                 top_k: int = 8, n_group: int = 8, topk_group: int = 4,
                 routed_scale: float = 2.5, n_shared: int = 1,
                 q_rank: int, kv_rank: int, d_nope: int, d_rope: int,
                 d_v: int, rope_theta: float = 10000.0,
                 rope_scaling: Optional[dict] = None, eps: float = 1e-6,
                 max_len: int = 2048, dtype=jnp.bfloat16,
                 init_std: float = 0.02):
        super().__init__()
        self.vocab, self.max_len, self.dtype = vocab, max_len, dtype
        rs = rope_scaling or {}
        factor = float(rs.get("factor", 1.0))
        inv_freq = nn.yarn_inv_freq(
            d_rope, rope_theta, factor=factor,
            original_max_position=rs.get("original_max_position_embeddings",
                                         4096),
            beta_fast=rs.get("beta_fast", 32), beta_slow=rs.get("beta_slow",
                                                                1))
        m = nn.yarn_mscale(factor, rs.get("mscale_all_dim", 0.0)) \
            if rs.get("mscale_all_dim") else 1.0
        attn_kw = dict(n_heads=n_heads, q_rank=q_rank, kv_rank=kv_rank,
                       d_nope=d_nope, d_rope=d_rope, d_v=d_v,
                       inv_freq=inv_freq,
                       scale=(d_nope + d_rope) ** -0.5 * m * m)
        held = list(range(n_experts)) if experts_held is None \
            else list(experts_held)
        moe_kw = dict(d_expert=expert_width, n_experts=n_experts,
                      experts_held=held, top_k=top_k, n_group=n_group,
                      topk_group=topk_group, routed_scale=routed_scale,
                      n_shared=n_shared)
        self.row = kv_rank + d_rope
        self.n_moe, self.n_held = n_layers - n_dense, len(held)
        self.top_k = top_k
        self.embed = nn.Embedding(vocab, d_model, dtype=dtype,
                                  w_init=normal(0.0, init_std))
        self.blocks = [
            DeepseekV3Block(d_model, attn_kw, eps=eps, dtype=dtype,
                            init_std=init_std,
                            **(dict(dense_width=dense_width) if i < n_dense
                               else dict(moe_kw=moe_kw)))
            for i in range(n_layers)]
        self.norm_f = nn.RMSNorm(d_model, eps, dtype=dtype)
        self.param("head", (d_model, vocab), normal(0.0, init_std),
                   dtype=dtype)

    # -- what the page pool asks -------------------------------------------
    def cache_rows(self, params, kv_dtype: Optional[str] = None):
        """The per-layer cache: one latent row a token a layer."""
        self._no_kv_dtype(kv_dtype)
        dt = self._compute_dtype(params)
        return [CacheRow(f"kv{i}", (self.row,), dt)
                for i in range(len(self.blocks))]

    #: the decode read's registered cost model (obs/roofline.kernel_cost)
    paged_read_kernel = "paged_latent_attention"

    def paged_read_geometry(self, params, kv_dtype=None):
        return {"row": self.row,
                "itemsize": jnp.dtype(self._compute_dtype(params)).itemsize}

    # -- whole sequences (the expanded path) -------------------------------
    def _sequence(self, params, ids, lengths):
        """ids [B, T] -> (h [B, T, d] f32, ``kv{i}`` [B, T, row] of every
        layer, stats)."""
        B, T = ids.shape
        positions, live = self._positions_live(ids, lengths)
        h = self._embed(params, ids)
        latents, counts = {}, []
        for i, blk in enumerate(self.blocks):
            p = params[f"blocks_{i}"]
            x = blk.attn_norm(p["attn_norm"], h)
            q_nope, q_rope, lat = blk.attn.project(p["attn"], x, positions)
            o = blk.attn.expanded(p["attn"], q_nope, q_rope, lat)
            h = h + _dot(o, p["attn"]["w_o"])
            h, c = blk.feed_forward(p, h, live)
            if c is not None:
                counts.append(c)
            latents[f"kv{i}"] = lat
        stats = self._add_stats(self.program_stats_zero(), counts, live,
                                B * T)
        return h, latents, stats

    # -- one token against the paged cache (the absorbed path) -------------
    def _decode_layer(self, i, blk, p, h, cell, step):
        """The step's latent row written into ``kv{i}`` [P, bs, row], then
        the absorbed read over the live pages."""
        rd = step.full
        x = blk.attn_norm(p["attn_norm"], h)
        q_nope, q_rope, lat = blk.attn.project(p["attn"], x, rd.pos)
        pool = cell[f"kv{i}"].at[rd.page, rd.row].set(lat)
        o_lat = pk.paged_latent_attention(
            blk.attn.absorb(p["attn"], q_nope, q_rope), pool, rd.tables,
            rd.pos, d_value=blk.attn.kv_rank, scale=blk.attn.scale,
            work=rd.work, route=step.attn_route)
        h = h + _dot(blk.attn.unabsorb(p["attn"], o_lat), p["attn"]["w_o"])
        h, c = blk.feed_forward(p, h, step.live)
        return h, {f"kv{i}": pool}, c
