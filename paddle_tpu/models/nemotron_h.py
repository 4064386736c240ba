"""Nemotron-H's stack (``nemotron_h``: NVIDIA-Nemotron-3-Nano) as a served
language model: every layer is ONE mixer, ``h += mixer_i(RMSNorm_i(h))``,
and ``pattern`` says which — ``M`` a Mamba-2 state-space mixer
(nn.Mamba2Mixer), ``E`` a routed-expert layer (parallel/expert_share.py
with ``gated=False``: experts and the shared expert are ``down(relu(up(y))
^ 2)``, two matrices; DeepSeek-V3's sigmoid router without groups), ``*``
grouped-query attention WITHOUT any positional term (position comes from
the state-space layers) — then a final RMSNorm and an untied head. The
fourth model class behind ``serve --config``, a ``PagedLM``
(models/paged_lm.py).

Two kinds of state, the larger one not pages. An attention layer keeps
keys and values in PAGES (``kv_heads`` heads a row; a KV head serves its
group of query heads through pk.paged_decode_attention and, in prefill,
through the flash kernel's index map). A Mamba layer keeps, PER SLOT and
fixed in size whatever the context (``SlotRow``), the recurrence's carry
``ssm{i}`` — every head's ``S``, float32, packed as pk.ssm_pack lays it out
— and ``conv{i}``, the last ``taps - 1`` inputs of its convolution: at the
published sizes 2.1 MB a layer a slot, megabytes where ``Lfm2MoeLM``'s
tails are kilobytes. So the slot rows are written IN PLACE
(``slot_rows_in_place``): ``prefill`` is handed the pool's own arrays and
sets the rows of the slots it fills at their indices, and a decode step's
state update aliases its input (pk.ssm_state_update) — the pool never
holds a second copy of the carry.

Precision: parameters, pages and conv tails in ``dtype`` (bfloat16 as
published), every product with operands in that dtype and float32
accumulation; the residual stream, the norms, the softmax, ``dt``,
``exp(dt A)``, the state ``S`` and the router in float32.

A chip's share of a wide deployment is built by passing ``experts_held``
(and a sliced ``vocab``).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax.numpy as jnp

from .. import nn
from ..nn.initializer import normal
from ..ops import pallas_kernels as pk
from ..parallel.expert_share import ExpertShare
from .paged_lm import CacheRow, PagedLM, SlotRow, _dot

KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


class PlainGroupedAttention(nn.Module):
    """``n_heads`` query heads over ``kv_heads`` key/value heads of
    ``d_head`` (``n_heads * d_head`` need not be ``d_model``), no bias, no
    positional term, no norm on q or k. ``w_qkv`` holds the published q, k
    and v projections side by side."""

    def __init__(self, d_model, n_heads, kv_heads, d_head, *, dtype,
                 init_std):
        super().__init__()
        if n_heads % kv_heads:
            raise ValueError(f"{n_heads} query heads are not whole groups "
                             f"over {kv_heads} KV heads")
        self.n_heads, self.kv_heads, self.d_head = n_heads, kv_heads, d_head
        self.scale = d_head ** -0.5
        init = normal(0.0, init_std)
        self.param("w_qkv", (d_model, (n_heads + 2 * kv_heads) * d_head),
                   init, dtype=dtype)
        self.param("w_o", (n_heads * d_head, d_model), init, dtype=dtype)

    def project(self, params, x):
        """x [..., d] (normed) -> (q [..., H, D] f32, k, v [..., Hkv, D] in
        the cache dtype)."""
        dt = params["w_qkv"].dtype
        H, K, D = self.n_heads, self.kv_heads, self.d_head
        qkv = _dot(x, params["w_qkv"])
        lead = x.shape[:-1]
        q = qkv[..., :H * D].reshape(lead + (H, D))
        k = qkv[..., H * D:(H + K) * D].reshape(lead + (K, D))
        v = qkv[..., (H + K) * D:].reshape(lead + (K, D))
        return q, k.astype(dt), v.astype(dt)


class NemotronHBlock(nn.Module):
    """``h += mixer(norm(h))`` with ``kind`` the mixer: "mamba", "moe" or
    "attention"."""

    def __init__(self, d_model, kind, *, mamba_kw, moe_kw, attn_kw, eps,
                 dtype, init_std):
        super().__init__()
        self.kind = kind
        self.norm = nn.RMSNorm(d_model, eps, dtype=dtype)
        if kind == "mamba":
            self.mixer = nn.Mamba2Mixer(d_model, eps=eps, dtype=dtype,
                                        w_init=normal(0.0, init_std),
                                        **mamba_kw)
        elif kind == "moe":
            self.moe = ExpertShare(d_model, dtype=dtype, init_std=init_std,
                                   **moe_kw)
        elif kind == "attention":
            self.attn = PlainGroupedAttention(d_model, dtype=dtype,
                                              init_std=init_std, **attn_kw)
        else:
            raise ValueError(f"unknown layer kind {kind!r}")


class NemotronHLM(PagedLM):
    """``vocab`` rows of embedding and of an untied head, one block per
    character of ``pattern`` (``M`` / ``E`` / ``*``); the expert layers
    over ``experts_held`` of ``n_experts``."""

    #: the pool hands ``prefill`` its own slot-row arrays and takes them
    #: back written at the filled slots' indices (serving/paged.py)
    slot_rows_in_place = True

    def __init__(self, vocab: int, *, d_model: int, pattern: str,
                 n_heads: int, kv_heads: int, d_head: int,
                 mamba_heads: int, mamba_head_dim: int, ssm_groups: int,
                 ssm_state: int, expert_width: int, shared_width: int,
                 n_experts: int,
                 experts_held: Optional[Sequence[int]] = None,
                 top_k: int = 6, routed_scale: float = 2.5,
                 conv_taps: int = 4, chunk: int = 128, eps: float = 1e-5,
                 max_len: int = 4096, dtype=jnp.bfloat16,
                 init_std: float = 0.02):
        super().__init__()
        unknown = set(pattern) - set(KINDS)
        if unknown:
            raise ValueError(f"pattern {pattern!r}: unknown layer letters "
                             f"{sorted(unknown)} (M, E, * are known)")
        self.vocab, self.max_len, self.dtype = vocab, max_len, dtype
        held = list(range(n_experts)) if experts_held is None \
            else list(experts_held)
        mamba_kw = dict(heads=mamba_heads, head_dim=mamba_head_dim,
                        groups=ssm_groups, state=ssm_state, taps=conv_taps,
                        chunk=chunk)
        moe_kw = dict(d_expert=expert_width, n_experts=n_experts,
                      experts_held=held, top_k=top_k, n_group=1,
                      topk_group=1, routed_scale=routed_scale,
                      norm_eps=1e-20, gated=False,
                      shared_width=shared_width,
                      up_transposed=True)     # [out, in], as published:
        #                       1856 is no multiple of 128, d_model is
        attn_kw = dict(n_heads=n_heads, kv_heads=kv_heads, d_head=d_head)
        self.d_model = d_model
        self.n_heads, self.kv_heads, self.d_head = n_heads, kv_heads, d_head
        self.embed = nn.Embedding(vocab, d_model, dtype=dtype,
                                  w_init=normal(0.0, init_std))
        self.blocks = [
            NemotronHBlock(d_model, KINDS[c], mamba_kw=mamba_kw,
                           moe_kw=moe_kw, attn_kw=attn_kw, eps=eps,
                           dtype=dtype, init_std=init_std)
            for c in pattern]
        self.attn_layers = [i for i, b in enumerate(self.blocks)
                            if b.kind == "attention"]
        self.mamba_layers = [i for i, b in enumerate(self.blocks)
                             if b.kind == "mamba"]
        if not self.attn_layers:
            raise ValueError("the paged engine needs at least one attention "
                             "layer (its pages carry the positions)")
        self.n_moe = sum(b.kind == "moe" for b in self.blocks)
        self.n_held, self.top_k = len(held), top_k
        self.norm_f = nn.RMSNorm(d_model, eps, dtype=dtype)
        self.head = nn.Embedding(vocab, d_model, dtype=dtype,
                                 w_init=normal(0.0, init_std))

    # -- what the page pool asks -------------------------------------------
    def cache_rows(self, params, kv_dtype: Optional[str] = None):
        """Pages for the attention layers only — ``k{i}`` / ``v{i}`` rows of
        ``kv_heads`` heads — and for every Mamba layer two per-slot rows:
        ``ssm{i}``, the packed carry in float32, and ``conv{i}``, the
        convolution's last ``taps - 1`` inputs."""
        self._no_kv_dtype(kv_dtype)
        dt = self._compute_dtype(params)
        rows = []
        for i, blk in enumerate(self.blocks):
            if blk.kind == "mamba":
                m = blk.mixer
                rows += [SlotRow(f"ssm{i}", (m.heads // 2, m.state,
                                             2 * m.head_dim),
                                 jnp.float32),
                         SlotRow(f"conv{i}", (m.taps - 1, m.conv_dim), dt)]
            elif blk.kind == "attention":
                rows += [CacheRow(f"{n}{i}", (self.kv_heads, self.d_head),
                                  dt) for n in "kv"]
        return rows

    prefill_chunk_tokens = PagedLM.solo_row_chunk_tokens

    @property
    def paged_read_layers(self):
        """Layers of a decode step that read the pages."""
        return len(self.attn_layers)

    # -- what a program returns beside its tokens ---------------------------
    def program_stats_zero(self):
        """ProgramStats' tree (the expert layers') and the state-space
        layers': ``ssm_updates`` — (live slot, Mamba layer) state updates
        of the decode steps; ``scan_real`` / ``scan_padded`` — (position,
        Mamba layer) pairs the chunked scan ran that lie inside / past
        their row's own length."""
        zero = jnp.zeros((), jnp.int32)
        return dict(super().program_stats_zero(), ssm_updates=zero,
                    scan_real=zero, scan_padded=zero)

    def note_program_stats(self, stats, program: str):
        from .. import obs
        attrs = super().note_program_stats(stats, program)
        m = self.blocks[self.mamba_layers[0]].mixer if self.mamba_layers \
            else None
        if m is None:
            return attrs
        shape = dict(heads=m.heads, head_dim=m.head_dim, state=m.state,
                     groups=m.groups)
        updates = int(stats["ssm_updates"])
        real, padded = int(stats["scan_real"]), int(stats["scan_padded"])
        if updates:
            obs.count("ssm.state_updates_total", updates, program=program)
            obs.count("kernels.bytes_total", obs.roofline.kernel_cost(
                "ssm_state_update", updates=updates, **shape) or 0.0,
                kernel="ssm_state_update")
        if real or padded:
            obs.count("ssm.scan_tokens_total", real, state="real")
            obs.count("ssm.scan_tokens_total", padded, state="padded")
            obs.count("kernels.bytes_total", obs.roofline.kernel_cost(
                "ssd_chunk_scan", tokens=real + padded,
                itemsize=jnp.dtype(self.dtype).itemsize, **shape) or 0.0,
                kernel="ssd_chunk_scan")
        return attrs

    # -- whole sequences ---------------------------------------------------
    def _sequence(self, params, ids, lengths):
        """ids [B, T] -> (h [B, T, d] f32, state: ``k{i}`` / ``v{i}`` [B,
        T, Hkv, D], ``ssm{i}`` and ``conv{i}`` at each row's length,
        stats)."""
        B, T = ids.shape
        _, live = self._positions_live(ids, lengths)
        h = self._embed(params, ids)
        state, counts = {}, []
        for i, blk in enumerate(self.blocks):
            p = params[f"blocks_{i}"]
            x = blk.norm(p["norm"], h)
            if blk.kind == "mamba":
                y, ssm, state[f"conv{i}"] = blk.mixer(p["mixer"], x,
                                                      lengths)
                state[f"ssm{i}"] = ssm
                h = h + y
            elif blk.kind == "attention":
                q, k, v = blk.attn.project(p["attn"], x)
                o = pk.flash_attention(q.astype(k.dtype), k, v, causal=True,
                                       scale=blk.attn.scale)
                h = h + _dot(o.reshape(B, T, -1), p["attn"]["w_o"])
                state[f"k{i}"], state[f"v{i}"] = k, v
            else:
                out, c = blk.moe(p["moe"], x.reshape(B * T, -1),
                                 None if live is None else live.reshape(-1))
                h = h + out.reshape(h.shape)
                counts.append(c)
        n_real = B * T if live is None else jnp.sum(live, dtype=jnp.int32)
        n_m = len(self.mamba_layers)
        stats = self._add_stats(
            self.program_stats_zero(), counts, live, B * T,
            scan_real=n_real * n_m, scan_padded=(B * T - n_real) * n_m)
        return h, state, stats

    # -- one token against the paged cache ---------------------------------
    def _decode_layer(self, i, blk, p, h, cell, step):
        """A Mamba layer rolls the slot's ``conv{i}`` and updates ``ssm{i}``
        through pk.ssm_state_update — in place, the live slots alone; an
        attention layer writes the step's k, v into its pools ``k{i}`` /
        ``v{i}`` [P, bs, Hkv, D] and reads them back; an expert layer runs
        the live slots' tokens."""
        x = blk.norm(p["norm"], h)
        if blk.kind == "mamba":
            y, ssm, conv = blk.mixer.step(p["mixer"], x, cell[f"ssm{i}"],
                                          cell[f"conv{i}"], step.live)
            return h + y, {f"ssm{i}": ssm, f"conv{i}": conv}, None
        if blk.kind == "attention":
            q, k, v = blk.attn.project(p["attn"], x)
            o, kp, vp = step.full.write_and_attend(
                q, k, v, cell[f"k{i}"], cell[f"v{i}"], scale=blk.attn.scale,
                route=step.attn_route)
            return (h + _dot(o.reshape(h.shape[0], -1), p["attn"]["w_o"]),
                    {f"k{i}": kp, f"v{i}": vp}, None)
        out, c = blk.moe(p["moe"], x, step.live)
        return h + out, {}, c

    def _step_stats(self, step, notes):
        B = step.full.pos.shape[0]
        n_live = B if step.live is None \
            else jnp.sum(step.live, dtype=jnp.int32)
        return dict(ssm_updates=n_live * len(self.mamba_layers))
