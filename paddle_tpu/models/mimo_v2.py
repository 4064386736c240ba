"""Xiaomi's MiMo-V2-Flash as a served stack: a pre-norm block of
grouped-query attention whose keys are WIDER than its values (q, k of
``d_head`` 192, v and o of ``d_value`` 128), with rotary on the leading part
of a head alone and a scalar on the values, followed by a SwiGLU FFN —
dense in layer 0, a routed-expert layer with no shared expert after it
(parallel/expert_share.py: sigmoid scores, top-k of score + bias, no
groups). A final RMSNorm, an untied head. The seventh model class behind
``serve --config``, a ``PagedLM`` (models/paged_lm.py).

``layer_kinds`` makes two kinds of attention layer, and they differ in four
things. A GLOBAL layer (0): ``kv_heads`` KV heads, rope base
``rope_theta``, every key ``j <= p``. A SLIDING layer (1):
``swa_kv_heads`` KV heads, rope base ``swa_rope_theta``, the keys ``p -
window < j <= p`` — and a learned SINK: a logit a query head that joins the
softmax's denominator and no value, ``P_j = exp(s_j) / (sum exp(s) +
exp(sink))``, so a head may attend to nothing.

So there are two kinds of CACHE and the model states both (``cache_rows``),
each layer's k and v apart: a global layer's ``k{i}`` ``(kv_heads,
d_head)`` and ``v{i}`` ``(kv_heads, d_value)`` are pages that grow with the
context; a sliding layer's, of ``swa_kv_heads`` heads, state their reach
(``CacheRow(window=)``) and live in the slot's ring. Keys are HELD at the
chip's lane width (192 -> 256): a ``[pages, 64, 4, 192]`` bfloat16 array
does not lie row-major on a v5e and the pool would pad it to ``(8, 256)``
itself, twice the bytes.

A decode step builds two work lists, one a kind (PagedLM's), and a layer
reads through pk.paged_decode_attention — the sliding ones with ``window``
and ``sink`` (``paged_window_attention`` in a trace).

An admission runs ONE row at a time and, inside the row, a BLOCK of
``block_tokens`` positions at a time through the whole depth
(``_sequence``; KeyeSparseLM's walk), so a 49,152-token prompt is admitted
by the program a 4,096-token one is. What a block hands the next: a global
layer's k and v of the row so far — the block's queries read its own keys
through the causal flash kernel and every earlier block's through the
plain one, merged by their log-sum-exps —; a SLIDING layer's last few
pages alone: the block's queries read its own keys through the banded
kernel (sink inside) and the ``window`` keys before the block densely, and
the pool's ring takes the row's last pages at the end (``prefill(tail=)``),
never the row.

Precision: parameters and pages in ``dtype`` (bfloat16 as published), every
product with operands in that dtype and float32 accumulation; the residual
stream, the norms, RoPE, the softmax, the sink and the router in float32.

A chip's share of a wide deployment is built by passing ``experts_held``
(and a sliced ``vocab``). The published multi-token-prediction layers are
not part of this class (the engine yields one token a step).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.initializer import normal, zeros
from ..ops import pallas_kernels as pk
from ..parallel.expert_share import ExpertShare, ffn_or_experts
from .paged_lm import CacheRow, PagedLM, _dot

#: the lane width a key row is held at (module docstring)
LANES = 128


class SplitWidthAttention(nn.Module):
    """``n_heads`` query heads over ``kv_heads`` key/value heads: q and k
    ``d_head`` wide, the leading ``rotary`` values of a head rotated
    (half-split); v ``d_value`` wide, times ``value_scale``; ``sink``: a
    learned logit a query head. ``w_qkv`` holds the published q, k and v
    projections side by side."""

    def __init__(self, d_model, n_heads, kv_heads, d_head, d_value, *,
                 rotary, theta, value_scale, sink, dtype, init_std):
        super().__init__()
        if n_heads % kv_heads:
            raise ValueError(f"{n_heads} query heads are not whole groups "
                             f"over {kv_heads} KV heads")
        self.n_heads, self.kv_heads = n_heads, kv_heads
        self.d_head, self.d_value = d_head, d_value
        self.rotary, self.value_scale = rotary, value_scale
        self.inv_freq = nn.yarn_inv_freq(rotary, theta)
        self.scale = d_head ** -0.5
        init = normal(0.0, init_std)
        self.param("w_qkv", (d_model, (n_heads + kv_heads) * d_head
                             + kv_heads * d_value), init, dtype=dtype)
        self.param("w_o", (n_heads * d_value, d_model), init, dtype=dtype)
        if sink:
            self.param("sink", (n_heads,), zeros, dtype=jnp.float32)

    def _rotate(self, x, positions):
        R = self.rotary
        return jnp.concatenate(
            [nn.apply_rope(x[..., :R], positions, self.inv_freq,
                           layout="half"), x[..., R:]], axis=-1)

    def project(self, params, x, positions):
        """x [..., d] (normed) at ``positions`` [...] -> (q [..., H, Dk]
        f32, k [..., Hkv, Dk], v [..., Hkv, Dv] scaled; k and v in the
        cache dtype)."""
        dt = params["w_qkv"].dtype
        H, K, Dk, Dv = self.n_heads, self.kv_heads, self.d_head, self.d_value
        y = _dot(x, params["w_qkv"])
        lead = x.shape[:-1]
        q = self._rotate(y[..., :H * Dk].reshape(lead + (H, Dk)), positions)
        k = self._rotate(y[..., H * Dk:(H + K) * Dk].reshape(lead + (K, Dk)),
                         positions)
        v = y[..., (H + K) * Dk:].reshape(lead + (K, Dv)) * self.value_scale
        return q, k.astype(dt), v.astype(dt)

    def output(self, params, o):
        """o [..., H, Dv] -> [..., d]."""
        return _dot(o.reshape(o.shape[:-2] + (-1,)), params["w_o"])


class MimoBlock(nn.Module):
    """One layer: ``h += attn(input_norm(h))``; ``h +=
    ffn_or_experts(ffn_norm(h))``. ``sliding``: the layer's kind."""

    def __init__(self, d_model, sliding, *, attn_kw, dense_width=None,
                 moe_kw=None, eps, dtype, init_std):
        super().__init__()
        self.sliding = bool(sliding)
        self.input_norm = nn.RMSNorm(d_model, eps, dtype=dtype)
        self.attn = SplitWidthAttention(d_model, dtype=dtype,
                                        init_std=init_std, **attn_kw)
        self.ffn_norm = nn.RMSNorm(d_model, eps, dtype=dtype)
        self.is_moe = moe_kw is not None
        if self.is_moe:
            self.moe = ExpertShare(d_model, dtype=dtype, init_std=init_std,
                                   **moe_kw)
        else:
            self.ffn = nn.SwiGLU(d_model, dense_width,
                                 w_init=normal(0.0, init_std), dtype=dtype)


def _merge(o1, l1, o2, l2):
    """Two partial reads of the same queries over disjoint keys, each with
    its log-sum-exp (o [..., Dv] f32, l [...] f32, -inf: read nothing) ->
    the read over both."""
    l = jnp.logaddexp(l1, l2)
    return (o1 * jnp.exp(l1 - l)[..., None]
            + o2 * jnp.exp(l2 - l)[..., None]), l


class MimoV2LM(PagedLM):
    """``vocab`` rows of embedding and of an untied head, one block per
    entry of ``layer_kinds`` (0 global, 1 sliding); ``moe_layers[i]`` 0: the
    dense FFN, 1: the expert layer over ``experts_held`` of ``n_experts``."""

    #: rows of up to 49,152 positions: no [slots, prompt bucket] copy of
    #: them beside the pools, and of a windowed row the pool is handed its
    #: last ``ring`` pages alone (``prefill(tail=)``)
    admits_in_place = True
    admits_window_tails = True

    def __init__(self, vocab: int, *, d_model: int, n_heads: int,
                 kv_heads: int, swa_kv_heads: int, d_head: int, d_value: int,
                 rotary: int, layer_kinds: Sequence[int],
                 moe_layers: Sequence[int], window: int, dense_width: int,
                 expert_width: int, n_experts: int,
                 experts_held: Optional[Sequence[int]] = None,
                 top_k: int = 8, rope_theta: float = 5e6,
                 swa_rope_theta: float = 1e4, value_scale: float = 1.0,
                 sink: Sequence[bool] = (False, True), eps: float = 1e-5,
                 max_len: int = 4096, block_tokens: int = 2048,
                 dtype=jnp.bfloat16, init_std: float = 0.02):
        super().__init__()
        if len(layer_kinds) != len(moe_layers):
            raise ValueError("layer_kinds and moe_layers name different "
                             "depths")
        self.vocab, self.max_len, self.dtype = vocab, max_len, dtype
        held = list(range(n_experts)) if experts_held is None \
            else list(experts_held)
        self.n_heads, self.d_head, self.d_value = n_heads, d_head, d_value
        #: KV heads by layer kind (0 global, 1 sliding)
        self.kv_heads_of = (kv_heads, swa_kv_heads)
        self.d_model, self.window = d_model, window
        self.block_tokens = block_tokens
        self.n_moe, self.n_held = sum(map(bool, moe_layers)), len(held)
        self.top_k = top_k
        self.embed = nn.Embedding(vocab, d_model, dtype=dtype,
                                  w_init=normal(0.0, init_std))
        moe_kw = dict(d_expert=expert_width, n_experts=n_experts,
                      experts_held=held, top_k=top_k, n_group=1,
                      topk_group=1, routed_scale=1.0, norm_eps=1e-20,
                      shared=False, bias=True, score="sigmoid")
        thetas = (rope_theta, swa_rope_theta)
        self.blocks = [
            MimoBlock(d_model, kind, eps=eps, dtype=dtype, init_std=init_std,
                      attn_kw=dict(
                          n_heads=n_heads, kv_heads=self.kv_heads_of[kind],
                          d_head=d_head, d_value=d_value, rotary=rotary,
                          theta=thetas[kind], value_scale=value_scale,
                          sink=sink[kind]),
                      **(dict(moe_kw=moe_kw) if moe
                         else dict(dense_width=dense_width)))
            for kind, moe in zip(layer_kinds, moe_layers)]
        self.window_read_layers = sum(b.sliding for b in self.blocks)
        if not self.paged_read_layers:
            raise ValueError("the paged engine needs at least one global "
                             "layer (its pages carry the positions the pool "
                             "counts)")
        self.norm_f = nn.RMSNorm(d_model, eps, dtype=dtype)
        self.head = nn.Embedding(vocab, d_model, dtype=dtype,
                                 w_init=normal(0.0, init_std))

    # -- what the page pool asks -------------------------------------------
    def cache_rows(self, params, kv_dtype: Optional[str] = None):
        """``k{i}`` of ``(Hkv_i, d_head)`` and ``v{i}`` of ``(Hkv_i,
        d_value)`` for every layer, the layer kind's own head count; a
        sliding layer's state their reach, ``window``; a key row is held
        at the lane width (module docstring)."""
        self._no_kv_dtype(kv_dtype)
        dt = self._compute_dtype(params)
        wide = -(-self.d_head // LANES) * LANES
        rows = []
        for i, blk in enumerate(self.blocks):
            K = blk.attn.kv_heads
            window = self.window if blk.sliding else None
            rows += [CacheRow(f"k{i}", (K, self.d_head), dt, window=window,
                              held=(K, wide)),
                     CacheRow(f"v{i}", (K, self.d_value), dt, window=window)]
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

    @property
    def paged_read_layers(self):
        """The global layers: their decode read's registered cost model is
        ``paged_read_kernel``, the sliding layers' (``window_read_layers``)
        ``paged_window_attention``, each over its kind's geometry."""
        return len(self.blocks) - self.window_read_layers

    def _geometry(self, kind: str, dtype):
        return {"n_heads": self.n_heads,
                "kv_heads": self.kv_heads_of[kind == "window"],
                "d_head": self.d_head, "d_value": self.d_value,
                "itemsize": jnp.dtype(dtype).itemsize}

    def paged_read_geometry(self, params, kv_dtype: Optional[str] = None,
                            kind: str = "full"):
        """A layer KIND's geometry: its own KV heads, k and v widths
        apart."""
        return dict(self._geometry(kind, self._compute_dtype(params)),
                    kv_dtype=None)

    # -- what a program returns beside its tokens ---------------------------
    def program_stats_zero(self):
        """ProgramStats' tree (the expert layers'), ``band_positions``: the
        (position, sliding layer) pairs the banded flash kernel ran,
        ``sink_rows``: the (live query, layer) pairs whose read was HANDED a
        sink operand, counted where the kernels are called (a layer whose
        parameters hold none, or a call that dropped it, adds nothing) — a
        decode step's live slots, an admission's real positions —
        and ``pairs_causal`` / ``pairs_band``: the (query, key) pairs of an
        admission's real positions, ONE layer's, under the causal triangle
        and under the window's band (float32: a share's numerator, not an
        account)."""
        zero, none = jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32)
        return dict(super().program_stats_zero(), band_positions=zero,
                    sink_rows=zero, pairs_causal=none, pairs_band=none)

    def note_program_stats(self, stats, program: str):
        from .. import obs
        attrs = super().note_program_stats(stats, program)
        attrs["sink_rows"] = int(stats["sink_rows"])
        obs.count("attention.sink_rows_total", attrs["sink_rows"],
                  program=program)
        if program != "segment":
            attrs.update(pairs_causal=int(stats["pairs_causal"]),
                         pairs_band=int(stats["pairs_band"]))
        band = int(stats["band_positions"])
        if band:
            obs.count("kernels.bytes_total", obs.roofline.kernel_cost(
                "flash_window_attention_fwd", positions=band,
                **self._geometry("window", self.dtype)) or 0.0,
                kernel="flash_window_attention_fwd")
        return attrs

    # -- whole sequences ---------------------------------------------------
    def _attend_block(self, blk, sink, q, k, v, q0, j, bufs):
        """One layer's attention for a block of ``Q`` queries at positions
        ``q0 ..`` (``j``-th block of the row): q [Q, H, Dk] f32, the
        block's own k [Q, Hkv, Dk], v [Q, Hkv, Dv]; ``bufs`` — a global
        layer's (k, v) of the whole row with this block's rows written, a
        sliding layer's (k, v) of the positions BEFORE the block (the last
        ``window`` of them are read) -> o [Q, H, Dv] f32."""
        Q, H, Dk = q.shape
        qd = q.astype(k.dtype)
        kw = dict(scale=blk.attn.scale, short_dense=True)
        if not blk.sliding:
            o, lse = pk.flash_attention_with_lse(qd[None], k[None], v[None],
                                                 causal=True, **kw)

            def earlier(c, carry):
                kc, vc = (jax.lax.dynamic_slice(
                    b, (c * Q, 0, 0), (Q,) + b.shape[1:])[None]
                    for b in bufs)
                oc, lc = pk.flash_attention_with_lse(qd[None], kc, vc, **kw)
                return _merge(*carry, oc.astype(jnp.float32), lc)
            o, _ = jax.lax.fori_loop(0, j, earlier,
                                     (o.astype(jnp.float32), lse))
            return o[0]
        W = self.window
        o, lse = pk.flash_attention_with_lse(
            qd[None], k[None], v[None], causal=True, window=W, sink=sink,
            **kw)
        o, lse = o[0].astype(jnp.float32), lse[0]
        # the block's first queries also see keys before the block: query
        # i sees the c-th of the ``W`` before it where c > i
        m = min(W, Q)
        hk, hv = (b[-W:] for b in bufs)                    # [W, Hkv, .]
        K = hk.shape[1]
        s = jnp.einsum("qkgd,ckd->kgqc", qd[:m].reshape(m, K, H // K, Dk),
                       hk, preferred_element_type=jnp.float32) \
            * blk.attn.scale
        c = jnp.arange(W)[None, :]
        seen = (c > jnp.arange(m)[:, None]) & (q0 - W + c >= 0)    # [m, W]
        s = jnp.where(seen, s, -jnp.inf)
        top = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.where(seen, jnp.exp(s - jnp.where(
            jnp.isfinite(top), top, 0.0)), 0.0)
        z = jnp.sum(e, axis=-1)                                 # [K, G, m]
        oh = jnp.einsum("kgqc,ckd->qkgd", (e / jnp.maximum(
            z, 1e-30)[..., None]).astype(hv.dtype), hv,
            preferred_element_type=jnp.float32).reshape(m, H, -1)
        lh = jnp.moveaxis((top[..., 0] + jnp.log(z)).reshape(H, m), 0, 1)
        om, lm = _merge(o[:m], lse[:m], oh, lh)
        return jnp.concatenate([om, o[m:]], axis=0)

    def _sequence(self, params, ids, lengths, tail=None):
        """ids [1, T] (T whole blocks of ``min(block_tokens, T)``), lengths
        [1] or None -> (the hidden state at the row's last position [1, d]
        f32 — or, ``lengths`` None, every position's [1, T, d] —, state,
        stats). The row's blocks run one after another through the whole
        depth, for as long as they hold a token of the row. ``state``:
        ``k{i}`` / ``v{i}`` [1, T, ...] of every layer — or, with ``tail``
        = (pages, page_block), of a SLIDING layer the row's last ``pages``
        pages alone, [1, pages x page_block, ...]: the positions up to the
        end of the page that holds the row's last token (what the pool's
        ring takes, serving/paged.py)."""
        R, T = ids.shape
        if R != 1:
            raise ValueError(f"a chunk of {R} rows: MimoV2LM admits one row "
                             "at a time (prefill_chunk_tokens)")
        Q, W = min(self.block_tokens, T), self.window
        if T % Q:
            raise ValueError(f"a row of {T} positions is not whole blocks "
                             f"of {Q}")
        keep = W if tail is None else tail[0] * tail[1]
        if tail is not None and (Q % tail[1] or keep < W):
            raise ValueError(f"blocks of {Q} positions over pages of "
                             f"{tail[1]}, a tail of {keep} under a window of "
                             f"{W}")
        n = jnp.int32(T) if lengths is None \
            else jnp.asarray(lengths, jnp.int32)[0]
        dt = self._compute_dtype(params)
        whole = lengths is None

        def rows_of(blk, length):
            K = blk.attn.kv_heads
            return (jnp.zeros((length, K, self.d_head), dt),
                    jnp.zeros((length, K, self.d_value), dt))
        # a global layer's row so far; a sliding layer's ``keep`` positions
        # before the block and the block (and, no tail asked, its row too)
        bufs0 = [rows_of(blk, keep + Q) + (rows_of(blk, T) if tail is None
                                           else ())
                 if blk.sliding else rows_of(blk, T) for blk in self.blocks]
        last0 = jnp.zeros((T if whole else 1, self.d_model), jnp.float32)

        def block(carry):
            j, last, bufs, stats = carry
            q0 = j * Q
            pos = q0 + jnp.arange(Q, dtype=jnp.int32)
            live = pos < n
            h = self._embed(params, jax.lax.dynamic_slice(ids[0], (q0,),
                                                          (Q,)))
            new, counts, sunk = [], [], 0
            for i, blk in enumerate(self.blocks):
                p = params[f"blocks_{i}"]
                x = blk.input_norm(p["input_norm"], h)
                q, k, v = blk.attn.project(p["attn"], x, pos)
                at = (q0, 0, 0)
                if blk.sliding:
                    wk, wv, *row = bufs[i]
                    sink = p["attn"].get("sink")
                    o = self._attend_block(blk, sink, q, k, v, q0, j,
                                           (wk, wv))
                    sunk += sink is not None
                    b = (jnp.concatenate([wk[Q:], k]),
                         jnp.concatenate([wv[Q:], v])) + tuple(
                        jax.lax.dynamic_update_slice(buf, x_, at)
                        for buf, x_ in zip(row, (k, v)))
                else:
                    b = tuple(jax.lax.dynamic_update_slice(buf, x_, at)
                              for buf, x_ in zip(bufs[i], (k, v)))
                    o = self._attend_block(blk, None, q, k, v, q0, j, b)
                h = h + blk.attn.output(p["attn"], o)
                h, c = ffn_or_experts(blk, p, h, live)
                new.append(b)
                if c is not None:
                    counts.append(c)
            if whole:
                last = jax.lax.dynamic_update_slice(last, h, (q0, 0))
            else:
                at = jnp.clip(n - 1 - q0, 0, Q - 1)
                last = jnp.where((n - 1 >= q0) & (n - 1 < q0 + Q),
                                 jax.lax.dynamic_slice(h, (at, 0),
                                                       (1, h.shape[1])), last)
            lo = q0.astype(jnp.float32)
            hi = jnp.minimum(n, q0 + Q).astype(jnp.float32)

            def band(x):            # sum over i < x of min(i + 1, W)
                full = jnp.minimum(x, W)
                return full * (full + 1) / 2 + (x - full) * W
            stats = self._add_stats(
                stats, counts, live, Q,
                band_positions=Q * self.window_read_layers,
                sink_rows=jnp.sum(live, dtype=jnp.int32) * sunk,
                pairs_causal=(hi * (hi + 1) - lo * (lo + 1)) / 2,
                pairs_band=band(hi) - band(lo))
            return j + 1, last, new, stats
        j, last, bufs, stats = jax.lax.while_loop(
            lambda c: c[0] * Q < n, block,
            (jnp.int32(0), last0, bufs0, self.program_stats_zero()))
        state = {}
        for i, (blk, b) in enumerate(zip(self.blocks, bufs)):
            if blk.sliding and tail is not None:
                # the window buffer ends at the last block's end, ``j * Q``;
                # the ring's pages end with the row's last token's page
                end = ((n - 1) // tail[1] + 1) * tail[1]
                off = end - (j * Q - Q)
                b = tuple(jax.lax.dynamic_slice(
                    x, (off, 0, 0), (keep,) + x.shape[1:]) for x in b)
            state[f"k{i}"], state[f"v{i}"] = (x[None] for x in b[-2:])
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
    def _decode_layer(self, i, blk, p, h, cell, step):
        """The step's k, v (rotated, scaled) written into the layer's
        pools ``k{i}`` [P, bs, Hkv, Dk] / ``v{i}`` [P, bs, Hkv, Dv] and
        read back through the read of its kind: a global layer's at
        ``tables`` and every row to ``pos``, a sliding layer's at the ring,
        the window's rows and the sink."""
        rd = step.ringed if blk.sliding else step.full
        x = blk.input_norm(p["input_norm"], h)
        q, k, v = blk.attn.project(p["attn"], x, rd.pos)
        sink = p["attn"].get("sink")
        o, kp, vp = rd.write_and_attend(
            q, k, v, cell[f"k{i}"], cell[f"v{i}"], scale=blk.attn.scale,
            route=step.attn_route, sink=sink)
        h = h + blk.attn.output(p["attn"], o)
        h, c = ffn_or_experts(blk, p, h, step.live)
        # the note: whether this layer's read was HANDED a sink
        return h, {f"k{i}": kp, f"v{i}": vp}, c, sink is not None

    def _step_stats(self, step, notes):
        live = step.live
        n = step.full.pos.shape[0] if live is None \
            else jnp.sum(live, dtype=jnp.int32)
        return dict(sink_rows=n * sum(notes))
