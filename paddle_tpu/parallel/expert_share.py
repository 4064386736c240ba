"""One chip's share of a routed-expert layer (DeepSeek-V3's router).

``parallel/moe.py`` is the GShard layer: softmax gate, fixed capacity,
over-capacity tokens dropped, all experts on the mesh. This layer is what
expert parallelism asks of ONE chip of a wide deployment: it is TOLD which
experts it holds (``experts_held``), routes every token over ALL
``n_experts`` (the router keeps its published width), and computes the part
of the result its own experts give, plus the shared expert that every chip
computes alike. A (token, choice) that lands on a held expert is always
computed — there is no capacity — and one that lands elsewhere is left out:
that partial result is what goes on. No code stands in for the absent chips
or their exchange; summed over all shares (the shared expert counted once)
the parts add up to the whole layer (tests/test_deepseek_v3.py).

Routing, in float32: ``s = sigmoid(y W_r)``; SELECTION uses ``s + bias``:
the experts form ``n_group`` groups, a group scores the sum of its top 2,
the best ``topk_group`` groups stay, the top ``top_k`` experts inside them
are chosen; WEIGHTS are the original ``s`` at the chosen experts, divided by
their sum (+ ``norm_eps``), times ``routed_scale``. ``n_group`` 1 is the
router without groups (LFM2's: top ``top_k`` of ``s + bias`` over all the
experts, ``norm_eps`` 1e-6 as published); 1e-20 is DeepSeek-V3's.

The held experts' products are GROUPED: the (token, choice) pairs that
landed here are sorted by expert and laid out in tiles of ``tm`` rows, each
tile one expert's (ops/pallas_kernels.grouped_matmul). Work follows the
pairs that landed here, not the pairs routed: in a program with more pairs
than ``CHUNK`` the sorted pairs are walked ``CHUNK`` at a time for as long
as held pairs remain, so the worst case (every pair lands here) is computed
exactly and the usual case costs its own size.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..nn.initializer import normal, zeros
from ..ops import pallas_kernels as pk

#: (token, choice) pairs gathered at once: bounds the gathered activations
#: ([CHUNK + held * tm, d_model]) whatever the program's token count
CHUNK = 8192


def tile_rows(n_pairs: int) -> int:
    """``tm``, the rows of a tile, for a program of ``n_pairs`` (token,
    choice) pairs: short tiles for a decode step's few, tall ones for an
    admission's, the tallest where the pairs are walked ``CHUNK`` at a
    time."""
    return 16 if n_pairs <= 1024 else 128 if n_pairs <= CHUNK else 256


def row_tiles(counts, n_pairs: int):
    """The row tiles the grouped products walk for ``counts`` [..., held]
    held pairs an expert in a program of ``n_pairs`` pairs: Σ ceil(count /
    tm), an expert's run cut where the walk in ``CHUNK``s cuts it (each
    chunk lays its own tiles out). int32 scalar."""
    tm = tile_rows(n_pairs)
    end = jnp.cumsum(counts, axis=-1)
    if n_pairs > CHUNK:
        cut = jnp.arange(-(-n_pairs // CHUNK), dtype=jnp.int32) * CHUNK
        counts = jnp.clip(jnp.minimum(end[..., None], cut + CHUNK)
                          - jnp.maximum((end - counts)[..., None], cut),
                          0, None)
    return jnp.sum((counts + tm - 1) // tm, dtype=jnp.int32)


def route(scores_logits, bias, *, n_group: int, topk_group: int, top_k: int,
          routed_scale: float, norm_eps: float = 1e-20,
          score: str = "sigmoid"):
    """scores_logits [T, E] f32 (``y W_r``), bias [E] or None -> (experts
    [T, top_k] int32, weights [T, top_k] f32). ``score``: the function the
    model STATES — "sigmoid" of each logit (DeepSeek-V3 and its kin), or
    "softmax" over ALL ``E`` logits (the width the router is published at,
    before any share is applied)."""
    T, E = scores_logits.shape
    if score == "sigmoid":
        s = jax.nn.sigmoid(scores_logits.astype(jnp.float32))
    elif score == "softmax":
        s = jax.nn.softmax(scores_logits.astype(jnp.float32), axis=-1)
    else:
        raise ValueError(f"unknown router score function {score!r}")
    pick = s if bias is None else s + bias.astype(jnp.float32)
    if n_group > 1:
        grouped = pick.reshape(T, n_group, E // n_group)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, keep = jax.lax.top_k(group_score, topk_group)        # [T, kg]
        kept = jnp.zeros((T, n_group), bool).at[
            jnp.arange(T)[:, None], keep].set(True)
        pick = jnp.where(jnp.repeat(kept, E // n_group, axis=1), pick,
                         -jnp.inf)
    _, experts = jax.lax.top_k(pick, top_k)
    w = jnp.take_along_axis(s, experts, axis=1)
    w = w / (jnp.sum(w, axis=1, keepdims=True) + norm_eps) * routed_scale
    return experts.astype(jnp.int32), w


def tile_layout(group_of, n_groups: int, tm: int):
    """Lay ``A`` rows out in tiles of ``tm``, every tile one group's.

    group_of [A] int32 in 0..n_groups, where ``n_groups`` itself means
    "not here". Returns (src [M] int32 — the row each padded position
    reads, ``A`` where it is padding; tile_group [M // tm] int32; n_tiles
    [1] int32; counts [n_groups] int32) with the static
    ``M = ceil(A / tm) * tm + n_groups * tm``. Tiles are SORTED BY GROUP
    (group 0's tiles, then group 1's ...), and callers rely on it:
    pk.grouped_matmul fetches a group's matrix once for the run of
    adjacent tiles that name it."""
    A = group_of.shape[0]
    M = -(-A // tm) * tm + n_groups * tm
    counts = jnp.zeros((n_groups + 1,), jnp.int32).at[group_of].add(1)
    counts = counts[:n_groups]
    tiles = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    row_start = (tile_end - tiles) * tm                        # padded
    first = jnp.cumsum(counts) - counts                        # in sorted
    order = jnp.argsort(group_of, stable=True).astype(jnp.int32)
    g = group_of[order]
    here = g < n_groups
    gc = jnp.minimum(g, n_groups - 1)
    dest = row_start[gc] + jnp.arange(A, dtype=jnp.int32) - first[gc]
    src = jnp.full((M,), A, jnp.int32).at[
        jnp.where(here, dest, M)].set(order, mode="drop")
    tile_group = jnp.minimum(
        jnp.sum(jnp.arange(M // tm, dtype=jnp.int32)[:, None]
                >= tile_end[None, :], axis=1, dtype=jnp.int32),
        n_groups - 1)
    return src, tile_group, tile_end[-1:], counts


class ExpertShare(nn.Module):
    """The routed experts this chip holds + the shared expert.

    ``experts_held``: the global ids of the experts whose weights live here
    (``w_gate``/``w_up`` [held, d, f] — [held, f, d] where
    ``up_transposed`` — and ``w_down`` [held, f, d], in that order).
    ``shared=False`` leaves the shared expert out (a share that is summed
    with another's).

    An expert's FORM is stated, not assumed: ``gated=True`` is SwiGLU,
    ``down(silu(gate(y)) * up(y))`` — three matrices, three grouped
    products (DeepSeek-V3, LFM2); ``gated=False`` is ``down(relu(up(y))^2)``
    — ``w_up`` and ``w_down`` only, two grouped products (Nemotron-H's
    ``relu2``). The shared expert has the same form at ``shared_width``
    (``n_shared * d_expert`` unless stated: Nemotron-H's is 3712 beside
    experts of 1856)."""

    def __init__(self, d_model: int, d_expert: int, *, n_experts: int,
                 experts_held: Sequence[int], top_k: int, n_group: int,
                 topk_group: int, routed_scale: float,
                 norm_eps: float = 1e-20, n_shared: int = 1,
                 shared: bool = True, gated: bool = True,
                 shared_width: Optional[int] = None,
                 up_transposed: bool = False, score: str = "sigmoid",
                 bias: bool = True, dtype=jnp.float32,
                 init_std: float = 0.02):
        super().__init__()
        self.gated = gated
        # gate / up held [held, f, d] — as published, [out, in] — for an
        # expert width that is no multiple of the lane width (1856), so
        # that the multiple (d) is minor (pk.grouped_matmul says why)
        self.up_transposed = up_transposed
        held = [int(e) for e in experts_held]
        if not held or len(set(held)) != len(held) or \
                min(held) < 0 or max(held) >= n_experts:
            raise ValueError(f"experts_held {held} must be distinct ids in "
                             f"0..{n_experts - 1}")
        self.n_experts, self.held = n_experts, held
        self.route_kw = dict(n_group=n_group, topk_group=topk_group,
                             top_k=top_k, routed_scale=routed_scale,
                             norm_eps=norm_eps, score=score)
        # global expert id -> local index; n_held where it is not here
        table = np.full((n_experts,), len(held), np.int32)
        table[held] = np.arange(len(held), dtype=np.int32)
        self._local = table
        init = normal(0.0, init_std)
        self.param("w_router", (d_model, n_experts), init, dtype=dtype)
        if bias:
            self.param("e_bias", (n_experts,), zeros, dtype=jnp.float32)
        up = (len(held), d_expert, d_model) if self.up_transposed \
            else (len(held), d_model, d_expert)
        if gated:
            self.param("w_gate", up, init, dtype=dtype)
        self.param("w_up", up, init, dtype=dtype)
        self.param("w_down", (len(held), d_expert, d_model), init,
                   dtype=dtype)
        width = shared_width or n_shared * d_expert
        if shared and width:
            self.shared = (nn.SwiGLU if gated else nn.ReluSquaredMLP)(
                d_model, width, w_init=init, dtype=dtype)
        else:
            self.shared = None

    def routing(self, params, y):
        """(experts [T, k] global ids, weights [T, k]) — f32 throughout,
        the router product at full precision."""
        logits = jnp.dot(y.astype(jnp.float32),
                         params["w_router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        return route(logits, params.get("e_bias"), **self.route_kw)

    def _held_part(self, params, y, tok, local, w, tm, route_):
        """Σ over the pairs (tok, local expert, weight) of w * expert_e(y):
        one pass of the grouped products (three of a gated expert, two of
        a ``relu2`` one) over a tile layout."""
        T, d = y.shape
        A = tok.shape[0]
        n_held = len(self.held)
        src, tile_group, n_tiles, _ = tile_layout(local, n_held, tm)
        ok = src < A
        srcc = jnp.minimum(src, A - 1)
        rows = y[tok[srcc]]                                    # [M, d]
        kw = dict(tm=tm, route=route_)
        up = dict(kw, transposed=self.up_transposed)
        if self.gated:
            g = pk.grouped_matmul(rows, params["w_gate"], tile_group,
                                  n_tiles, **up)
            u = pk.grouped_matmul(rows, params["w_up"], tile_group, n_tiles,
                                  **up)
            a = (jax.nn.silu(g) * u).astype(y.dtype)
        else:
            u = pk.grouped_matmul(rows, params["w_up"], tile_group, n_tiles,
                                  **up)
            a = jnp.square(jax.nn.relu(u)).astype(y.dtype)
        out = pk.grouped_matmul(a, params["w_down"], tile_group, n_tiles,
                                **kw)
        # rows past the tiles in use are undefined on the kernel route
        out = jnp.where(ok[:, None], out * w[srcc][:, None], 0.0)
        return jnp.zeros((T, d), jnp.float32).at[
            jnp.where(ok, tok[srcc], T)].add(out, mode="drop")

    def __call__(self, params, y, live=None, *, route_: Optional[str] = None,
                 **kw):
        """y [T, d] (normed, f32) -> (out [T, d] f32, counts [held] int32:
        the live (token, choice) pairs that landed on each held expert).
        ``live`` [T] bool leaves dead rows (drained slots, prompt padding)
        out of the experts' work and of the counts."""
        T, d = y.shape
        dt = params["w_down"].dtype
        experts, weights = self.routing(params, y)
        k = experts.shape[1]
        local = jnp.asarray(self._local)[experts]              # [T, k]
        if live is not None:
            local = jnp.where(live[:, None], local, len(self.held))
        local = local.reshape(-1)
        w = weights.reshape(-1)
        tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
        n_held = len(self.held)
        counts = jnp.zeros((n_held + 1,), jnp.int32).at[local].add(
            1)[:n_held]
        yb = y.astype(dt)
        A = T * k
        tm = tile_rows(A)
        if A <= CHUNK:
            out = self._held_part(params, yb, tok, local, w, tm, route_)
        else:
            # held pairs first, expert by expert; walk them CHUNK at a time
            order = jnp.argsort(local, stable=True).astype(jnp.int32)
            here = jnp.sum(counts)
            pad = -A % CHUNK
            order = jnp.concatenate([order, jnp.full((pad,), A, jnp.int32)])
            local_x = jnp.concatenate([local, jnp.full((1,), n_held,
                                                       jnp.int32)])
            tok_x = jnp.concatenate([tok, jnp.zeros((1,), jnp.int32)])
            w_x = jnp.concatenate([w, jnp.zeros((1,), w.dtype)])

            def body(carry):
                i, acc = carry
                idx = jax.lax.dynamic_slice(order, (i * CHUNK,), (CHUNK,))
                return i + 1, acc + self._held_part(
                    params, yb, tok_x[idx], local_x[idx], w_x[idx], tm,
                    route_)
            _, out = jax.lax.while_loop(
                lambda c: c[0] * CHUNK < here, body,
                (jnp.int32(0), jnp.zeros((T, d), jnp.float32)))
        if self.shared is not None:
            out = out + self.shared(params["shared"], yb)
        return out, counts


def ffn_or_experts(blk, params, h, live):
    """The second half of a block that carries ``ffn_norm`` and either a
    dense ``ffn`` or a routed ``moe`` (``blk.is_moe``): h [..., d] f32 ->
    (h + FFN(norm(h)), counts or None)."""
    y = blk.ffn_norm(params["ffn_norm"], h)
    if not blk.is_moe:
        return h + blk.ffn(params["ffn"], y), None
    flat = y.reshape(-1, y.shape[-1])
    out, counts = blk.moe(params["moe"], flat,
                          None if live is None else live.reshape(-1))
    return h + out.reshape(h.shape), counts


class ProgramStats:
    """What a served model returns beside a program's tokens, and what the
    host makes of it: the expert layers' account where the model has any
    — it gives ``n_moe`` (expert layers), ``n_held`` (experts held here)
    and ``top_k`` — and whatever a model counts beside that, by name."""

    n_moe = n_held = top_k = 0

    def program_stats_zero(self):
        """Accumulators a program returns beside its tokens: the live
        (token, choice) pairs that landed on each held expert, per expert
        layer; the held experts touched and the row tiles their grouped
        products walked (:func:`row_tiles`), each summed over steps and
        layers; the live tokens routed, summed over steps. None of them
        for a model without expert layers: no stats."""
        if not self.n_moe:
            return {}
        return {"routed": jnp.zeros((self.n_moe, self.n_held), jnp.int32),
                "touched": jnp.zeros((), jnp.int32),
                "row_tiles": jnp.zeros((), jnp.int32),
                "tokens": jnp.zeros((), jnp.int32)}

    def _add_stats(self, stats, counts, live, n_rows, **more):
        """``stats`` with the expert layers' ``counts`` of ``n_rows`` rows
        (those ``live`` marks) added, and every ``more[name]`` to the
        accumulator of its name."""
        out = dict(stats)
        if counts:
            c = jnp.stack(counts)
            n = n_rows if live is None else jnp.sum(live, dtype=jnp.int32)
            out.update(
                routed=stats["routed"] + c,
                touched=stats["touched"] + jnp.sum(c > 0, dtype=jnp.int32),
                row_tiles=stats["row_tiles"]
                + row_tiles(c, n_rows * self.top_k),
                tokens=stats["tokens"] + n)
        for k, v in more.items():
            out[k] = stats[k] + jnp.asarray(v, stats[k].dtype)
        return out

    def note_program_stats(self, stats, program: str):
        """Host side of :meth:`program_stats_zero`: count what a program
        (``admit`` or ``segment``) routed, mark it on the timeline
        (``moe.program``), and return what the enclosing span should carry
        — the pairs that landed here, the expert visits, the row tiles
        those visits were laid out in (``row_tiles / experts_touched``: how
        many tiles found their expert's matrix already fetched, plus one),
        and the busiest (layer, held expert) cell of the program."""
        from .. import obs
        routed = stats["routed"]
        here, touched = int(routed.sum()), int(stats["touched"])
        obs.count("moe.assignments_total",
                  int(stats["tokens"]) * self.top_k * self.n_moe,
                  program=program)
        obs.count("moe.assignments_here_total", here, program=program)
        obs.count("moe.experts_touched_total", touched, program=program)
        tiles = int(stats["row_tiles"])
        obs.count("moe.row_tiles_total", tiles, program=program)
        attrs = {"routed_here": here, "experts_touched": touched,
                 "row_tiles": tiles, "load_max": int(routed.max())}
        obs.instant("moe.program", program=program, **attrs)
        return attrs
