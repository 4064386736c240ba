"""What a served language model is, once: the nouns a model states its cache
in, the contract the page pool (serving/paged.py) reads off it, and the two
programs every serve cell drives — ``prefill`` (an admission) and
``decode_step_paged`` (one token against the paged cache).

A family class (``DeepseekV3LM``, ``Lfm2MoeLM``, ``NemotronHLM``,
``AfmoeLM``, ``KeyeSparseLM``) derives from :class:`PagedLM` and writes
what really differs between models: its blocks, the rows it keeps
(``cache_rows``), how a chunk of prompt runs the depth (``_sequence``) and
what one layer does with one token (``_decode_layer``). Everything the pool
asks beyond that has a default here. ``TransformerLM`` derives from it for
the contract's attributes and keeps its own two programs (quantised rows,
learned positions, the prefix-hit ``prefill_paged``).
docs/design/serving.md, "What a served model states", is the prose of this
file.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..ops import pallas_kernels as pk
from ..parallel.expert_share import ProgramStats


class CacheRow(NamedTuple):
    """One array of a model's per-layer cache as it states it to the page
    pool (serving/paged.py), which allocates ``[pages, page_block, *shape]``
    of ``dtype`` filled with ``fill`` and never names an array itself.

    ``window``: the row's REACH — a layer that reads only the last
    ``window`` positions states it, and the pool keeps such a row in a RING
    of its slot's own (``[slots x ring + 1, page_block, *shape]``, position
    p in ring entry ``(p // page_block) % ring``) that stops growing with
    the context; ``None``: every position is read for as long as the
    request lives, and the row's pages grow with it.

    ``held``: the shape the pool HOLDS the row in where the model's kernels
    need one wider than the stated (KeyeSparseLM's 64-wide indexer key,
    held at the chip's 128 lanes so that a page of it can be fetched by a
    DMA): the stated row is the leading corner of the held one, what lies
    past it keeps its fill, and everything that leaves the pool (a
    shipment, ``pk.pool_rows``) is the stated row."""
    name: str
    shape: tuple
    dtype: object
    fill: float = 0.0
    window: Optional[int] = None
    held: Optional[tuple] = None


class SlotRow(NamedTuple):
    """Per-SLOT state a model states beside its pages (``cache_rows`` may
    list both): state of a fixed size whatever the context — a short
    convolution's tail, a recurrence's carry. The page pool allocates
    ``[slots, *shape]`` of ``dtype``, writes an admitted slot's entry from
    the ``[B, *shape]`` array of this name in ``prefill``'s cell, hands it
    to ``decode_step_paged`` in the cell and keeps what comes back for the
    live slots (any other slot's goes back to ``fill``: a freed slot is
    clear after the next segment), and ships it with the slot's pages."""
    name: str
    shape: tuple
    dtype: object
    fill: float = 0.0


#: tokens a chunked prefill runs through the depth at once (rows x width)
PREFILL_TOKENS = 2048
#: prompt rows of at least this many tokens are admitted ONE a chunk by the
#: deep stacks (NemotronHLM, AfmoeLM); under it a chunk fills
#: ``PREFILL_TOKENS`` with rows. A chunk of TWO rows of 1,024 never returns on
#: a v5e from 13 of NemotronHLM's layers on (6 layers: it does), with the
#: chunked scan or the flash kernel on their dense routes just the same; 8 x
#: 256, 4 x 512, 1 x 1,024 and 1 x 2,048 return at all 52. The cause is not
#: found (PERF.md sections 6 and 7, PR 35).
SOLO_ROW_TOKENS = 1024


def _dot(x, w):
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def live_row_walk(n_rows, width, chunk_tokens, n_live):
    """The shape of :func:`prefill_live_rows`' walk over ``[n_rows, width]``
    prompts of which ``n_live`` hold one: (rows a chunk — the most that
    divide ``n_rows`` within ``chunk_tokens`` of ``width``-wide rows, at
    least one — and the chunks walked). Arithmetic alone, so the traced
    walk calls it with a traced ``n_live`` and the host, which has the
    lengths, with an int: the page pool's count of the positions an
    admission ran (``prefill_positions``) is the walk's own."""
    rows = next(r for r in range(max(1, min(n_rows, chunk_tokens // width)),
                                 0, -1) if n_rows % r == 0)
    return rows, (n_live + rows - 1) // rows


def prefill_live_rows(sequence, prompt, pos, d_model, state0, stats0,
                      chunk_tokens, in_place=(), write=None):
    """The admission walk every served model shares. Rows
    are independent of one another, so the depth runs a few rows at a time
    (``chunk_tokens``, the caller's ``PREFILL_TOKENS``): what a chunk
    expands is bounded by that, not by slots x prompt bucket. And only rows
    that HOLD a prompt run at
    all: the page pool hands every admission the whole pool's width with
    length 0 in the slots it is not filling, so the rows are taken live
    ones first and the walk stops after the last chunk that has one — an
    admission costs what was admitted (but for the rows that fill up the
    last live chunk).

    ``sequence(ids [R, T0], lengths [R]) -> (h [R, T0, d] f32 — or [R, d],
    each row's hidden state at its last position already, from a sequence
    that walks a row in blocks and keeps no more —, state, stats)``;
    ``state0``: a pytree of ``[B, ...]`` buffers the chunks'
    ``state`` (same tree, ``[R, ...]``) is written into; ``stats0``: the
    tree the chunks' ``stats`` are summed into. Returns (each row's hidden
    state at its last position [B, d], state, stats); rows of length 0
    keep their zeros. ``in_place``: names of ``state0`` (a dict then)
    whose buffers are NOT fresh zeros but somebody's live arrays (the
    pool's per-slot rows, ``PagedLM.prefill(slot_state=)``): a chunk writes
    them at the rows that hold a prompt and nowhere else — the rows of
    length 0 that fill up the last chunk keep what they hold. ``write(state,
    idx, n, new) -> state``: the caller's own way of putting a chunk's
    ``new`` (rows ``idx`` of lengths ``n``) into ``state`` — the page pool's
    scatter into its pages, so that no ``[B, T0, ...]`` buffer of every
    row's keys and values stands between a chunk and the pool."""
    B, T0 = prompt.shape
    R, n_chunks = live_row_walk(B, T0, chunk_tokens,
                                jnp.sum(pos > 0, dtype=jnp.int32))
    order = jnp.argsort(pos == 0, stable=True).astype(jnp.int32)

    def chunk(carry):
        i, last, state, stats = carry
        idx = jax.lax.dynamic_slice(order, (i * R,), (R,))
        n = pos[idx]
        h, new, st = sequence(prompt[idx], n)
        last = last.at[idx].set(h if h.ndim == 2
                                else h[jnp.arange(R), n - 1])
        if write is not None:
            state = write(state, idx, n, new)
        elif in_place:
            held = jnp.where(n > 0, idx, B)         # B: dropped
            state = {k: (buf.at[held].set(new[k], mode="drop")
                         if k in in_place else buf.at[idx].set(new[k]))
                     for k, buf in state.items()}
        else:
            state = jax.tree_util.tree_map(
                lambda buf, x: buf.at[idx].set(x), state, new)
        return (i + 1, last, state,
                jax.tree_util.tree_map(jnp.add, stats, st))
    _, last, state, stats = jax.lax.while_loop(
        lambda c: c[0] < n_chunks, chunk,
        (jnp.int32(0), jnp.zeros((B, d_model), jnp.float32), state0,
         stats0))
    return last, state, stats


def paged_greedy(model, params, prompt, steps: int, page_block: int):
    """Greedy continuation through ``model.prefill`` + its paged decode
    step, one private block table a sample: prompt [B, T0] -> [B, T0 +
    steps]. The solo decode a served stream is compared with, for any
    model that states its rows (``cache_rows``: pages are cut from the
    prefill's cell, slot rows carried as they come)."""
    B = prompt.shape[0]
    nb = model.max_len // page_block
    cell, last = model.prefill(params, prompt)
    tables = 1 + jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    state = {"pos": cell["pos"]}
    for r in model.cache_rows(params):
        if isinstance(r, SlotRow):
            state[r.name] = cell[r.name]
            continue
        rows = cell[r.name].reshape((B * nb, page_block) + r.shape)
        state[r.name] = jnp.concatenate(
            [jnp.zeros((1, page_block) + r.shape, r.dtype), rows])
    cur = jnp.argmax(last, axis=-1).astype(prompt.dtype)
    out = [prompt, cur[:, None]]
    for _ in range(steps - 1):
        logits, state = model.decode_step_paged(params, state, cur, tables)
        cur = jnp.argmax(logits, axis=-1).astype(prompt.dtype)
        out.append(cur[:, None])
    return jnp.concatenate(out, axis=1)


class PagedRead(NamedTuple):
    """Where the layers of one kind write a decode step's row and what
    their read walks: ``page`` / ``row`` [B] of position ``pos`` under
    ``tables`` (the block table — or, with ``window``, the ring's), and the
    paged read's work list over them (pk.paged_work_list: one a step a
    kind, shared by every layer of the kind)."""
    pos: jax.Array
    page: jax.Array
    row: jax.Array
    tables: jax.Array
    work: tuple
    window: Optional[int] = None

    def put(self, pool, new):
        """``new`` [B, *shape] written at this step's place -> (the pool,
        its rows as stated: what a paged read takes)."""
        return pk.put_rows(pool, self.page, self.row, new)

    def attend(self, q, k_rows, v_rows, *, scale, route=None, sink=None):
        """The grouped-query read: q [B, H, D] over the live pages'
        ``k_rows`` / ``v_rows`` of ``Hkv`` heads, a KV head serving its
        group of query heads, the window's rows alone where one is
        stated; ``sink`` [H]: the heads' sink logits."""
        return pk.paged_decode_attention(
            q, k_rows, v_rows, self.tables, self.pos, scale=scale,
            work=self.work, window=self.window, sink=sink, route=route)

    def write_and_attend(self, q, k, v, k_pool, v_pool, *, scale,
                         route=None, sink=None):
        """A grouped-query layer's whole step against its two pools: the
        step's k, v written, then the read -> (o [B, H, Dv], the pools)."""
        k_pool, k_rows = self.put(k_pool, k)
        v_pool, v_rows = self.put(v_pool, v)
        return (self.attend(q, k_rows, v_rows, scale=scale, route=route,
                            sink=sink), k_pool, v_pool)


class DecodeStep(NamedTuple):
    """What the layers of one decode step share: ``full`` — the read of the
    rows that grow with the context; ``ringed`` — that of the rows that
    state a window (None where the model states none); ``page_block`` —
    positions a page; ``live`` [B] bool or None — the slots whose tokens
    count; ``extra`` — the class's own (``PagedLM._step_extra``)."""
    full: PagedRead
    ringed: Optional[PagedRead]
    page_block: int
    live: Optional[jax.Array]
    attn_route: Optional[str]
    extra: object = None


class PagedLM(ProgramStats, nn.Module):
    """A served language model: the contract ``PagePool`` reads — every
    attribute below is read plainly there, none is probed for — and the two
    programs built on it. A subclass holds ``max_len``, ``embed``,
    ``blocks``, ``norm_f`` and a head (``logits``), and writes
    ``cache_rows``, ``_sequence`` and ``_decode_layer``."""

    # -- the contract ------------------------------------------------------
    #: positions a request may reach (a multiple of the pool's page block)
    max_len: int
    #: the layers, one module each (``params["blocks_<i>"]``)
    blocks: list
    #: the decode read's registered cost model (obs/roofline.kernel_cost)
    paged_read_kernel = "paged_decode_attention"
    #: layers of a step that read a ring (``CacheRow(window=)``) so
    window_read_layers = 0
    #: the pool hands ``prefill`` its pools and its ``write``: no [slots,
    #: prompt bucket] copy of the rows stands beside the pools
    admits_in_place = False
    #: of a row that states a window the pool is handed its last ``ring``
    #: pages alone (``prefill(tail=)``), not the row: a model whose
    #: ``_sequence`` walks a row in blocks and keeps no more of such a row
    admits_window_tails = False
    #: the pool hands ``prefill`` its own slot-row arrays (``slot_state=``)
    #: and takes them back written at the filled slots' indices
    slot_rows_in_place = False
    #: what the model asks of the TPU's compiler for the program that runs
    #: its decode steps (serving/paged.py's segment, and no other)
    decode_compiler_options = None
    #: the prefix-hit admission, ``prefill_paged(params, pools, tokens,
    #: offsets, lengths, tables)``: a model without one serves with
    #: ``--no_prefix_cache``
    prefill_paged = None

    @property
    def paged_read_layers(self) -> int:
        """Layers of a decode step that make the ``paged_read_kernel``
        read over the growing pages."""
        return len(self.blocks)

    def paged_read_geometry(self, params, kv_dtype: Optional[str] = None,
                            kind: str = "full"):
        """The shape facts the read's cost model takes beside (pages,
        page_block), for the layers of ``kind`` — "full" (the growing
        pages' read, ``paged_read_kernel``) or "window" (the rings'): the
        grouped-query form, the same for both kinds unless the model's
        kinds differ (MimoV2LM: KV heads a kind, k and v widths apart)."""
        return {"n_heads": self.n_heads, "kv_heads": self.kv_heads,
                "d_head": self.d_head, "kv_dtype": None,
                "itemsize": jnp.dtype(self._compute_dtype(params)).itemsize}

    def cache_rows(self, params, kv_dtype: Optional[str] = None):
        """The model's state as it states it: a ``CacheRow`` for what lives
        in pages, a row a token, a ``SlotRow`` for what lives per slot."""
        raise NotImplementedError

    def prefill_chunk_tokens(self, width: int) -> int:
        """Tokens a chunk of the admission walk may hold (rows x width)."""
        return PREFILL_TOKENS

    def solo_row_chunk_tokens(self, width: int) -> int:
        """The deep stacks' ``prefill_chunk_tokens``: ``PREFILL_TOKENS`` of
        rows a chunk, a row of ``SOLO_ROW_TOKENS`` or more alone in its
        chunk."""
        return width if width >= SOLO_ROW_TOKENS else PREFILL_TOKENS

    def prefill_positions(self, n_rows: int, width: int, n_live: int) -> int:
        """Positions ``prefill`` runs for ``[n_rows, width]`` prompts of
        which ``n_live`` hold one: chunks walked x rows a chunk x width."""
        rows, chunks = live_row_walk(
            n_rows, width, self.prefill_chunk_tokens(width), n_live)
        return chunks * rows * width

    def admitted_positions(self, lengths, width: int) -> int:
        """Positions an admission of rows of ``lengths`` ([rows] on the
        host, 0 where a row holds no prompt) ran through the depth: the
        walk's own count."""
        return self.prefill_positions(
            len(lengths), width, sum(int(n) > 0 for n in lengths))

    # -- one of each -------------------------------------------------------
    def _compute_dtype(self, params):
        """dtype of the cache rows (follows the embedding table)."""
        return params["embed"]["w"].dtype

    def _no_kv_dtype(self, kv_dtype):
        if kv_dtype is not None:
            raise ValueError(
                f"kv_dtype {kv_dtype!r}: {type(self).__name__} keeps its "
                "rows as it states them; there is no quantised cache for "
                "this model")

    def _embed(self, params, ids):
        return self.embed(params["embed"], ids).astype(jnp.float32)

    def logits(self, params, h):
        """h [..., d] f32 -> [..., V] f32 through the final norm and the
        head, of the kind the parameters hold: ``head`` [d, V], ``head.w``
        [V, d] as published, or none — tied to the embedding."""
        x = self.norm_f(params["norm_f"], h)
        head = params.get("head")
        if head is not None and not isinstance(head, dict):
            return _dot(x, head)
        w = params["embed"]["w"] if head is None else head["w"]
        return jax.lax.dot_general(x.astype(w.dtype), w,
                                   (((x.ndim - 1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def __call__(self, params, ids, **kw):
        """ids [B, T] -> logits [B, T, V] f32."""
        h, _, _ = self._sequence(params, ids, None)
        return self.logits(params, h)

    def generate_cached(self, params, prompt, steps: int, *,
                        page_block: int = 64):
        """Greedy continuation through prefill + the paged decode step
        (one private table a sample): prompt [B, T0] -> [B, T0 + steps].
        The solo decode a served stream is compared with."""
        return paged_greedy(self, params, prompt, steps, page_block)

    # -- an admission ------------------------------------------------------
    def _sequence(self, params, ids, lengths):
        """One chunk of the admission walk through the depth: ids [R, T],
        lengths [R] or None (every position real) -> (h [R, T, d] f32 — or
        [R, d], each row's last position —, the chunk's rows by
        ``cache_rows``' names: [R, T, *shape] a ``CacheRow``, [R, *shape]
        at each row's own length a ``SlotRow``, the chunk's stats)."""
        raise NotImplementedError

    @staticmethod
    def _positions_live(ids, lengths):
        """ids [R, T], lengths [R] or None -> (positions [R, T] int32,
        which of them lie inside their row's length — None: all)."""
        positions = jnp.broadcast_to(
            jnp.arange(ids.shape[1], dtype=jnp.int32), ids.shape)
        return positions, None if lengths is None else \
            positions < jnp.asarray(lengths, jnp.int32)[:, None]

    def _blocks_of(self, width: int):
        """(block, padded width) of a row ``width`` wide, for a model whose
        ``_sequence`` walks a row in whole blocks."""
        return width, width

    def prefill(self, params, prompt, lengths=None, *,
                kv_dtype: Optional[str] = None, pad_to: Optional[int] = None,
                pools=None, write=None, slot_state=None, tail=None):
        """Run the prompts [B, T0] once -> (cell, last logits [B, V]). The
        cell holds ``pos`` (``lengths``, or T0 a row), ``stats``
        (:meth:`program_stats_zero`'s tree over the live prompt tokens) and
        every row of ``cache_rows`` by name: ``[B, pad_to, *shape]`` a
        ``CacheRow`` (``pad_to`` defaults to, and is bounded by,
        ``max_len``; the page pool asks for its prompt bucket), ``[B,
        *shape]`` a ``SlotRow``. Only the rows that HOLD a prompt run
        (:func:`prefill_live_rows`: ``prefill_chunk_tokens`` at a time, live
        rows first); a row of length 0 comes back at its fill or, where it
        filled up the last live chunk, as garbage, and its logits mean
        nothing — the pool reads neither. Only each row's last position
        reaches the head.

        The pool's three ways to take the rows (serving/paged.py
        ``_admit_fn``): none of the keywords — the cell as above, which it
        scatters; ``slot_state`` — its own ``[slots, *shape]`` arrays of the
        slot rows, which come back in the cell WRITTEN at the rows that
        hold a prompt and untouched elsewhere (``slot_rows_in_place``);
        ``pools`` + ``write`` — the page arrays themselves, every chunk's
        rows going into them through ``write(pools, idx, n, rows)`` and
        coming back written, no ``[B, T0]`` cell at all
        (``admits_in_place``, or rows that state a window). Donated
        buffers come back the same buffers. ``tail`` = (pages, page_block)
        (``admits_window_tails``): ``_sequence`` hands ``write`` a windowed
        row's last ``pages`` pages alone."""
        self._no_kv_dtype(kv_dtype)
        prompt = jnp.asarray(prompt)
        B, T0 = prompt.shape
        limit = self.max_len if pad_to is None else min(pad_to, self.max_len)
        if limit < T0:
            raise ValueError(f"prefill cache limit {limit} (pad_to/max_len) "
                             f"is narrower than the prompt ({T0})")
        pos = (jnp.full((B,), T0, jnp.int32) if lengths is None
               else jnp.asarray(lengths, jnp.int32))
        Tp = self._blocks_of(T0)[1]
        if pools is not None and Tp != T0:
            raise ValueError(f"a prompt bucket of {T0} is not whole blocks "
                             f"of {self._blocks_of(T0)[0]} positions")
        rows = self.cache_rows(params)
        given = dict(pools or {}, **(slot_state or {}))
        state0 = {r.name: jnp.full(
            (B,) + (() if isinstance(r, SlotRow) else (Tp,)) + tuple(r.shape),
            r.fill, r.dtype) for r in rows if r.name not in given}
        state0.update(given)
        if Tp != T0:
            prompt = jnp.pad(prompt, ((0, 0), (0, Tp - T0)))
        # (only a model that ``admits_window_tails`` takes the keyword)
        kw = {} if tail is None else {"tail": tail}
        last, state, stats = prefill_live_rows(
            lambda ids, n: self._sequence(params, ids, n, **kw), prompt, pos,
            params["embed"]["w"].shape[1], state0, self.program_stats_zero(),
            self.prefill_chunk_tokens(Tp), in_place=tuple(slot_state or ()),
            write=write)
        cell = {"pos": pos, "stats": stats}
        stated = {r.name: r for r in rows}
        for nm, buf in state.items():
            r = stated[nm]
            if isinstance(r, CacheRow) and nm not in given:
                # (whole blocks may reach past the limit; a fill of zero
                # pads with ``jnp.pad``'s own default)
                buf = jnp.pad(
                    buf[:, :min(Tp, limit)],
                    ((0, 0), (0, max(limit - Tp, 0)))
                    + ((0, 0),) * len(r.shape), constant_values=r.fill or 0)
            cell[nm] = buf
        return cell, self.logits(params, last)

    # -- one token against the paged cache ---------------------------------
    def _decode_layer(self, i, blk, p, h, cell, step: DecodeStep):
        """Layer ``i`` (``blk``, its parameters ``p``) of a decode step: h
        [B, d] f32, the step's ``cell`` -> (h, the layer's entries of the
        new cell by name, the experts' counts or None[, a note for
        :meth:`_step_stats`])."""
        raise NotImplementedError

    def _step_extra(self, pos, live):
        """What the class's layers share in a step beyond the reads
        (``DecodeStep.extra``)."""
        return None

    def _step_stats(self, step: DecodeStep, notes) -> dict:
        """What a step adds to the stats beyond the experts' counts, by
        name (``notes``: the layers' fourth items, in order)."""
        return {}

    def decode_step_paged(self, params, cell, tokens, tables, *, live=None,
                          attn_route: Optional[str] = None,
                          ring_tables=None):
        """One incremental step against the PAGED cache: tokens [B] ->
        (logits [B, V], new cell). The cell holds ``pos`` [B], every
        ``CacheRow`` as the pool holds it (``[P, page_block, *shape]``,
        shared by every request) and every ``SlotRow`` ``[B, *shape]``;
        ``tables`` [B, NB] names the pages of each request's positions,
        sliced by the caller to the live read bound. The step's rows are
        written at page ``tables[b, pos // bs]``, row ``pos % bs`` (the
        null page 0 takes a drained slot's), and read through the layer's
        kernel over ONE work list a kind of layer. Rows that state a
        window live under ``ring_tables`` [B, ring], position p in entry
        ``(p // bs) % ring`` (None: under ``tables`` itself, a ring that
        never wraps — the solo decode). ``live`` [B] bool marks the slots
        whose tokens count (whose experts run, whose state moves);
        ``cell["stats"]``, when present, accumulates
        :meth:`program_stats_zero`'s tree."""
        pos = cell["pos"]
        paged = [r for r in self.cache_rows(params)
                 if isinstance(r, CacheRow)]
        bs = cell[paged[0].name].shape[1]
        window = next((r.window for r in paged if r.window is not None),
                      None)

        def place(table, window):
            work = pk.paged_work_list(table, pos, bs, window)
            entry = pos // bs if window is None \
                else (pos // bs) % table.shape[1]
            return table, work, window, jnp.take_along_axis(
                table, entry[:, None], axis=1)[:, 0]
        places = [place(tables, None)]
        if window is not None:
            places.append(place(tables if ring_tables is None
                                else ring_tables, window))
        row = pos % bs
        reads = [PagedRead(pos, page, row, table, work, w)
                 for table, work, w, page in places]
        step = DecodeStep(reads[0], None if window is None else reads[1],
                          bs, live, attn_route, self._step_extra(pos, live))
        h = self._embed(params, tokens)
        new_cell = {"pos": pos + 1}
        counts, notes = [], []
        for i, blk in enumerate(self.blocks):
            h, rows, c, *note = self._decode_layer(
                i, blk, params[f"blocks_{i}"], h, cell, step)
            new_cell.update(rows)
            if c is not None:
                counts.append(c)
            notes += note
        if "stats" in cell:
            new_cell["stats"] = self._add_stats(
                cell["stats"], counts, live, tokens.shape[0],
                **self._step_stats(step, notes))
        return self.logits(params, h), new_cell
