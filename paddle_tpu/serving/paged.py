"""Paged KV-cache — the serving plane's block-pool memory manager.

The pinned batcher (batcher.py) gives every slot a max_len-padded cache row:
a 9-token request in a 1024-position pool pins 1024 rows of HBM for its
whole life. Here the cache is a shared POOL of fixed-size pages
(``page_block`` positions each, vLLM-style) plus a per-slot block table
naming which pages hold positions ``j*bs .. (j+1)*bs-1`` — HBM holds live
tokens instead of padding, mixed-length sessions share one pool, pages
allocate as positions grow, and a finished/cancelled request returns its
pages to the free list immediately.

With ``prefix_cache=True`` the pool additionally shares pages ACROSS
requests through a radix index over prompt prefixes (serving/prefix.py):
a request whose prompt starts with a cached prefix admits with only the
non-shared suffix prefilled (``TransformerLM.prefill_paged`` — prefill
from an offset over pre-populated block tables), full prefix pages are
read in place under refcounts, and the last partial page copies on write
before any append touches it. Cold entries evict by measured reuse.

Invariants the exactness contract rides on:

* live slots never share an OWNED page (allocation pops unique pages);
  index-owned pages are shared read-only and never written after the
  admission wave that populated them;
* page 0 is the reserved NULL page: padded block-table entries and
  drained-slot writes land there, and no live read is ever unmasked into
  it (assembled position ``j*bs + r`` of a padded entry is > ``pos``);
* admission RESERVES each request's worst-case OWNED page count up front
  (prompt + capped budget + one segment of overshoot, minus the shared
  prefix pages), and the fit check counts index-held pages too — so a
  live slot can never fail a mid-flight allocation and a cold cache can
  always be evicted out of the way: backpressure happens at admission,
  not in the decode loop;
* the paged read (ops/pallas_kernels.paged_decode_attention) shares the
  dense-row masked-softmax formulation, so greedy tokens are bit-equal to
  the pinned pool and to solo decode (tests/test_serving_paged.py); the
  suffix-prefill hit path mirrors the same formulation and precision mix
  (tests/test_serving_prefix.py pins parity per interleaving).
"""

from __future__ import annotations

import functools
import warnings
import weakref
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.lod import bucket_length
from ..models.paged_lm import SlotRow
from ..ops import pallas_kernels as pk
from . import ship
from .batcher import Request, clip_emission, validate_request
from .prefix import Match, PrefixIndex

#: per-model shared jitted-program cache: every PagePool over the same
#: model instance resolves its admit/hit/segment programs here, keyed by
#: the full closure signature (kind, kv_dtype, page size, segment, bucket
#: dims) — pool ARRAYS are call arguments, so pools of any page count
#: share one traced executable per shape family. Weak-keyed: a gc'd model
#: drops its programs with it.
_SHARED_FNS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _shared_fn_cache(model) -> dict:
    d = _SHARED_FNS.get(model)
    if d is None:
        d = _SHARED_FNS[model] = {}
    return d


def _held_shape(a) -> tuple:
    """The shape a pool array is HELD in on its device, read off the array
    as the runtime lays it out at its STATED shape ``a.shape`` = [pages,
    page_block, *row]. Where that is row-major — pages outermost, a head's
    row minor: the order the paged read kernels take a pool in — the pool
    holds the stated shape. Where it is not (64-wide rows lie pages-MINOR
    on a TPU: ``f32[105, 64, 20, 64]`` comes ``{0,3,2,1}``), every program
    that carries such a pool re-lays all of it out and back; the pool then
    holds the row's last two dims padded up to the layout's tile, ``(24,
    128)`` for that array, which the runtime does lay out row-major (a
    rule read off this runtime, not a law of it: the pool looks at the
    padded array's own layout too and keeps the stated one where the
    padding bought nothing — ``PagePool``'s ``fresh``). The stated rows are the leading corner of the held ones, the same bytes
    the kernel's row-major copy of the stated shape had (``pk.pool_rows``
    is a bitcast in the compiled program), and the padding is bytes the
    chip's tiles held anyway. (A stated ``Format`` on the arguments would
    say the same without a second shape, and does not survive the
    persistent compile cache: an executable read back from it expects the
    default layout again — PERF.md section 6, PR 40.)"""
    layout = a.format.layout
    if a.ndim < 4 or _row_major(a):
        return tuple(a.shape)
    tile = layout.tiling[0]
    if len(tile) != 2:
        raise ValueError(f"a pool array {a.shape} lies under a first tile "
                         f"{tile} of {len(tile)} dims; the held shape pads "
                         "a row's last two")
    return tuple(a.shape[:-2]) + tuple(
        -(-n // t) * t for n, t in zip(a.shape[-2:], tile))


def _row_major(a) -> bool:
    return tuple(a.format.layout.major_to_minor) == tuple(range(a.ndim))


def _write_pages(pools, cells, src, dst, n):
    """Write pages into the pools WHERE THE PAGES LIE: for i < ``n``, pool
    page ``dst[i]`` of every array takes the ``page_block`` positions from
    ``src[i, 1] * page_block`` of row ``src[i, 0]`` of ``cells[nm]`` [rows,
    T, *shape] (``src`` [N, 2], ``dst`` [N]). One ``dynamic_update_slice``
    a page at a dynamic leading index, which the compiler performs in
    place on a row-major pool; a scatter over the pages re-lays the WHOLE
    pool out for itself and back, whatever order it is handed (PERF.md
    section 6, PR 40). The page is cut out of a view of the cell with the
    positions minor and turned round AFTER the cut: the compiler may hold
    a cell in any order (GPT-2's lies positions-minor) and then changes
    the order of the written pages alone; cut straight out of ``[rows, T,
    ...]`` it re-lays every array of the cell out first, whole. A pool
    held wider than the cell's rows (:func:`_held_shape`) takes the page
    in its leading corner. What is past ``n`` is not written at all."""
    def body(i, pools):
        out = {}
        for nm, pool in pools.items():
            bs, rest = pool.shape[1], cells[nm].shape[2:]
            view = jnp.moveaxis(cells[nm], 1, -1)        # [rows, *shape, T]
            page = jax.lax.dynamic_slice(
                view, (src[i, 0],) + (0,) * len(rest) + (src[i, 1] * bs,),
                (1,) + rest + (bs,))
            out[nm] = jax.lax.dynamic_update_slice(
                pool, jnp.moveaxis(page, -1, 1).astype(pool.dtype),
                (dst[i],) + (0,) * (pool.ndim - 1))
        return out
    return jax.lax.fori_loop(0, n, body, pools)


def _listed(ok, src_page, dst):
    """``(src, dst, n)`` of :func:`_write_pages` from [R, C] grids — pair
    (r, c) takes page ``src_page[r, c]`` of row r to pool page ``dst[r,
    c]`` — with the ``n`` pairs that are ``ok`` first."""
    order = jnp.argsort(~ok.reshape(-1), stable=True)
    return (jnp.stack([order // ok.shape[1], src_page.reshape(-1)[order]],
                      axis=1), dst.reshape(-1)[order], ok.sum())


def _copy_pages(pools, src, dst, n):
    """Copy-on-write where the pages lie: for i < ``n``, pool page
    ``dst[i]`` of every array becomes page ``src[i]`` (``src``, ``dst``
    [N]), a page at a time like :func:`_write_pages` and for its reason.
    A ``dst`` page is freshly owned and a ``src`` page a stored one, so no
    copy reads what another wrote. What is past ``n`` is not copied."""
    def body(i, pools):
        out = {}
        for nm, pool in pools.items():
            rest = (0,) * (pool.ndim - 1)
            page = jax.lax.dynamic_slice(pool, (src[i],) + rest,
                                         (1,) + pool.shape[1:])
            out[nm] = jax.lax.dynamic_update_slice(pool, page,
                                                   (dst[i],) + rest)
        return out
    return jax.lax.fori_loop(0, n, body, pools)


class _AdmitPlan:
    """One request's host-side admission plan: the prefix-index match (or
    None), the OWNED pages it must reserve, and the labels its metrics
    carry. Computed by :meth:`PagePool.plan_admission` with no pool
    mutation, so schedulers can check :meth:`PagePool.fits` (and evict)
    before committing anything."""

    __slots__ = ("prompt", "left", "plen", "tenant", "prefix_cap",
                 "match", "need_pages", "offset")

    def __init__(self, prompt, left, tenant, prefix_cap, match, need_pages):
        self.prompt = prompt
        self.left = left
        self.plen = int(prompt.size)
        self.tenant = tenant
        self.prefix_cap = prefix_cap
        self.match: Optional[Match] = match
        self.need_pages = need_pages
        self.offset = match.shared_len if match is not None else 0


class PagePool:
    """Device page pools + host page accounting + the jitted admit/segment
    programs. Compile surface is bounded exactly like the pinned batcher:
    one admission program per prompt-pad bucket, one suffix-admission
    program per (suffix-pad, read-pages) bucket pair, one segment program
    per cache-read bucket (in pages).

    The model is a ``PagedLM`` (models/paged_lm.py): everything this class
    reads off it is an attribute declared there, with its default.
    What the pool holds is what the MODEL states (``cache_rows``): a
    ``CacheRow`` becomes a page pool ``[pages, page_block, *shape]``, a
    ``SlotRow`` (models/paged_lm.py: state of a fixed size whatever the
    context, e.g. Lfm2MoeLM's convolution tails) an array ``[slots,
    *shape]`` beside the pages (``slot_state``): written for the admitted
    slots from ``prefill``'s cell, carried through the segment loop in the
    cell — which keeps the LIVE slots' rows and puts every other slot's
    back to its fill, so a freed slot is clear after the next segment with
    no program of its own — shipped by :meth:`export_slot` /
    :meth:`adopt_slot` under its own name, checked by
    :meth:`check_shipment`. A model that states no slot row runs the
    programs it ran before there were any. Slot rows are not shared by
    prefix (a model with them has no ``prefill_paged``).

    A model whose slot rows are LARGE says ``slot_rows_in_place``
    (NemotronHLM: a recurrence's carry, 49 MB a slot, 1.57 GB at 32
    slots) and the pool never holds a second copy of them: the admit
    program hands ``prefill`` the donated arrays themselves
    (``slot_state=``) and takes them back written at the admitted slots'
    indices — no full-width ``[slots, ...]`` array of fresh rows, no
    ``where`` over all of it; the segment program carries them through
    the loop, where the model updates them in place, and puts the dead
    slots' rows back to their fill by a scatter at those slots alone.
    Every other model keeps the programs it had — why there are two
    paths: the ``where`` blend is what ``lfm2-serve-rag`` is measured on
    (82 KB of tails a slot, where a second copy costs nothing), and its
    programs change only with a parent / change pair of that cell on the
    chip; Lfm2MoeLM's tails would run on the scatter path too, and the
    blend can go once such a pair shows nothing lost (PERF.md section 7).

    A ``CacheRow`` that states a REACH (``window``: AfmoeLM's sliding
    layers) is a second kind of cache in the same pool: a pool of its own,
    ``[slots x ring + 1, page_block, *shape]``, under a table of its own,
    ``ring_tables`` [slots, ring] with ``ring = ceil((window + segment) /
    page_block) + 1`` — position p of slot b lives in page ``ring_tables[b,
    (p // page_block) % ring]``, so the newest page overwrites the one that
    slid out of every reach. A slot's ring is its own from admission to
    ``free_slot``: nothing is allocated or freed as the context slides, and
    ``required_pages`` / ``fits`` / ``_ensure`` / ``page_bytes`` /
    ``live_tokens`` count the GROWING rows alone. Such a model's admission
    writes its pages IN PLACE: ``prefill`` is handed the pools and the
    pool's ``write`` (a chunk's rows into the growing rows' pages, its
    last ``ring`` pages into the ring), so no ``[slots, prompt bucket]``
    copy of every layer's keys and values stands beside the pools; its
    decode step is handed ``ring_tables``. A ring's rows belong to a slot,
    not to a prefix: no prefix index over such a model. They ship
    (:meth:`export_slot`) as the context's last ``ring`` pages under the
    rows' names. A model that states no reach runs the programs it ran.

    A model that states no reach may still say ``admits_in_place``
    (KeyeSparseLM: rows of up to 32,768 positions, three arrays a layer):
    its admission is handed the pools and the pool's ``write`` the same
    way, without a ring table, and says itself how many positions its walk
    ran (``admitted_positions``: a row's own blocks, not its bucket). A
    ``CacheRow`` may state the shape it is HELD in (``held``: that model's
    64-wide indexer key at the chip's 128 lanes, so that a kernel can
    fetch a page of it by DMA): the array is allocated at it, the stated
    row is its leading corner (``pk.put_rows`` / ``pk.pool_rows``, as for
    a row the pool pads itself), and a shipment carries the stated row.

    The geometry defaults (``page_block`` 64, ``cache_bucket`` 256,
    ``prompt_buckets`` 32..512) are the values the GPT-2 and GigaChat serve
    cells of the chip benchmark run and warm up; ``lfm2-serve-rag`` passes
    ``prompt_buckets`` 512..4096 and one ``cache_bucket`` for the whole
    table (chipbench/workloads/*.json, PERF.md section 4; ``serve
    --prompt_buckets``). Page size changes read geometry only: the assembled row
    order is the same at any block, so tokens never change
    (tests/test_serving_paged.py holds paged == solo over page sizes)."""

    def __init__(self, model, params, *, slots: int, segment: int = 32,
                 page_block: int = 64,
                 pages: Optional[int] = None,
                 cache_bucket: int = 256,
                 prompt_buckets: Sequence[int] = (32, 64, 128, 256, 512),
                 kv_dtype: Optional[str] = None,
                 prefix_cache: bool = False,
                 prefix_half_life: int = 64):
        if model.max_len % page_block:
            raise ValueError(f"page_block {page_block} must divide "
                             f"max_len {model.max_len}")
        if cache_bucket % page_block:
            raise ValueError(f"cache_bucket {cache_bucket} must be a "
                             f"multiple of page_block {page_block}")
        if prefix_cache and model.prefill_paged is None:
            raise ValueError(
                f"prefix_cache needs the model's suffix admission "
                f"(prefill_paged), which {type(model).__name__} does not "
                "have: build the pool with prefix_cache=False "
                "(serve --no_prefix_cache)")
        self.model, self.params = model, params
        self.n_slots, self.segment = slots, segment
        self.bs = page_block
        self.cache_bucket = cache_bucket
        self.prompt_buckets = prompt_buckets
        self.kv_dtype = kv_dtype
        self.nb_max = model.max_len // page_block
        # pool sizing: default worst case (every slot at max_len) + null
        # page — callers shrink it for the residency win and let admission
        # control queue what no longer fits
        self.pages = (slots * self.nb_max + 1) if pages is None else pages
        if self.pages < 2:
            raise ValueError("pages must be >= 2 (null page + one live)")
        self.capacity_pages = self.pages - 1
        self.capacity_tokens = self.capacity_pages * self.bs

        # the MODEL states its per-layer cache rows (name, trailing shape,
        # dtype, fill): k/v per head for TransformerLM, one latent row for
        # DeepseekV3LM. Everything below — allocation, donation, the
        # admission scatter, CoW copies, shipping, byte counts — follows
        # that statement; the pool names no array itself.
        stated = model.cache_rows(params, kv_dtype)
        rows = [r for r in stated if not isinstance(r, SlotRow)]
        # rows that state a reach live in a ring a slot (class docstring)
        reach = {r.window for r in rows if r.window is not None}
        if len(reach) > 1:
            raise ValueError(f"cache rows state different windows "
                             f"{sorted(reach)}: the pool holds one ring")
        self.window = reach.pop() if reach else None
        self.ring = 0 if self.window is None else \
            -(-(self.window + segment) // page_block) + 1
        if self.ring and prefix_cache:
            raise ValueError("prefix_cache over a model with windowed cache "
                             "rows: a ring's rows belong to a slot, not to "
                             "a prefix (serve --no_prefix_cache)")
        self._ring_names = {r.name for r in rows if r.window is not None}
        self.ring_tables = 1 + np.arange(
            slots * self.ring, dtype=np.int32).reshape(slots, self.ring)
        # what a program of a model with rings takes beside the others'
        # arguments (an argument, not a constant of the closure: pools of
        # any slot count share a model's programs)
        self._ring_args = (jnp.asarray(self.ring_tables),) if self.ring \
            else ()
        # a segment's step count as the program takes it, [] int32 on the
        # device, one a value: a dispatch puts nothing there for it
        self._step_args = [jnp.asarray(n, jnp.int32)
                           for n in range(segment + 1)]
        #: the rows as the model STATES them; the arrays may be held
        #: wider (:func:`_held_shape`)
        self._row_shapes = {r.name: tuple(r.shape) for r in rows}

        def fresh(r):
            # (a row that states a held shape is allocated at it: the
            # stated row is its leading corner, like a padded one's)
            a = jnp.full(((slots * self.ring + 1 if r.window else self.pages),
                          self.bs) + tuple(r.held or r.shape), r.fill,
                         r.dtype)
            held = _held_shape(a)
            if held == a.shape:
                return a
            wide = jnp.full(held, r.fill, r.dtype)
            if _row_major(wide):
                return wide
            # the padding bought nothing: the stated array, as ever
            warnings.warn(
                f"pool arrays {a.shape} lie "
                f"{a.format.layout.major_to_minor} on this device and "
                f"{wide.format.layout.major_to_minor} padded to {held}: "
                "held as stated, every program re-lays them out")
            return a
        self.pools = {r.name: fresh(r) for r in rows}
        # the arrays' bytes as stated and as they lie on the device (a
        # (20, 64) row is held (24, 128) there: PERF.md section 7)
        for state, n in (
                ("logical", sum(
                    int(np.prod(a.shape[:2] + self._row_shapes[nm]))
                    * a.dtype.itemsize for nm, a in self.pools.items())),
                ("device", sum(a.on_device_size_in_bytes()
                               for a in self.pools.values()))):
            obs.gauge_set("serving.pool_bytes_held", n, state=state)
        # ... and its per-SLOT rows (SlotRow; the class docstring says
        # what becomes of them): [slots, *shape], none for most models
        self._slot_rows = [r for r in stated if isinstance(r, SlotRow)]
        self._in_place = bool(self._slot_rows) and model.slot_rows_in_place
        self.slot_state = {
            r.name: jnp.full((slots,) + tuple(r.shape), r.fill, r.dtype)
            for r in self._slot_rows}
        self.slot_state_bytes = float(sum(
            int(np.prod(r.shape, dtype=np.int64)) * jnp.dtype(r.dtype).itemsize
            for r in self._slot_rows))          # one slot's
        obs.gauge_set("serving.slot_state_bytes_held",
                      slots * self.slot_state_bytes)
        # the decode read's registered cost model, the shape facts it
        # takes beside (pages, page_block), and how many layers of a step
        # make that read
        self._read_kernel = model.paged_read_kernel
        self._read_geom = model.paged_read_geometry(params, kv_dtype)
        self._read_layers = model.paged_read_layers
        # ... and the rings' read's, the layer KIND's own (MimoV2LM's
        # sliding layers hold other heads than its global ones)
        self._ring_geom = model.paged_read_geometry(
            params, kv_dtype, kind="window") if self.ring else None
        # one page of every GROWING array in HBM bytes — the prefix index's
        # reuse-ledger credit unit — and of every ringed one
        def page_bytes(ringed):
            return float(self.bs * sum(
                int(np.prod(r.shape, dtype=np.int64))
                * jnp.dtype(r.dtype).itemsize
                for r in rows if (r.window is not None) == ringed))
        self.page_bytes = page_bytes(False)
        if self.ring:
            obs.gauge_set("serving.ring_bytes_held",
                          (slots * self.ring + 1) * page_bytes(True))
        #: what the model made of the last program's stats (span attrs)
        self.last_stats: Dict[str, float] = {}
        self.index: Optional[PrefixIndex] = (
            PrefixIndex(self.bs, self.page_bytes,
                        half_life=prefix_half_life)
            if prefix_cache else None)

        # host accounting
        self.free: List[int] = list(range(self.pages - 1, 0, -1))
        self.tables = np.zeros((slots, self.nb_max), np.int32)
        self.pos = np.zeros((slots,), np.int64)
        self.cur = np.zeros((slots,), np.int32)
        self.slot_pages: List[List[int]] = [[] for _ in range(slots)]
        self.slot_shared: List[list] = [[] for _ in range(slots)]
        self.slot_partial: List[Optional[object]] = [None] * slots
        self.slot_reserve = np.zeros((slots,), np.int64)
        self.reserved = 0
        self.peak_pages_used = 0
        # roofline/occupancy tallies (plain host ints — always on, the
        # bench rows read them without an obs session)
        self.segments_total = 0
        self.read_bytes_total = 0
        self.occupancy_num = 0      # live tokens, summed per segment
        self.occupancy_den = 0      # allocated page capacity, ditto
        self.prompt_tokens_total = 0     # tokens ADMITTED (prompt lengths)
        self.prefill_tokens_total = 0    # tokens actually PREFILLED
        self.cow_copies_total = 0        # last-partial-page CoW copies
        self.admit_flops_total = 0.0     # PR 9 cost-ledger FLOPs of the
        #                                  admission dispatches (0 when the
        #                                  obs plane is off)
        # jitted admission/segment programs are shared PER MODEL INSTANCE
        # across pools (keys carry everything else the closures capture:
        # kv_dtype, page size, segment, bucket dims): a rebuilt
        # pool/engine over the same model re-traces nothing, and the test
        # suite's session-shared model turns the paged parity suite's
        # per-test pools into one traced executable per shape family
        self._fns = _shared_fn_cache(model)

    # -- accounting --------------------------------------------------------
    @property
    def pages_used(self) -> int:
        return self.capacity_pages - len(self.free)

    @property
    def index_pages(self) -> int:
        return self.index.total_pages if self.index is not None else 0

    def reset_tallies(self) -> None:
        """Zero the always-on measurement tallies (peak pages, segment and
        byte counts, occupancy sums, prefix/prefill token counts) —
        benches call this between a warm-up pass and the measured pass so
        warm-up traffic never leaks into the reported row."""
        self.peak_pages_used = 0
        self.segments_total = 0
        self.read_bytes_total = 0
        self.occupancy_num = 0
        self.occupancy_den = 0
        self.prompt_tokens_total = 0
        self.prefill_tokens_total = 0
        self.cow_copies_total = 0
        self.admit_flops_total = 0.0
        if self.index is not None:
            self.index.hits = self.index.misses = 0
            self.index.evictions = 0

    def required_pages(self, plen: int, left: int) -> int:
        """Worst-case pages a (prompt, capped budget) request can touch:
        positions up to plen + left - 1 live, plus up to one segment of
        discarded overshoot in its final dispatch, all capped at max_len
        (overshoot past max_len clamps into already-owned pages)."""
        hi = min(plen + left + self.segment - 1, self.model.max_len)
        return -(-hi // self.bs)

    def fits(self, need_pages: int, pending: int = 0) -> bool:
        """Can a request needing ``need_pages`` OWNED pages be admitted?
        ``pending`` is the page count the CURRENT admission wave has
        already claimed: ``reserved`` only updates inside :meth:`admit`,
        so a wave checking each request against the pre-wave value alone
        would over-commit the pool and exhaust the free list mid-decode —
        exactly the failure reservations exist to prevent. Pages held by
        the prefix index count against capacity too (they are not in the
        free list); :meth:`evict_for` reclaims cold ones."""
        return (self.reserved + pending + need_pages + self.index_pages
                <= self.capacity_pages)

    def evict_for(self, need_pages: int, pending: int = 0,
                  protect: Sequence[_AdmitPlan] = ()) -> bool:
        """Evict cold prefix-cache entries (lowest decayed measured-reuse
        score first) until ``need_pages`` fits; True on success. Pinned
        entries never evict, so this cannot steal pages from live
        readers — and ``protect`` (the current admission wave's plans,
        including the one being priced) shields entries a plan has
        MATCHED but not yet pinned: plans pin only inside :meth:`admit`,
        so without the shield a same-wave eviction could free a page a
        block table is about to reference."""
        if self.index is None:
            return self.fits(need_pages, pending)
        keep = set()
        for plan in protect:
            if plan.match is not None:
                keep.update(id(n) for n in plan.match.nodes)
                if plan.match.partial is not None:
                    keep.add(id(plan.match.partial))
        while True:
            deficit = (self.reserved + pending + need_pages
                       + self.index_pages) - self.capacity_pages
            if deficit <= 0:
                return True
            freed = self.index.evict_pages(deficit, keep)
            if not freed:
                return False
            self.free.extend(freed)
            obs.count("serving.prefix_evictions_total", len(freed))

    def clear_prefix_cache(self) -> int:
        """Drop every unpinned prefix-cache entry back to the free list
        (drain / tests); returns the number of pages reclaimed. A drain
        is deliberate, not capacity pressure, so it does not count into
        ``serving.prefix_evictions_total``."""
        if self.index is None:
            return 0
        freed = self.index.clear()
        self.free.extend(freed)
        return len(freed)

    def effective_budget(self, prompt_len: int, max_new: int) -> int:
        """The max_len-capped token budget a (prompt, max_new) can hold."""
        return min(max_new, self.model.max_len - prompt_len)

    def validate(self, r: Request,
                 max_prefix_len: Optional[int] = None) -> int:
        """Submit-time validation; returns the request's worst-case page
        need (prefix hits can only shrink it). Raises ValueError for
        malformed requests AND for requests no empty pool could ever hold
        (the page-budget check). ``max_prefix_len`` passes the recorded
        original of a router-forwarded resubmission through to the
        replay-hardening check (batcher.prefix_resubmission_error)."""
        validate_request(r, self.model, max_prefix_len=max_prefix_len)
        need = self.required_pages(
            r.prompt.size, self.effective_budget(r.prompt.size, r.max_new))
        if need > self.capacity_pages:
            who = f"request {r.rid}" if r.rid >= 0 else "request"
            raise ValueError(
                f"{who}: needs {need} pages (prompt "
                f"{r.prompt.size} + budget "
                f"{self.effective_budget(r.prompt.size, r.max_new)} at "
                f"page_block {self.bs}) but the pool holds "
                f"{self.capacity_pages}; shrink max_new or grow pages")
        return need

    def plan_admission(self, prompt: np.ndarray, left: int, *,
                       tenant: str = "default",
                       prefix_len: Optional[int] = None) -> _AdmitPlan:
        """Match ``prompt`` against the prefix index (read-only — nothing
        is pinned until :meth:`admit` commits the plan) and price the
        admission in OWNED pages. The match is capped at ``plen - 1`` so
        at least one prompt token always re-prefills: the last token's
        logits are the admission's first-token source and logits are not
        cached."""
        plen = int(prompt.size)
        match = None
        if self.index is not None:
            match = self.index.match(prompt, plen - 1)
        shared_full = len(match.nodes) if match is not None else 0
        need = self.required_pages(plen, left) - shared_full
        return _AdmitPlan(prompt, left, tenant, prefix_len, match, need)

    def _alloc(self) -> int:
        if not self.free:       # reservation accounting makes this a bug
            raise RuntimeError("page pool exhausted past its reservations")
        page = self.free.pop()
        self.peak_pages_used = max(self.peak_pages_used, self.pages_used)
        return page

    def _ensure(self, slot: int, upto_pos: int) -> None:
        """Grow ``slot``'s table to cover positions < upto_pos. Shared
        prefix pages occupy the leading table entries; only the tail past
        them allocates."""
        need = -(-min(upto_pos, self.model.max_len) // self.bs)
        have = len(self.slot_shared[slot]) + len(self.slot_pages[slot])
        while have < need:
            page = self._alloc()
            self.tables[slot, have] = page
            self.slot_pages[slot].append(page)
            have += 1

    def free_slot(self, slot: int) -> None:
        """Return every OWNED page immediately, un-pin the shared prefix
        path (refcounts decrement; pages return to the free list only at
        refcount 0 via eviction), hand the last partial prompt page to the
        index (it keys a stored tail), and park the slot: table -> null
        page, pos -> 0, so its idle decode writes/reads only ever touch
        page 0."""
        entry = self.slot_partial[slot]
        pages = self.slot_pages[slot]
        if entry is not None:
            if (self.index is not None
                    and entry.node.partials.get(entry.key) is entry):
                # the index adopts the page: it stays allocated as a cold
                # cached tail instead of returning to the free list
                self.index.adopt(entry)
                pages.remove(entry.page)
            self.slot_partial[slot] = None
        self.free.extend(pages)
        self.slot_pages[slot] = []
        if self.index is not None and self.slot_shared[slot]:
            self.index.release(self.slot_shared[slot])
        self.slot_shared[slot] = []
        self.reserved -= int(self.slot_reserve[slot])
        self.slot_reserve[slot] = 0
        self.tables[slot, :] = 0
        self.pos[slot] = 0

    # -- disaggregation: export / adopt (serving/ship.py) ------------------
    def export_slot(self, slot: int, first: int):
        """Serialize ``slot``'s prefilled page contents for shipping to a
        decode worker's pool: gather the slot's table pages from every
        pool array (k/v per layer + int8 scales) and pack them with the
        request state (``pos``/first token) under a payload CRC. Rows past
        ``pos`` inside the last page are garbage on BOTH ends — the paged
        read masks by ``pos``, so shipping them changes nothing."""
        plen = int(self.pos[slot])
        npg = -(-plen // self.bs)
        pages = jnp.asarray(self.tables[slot, :npg])
        ringed = jnp.asarray(self._ring_pages(slot, plen))
        # ... as the model states them, whatever the pool holds them in
        arrays = {nm: np.asarray(pk.pool_rows(
            arr[ringed if nm in self._ring_names else pages],
            self._row_shapes[nm])) for nm, arr in self.pools.items()}
        # the slot's per-slot rows travel under their own names, [*shape]
        arrays.update({nm: np.asarray(arr[slot])
                       for nm, arr in self.slot_state.items()})
        manifest, payload = ship.pack(arrays, plen=plen, first=first,
                                      page_block=self.bs,
                                      kv_dtype=self.kv_dtype)
        obs.count("serving.ship_pages_total", npg)
        obs.count("serving.ship_bytes_total", len(payload))
        return manifest, payload

    def _ring_pages(self, slot: int, plen: int) -> np.ndarray:
        """``slot``'s ring pages that hold rows of a context of ``plen``
        positions, oldest first: its last ``ring`` pages (all of them
        where there are fewer) — what ships of a windowed row."""
        if not self.ring:
            return np.zeros((0,), np.int32)
        npg = -(-int(plen) // self.bs)
        span = np.arange(max(npg - self.ring, 0), npg)
        return self.ring_tables[slot, span % self.ring]

    def check_shipment(self, plen: int, arrays: Dict[str, np.ndarray]
                       ) -> None:
        """Validate shipped arrays against THIS pool's layout without
        touching any page. Callable at submit time (the engine's
        ``submit_prefilled``) so a mismatched shipment is a structured
        ValueError refusal at the wire edge, never a scheduler-thread
        death mid-adoption."""
        npg = -(-int(plen) // self.bs)
        here = set(self.pools) | set(self.slot_state)
        missing = here - set(arrays)
        extra = set(arrays) - here
        if missing or extra:
            raise ValueError(
                f"shipped arrays disagree with this pool's layout "
                f"(missing {sorted(missing)}, unexpected {sorted(extra)}) "
                "— prefill and decode pools must share model depth and "
                "kv_dtype")
        for nm, rows in arrays.items():
            if nm in self.slot_state:
                ref = self.slot_state[nm]
                want = tuple(ref.shape[1:])
            else:
                ref = self.pools[nm]
                want = (min(npg, self.ring) if nm in self._ring_names
                        else npg, self.bs) + self._row_shapes[nm]
            if tuple(rows.shape) != want:
                raise ValueError(
                    f"shipped {nm!r} shape {tuple(rows.shape)} != expected "
                    f"{want} (page_block/heads/width mismatch)")
            if np.dtype(rows.dtype) != np.dtype(ref.dtype):
                raise ValueError(
                    f"shipped {nm!r} dtype {rows.dtype} != pool "
                    f"{ref.dtype}; refusing a lossy cast")

    def adopt_slot(self, slot: int, plen: int, first: int,
                   arrays: Dict[str, np.ndarray], need_pages: int) -> None:
        """Land a shipped slot (the decode half of :meth:`export_slot`):
        reserve its worst-case OWNED pages, allocate the table, scatter
        the shipped rows in BYTE-IDENTICAL (dtype-checked — a silent cast
        would break wire-greedy parity), and arm ``pos``/``cur`` so the
        next segment continues exactly where the prefill worker's
        admission stopped. Caller (the engine scheduler) has already
        checked :meth:`fits`/:meth:`evict_for` for ``need_pages``."""
        plen = int(plen)
        npg = -(-plen // self.bs)
        self.check_shipment(plen, arrays)
        self.slot_reserve[slot] = need_pages
        self.reserved += need_pages
        self.slot_shared[slot] = []
        self.slot_partial[slot] = None
        self._ensure(slot, plen)
        pages = jnp.asarray(self.tables[slot, :npg])
        ringed = jnp.asarray(self._ring_pages(slot, plen))
        for nm, rows in arrays.items():
            rows = jnp.asarray(np.ascontiguousarray(rows))
            if nm in self.slot_state:
                self.slot_state[nm] = self.slot_state[nm].at[slot].set(rows)
            else:
                at = ringed if nm in self._ring_names else pages
                self.pools[nm] = self._put_fn()(self.pools[nm], rows, at)
        self.pos[slot] = plen
        self.cur[slot] = int(first)
        self.prompt_tokens_total += plen
        obs.count("serving.adopted_total")

    # -- jitted programs ---------------------------------------------------
    @property
    def _tail(self):
        """(ring, page_block) where the model's admission hands the pool a
        windowed row's last ``ring`` pages alone (``admits_window_tails``),
        else None: what ``prefill(tail=)`` is told and ``_page_write``
        reads its source pages by."""
        return (self.ring, self.bs) if self.ring \
            and self.model.admits_window_tails else None

    def _put_fn(self):
        """:meth:`adopt_slot`'s write of shipped pages ``rows`` [n,
        page_block, *shape] at pool pages ``at`` [n]: a program like the
        others — the pool donated, the pages written where they lie — and
        not an eager scatter, which re-lays the whole array out for itself
        and back."""
        fn = self._fns.get("put")
        if fn is None:
            def put(pool, rows, at):
                n = at.shape[0]
                src = jnp.stack([jnp.zeros((n,), jnp.int32),
                                 jnp.arange(n, dtype=jnp.int32)], axis=1)
                cell = rows.reshape((1, n * rows.shape[1]) + rows.shape[2:])
                return _write_pages({"p": pool}, {"p": cell}, src, at, n)["p"]
            fn = self._fns["put"] = jax.jit(put, donate_argnums=(0,))
        return fn

    def _page_write(self, nbp: int):
        """The admission's write for a model with rings, which writes its
        pages IN PLACE a chunk at a time (``prefill(pools=, write=)``):
        ``write(ring_tables, pages, pools, idx, n, new) -> pools`` puts a
        chunk's rows (slots ``idx``, lengths ``n``; ``new[nm]`` [R, T,
        *shape], T at most ``nbp`` pages) into the pools — every page of a
        growing row that holds a prompt at ``pages[idx]``, the last
        ``ring`` pages of a row's context into its ring — a page at a time
        where it lies (:func:`_write_pages`: the pools stay in the order
        they arrive in and the decode kernels read, through the walk's
        loop too). What holds no prompt is not written. From a model that
        ``admits_window_tails``, ``new`` holds of a windowed row its last
        ``ring`` pages alone, [R, ring x page_block, *shape] — the pages up
        to the one with the row's last token — and never the row."""
        bs, ring, ringed = self.bs, self.ring, self._ring_names
        n_ring = min(nbp, ring)
        tails = self._tail is not None

        def write(ring_tables, pages, pools, idx, n, new):
            R = idx.shape[0]
            j = jnp.broadcast_to(jnp.arange(nbp)[None, :], (R, nbp))
            grown = _listed(j * bs < n[:, None], j, pages[idx])
            rung = None     # no ring: a model that only ``admits_in_place``
            if ring_tables is not None:
                top = jnp.maximum(n - 1, 0) // bs
                a = top[:, None] - (n_ring - 1) + jnp.arange(n_ring)
                rung = _listed(
                    (a >= 0) & (n > 0)[:, None],
                    jnp.broadcast_to(ring - n_ring + jnp.arange(n_ring),
                                     a.shape) if tails
                    else jnp.clip(a, 0, nbp - 1),
                    jnp.take_along_axis(ring_tables[idx], a % ring, axis=1))
            cells = {nm: rows if tails and nm in ringed else jnp.pad(
                rows, ((0, 0), (0, nbp * bs - rows.shape[1]))
                + ((0, 0),) * (rows.ndim - 2)) for nm, rows in new.items()}
            # an array at a time: one loop over all the arrays of a kind
            # keeps every one's chunk alive beside it (+0.5 GB of
            # temporaries at the 8,192-token bucket of the trinity cell)
            out = {}
            for nm in pools:
                out.update(_write_pages({nm: pools[nm]}, {nm: cells[nm]},
                                        *(rung if nm in ringed else grown)))
            return out
        return write

    def _admit_fn(self, tpad: int, nbp: int):
        key = ("admit", self.kv_dtype, self.bs, tpad, nbp, self.ring)
        fn = self._fns.get(key)
        if fn is None:
            # a compile inside a serving window names its shape bucket
            obs.instant("serving.program_build", kind="admit", tpad=tpad)
            model, kv_dtype, bs = self.model, self.kv_dtype, self.bs
            tpp, in_place = nbp * bs, self._in_place
            write = self._page_write(nbp)
            pages_in_place = bool(self.ring) or model.admits_in_place
            tail = self._tail

            def admit(params, state, prompts, lens, pages, *ring_tables):
                # the pool's three ways to take an admission's rows
                # (``PagedLM.prefill``). pad_to=tpp: a transient cell holds
                # prompt-bucket rows, not a max_len-padded
                # (pinned-pool-sized) cache — the admission HBM spike stays
                # proportional to the prompts ...
                pools, slot_state = state
                cell, last = model.prefill(
                    params, prompts, lens, kv_dtype=kv_dtype, pad_to=tpp,
                    pools=pools if pages_in_place else None,
                    write=functools.partial(
                        write, ring_tables[0] if ring_tables else None,
                        pages) if pages_in_place else None,
                    slot_state=slot_state if in_place else None, tail=tail)
                first = jnp.argmax(last, axis=-1).astype(prompts.dtype)
                if pages_in_place:
                    # ... and a model with rings (or one that says
                    # ``admits_in_place``) wrote its pages in place, a
                    # chunk at a time: no cell of keys and values at all
                    return (({nm: cell[nm] for nm in pools}, slot_state),
                            first, cell.get("stats", {}))
                # the pages that hold an admitted prompt: page j of a row
                # with j * bs < its length (a row not admitted has length
                # 0, so nothing is sent to the null page)
                j = jnp.broadcast_to(jnp.arange(nbp), pages.shape)
                out = _write_pages(
                    pools, {nm: cell[nm][:, :tpp] for nm in pools},
                    *_listed(j * bs < lens[:, None], j, pages))
                # per-slot rows: only the slots this admission fills —
                # in place, the model wrote them at those indices itself
                if in_place:
                    slot_out = {nm: cell[nm] for nm in slot_state}
                else:
                    took = lens > 0
                    slot_out = {
                        nm: jnp.where(
                            took.reshape((-1,) + (1,) * (v.ndim - 1)),
                            cell[nm].astype(v.dtype), v)
                        for nm, v in slot_state.items()}
                return (out, slot_out), first, cell.get("stats", {})
            # cost-instrumented (PR 9 ledger): under an obs session the
            # dispatch feeds fluid.device_flops_total and admit() reads
            # the per-executable FLOPs into admit_flops_total — the
            # prefill-FLOPs-per-token evidence of the prefix bench row
            fn = obs.roofline.instrument(
                jax.jit(admit, donate_argnums=(1,)), "serving.admit")
            self._fns[key] = fn
        return fn

    def _hit_fn(self, tpad: int, nbr: int):
        """The prefix-HIT admission program: copy-on-write the matched
        partial pages, then prefill only the non-shared suffixes from
        their offsets against the pre-populated block tables
        (models/transformer.py prefill_paged). One compile per
        (suffix-pad, read-pages) bucket pair."""
        key = ("hit", self.kv_dtype, self.bs, tpad, nbr)
        fn = self._fns.get(key)
        if fn is None:
            obs.instant("serving.program_build", kind="admit_prefix",
                        tpad=tpad)
            model = self.model

            def admit_sfx(params, pools, suffix, offsets, lens, tables,
                          copy_src, copy_dst, n_copies):
                # CoW first: dst pages are freshly-owned copies of the
                # stored partial pages (the first n_copies pairs)
                out = _copy_pages(pools, copy_src, copy_dst, n_copies)
                out, last = model.prefill_paged(params, out, suffix,
                                                offsets, lens, tables)
                first = jnp.argmax(last, axis=-1).astype(suffix.dtype)
                return out, first
            fn = obs.roofline.instrument(
                jax.jit(admit_sfx, donate_argnums=(1,)),
                "serving.admit_prefix")
            self._fns[key] = fn
        return fn

    def _seg_fn(self, nb: int):
        key = ("seg", self.kv_dtype, self.bs, self.segment, nb)
        fn = self._fns.get(key)
        if fn is None:
            obs.instant("serving.program_build", kind="segment", nb=nb)
            model, segment = self.model, self.segment
            fills = {r.name: r.fill for r in self._slot_rows}
            in_place = self._in_place

            def seg(params, state, tables, pos, cur, live, steps,
                    *ring_tables):
                pools, slot_state = state
                ring_kw = dict(ring_tables=ring_tables[0]) if ring_tables \
                    else {}
                cell = dict(pools, **slot_state, pos=pos)
                stats = model.program_stats_zero()
                if stats:               # (a model that counts nothing: {})
                    cell["stats"] = stats

                # ``steps`` [] int32 is TRACED, 1..segment: the one program
                # of this table width runs the steps the host asks for and
                # writes step i's tokens into row i of a [segment, slots]
                # block; the rows from ``steps`` on hold nothing
                def body(i, carry):
                    cell, cur, toks = carry
                    logits, cell = model.decode_step_paged(
                        params, cell, cur, tables, live=live, **ring_kw)
                    nxt = jnp.argmax(logits, axis=-1).astype(cur.dtype)
                    return cell, nxt, toks.at[i].set(cur)
                cell, cur, toks = jax.lax.fori_loop(
                    0, steps, body,
                    (cell, cur, jnp.zeros((segment,) + cur.shape, cur.dtype)))
                # a slot that is not live keeps no per-slot state: a freed
                # slot's rows are back at their fill after the next segment
                # (no program of its own for that; none rolls idle either)
                if in_place:
                    # ... by a scatter at the dead slots alone (a live
                    # slot's index is out of range, and dropped): no pass
                    # over the live slots' rows, no second array
                    dead = jnp.where(live, live.shape[0],
                                     jnp.arange(live.shape[0]))
                    slot_out = {
                        k: cell[k].at[dead].set(
                            jnp.asarray(fills[k], v.dtype), mode="drop")
                        for k, v in slot_state.items()}
                else:
                    slot_out = {
                        k: jnp.where(
                            live.reshape((-1,) + (1,) * (v.ndim - 1)),
                            cell[k], jnp.asarray(fills[k], v.dtype))
                        for k, v in slot_state.items()}
                state_out = ({k: cell[k] for k in pools}, slot_out)
                return (state_out, cur, jnp.moveaxis(toks, 0, 1),
                        cell.get("stats", {}))
            # what the model asks of the TPU's compiler for its decode
            # steps, if anything (another backend knows no such option)
            options = model.decode_compiler_options if pk._on_tpu() \
                else None
            fn = obs.roofline.instrument(
                jax.jit(seg, donate_argnums=(1,), compiler_options=options),
                "serving.segment")
            self._fns[key] = fn
        return fn

    # -- the two scheduler-visible operations ------------------------------
    def admit(self, group: List[Tuple[int, _AdmitPlan]]) -> Dict[int, int]:
        """Commit ``group`` = [(slot, plan)] (plans from
        :meth:`plan_admission`; caller has checked :meth:`fits` /
        :meth:`evict_for` per plan): reserve worst-case OWNED pages, pin
        matched prefix paths, allocate the prompts' tail pages, run the
        full-prefill dispatch for misses and the CoW + suffix-prefill
        dispatch for hits, insert the new full prompt blocks (and the
        last partial page) into the index, and return {slot: first
        generated token}. ``last_stats`` then holds the admission's
        account — ``rows`` that held a prompt, the ``prompt_tokens`` they
        had to run (a prefix hit's shared part is not run), the
        ``positions`` its programs ran through the depth — beside what
        the model made of its program's stats."""
        work = dict.fromkeys(("rows", "prompt_tokens", "positions"), 0)
        self.last_stats = work
        if not group:
            return {}
        if self.index is not None:
            self.index.tick += 1
        miss: List[Tuple[int, _AdmitPlan]] = []
        hits: List[Tuple[int, _AdmitPlan]] = []
        cow: Dict[int, Tuple[int, int]] = {}      # slot -> (src, dst)
        with obs.span("serving.stage", what="pages"):
            self._place(group, miss, hits, cow)

        first = np.zeros((self.n_slots,), np.int32)
        if miss:
            self._dispatch_miss(miss, first, work)
        if hits:
            self._dispatch_hits(hits, cow, first, work)
        self.last_stats = dict(self.last_stats, **work)
        if self.index is not None:
            with obs.span("serving.index"):
                for slot, plan in group:
                    self._insert_after(slot, plan)
        out = {}
        for slot, plan in group:
            self.pos[slot] = plan.plen
            self.cur[slot] = int(first[slot])
            out[slot] = int(first[slot])
        return out

    def _place(self, group, miss, hits, cow) -> None:
        """admit()'s host bookkeeping before any dispatch: reserve, pin
        matched prefix paths, allocate tail pages, and sort the group into
        ``miss`` / ``hits`` (and the copy-on-write pairs into ``cow``)."""
        for slot, plan in group:
            self.slot_reserve[slot] = plan.need_pages
            self.reserved += plan.need_pages
            self.slot_partial[slot] = None
            if plan.match is not None:
                self.index.acquire(plan.match)
                self.slot_shared[slot] = list(plan.match.nodes)
                for j, node in enumerate(plan.match.nodes):
                    self.tables[slot, j] = node.page
                if plan.offset:
                    obs.count("serving.prefix_hits_total",
                              tenant=plan.tenant)
                else:
                    obs.count("serving.prefix_misses_total",
                              tenant=plan.tenant)
            else:
                self.slot_shared[slot] = []
            self._ensure(slot, plan.plen)
            if plan.match is not None and plan.match.partial_len > 0:
                # CoW: the block after the shared full pages is this
                # slot's first OWNED page; the stored tail copies into it
                # before the suffix prefill appends a single row
                dst = self.slot_pages[slot][0]
                cow[slot] = (plan.match.partial.page, dst)
                self.cow_copies_total += 1
            self.prompt_tokens_total += plan.plen
            self.prefill_tokens_total += plan.plen - plan.offset
            (hits if plan.offset else miss).append((slot, plan))

    def _account(self, work, rows: int, prompt_tokens: int,
                 positions: int) -> None:
        """Add one admit program to the admission's account ``work``: the
        ``rows`` that held a prompt, the ``prompt_tokens`` they had to run
        and the ``positions`` the program ran through the depth (the
        model's own count of its walk, ``admitted_positions``, or the
        prefix-hit program's slots x width)."""
        for k, v in (("rows", rows), ("prompt_tokens", prompt_tokens),
                     ("positions", positions)):
            work[k] += v
        obs.count("serving.admit_positions_total", prompt_tokens,
                  state="prompt")
        obs.count("serving.admit_positions_total",
                  positions - prompt_tokens, state="padding")

    def _dispatch_miss(self, miss, first, work) -> None:
        """The cold path: ONE jitted prefill-and-scatter over the pool's
        whole width, length 0 in the slots it is not filling (the model's
        ``prefill`` walks the rows that hold a prompt); numerically
        identical to the pre-prefix-cache admission."""
        with obs.span("serving.stage", what="prompts"):
            tpad = bucket_length(max(p.plen for _, p in miss),
                                 self.prompt_buckets)
            tpad = min(tpad, self.model.max_len - 1)
            nbp = -(-tpad // self.bs)
            prompts = np.zeros((self.n_slots, tpad), np.int32)
            lens = np.zeros((self.n_slots,), np.int32)
            pages = np.zeros((self.n_slots, nbp), np.int32)
            for slot, plan in miss:
                prompts[slot, :plan.plen] = plan.prompt
                lens[slot] = plan.plen
                n = min(nbp, len(self.slot_pages[slot]))
                pages[slot, :n] = self.slot_pages[slot][:n]
            self._account(work, len(miss), int(lens.sum()),
                          self.model.admitted_positions(lens, tpad))
            fn = self._admit_fn(tpad, nbp)
            args = (self.params, (self.pools, self.slot_state),
                    jnp.asarray(prompts), jnp.asarray(lens),
                    jnp.asarray(pages)) + self._ring_args
        with obs.span("serving.dispatch", program="admit"):
            (self.pools, self.slot_state), f, stats = fn(*args)
            obs.count("serving.admit_pages_written_total",
                      int((-(-lens // self.bs)).sum()))
            obs.count("serving.admit_pages_bucket_total", self.n_slots * nbp)
            self._note_admit_cost(fn, args)
            if self.slot_state:
                obs.count("serving.slot_state_writes_total", len(miss))
        with obs.span("serving.fetch", program="admit"):
            f = np.asarray(f)
            self._note_stats(stats, "admit")
        for slot, _ in miss:
            first[slot] = f[slot]

    def _dispatch_hits(self, hits, cow, first, work) -> None:
        """The warm path: CoW copies + suffix prefill from each slot's
        offset, reading the shared prefix pages through the block table."""
        with obs.span("serving.stage", what="suffixes"):
            max_sfx = max(p.plen - p.offset for _, p in hits)
            tpad = min(bucket_length(max_sfx, self.prompt_buckets),
                       self.model.max_len - 1)
            nbr = -(-min(bucket_length(max(p.plen for _, p in hits),
                                       self.prompt_buckets),
                         self.model.max_len) // self.bs)
            suffix = np.zeros((self.n_slots, tpad), np.int32)
            offsets = np.zeros((self.n_slots,), np.int32)
            lens = np.zeros((self.n_slots,), np.int32)
            src = np.zeros((self.n_slots,), np.int32)
            dst = np.zeros((self.n_slots,), np.int32)
            for slot, plan in hits:
                sfx = plan.prompt[plan.offset:]
                suffix[slot, :sfx.size] = sfx
                offsets[slot] = plan.offset
                lens[slot] = sfx.size
            # the copy-on-write pairs, listed first (the program copies
            # that many and no null page onto itself)
            pairs = [cow[slot] for slot, _ in hits if slot in cow]
            for i, pair in enumerate(pairs):
                src[i], dst[i] = pair
            # prefill_paged runs every slot at the suffix bucket's width
            self._account(work, len(hits), int(lens.sum()),
                          self.n_slots * tpad)
            fn = self._hit_fn(tpad, nbr)
            args = (self.params, self.pools, jnp.asarray(suffix),
                    jnp.asarray(offsets), jnp.asarray(lens),
                    jnp.asarray(self.tables[:, :nbr]), jnp.asarray(src),
                    jnp.asarray(dst), jnp.int32(len(pairs)))
        with obs.span("serving.dispatch", program="admit_prefix"):
            self.pools, f = fn(*args)
            self._note_admit_cost(fn, args)
            # modeled HBM bytes of the gathered prefix read (the hit path's
            # bytes term), through the ONE registered model
            read = obs.roofline.kernel_cost(
                "paged_prefill_attention", batch=self.n_slots, pages=nbr,
                page_block=self.bs, layers=len(self.model.blocks),
                **self._read_geom) or 0.0
            obs.count("kernels.bytes_total", read,
                      kernel="paged_prefill_attention")
        with obs.span("serving.fetch", program="admit_prefix"):
            f = np.asarray(f)
        for slot, _ in hits:
            first[slot] = f[slot]

    def _note_admit_cost(self, fn, args) -> None:
        """Accumulate the admission executable's FLOPs from the PR 9 cost
        ledger (None while the obs plane is off or analysis failed) —
        benchmarks/serving_prefix.py divides this by prompt tokens for
        its prefill-FLOPs-per-token column."""
        cost = fn.cost_of(*args)
        if cost is not None and cost.flops:
            self.admit_flops_total += cost.flops

    def _insert_after(self, slot: int, plan: _AdmitPlan) -> None:
        """Grow the radix index from this admission: every full prompt
        block past the matched depth becomes a shared node (the slot's
        page transfers to the index, or dedups onto an existing node's
        page), and a partial prompt tail registers for copy-on-write
        sharing. ``prefix_len`` (when declared) caps what is cached so
        unique continuations never pollute the index."""
        idx = self.index
        prompt, plen = plan.prompt, plan.plen
        cap = plen if plan.prefix_cap is None else min(plan.prefix_cap,
                                                       plen)
        q0 = len(plan.match.nodes) if plan.match is not None else 0
        parent = (plan.match.nodes[-1] if plan.match is not None
                  and plan.match.nodes else idx.root)
        kfull = cap // self.bs
        for j in range(q0, kfull):
            page = self.slot_pages[slot].pop(0)
            key = tuple(int(t) for t in prompt[j * self.bs:
                                               (j + 1) * self.bs])
            node, created = idx.insert_full(parent, key, page)
            if created:
                # first use counts as one reuse credit, so a brand-new
                # prefix survives an eviction scan long enough to be hit
                idx._credit(node, idx.page_bytes)
            else:
                # duplicate admission (e.g. two misses sharing a prefix
                # in one wave): keep the existing shared page, free ours
                self.free.append(page)
                self.tables[slot, j] = node.page
            idx.ref(node)
            self.slot_shared[slot].append(node)
            # the page is no longer (to be) owned by the slot
            self.slot_reserve[slot] -= 1
            self.reserved -= 1
            parent = node
        tail = tuple(int(t) for t in prompt[kfull * self.bs:cap])
        if tail and kfull >= q0 and self.slot_pages[slot]:
            entry = idx.insert_partial(parent, tail,
                                       self.slot_pages[slot][0], slot)
            if entry is not None:
                idx._credit(entry, idx.page_bytes * len(tail) / self.bs)
                self.slot_partial[slot] = entry

    def run_segment(self, live: Sequence[int],
                    steps: Optional[int] = None) -> np.ndarray:
        """One decode segment across the whole pool: ``steps`` decode steps
        (``segment``, the most a dispatch runs, unless the caller knows
        that no live slot can use as many); returns the emitted token block
        [slots, steps] (drained slots' rows are garbage). The count is an
        ARGUMENT of the table width's one program, not a program of its
        own. Grows live slots' tables first, so no mid-loop allocation
        exists. A slot outside ``live`` decodes from pos 0 against its null
        table: one work item of the paged read, page 0."""
        steps = self.segment if steps is None else int(steps)
        if not 1 <= steps <= self.segment:
            raise ValueError(f"steps {steps} outside 1..{self.segment}")
        with obs.span("serving.stage", what="tables"):
            for i in live:
                self._ensure(i, int(self.pos[i]) + self.segment)
            max_pos = max((int(self.pos[i]) for i in live), default=0)
            cache_len = min(
                -(-(max_pos + self.segment + 1) // self.cache_bucket)
                * self.cache_bucket, self.model.max_len)
            nb = cache_len // self.bs
            fn = self._seg_fn(nb)
            idx = np.asarray(live, np.int64)
            pos = np.zeros((self.n_slots,), np.int32)
            pos[idx] = self.pos[idx].clip(0, self.model.max_len - 1)
            alive = np.zeros((self.n_slots,), bool)
            alive[idx] = True
            args = (self.params, (self.pools, self.slot_state),
                    jnp.asarray(self.tables[:, :nb]), jnp.asarray(pos),
                    jnp.asarray(self.cur), jnp.asarray(alive),
                    self._step_args[steps]) + self._ring_args
        with obs.span("serving.dispatch", program="segment"):
            (self.pools, self.slot_state), cur, toks, stats = fn(*args)
            obs.count("decode.dispatches_total", route="serve_segment")
            # the paged read's programs this segment, from the host's own
            # pos (pk.paged_work_list's rule, step by step) against the
            # whole table's — one per (layer, step, slot, page)
            calls = self._read_layers
            ran = np.arange(steps, dtype=np.int32)
            walked = calls * int(np.minimum(
                (pos[:, None] + ran[None, :]) // self.bs + 1, nb).sum())
            obs.count("serving.decode_pages_walked_total", walked)
            obs.count("serving.decode_pages_table_total",
                      calls * steps * self.n_slots * nb)
            # modeled cache-read bytes through the ONE registered model of
            # the model's own decode read (ops/pallas_kernels
            # ._paged_decode_attention_bytes / _paged_latent_attention_bytes)
            # — the same resolution the bench rows and the roofline ledger
            # use
            read = obs.roofline.kernel_cost(
                self._read_kernel, pages=walked, page_block=self.bs,
                **self._read_geom) or 0.0
            obs.count("kernels.bytes_total", read, kernel=self._read_kernel)
            reach = self._note_ring_reads(pos, ran, idx) if self.ring \
                else {}
            self.segments_total += 1
            self.read_bytes_total += read
            self.occupancy_num += self.live_tokens(live)
            self.occupancy_den += max(self.pages_used, 1) * self.bs
            self.pos[idx] += steps
        with obs.span("serving.fetch", program="segment"):
            self.cur = np.array(cur)  # writable copy: admit() merges into it
            self._note_stats(stats, "segment")
            self.last_stats = dict(self.last_stats, **reach)
            return np.asarray(toks)[:, :steps]        # [slots, steps]

    def _note_ring_reads(self, pos, steps, live) -> Dict[str, int]:
        """A segment's reads of a model with rings, from the host's own
        ``pos``: the ring pages the windowed read's programs walked (every
        slot, as pk.paged_work_list(window=) lists them, x the layers that
        read so) with their modeled bytes, and the rows a windowed / a full
        layer's read COVERED over the live slots and the steps — what the
        enclosing ``serving.segment`` span carries."""
        at = pos[:, None].astype(np.int64) + steps[None, :]
        walked = self.model.window_read_layers * int(
            (at // self.bs - np.maximum(at - self.window + 1, 0) // self.bs
             + 1).sum())
        obs.count("kernels.bytes_total", obs.roofline.kernel_cost(
            "paged_window_attention", pages=walked, page_block=self.bs,
            **self._ring_geom) or 0.0, kernel="paged_window_attention")
        rows = {"window_rows": int(np.minimum(at[live] + 1,
                                              self.window).sum()),
                "full_rows": int((at[live] + 1).sum())}
        for kind in ("window", "full"):
            obs.count("serving.cache_rows_read_total", rows[f"{kind}_rows"],
                      kind=kind)
        return rows

    def _note_stats(self, stats, program: str) -> None:
        """What a program returned beside its tokens (a model with
        ``program_stats_zero``; nothing otherwise), fetched here and handed
        to the model, which counts it and names what the enclosing span
        should carry (``last_stats``)."""
        self.last_stats = (self.model.note_program_stats(
            {k: np.asarray(v) for k, v in stats.items()}, program)
            if stats else {})

    def live_tokens(self, live: Sequence[int]) -> int:
        """Cache rows written across ``live`` slots (occupancy numerator).
        Rows 0..pos-1 exist (each step writes AT pos then advances), so the
        count is pos, capped at max_len where overshoot writes clamp.
        Shared prefix rows count once per READER (each slot's positions
        include them), so occupancy can legitimately exceed 1.0 under
        prefix sharing — the sharing win made visible."""
        return int(sum(min(int(self.pos[i]), self.model.max_len)
                       for i in live))

    def prefix_stats(self) -> Dict[str, float]:
        """Host tallies for stats()/benches: hit/miss counts, shared and
        cached page counts, prefill-vs-prompt token totals."""
        out = {"prefix_cache": 1.0 if self.index is not None else 0.0,
               "prompt_tokens": self.prompt_tokens_total,
               "prefill_tokens": self.prefill_tokens_total,
               "cow_copies": self.cow_copies_total}
        if self.index is not None:
            out.update(self.index.stats())
        return out


class PagedBatcher:
    """Continuous batching over the paged pool — same serve() contract as
    :class:`~paddle_tpu.serving.batcher.ContinuousBatcher` (greedy outputs
    token-for-token equal to solo decode; schedule is a throughput knob
    only), with cache residency proportional to LIVE tokens instead of
    slots * max_len. ``prefix_cache=True`` turns on cross-request prefix
    sharing (copy-on-write radix index; see :class:`PagePool`)."""

    def __init__(self, model, params, *, slots: int = 8, segment: int = 32,
                 page_block: int = 64,
                 pages: Optional[int] = None,
                 cache_bucket: int = 256,
                 prompt_buckets: Sequence[int] = (32, 64, 128, 256, 512),
                 schedule: str = "longest_first",
                 kv_dtype: Optional[str] = None,
                 prefix_cache: bool = False):
        if schedule not in ("longest_first", "fifo"):
            raise ValueError(f"unknown schedule {schedule!r}")
        self.model, self.params = model, params
        self.schedule = schedule
        self.pool = PagePool(model, params, slots=slots, segment=segment,
                             page_block=page_block, pages=pages,
                             cache_bucket=cache_bucket,
                             prompt_buckets=prompt_buckets,
                             kv_dtype=kv_dtype, prefix_cache=prefix_cache)

    def _effective_budget(self, r: Request) -> int:
        return self.pool.effective_budget(r.prompt.size, r.max_new)

    def validate(self, r: Request) -> int:
        return self.pool.validate(r)

    def serve(self, requests: Sequence[Request]) -> Dict[int, np.ndarray]:
        pool = self.pool
        queue = list(requests)
        for r in queue:
            self.validate(r)
        if self.schedule == "longest_first":
            queue.sort(key=lambda r: -self._effective_budget(r))
        slots: List[Optional[Request]] = [None] * pool.n_slots
        left = np.zeros((pool.n_slots,), np.int64)
        outs: List[List[int]] = [[] for _ in range(pool.n_slots)]
        results: Dict[int, np.ndarray] = {}

        def admit():
            group, pending = [], 0
            for i in range(pool.n_slots):
                if slots[i] is not None or not queue:
                    continue
                r = queue[0]
                plan = pool.plan_admission(
                    r.prompt, self._effective_budget(r), tenant=r.tenant,
                    prefix_len=r.prefix_len)
                if not pool.evict_for(plan.need_pages, pending,
                                      protect=[p for _, p in group]
                                      + [plan]):
                    break          # head-of-line: wait for pages to free
                pending += plan.need_pages
                queue.pop(0)
                slots[i] = r
                left[i] = self._effective_budget(r)
                outs[i] = []
                group.append((i, plan))
            pool.admit(group)

        admit()
        while any(s is not None for s in slots):
            live = [i for i, s in enumerate(slots) if s is not None]
            # no more steps than the longest live budget (the block's first
            # token is the prefill's, so budgets count from it)
            block = pool.run_segment(
                live, min(pool.segment, int(left[live].max())))
            for i in live:
                r = slots[i]
                take, done, _ = clip_emission(block[i], int(left[i]),
                                              r.eos_id)
                outs[i].extend(int(t) for t in take)
                obs.count("decode.tokens_total", len(take), route="serve")
                left[i] -= len(take)
                if done:
                    results[r.rid] = np.asarray(outs[i], np.int32)
                    slots[i] = None
                    pool.free_slot(i)   # pages return BEFORE next admit
            admit()
        return results
