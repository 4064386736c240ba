"""Continuous (in-flight) batching for KV-cache decode — the modern serving
loop on top of the incremental-decode path (models/transformer.py
prefill/decode_step; the 2017 reference's serving plane stops at the C
inference ABI, capi/gradient_machine.h:73 — this is the modern capability
axis on top of it).

Design for the TPU/XLA regime:

* The decode state is a fixed pool of ``slots`` — per-layer KV caches
  padded to max_len plus a per-slot position vector. ``decode_step`` is
  already per-sample-positional (writes at ``pos[b]``, masks reads at
  ``j <= pos[b]``), so slots at DIFFERENT sequence positions decode in one
  batched step — the core of continuous batching.
* Host control happens only at SEGMENT boundaries: the device runs a jitted
  ``lax.scan`` of ``segment`` steps, then the host collects the emitted
  block, finishes requests (EOS / budget), and refills free slots by a
  ragged ``prefill`` scattered into the pool. Per-token host round-trips
  would pay a dispatch RTT per token; per-segment sync amortizes it 32x.
* All shapes are bucketed (prompt pad bucket, cache-read bucket, fixed
  segment) so the number of compiled programs is bounded.

Exactness: each request's greedy continuation is token-for-token identical
to running it alone through ``generate_cached`` (tests/test_serving.py).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.lod import bucket_length


#: SLO classes a request may declare — the weighted-fair scheduler's queue
#: key (serving/engine.py). "interactive" is the latency class (chat,
#: completions a human is watching); "batch" the throughput class
#: (offline eval, bulk scoring) that yields slots under contention.
SLO_CLASSES = ("interactive", "batch")

#: the bounded-cardinality contract for the ``tenant`` metric label: a
#: short identifier from a closed alphabet (no path separators, no
#: payloads), so per-tenant `serving.*` series stay a bounded enum the
#: L005 lint's value heuristics accept. The engine additionally caps the
#: number of DISTINCT tenants it will mint series for (max_tenants).
TENANT_RE = re.compile(r"^[A-Za-z0-9][A-Za-z0-9._-]{0,31}$")


@dataclass
class Request:
    """One generation request: prompt ids, generation budget, optional EOS
    (generation stops BEFORE emitting eos_id; it is not returned).

    ``tenant``/``slo`` feed multi-tenant scheduling + per-tenant metric
    labels; ``prefix_len`` (optional) declares how many leading prompt
    tokens are a SHARED prefix (a system prompt) — the prefix cache only
    INSERTS blocks inside the declared span, so one-off continuations
    never pollute the radix index (matching is always attempted)."""
    rid: int
    prompt: np.ndarray
    max_new: int
    eos_id: Optional[int] = None
    tenant: str = "default"
    slo: str = "interactive"
    prefix_len: Optional[int] = None


def prefix_resubmission_error(declared, recorded) -> Optional[str]:
    """Replay-hardening shared by engine, daemon and router: a
    router-forwarded RESUBMISSION (same submit_key — a re-route or a
    transport replay) may not declare a ``prefix_len`` exceeding the
    recorded original. An inflated declaration would cache request-unique
    continuation tokens as a "shared" prefix under the original key —
    index poisoning. Returns the structured error string (the
    ``invalid_argument`` body) or None when the declaration is honest."""
    if declared is None:
        return None
    if int(declared) > int(recorded or 0):
        return (f"resubmission declares prefix_len {int(declared)} but the "
                f"recorded original was {int(recorded or 0)} — a forwarded "
                "replay may not inflate its cached-prefix claim "
                "(replay-hardening)")
    return None


def validate_request(r: Request, model, *,
                     max_prefix_len: Optional[int] = None) -> None:
    """Normalize + reject a malformed request AT SUBMIT TIME with a precise
    ValueError — before PR 8 these surfaced as shape errors deep inside the
    ragged prefill (an empty prompt's pos==0 gather wraps; max_new<=0 used
    to idle a slot forever). Mutates ``r.prompt`` to a flat int32 array.
    The paged pool's stronger page-budget check layers on top
    (serving/paged.py PagedBatcher.validate)."""
    r.prompt = np.asarray(r.prompt, np.int32).reshape(-1)
    # engine submissions validate BEFORE a rid exists (placeholder -1);
    # their errors must not name a bogus id to the caller
    who = f"request {r.rid}" if r.rid >= 0 else "request"
    if r.prompt.size == 0:
        # prefill's ragged gather reads logits[b, pos-1]; pos==0 wraps to
        # the last padded position and the "first token" would be silent
        # garbage — exactness demands a real prompt
        raise ValueError(f"{who}: empty prompt (prefill needs at least "
                         "one token)")
    if r.max_new <= 0:
        raise ValueError(f"{who}: max_new must be >= 1, got {r.max_new}")
    if r.prompt.size + 1 > model.max_len:
        raise ValueError(f"{who}: prompt longer than max_len "
                         f"{model.max_len}")
    if not TENANT_RE.match(str(r.tenant)):
        # the tenant value becomes a metric LABEL: an unbounded / path-like
        # value here would mint unbounded series (the L005 cardinality
        # failure mode) — refuse structured at submit, not at scrape
        raise ValueError(
            f"{who}: tenant {str(r.tenant)[:40]!r} violates the bounded-"
            "cardinality label contract (need [A-Za-z0-9][A-Za-z0-9._-]"
            "{0,31})")
    if r.slo not in SLO_CLASSES:
        raise ValueError(f"{who}: unknown slo class {r.slo!r} "
                         f"(one of {SLO_CLASSES})")
    if r.prefix_len is not None:
        if int(r.prefix_len) < 0 or int(r.prefix_len) > r.prompt.size:
            raise ValueError(
                f"{who}: declared prefix_len {r.prefix_len} outside the "
                f"prompt (len {r.prompt.size}) — a shared prefix cannot "
                "be longer than the prompt that carries it")
        r.prefix_len = int(r.prefix_len)
    if max_prefix_len is not None:
        # the resubmission bound (router-forwarded replays): the recorded
        # original caps what this submission may declare
        err = prefix_resubmission_error(r.prefix_len, max_prefix_len)
        if err is not None:
            raise ValueError(f"{who}: {err}")


def clip_emission(row, left: int, eos_id: Optional[int]):
    """Budget-cap + EOS-truncate one slot's emitted token row — the ONE
    owner of the take/done/reason decision every serving loop shares
    (pinned batcher, paged batcher, engine), so the exact-greedy contract
    cannot drift between them. Returns ``(take, done, reason)``; EOS stops
    BEFORE emitting ``eos_id`` (it is never returned)."""
    take = row[:min(int(left), len(row))]
    done, reason = len(take) >= left, "length"
    if eos_id is not None:
        hits = np.nonzero(take == eos_id)[0]
        if hits.size:
            take, done, reason = take[:hits[0]], True, "eos"
    return take, done, reason


@dataclass
class _Slot:
    req: Optional[Request] = None
    left: int = 0
    out: List[int] = field(default_factory=list)


class ContinuousBatcher:
    def __init__(self, model, params, *, slots: int = 8, segment: int = 32,
                 cache_bucket: int = 256,
                 prompt_buckets: Sequence[int] = (32, 64, 128, 256, 512),
                 schedule: str = "longest_first",
                 kv_dtype: Optional[str] = None):
        """``schedule``: admission order over the request queue.
        "longest_first" (default) admits the largest generation budgets
        first — classic longest-processing-time scheduling, which shortens
        the drained-slot tail where short stragglers leave most of the pool
        idle (measured +31% delivered tok/s on a mixed U[32,256] workload
        vs "fifo"). Per-request outputs are identical either way (greedy
        decode is batch-order independent; tests/test_serving.py).

        ``kv_dtype="int8"`` holds the slot pool's KV caches quantized
        (models/transformer.py prefill) — the decode segment's HBM cache
        read halves, which matters exactly here where decode is
        cache-bytes-bound. Tokens then follow the quantized-KV numerics
        contract (docs/design/kernels.md): identical to SOLO decode at the
        same kv_dtype, approximately equal to full-precision decode."""
        if schedule not in ("longest_first", "fifo"):
            raise ValueError(f"unknown schedule {schedule!r}")
        self.model, self.params = model, params
        self.n_slots, self.segment = slots, segment
        self.cache_bucket = cache_bucket
        self.prompt_buckets = prompt_buckets
        self.schedule = schedule
        self.kv_dtype = kv_dtype
        self._seg_fns = {}      # cache_len -> jitted segment scan
        self._prefill_fns = {}  # Tpad -> jitted ragged prefill
        self._merge = None      # jitted masked slot merge

    # -- jitted pieces (cached per static shape) ---------------------------
    def _seg_fn(self, cache_len: int):
        fn = self._seg_fns.get(cache_len)
        if fn is None:
            model = self.model

            def seg(params, cell, cur):
                def body(carry, _):
                    cell, cur = carry
                    logits, cell = model.decode_step(params, cell, cur,
                                                     cache_len=cache_len)
                    nxt = jnp.argmax(logits, axis=-1).astype(cur.dtype)
                    return (cell, nxt), cur
                (cell, cur), toks = jax.lax.scan(body, (cell, cur), None,
                                                 length=self.segment)
                return cell, cur, jnp.moveaxis(toks, 0, 1)   # [B, segment]
            fn = self._seg_fns.setdefault(cache_len, jax.jit(seg))
        return fn

    def _prefill_fn(self, tpad: int):
        """Always the pool's whole width [slots, tpad]: admissions place
        each new request at ITS slot row (length 0 elsewhere: rows the
        model's ``prefill`` walks past), so the only compile axis is the
        prompt pad bucket — never the group size."""
        fn = self._prefill_fns.get(tpad)
        if fn is None:
            model = self.model
            kv_dtype = self.kv_dtype

            def pf(params, prompts, lengths):
                cell, last = model.prefill(params, prompts, lengths,
                                           kv_dtype=kv_dtype)
                first = jnp.argmax(last, axis=-1).astype(prompts.dtype)
                return cell, first
            fn = self._prefill_fns.setdefault(tpad, jax.jit(pf))
        return fn

    def _merge_fn(self):
        if self._merge is None:
            def merge(cell, cur, new_cell, new_cur, mask):
                def mix(old, new):
                    m = mask.reshape((-1,) + (1,) * (old.ndim - 1))
                    return jnp.where(m, new, old)
                cell = {k: mix(v, new_cell[k]) for k, v in cell.items()}
                return cell, jnp.where(mask, new_cur, cur)
            self._merge = jax.jit(merge)
        return self._merge

    # -- the serving loop --------------------------------------------------
    def serve(self, requests: Sequence[Request]) -> Dict[int, np.ndarray]:
        """Run every request to completion; returns {rid: generated ids}.
        Order of completion depends on scheduling; results do not."""
        queue = list(requests)
        for r in queue:
            validate_request(r, self.model)
        if self.schedule == "longest_first":
            # sort by the EFFECTIVE budget (max_len caps it) — the work a
            # slot will actually hold
            queue.sort(key=lambda r: -min(r.max_new,
                                          self.model.max_len - r.prompt.size))
        slots = [_Slot() for _ in range(self.n_slots)]
        results: Dict[int, np.ndarray] = {}

        # device pool: allocate by prefilling a dummy full batch through the
        # JITTED prefill at the smallest prompt bucket — admissions at that
        # bucket reuse the compile, and nothing here runs eagerly (an eager
        # prefill is ~25 separate dispatches)
        tpad0 = min(bucket_length(1, self.prompt_buckets),
                    self.model.max_len - 1)
        dummy = np.zeros((self.n_slots, tpad0), np.int32)
        cell, _ = self._prefill_fn(tpad0)(
            self.params, jnp.asarray(dummy),
            jnp.zeros((self.n_slots,), jnp.int32))
        cur = jnp.zeros((self.n_slots,), jnp.int32)
        pos_host = np.zeros((self.n_slots,), np.int64)

        def admit():
            nonlocal cell, cur
            free = [i for i, s in enumerate(slots) if s.req is None]
            if not queue or not free:
                return
            group = []
            for i in free:
                if not queue:
                    break
                group.append((i, queue.pop(0)))
            tpad = bucket_length(max(r.prompt.size for _, r in group),
                                 self.prompt_buckets)
            tpad = min(tpad, self.model.max_len - 1)
            prompts = np.zeros((self.n_slots, tpad), np.int32)
            lens = np.zeros((self.n_slots,), np.int32)
            mask = np.zeros((self.n_slots,), bool)
            for i, r in group:
                prompts[i, :r.prompt.size] = r.prompt
                lens[i] = r.prompt.size
                mask[i] = True
            new_cell, first = self._prefill_fn(tpad)(
                self.params, jnp.asarray(prompts), jnp.asarray(lens))
            cell, cur = self._merge_fn()(cell, cur, new_cell, first,
                                         jnp.asarray(mask))
            for i, r in group:
                slots[i].req = r
                # the slot emits ``first`` then continues; cap the budget so
                # positions stay inside max_len
                slots[i].left = min(r.max_new,
                                    self.model.max_len - r.prompt.size)
                slots[i].out = []
                pos_host[i] = r.prompt.size

        def park_idle():
            nonlocal cell, cur, pos_host
            idle = [i for i, s in enumerate(slots) if s.req is None
                    and pos_host[i] + 2 * self.segment >= self.model.max_len]
            if idle:
                idx = jnp.asarray(idle, jnp.int32)
                newpos = cell["pos"].at[idx].set(0)
                cell = dict(cell, pos=newpos)
                pos_host[idle] = 0

        admit()
        while any(s.req is not None for s in slots):
            park_idle()
            # cache reads sized to the LIVE slots only: a drained slot
            # decoding garbage at a high position must not drag every
            # sample's HBM reads up (its own out-of-bound mask just reads
            # garbage, which is discarded)
            max_pos = max((int(pos_host[i]) for i, s in enumerate(slots)
                           if s.req is not None), default=0)
            cache_len = min(
                -(-(max_pos + self.segment + 1) // self.cache_bucket)
                * self.cache_bucket, self.model.max_len)
            cell, cur, toks = self._seg_fn(cache_len)(self.params, cell, cur)
            # one dispatch serves `segment` tokens across every live slot
            obs.count("decode.dispatches_total", route="serve_segment")
            pos_host += self.segment
            block = np.asarray(toks)               # [B, segment] host sync
            for i, s in enumerate(slots):
                if s.req is None:
                    continue
                take, done, _ = clip_emission(block[i], s.left,
                                              s.req.eos_id)
                s.out.extend(int(t) for t in take)
                obs.count("decode.tokens_total", len(take), route="serve")
                s.left -= len(take)
                if done:
                    results[s.req.rid] = np.asarray(s.out, np.int32)
                    slots[i] = _Slot()             # free the slot
            admit()
        return results


class SpeculativeDecoder:
    """Speculative greedy decoding: a small DRAFT model proposes ``k-1``
    tokens per round; the target verifies the whole span in ONE batched
    ``verify_step`` pass (models/transformer.py) and emits the longest
    agreeing prefix plus its own correction token.

    Exactness by construction: every emitted token is the target's greedy
    continuation of the emitted prefix — the draft only decides HOW MANY
    tokens each target dispatch yields, never WHICH — so the output equals
    plain greedy decode for ANY acceptance pattern, including an
    adversarial draft that never agrees (tests/test_serving.py). The win
    is dispatch/bytes economics: the target's weights stream once per
    ROUND instead of once per token, amortized over 1 + accepted tokens.

    Rollback rides the existing position-masked cache contract: rejected
    span rows (and the draft's rows for rejected proposals) sit past the
    reset write position, are never readable (mask j <= pos), and are
    overwritten before the position reaches them again — the same
    invariant prefill's ragged tail relies on.

    The draft is any model exposing ``prefill(params, prompt)`` and
    ``decode_step(params, cell, tokens)``; the bench's default is the
    target itself reading an int8 KV cache (a self-speculation draft with
    halved cache bytes and high agreement — docs/design/kernels.md).
    """

    def __init__(self, model, params, draft_model, draft_params, *, k: int = 4,
                 kv_dtype: Optional[str] = None,
                 draft_kv_dtype: Optional[str] = None):
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        self.model, self.params = model, params
        self.draft_model, self.draft_params = draft_model, draft_params
        self.k = k
        self.kv_dtype, self.draft_kv_dtype = kv_dtype, draft_kv_dtype
        draft = draft_model
        dkv = draft_kv_dtype

        def dpf(p, ids):
            cell, last = draft.prefill(p, ids, kv_dtype=dkv) \
                if dkv is not None else draft.prefill(p, ids)
            return cell
        self._draft_prefill = jax.jit(dpf)

        def dstep(p, cell, cur):
            logits, cell = draft.decode_step(p, cell, cur)
            return jnp.argmax(logits, axis=-1).astype(cur.dtype), cell
        self._draft_step = jax.jit(dstep)

    def generate(self, prompt, steps: int) -> Tuple[np.ndarray, Dict]:
        """prompt [B, T0] (or [T0]) -> (tokens [B, steps] int32, stats).
        stats: rounds / proposed / accepted / acceptance_rate — the bench
        row's headline numbers (benchmarks/speculative_decode.py)."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim == 1:
            prompt = prompt[None]
        B, T0 = prompt.shape
        if T0 == 0:
            raise ValueError("empty prompt (prefill needs >= 1 token)")
        # frozen samples keep re-writing up to k span rows past their last
        # position, and a final round can overshoot by k-1 — 2k of margin
        # keeps every write inside max_len
        need = T0 + steps + 2 * self.k
        for name, m in (("model", self.model), ("draft", self.draft_model)):
            if need > m.max_len:
                raise ValueError(
                    f"prompt ({T0}) + steps ({steps}) + 2k ({2 * self.k}) "
                    f"exceeds {name} max_len ({m.max_len})")
        ids = jnp.asarray(prompt)
        rng = jax.random.PRNGKey(0)                # greedy: never consumed
        cell, cur, _ = self.model._decode_fn(
            "prefill", kv_dtype=self.kv_dtype, sample="greedy", top_k=None,
            temperature=1.0)(self.params, ids, rng)
        obs.count("decode.dispatches_total", route="spec_prefill")
        dcell = self._draft_prefill(self.draft_params, ids)

        pos = np.full((B,), T0, np.int64)
        # the prefill's greedy token is the first emission; every round
        # then emits the tokens AFTER the current one
        emitted: List[List[int]] = [[int(t)] for t in np.asarray(cur)]
        rounds = proposed = accepted = 0
        verify = self.model._decode_fn("verify", cache_len=None)
        while min(len(e) for e in emitted) < steps:
            # draft proposes k-1 tokens from cur (its positions synced to
            # the target's accepted state), then one cache-fill step
            # consumes the LAST proposal: on a fully-accepted round the
            # next cur sits one past it, so without the fill the draft
            # cache would keep a permanently-live all-zero row at every
            # such round's final position — silently rotting proposal
            # quality (the partial-acceptance rows are overwritten before
            # they become readable, so only the last one needs this)
            dcell = dict(dcell, pos=jnp.asarray(pos, jnp.int32))
            d_cur, props = cur, []
            for i in range(self.k if self.k > 1 else 0):
                d_cur, dcell = self._draft_step(self.draft_params, dcell,
                                                d_cur)
                obs.count("decode.dispatches_total", route="spec_draft")
                if i < self.k - 1:
                    props.append(d_cur)    # the k-th output is discarded
            span = jnp.stack([cur] + props, axis=1)        # [B, k]
            t, cell = verify(self.params, cell, span)      # [B, k] greedy
            obs.count("decode.dispatches_total", route="spec_verify")
            t_np = np.asarray(t)
            props_np = t_np[:, :0] if not props else \
                np.stack([np.asarray(p) for p in props], axis=1)
            next_cur = np.asarray(cur).copy()
            for b in range(B):
                if len(emitted[b]) >= steps:
                    continue                       # frozen: pos/cur hold
                m = 0
                while m < self.k - 1 and props_np[b, m] == t_np[b, m]:
                    m += 1
                emitted[b].extend(int(x) for x in t_np[b, :m + 1])
                next_cur[b] = t_np[b, m]
                pos[b] += m + 1
                proposed += self.k - 1
                accepted += m
            cell = dict(cell, pos=jnp.asarray(pos, jnp.int32))
            cur = jnp.asarray(next_cur)
            rounds += 1
        obs.count("decode.spec_proposed_total", proposed)
        obs.count("decode.spec_accepted_total", accepted)
        obs.count("decode.tokens_total", B * steps, route="spec")
        out = np.asarray([e[:steps] for e in emitted], np.int32)
        return out, {"rounds": rounds, "proposed": proposed,
                     "accepted": accepted,
                     "acceptance_rate": (accepted / proposed if proposed
                                         else 1.0)}
