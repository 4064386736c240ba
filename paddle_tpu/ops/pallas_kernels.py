"""Pallas TPU kernels for the hot ops XLA doesn't fuse optimally.

The reference hand-writes CUDA for its hot ops (fused LSTM cells
cuda/src/hl_cuda_lstm.cu, attention-era building blocks); the TPU analog is a
Pallas kernel that keeps the whole inner loop in VMEM next to the MXU/VPU
(/opt/skills/guides/pallas_guide.md).

* :func:`flash_attention` — blockwise-softmax attention: Q tiles stream over
  KV tiles entirely in VMEM; the [T, T] score matrix never touches HBM. This
  is the single biggest HBM-bandwidth win for long sequences and the kernel
  under ring attention's per-chip step.
* Backward is real Pallas too: a dq kernel (grid over Q blocks, streaming KV
  tiles) and a dk/dv kernel (grid over KV blocks, streaming Q tiles), both
  recomputing the probability tiles in VMEM from the saved logsumexp — the
  [T, T] matrix never exists in HBM in either direction.
* :func:`flash_attention_with_lse` — forward-only variant returning the
  per-row logsumexp, the building block ring attention uses to merge partial
  attention results across ring steps (parallel/ring_attention.py).

Kernels run with ``interpret=True`` only where the backend is the CPU, so the
same code is testable on the CPU mesh (tests/test_pallas.py); numerics match
the jnp reference path. tests/test_chip_compile.py compiles the main-path
kernels for a described TPU v5e at GPT-2-small widths and at the benchmark
cells' own flash-attention calls.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -1e30


def _on_tpu() -> bool:
    """Is the default backend the TPU? A backend that fails to initialise
    raises here — an error never turns into "not a TPU" and with it into
    the interpreter or a dense route."""
    return jax.default_backend() == "tpu"


def _interpret(interpret: Optional[bool]) -> bool:
    """Resolve a kernel wrapper's ``interpret=None``: the Pallas interpreter
    only where the backend is positively the CPU (the test mesh); any other
    backend gets the compiled kernel and that backend's own errors."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


# How the causal kernels walk the square. One k-block holds the rows of
# r = ceil(block_k / block_q) q-blocks (_blocks(): several k-blocks are a
# multiple of block_q or divide it; ONE k-block is the whole of S, its last
# q-block ragged). For q-block ``qi`` the k-blocks that END at or before its
# first row are CLEAR: whole, no masked pair, no iota / compare / select.
# What is left of its row of the square is ONE tile against the diagonal,
# from the end of the clear blocks to the q-block's last row:
# min((qi % r + 1) * block_q, block_k) keys wide — a static width chosen by
# a branch on qi % r, so the tile's products, its softmax and its mask cover
# the live keys and nothing to their right. Everything past it is masked
# ENTIRELY and not visited: it contributes p == 0 exactly, so fwd lse and
# the bwd recomputation stay consistent by construction. The dk/dv kernel
# walks the same tiles from the k-block's side: its r diagonal tiles in a
# static loop and the q-blocks below them clear. Why one wide tile and not
# a walk of small blocks: on this chip 256 x 256 blocks, K/V resident under
# a dynamic loop or walked by the grid, ran 1.5-1.9x SLOWER than 512 x 1024
# computing the whole square (PERF.md, PR 31) — a tile costs its area plus
# a fixed part, and 512 streamed rows keep the MXU's weights busy.


def _mask_tile(s, q_pos, k_start, kv_len, causal: bool, whole: bool,
               window: Optional[int] = None):
    """Scores of one tile with its masked pairs at _NEG: keys at / past
    kv_len (padded, over-length) and, causally, keys past the row.
    ``q_pos`` [block_q, 1]; the tile's keys start at ``k_start``. A causal
    tile wider than it is tall ends on the diagonal, so only the columns
    of its last q-block can hold a pair past it (and, S == T, a padded key
    a real row sees): unless ``whole`` (kv_lens given), only those are
    touched. ``window``: the tile meets the band's LEFT edge — keys at
    ``q_pos - window`` and before are masked too, in any column."""
    block_q, width = s.shape
    skip = (width - 1) // block_q * block_q \
        if causal and not whole and window is None else 0
    k_pos = k_start + skip + jax.lax.broadcasted_iota(
        jnp.int32, (1, width - skip), 1)
    valid = k_pos < kv_len
    if causal:
        valid = valid & (q_pos >= k_pos)
    if window is not None:
        valid = valid & (k_pos > q_pos - window)
    if not skip:
        return jnp.where(valid, s, _NEG)
    return jnp.concatenate(
        [s[:, :skip], jnp.where(valid, s[:, skip:], _NEG)], axis=1)


def _diag_per_block(block_q: int, block_k: int) -> int:
    """r: q-blocks whose rows one k-block holds (1 when block_q >= block_k)."""
    return -(-block_k // block_q)


def _visited_units(n_q: int, n_k: int, block_q: int, block_k: int,
                   causal: bool, by_k: bool,
                   window: Optional[int] = None) -> Tuple[int, int]:
    """(visited, grid) area of one batch x head square in units of the
    smaller block side squared (rounded up), for the forward / dq walk or
    (``by_k``) the dk/dv walk — the arithmetic the kernels do on traced
    indices. ``window`` (the forward walk alone): the k-blocks wholly left
    of the band are not visited either."""
    u = min(block_q, block_k)
    grid = n_q * block_q * n_k * block_k
    if not causal:
        area = grid
    else:
        r = _diag_per_block(block_q, block_k)
        widths = [block_q if r == 1 and not by_k
                  else min(block_k, (c + 1) * block_q) for c in range(r)]
        if by_k:
            area = sum(max(0, n_q - (ki * block_k) // block_q - r)
                       * block_q * block_k + block_q * sum(widths)
                       for ki in range(n_k))
        else:
            left = [0 if window is None or n_k == 1 else
                    max(qi * block_q - window + 1, 0) // block_k
                    for qi in range(n_q)]
            area = sum(((qi * block_q) // block_k - left[qi]) * block_k
                       * block_q + block_q * widths[qi % r]
                       for qi in range(n_q))
    return -(-area // (u * u)), -(-grid // (u * u))


def _walk_k(tile, finish, carry, qi, block_q: int, block_k: int, n_k: int,
            kv_len, causal: bool, has_lens: bool,
            window: Optional[int] = None) -> None:
    """The forward / dq walk over one q-block's keys (above): fold
    ``tile(carry, start, width, masked)`` over the tiles it visits and hand
    the result to ``finish`` (which writes the program's outputs).

    ``window`` (causal, the forward alone): row p sees the keys ``p -
    window < j <= p``, a BAND. The walk starts at the k-block that holds
    the first key the q-block's first row sees, not at block 0; the blocks
    the band's left edge crosses are masked on the left (``tile(...,
    masked="left")``), those between them and the diagonal stay clear, and
    the diagonal tile is masked on the left only where a window narrower
    than block_q + block_k can reach into it (static)."""
    def blocks(hi, masked, carry, lo=0):
        return jax.lax.fori_loop(
            lo, hi, lambda ki, c: tile(c, ki * block_k, block_k, masked),
            carry)

    # k-blocks wholly at / past kv_len are masked entirely: not visited
    by_len = (kv_len + block_k - 1) // block_k
    if not causal:
        hi = jnp.minimum(n_k, by_len) if has_lens and n_k > 1 else n_k
        finish(blocks(hi, True, carry))
        return
    r = _diag_per_block(block_q, block_k)
    start = 0
    if n_k > 1:
        clear = (qi * block_q) // block_k
        start = clear * block_k
        lo = 0
        if window is not None:
            # [lo, inside): the blocks the band's left edge crosses —
            # block ki lies wholly inside the band from ki * block_k >=
            # (qi + 1) * block_q - window on
            lo = jnp.maximum(qi * block_q - window + 1, 0) // block_k
            inside = jnp.minimum(jnp.maximum(
                (qi + 1) * block_q - window + block_k - 1, 0) // block_k,
                clear)
            carry = blocks(jnp.minimum(inside, by_len) if has_lens
                           else inside, "left", carry, lo)
            lo = inside
        # a clear block holds no key past the diagonal; with kv_lens it may
        # hold one past the sample's length, so those calls mask throughout
        carry = blocks(jnp.minimum(clear, by_len) if has_lens else clear,
                       has_lens, carry, lo)
    diagonal = "left" if window is not None \
        and window < block_q + block_k else True
    if r == 1:
        finish(tile(carry, start, block_q, diagonal))
        return
    for c in range(r):
        @pl.when(jax.lax.rem(qi, r) == c)
        def _diagonal(width=min((c + 1) * block_q, block_k)):
            finish(tile(carry, start, width, diagonal))


def _fa_fwd_kernel(q_ref, k_ref, v_ref, len_ref, *rest,
                   block_k: int, scale: float, causal: bool, seq_len: int,
                   true_len: int, has_lens: bool,
                   window: Optional[int] = None):
    """One (batch*head, q-block) program: stream KV tiles, online softmax.

    q_ref: [1, block_q, D]; k_ref: [1, T, D]; v_ref: [1, T, Dv] (Dv = D,
    or narrower values); o_ref: [1, block_q, Dv];
    lse_ref: [1, block_q, 1] (f32 logsumexp residual for the backward pass;
    kept 3D with a trailing unit dim so the block obeys TPU tiling rules).
    len_ref: [1, 1, 1] int32 — THIS sample's true kv length (variable-length
    / LoD masking: keys at or past it never enter the softmax).
    ``rest``: (o_ref, lse_ref), after a ``sink_ref`` [1, 1, 1] f32 where the
    call has one — the head's sink LOGIT, which joins the finished softmax's
    denominator (under a max that includes it) and no value.
    """
    sink_ref, o_ref, lse_ref = rest if len(rest) == 3 else (None,) + rest
    block_q, d = q_ref.shape[1], v_ref.shape[2]
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    kv_len = jnp.minimum(len_ref[0, 0, 0], true_len)

    def tile(carry, start, width: int, masked):
        acc, m, l = carry
        k = k_ref[0, pl.ds(start, width), :]
        v = v_ref[0, pl.ds(start, width), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if masked:
            s = _mask_tile(s, q_pos, start, kv_len, causal, has_lens,
                           window if masked == "left" else None)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_new = acc * corr + pv
        return acc_new, m_new, l_new

    def finish(carry):
        acc, m, l = carry
        if sink_ref is not None:
            b = sink_ref[0]                                 # [1, 1]
            m_new = jnp.maximum(m, b)
            corr = jnp.exp(m - m_new)
            acc, l, m = acc * corr, l * corr + jnp.exp(b - m_new), m_new
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m + jnp.log(l_safe)

    carry = (jnp.zeros((block_q, d), jnp.float32),
             jnp.full((block_q, 1), _NEG, jnp.float32),
             jnp.zeros((block_q, 1), jnp.float32))
    _walk_k(tile, finish, carry, qi, block_q, block_k, seq_len // block_k,
            kv_len, causal, has_lens, window)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      len_ref, dq_ref, *, block_k: int, scale: float,
                      causal: bool, seq_len: int, true_len: int,
                      has_lens: bool):
    """dq for one (batch*head, q-block): recompute p tiles from saved lse.

    dS = P * (dO·Vᵀ − delta);   dQ = scale · dS·K.
    """
    _, block_q, d = q_ref.shape
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]                                # [block_q, 1]
    delta = delta_ref[0]                            # [block_q, 1]
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    kv_len = jnp.minimum(len_ref[0, 0, 0], true_len)

    def tile(dq, start, width: int, masked: bool):
        k = k_ref[0, pl.ds(start, width), :]
        v = v_ref[0, pl.ds(start, width), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if masked:
            s = _mask_tile(s, q_pos, start, kv_len, causal, has_lens)
        p = jnp.exp(s - lse)                        # [block_q, width]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    def finish(dq):
        dq_ref[0] = (dq * scale).astype(dq_ref.dtype)

    # the forward's walk: what it skipped has p == 0 and adds nothing to dq
    _walk_k(tile, finish, jnp.zeros((block_q, d), jnp.float32), qi, block_q,
            block_k, seq_len // block_k, kv_len, causal, has_lens)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       len_ref, dk_ref, dv_ref, *, block_q: int, scale: float,
                       causal: bool, seq_len: int, true_len: int,
                       has_lens: bool):
    """dk/dv for one (batch*head, kv-block): stream Q tiles.

    dV = Pᵀ·dO;   dK = scale · dSᵀ·Q.
    Padded query rows contribute nothing because dO (and hence delta) is
    zero-padded, making dS vanish there; padded key columns are masked.
    """
    _, block_k, d = k_ref.shape
    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    kv_len = jnp.minimum(len_ref[0, 0, 0], true_len)

    n_q = seq_len // block_q

    def tile(q_start, width: int, masked: bool):
        """(dk, dv) [width, D] that q rows [q_start, + block_q) add to this
        block's first ``width`` keys."""
        q = q_ref[0, pl.ds(q_start, block_q), :].astype(jnp.float32) * scale
        do = do_ref[0, pl.ds(q_start, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(q_start, block_q), :]
        delta = delta_ref[0, pl.ds(q_start, block_q), :]
        kw, vw = k[:width], v[:width]
        s = jax.lax.dot_general(q, kw, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if masked:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)
            s = _mask_tile(s, q_pos, ki * block_k, kv_len, causal, has_lens)
        p = jnp.exp(s - lse)                        # [block_q, width]
        dv = jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, vw, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk = jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return dk, dv

    def blocks(lo, masked):
        def body(qi, carry):
            dk, dv = tile(qi * block_q, block_k, masked)
            return carry[0] + dk, carry[1] + dv
        zero = jnp.zeros((block_k, d), jnp.float32)
        return jax.lax.fori_loop(lo, n_q, body, (zero, zero))

    if not causal:
        dk, dv = blocks(0, True)
    else:
        # q-blocks that end before this block's first key see none of it
        # (p == 0 rows only, no dk/dv) and are not visited; the r the
        # diagonal crosses see its first (c + 1) * block_q keys; those
        # below are clear (with kv_lens they mask: see the forward)
        r = _diag_per_block(block_q, block_k)
        if n_q > r:
            lo = (ki * block_k) // block_q
            dk, dv = blocks(lo + r, has_lens)
        else:                           # one k-block: no q-block is clear
            lo = 0
            dk = dv = jnp.zeros((block_k, d), jnp.float32)
        for c in range(r):
            w = min(block_k, (c + 1) * block_q)
            dk_d, dv_d = tile((lo + c) * block_q, w, True)
            if w < block_k:             # the keys past the tile get nothing
                rest = jnp.zeros((block_k - w, d), jnp.float32)
                dk_d = jnp.concatenate([dk_d, rest])
                dv_d = jnp.concatenate([dv_d, rest])
            dk, dv = dk + dk_d, dv + dv_d
    dk_ref[0] = dk.astype(dk_ref.dtype)             # scale folded into q
    dv_ref[0] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# layout helpers + pallas_call wrappers
# ---------------------------------------------------------------------------

def _blocks(T: int, S: int, block_q: int, block_k: int,
            causal: bool = False) -> Tuple[int, int, int, int]:
    """Block sizes + padded lengths for q (len T) and kv (len S). The two
    sides pad independently — cross-attention / half-block calls (zigzag
    ring steps) have S != T. A causal call is a square; when it has several
    k-blocks the larger block is rounded up to a multiple of the smaller
    and both sides pad to it, the geometry the causal walk's static tile
    widths stand on (ONE k-block is the whole of S, unpadded, as on any
    call)."""
    if causal and S != T:
        raise ValueError(f"causal flash attention is a square: S {S} != T {T}")
    blk_q = min(block_q, max(8, T))
    blk_k = min(block_k, max(8, S))
    if causal and blk_k < S:
        if blk_k >= blk_q:
            blk_k = -(-blk_k // blk_q) * blk_q
        else:
            blk_q = -(-blk_q // blk_k) * blk_k
        big = max(blk_q, blk_k)
        Tp = Sp = -(-T // big) * big
        return blk_q, blk_k, Tp, Sp
    Tp = -(-T // blk_q) * blk_q
    Sp = -(-S // blk_k) * blk_k
    return blk_q, blk_k, Tp, Sp


def _to_bh(x, Tp):
    """[B, T, H, D] -> [B*H, Tp, D] (zero pad)."""
    B, T, H, D = x.shape
    x = jnp.moveaxis(x, 2, 1).reshape(B * H, T, D)
    if Tp > T:
        x = jnp.pad(x, ((0, 0), (0, Tp - T), (0, 0)))
    return x


def _from_bh(x, B, T, H, D):
    """[B*H, Tp, D] -> [B, T, H, D]."""
    return jnp.moveaxis(x[:, :T].reshape(B, H, T, D), 1, 2)


def _row_to_bh(x, Tp):
    """[B, T, H] -> [B*H, Tp, 1] (zero pad; trailing unit dim for TPU tiling)."""
    B, T, H = x.shape
    x = jnp.moveaxis(x, 2, 1).reshape(B * H, T)
    if Tp > T:
        x = jnp.pad(x, ((0, 0), (0, Tp - T)))
    return x[..., None]


def _lens_to_bh(kv_lens, B, H, S):
    """Per-sample kv lengths -> [B*H, 1, 1] int32 (full length when None).

    3D with two trailing unit dims: a block whose last two dims EQUAL the
    array dims satisfies the TPU tiling rule, where a (1, 1) block over a
    [B*H, 1] array does not (Mosaic requires the second-to-last block dim
    to divide 8 or equal the array dim)."""
    if kv_lens is None:
        lens = jnp.full((B,), S, jnp.int32)
    else:
        lens = jnp.clip(kv_lens.astype(jnp.int32), 0, S)
    return jnp.repeat(lens, H)[:, None, None]


def _count_block_pairs(kernel: str, bh: int, n_q: int, n_k: int, blk_q: int,
                       blk_k: int, causal: bool, by_k: bool = False,
                       window: Optional[int] = None) -> None:
    """``kernels.flash_block_pairs_total``: the area of the square one
    traced call's kernel walks (state=visited) against the whole square
    (state=grid), over all batch x head squares, in block pairs of the
    smaller block side. Counted when the wrapper's Python runs — once a
    TRACE, as ``kernels.routes_total`` is. The causal structure alone: what
    ``kv_lens`` skips besides depends on data and is not counted."""
    from .. import obs
    visited, grid = _visited_units(n_q, n_k, blk_q, blk_k, causal, by_k,
                                   window)
    obs.count("kernels.flash_block_pairs_total", bh * visited,
              kernel=kernel, state="visited")
    obs.count("kernels.flash_block_pairs_total", bh * grid,
              kernel=kernel, state="grid")


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7),
                   static_argnames=("window",))
def _fa_fwd_call(q, k, v, causal, scale, block_q, block_k, interpret,
                 kv_lens=None, window=None, sink=None):
    """Returns (o [B,T,H,Dv], lse [B,T,H] f32). k/v may be shorter or longer
    than q (S != T) for cross-attention-shaped blocks; ``causal`` assumes
    S == T. ``kv_lens`` [B] masks each sample's keys past its true length
    (variable-length batches / cross-attention over padded sources).
    ``window``: the banded walk (:func:`_walk_k`), under a custom-call name
    of its own, ``flash_window_attention_fwd`` — a trace, and the counters,
    tell a band from a causal square. v may be NARROWER than q and k
    (``Dv`` wide: o is); ``sink`` [H] f32: a logit a head in the softmax's
    denominator (lse includes it). A call with neither is the program it
    was."""
    B, T, H, D = q.shape
    S, Dv = k.shape[1], v.shape[3]
    # grouped-query heads: k/v [B, S, Hkv, D]; program bh = b * H + h reads
    # KV row b * Hkv + h // G = bh // G, so a group's G x n_q consecutive
    # programs name the same K/V block and it is fetched once for them
    G = H // k.shape[2]
    kv_at = (lambda bh, qi: (bh, 0, 0)) if G == 1 else \
        (lambda bh, qi: (bh // G, 0, 0))
    blk_q, blk_k, Tp, Sp = _blocks(T, S, block_q, block_k, causal)
    qb, kb, vb = _to_bh(q, Tp), _to_bh(k, Sp), _to_bh(v, Sp)
    lensb = _lens_to_bh(kv_lens, B, H, S)
    name = "flash_attention_fwd" if window is None \
        else "flash_window_attention_fwd"
    kernel = functools.partial(_fa_fwd_kernel, block_k=blk_k, scale=scale,
                               causal=causal, seq_len=Sp, true_len=S,
                               has_lens=kv_lens is not None, window=window)
    n_q, n_k = Tp // blk_q, Sp // blk_k
    _count_block_pairs(name, B * H, n_q, n_k, blk_q, blk_k, causal,
                       window=window)
    grid = (B * H, n_q)
    row = pl.BlockSpec((1, 1, 1), lambda bh, qi: (bh, 0, 0))
    sinks = () if sink is None else (jnp.tile(
        sink.astype(jnp.float32), B)[:, None, None],)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_q, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, Sp, D), kv_at),
            pl.BlockSpec((1, Sp, Dv), kv_at),
            row,
        ] + [row] * len(sinks),
        out_specs=[
            pl.BlockSpec((1, blk_q, Dv), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, blk_q, 1), lambda bh, qi: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tp, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, Tp, 1), jnp.float32),
        ],
        interpret=interpret,
        name=name,
    )(qb, kb, vb, lensb, *sinks)
    o = _from_bh(out, B, T, H, Dv)
    lse = jnp.moveaxis(lse[:, :T, 0].reshape(B, H, T), 1, 2)
    return o, lse


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10))
def _fa_bwd_call(q, k, v, o, lse, do, causal, scale, block_q, block_k,
                 interpret, delta=None, kv_lens=None):
    """Returns (dq, dk, dv); dq follows q's [B,T,H,D], dk/dv follow k/v's
    [B,S,H,D] (S != T for the zigzag half-block steps)."""
    B, T, H, D = q.shape
    S = k.shape[1]
    blk_q, blk_k, Tp, Sp = _blocks(T, S, block_q, block_k, causal)
    if delta is None:
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)
    qb, dob = _to_bh(q, Tp), _to_bh(do, Tp)
    kb, vb = _to_bh(k, Sp), _to_bh(v, Sp)
    lseb, deltab = _row_to_bh(lse, Tp), _row_to_bh(delta, Tp)
    lensb = _lens_to_bh(kv_lens, B, H, S)
    n_q, n_k = Tp // blk_q, Sp // blk_k
    _count_block_pairs("flash_attention_bwd_dq", B * H, n_q, n_k, blk_q,
                       blk_k, causal)
    _count_block_pairs("flash_attention_bwd_dkv", B * H, n_q, n_k, blk_q,
                       blk_k, causal, by_k=True)

    q_spec = pl.BlockSpec((1, blk_q, D), lambda bh, qi: (bh, qi, 0))
    q_full_spec = pl.BlockSpec((1, Tp, D), lambda bh, i: (bh, 0, 0))
    kv_full_spec = pl.BlockSpec((1, Sp, D), lambda bh, i: (bh, 0, 0))
    row_q_spec = pl.BlockSpec((1, blk_q, 1), lambda bh, qi: (bh, qi, 0))
    row_full_spec = pl.BlockSpec((1, Tp, 1), lambda bh, i: (bh, 0, 0))
    k_spec = pl.BlockSpec((1, blk_k, D), lambda bh, ki: (bh, ki, 0))
    len_spec = pl.BlockSpec((1, 1, 1), lambda bh, i: (bh, 0, 0))

    # dq: grid over q blocks, stream kv tiles (loop bound Sp, mask keys >= S)
    dq_kernel = functools.partial(_fa_bwd_dq_kernel, block_k=blk_k,
                                  scale=scale, causal=causal, seq_len=Sp,
                                  true_len=S,
                                  has_lens=kv_lens is not None)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(B * H, n_q),
        in_specs=[q_spec, kv_full_spec, kv_full_spec, q_spec, row_q_spec,
                  row_q_spec, len_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, Tp, D), q.dtype),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(qb, kb, vb, dob, lseb, deltab, lensb)

    # dk/dv: grid over kv blocks, stream q tiles (loop bound Tp; padded q
    # rows have zero do/delta so they contribute nothing); mask keys >= S
    dkv_kernel = functools.partial(_fa_bwd_dkv_kernel, block_q=blk_q,
                                   scale=scale, causal=causal, seq_len=Tp,
                                   true_len=S, has_lens=kv_lens is not None)
    # q, do and the [Tp, 1] lse / delta rows (lane-padded 128x in VMEM) are
    # resident per program, double-buffered: 10 MiB at T 4096. Past half the
    # default scoped limit (16 MiB) the tiles' temporaries no longer fit
    # beside them, so such a call asks for 32 of the chip's 128 MiB.
    resident = 2 * 2 * Tp * (128 * 4 + D * q.dtype.itemsize)
    params = {}
    if resident > 8 * 2 ** 20:
        from jax.experimental.pallas import tpu as pltpu
        params["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=32 * 2 ** 20)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(B * H, n_k),
        in_specs=[q_full_spec, k_spec, k_spec, q_full_spec, row_full_spec,
                  row_full_spec, len_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[jax.ShapeDtypeStruct((B * H, Sp, D), k.dtype),
                   jax.ShapeDtypeStruct((B * H, Sp, D), v.dtype)],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
        **params,
    )(qb, kb, vb, dob, lseb, deltab, lensb)

    return (_from_bh(dq, B, T, H, D), _from_bh(dk, B, S, H, D),
            _from_bh(dv, B, S, H, D))


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _default_blocks(block_q: Optional[int],
                    block_k: Optional[int]) -> Tuple[int, int]:
    """512 / 1024 for every call that names no blocks, causal or not.
    Measured on this v5e at the train cell's call ([8, 1023, 16, 64] bf16,
    causal; fwd + dq + dk/dv ms a call, PERF.md PR 31): 512 x 1024 1.47,
    256 x 1024 1.56, 512 x 512 1.80, 256 x 512 2.04, 128 x 1024 2.14,
    256 x 256 2.97 — and 1.96 before the causal walk, when 512 x 1024
    computed the whole square. A tile's time is its area at ~43 % of the
    MXU's peak (what d_head 64 allows) plus a cost a tile and a program, so
    few wide tiles win and 512 rows keep the MXU's weights busy; the
    diagonal is followed by narrowing the one tile that meets it, not by
    small blocks. _blocks() still clamps to the actual lengths, so short
    sequences are unaffected."""
    return block_q or 512, block_k or 1024


# below this sequence length the Pallas kernels' per-program overhead beats
# their HBM saving on this chip (128 x 128 tiles measured 3x slower than
# 512 x 1024: PERF.md, PR 31; at S<=256 the whole [T,S] score tile fits
# comfortably in VMEM through XLA fusion anyway) — a masked dense einsum is
# faster
SHORT_SEQ_DENSE = 256


def decode_route(L: int, route: Optional[str] = None) -> str:
    """The route :func:`decode_attention` / :func:`paged_decode_attention`
    will take for a read of L rows — exposed so cost accounting
    (obs/roofline.py kernel models) can ask WITHOUT dispatching: modeled
    kernel bytes apply only on the kernel route; the dense route's bytes
    are already visible to XLA's own cost analysis.

    A forced ``route`` wins (the tests' way to pick the dense reference,
    or the kernel through the interpreter); otherwise the kernel runs for
    on-TPU reads of at least ``SHORT_SEQ_DENSE`` rows. Both routes share
    one masked-softmax formulation, so the choice never changes tokens."""
    if route is not None:
        return route
    return "kernel" if _on_tpu() and L >= SHORT_SEQ_DENSE else "dense"


def _dense_attention(q, k, v, causal, scale, kv_lens, window=None,
                     sink=None, with_lse=False):
    """Masked dense attention for short sequences — same semantics as the
    flash kernels (causal + per-sample kv_lens, the band of ``window``,
    values narrower than keys, a ``sink`` logit a head in the denominator),
    ordinary autodiff. ``with_lse``: (o, lse [B, T, H] f32)."""
    T, S = q.shape[1], k.shape[1]
    if k.shape[2] != q.shape[2]:        # grouped-query heads: h reads h // G
        k = jnp.repeat(k, q.shape[2] // k.shape[2], axis=2)
        v = jnp.repeat(v, q.shape[2] // v.shape[2], axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if kv_lens is not None:
        ok = (jnp.arange(S)[None, :]
              < jnp.clip(kv_lens, 0, S)[:, None])[:, None, None, :]
        s = jnp.where(ok, s, _NEG)
    if causal:
        mask = jnp.tril(jnp.ones((T, S), bool))
        if window is not None:
            mask = mask & ~jnp.tril(jnp.ones((T, S), bool), -window)
        s = jnp.where(mask[None, None], s, _NEG)
    if sink is None and not with_lse:
        p = jax.nn.softmax(s, axis=-1)
    else:
        m = jnp.max(s, axis=-1, keepdims=True)
        if sink is not None:
            b = sink.astype(jnp.float32)[None, :, None, None]
            m = jnp.maximum(m, b)
        e = jnp.exp(s - m)
        l = jnp.sum(e, axis=-1, keepdims=True)
        if sink is not None:
            l = l + jnp.exp(b - m)
        p = e / l
    o = jnp.einsum("bhts,bshd->bthd", p,
                   v.astype(jnp.float32)).astype(q.dtype)
    if with_lse:
        return o, jnp.moveaxis((m + jnp.log(l))[..., 0], 1, 2)
    return o


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _flash_window(q, k, v, kv_lens, static):
    """The banded forward (``static``: window, scale, blocks, interpret;
    blocks None: the dense route). A custom_vjp only so that a gradient
    is refused in words, before any kernel is differentiated."""
    window, scale, block_q, block_k, interpret = static
    if block_q is None:
        return _dense_attention(q, k, v, True, scale, kv_lens, window)
    return _fa_fwd_call(q, k, v, True, scale, block_q, block_k, interpret,
                        kv_lens=kv_lens, window=window)[0]


def _flash_window_bwd(static, res, g):
    raise NotImplementedError(
        "flash_attention(window=...) is forward-only: the backward kernels "
        "walk no band")


_flash_window.defvjp(lambda *a: (_flash_window(*a), None), _flash_window_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False, scale: Optional[float] = None,
                    kv_lens: Optional[jax.Array] = None,
                    window: Optional[int] = None,
                    sink: Optional[jax.Array] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Fused attention. q: [B, T, H, D], k/v: [B, S, H, D] -> [B, T, H, D]
    (S != T = cross attention). k/v may carry FEWER heads, a divisor of H
    (grouped-query attention: query head h reads KV head ``h // (H //
    Hkv)`` through the forward kernel's index map, no repeated copy in
    HBM); such a call is forward-only — the backward kernels sum nothing
    over a group — and differentiating it raises.

    T is padded to a block multiple internally; padded keys are masked in the
    kernel. ``kv_lens`` [B] int additionally masks each sample's keys at or
    past its true length — the variable-length (LoD) batch and padded-source
    cross-attention path; grads for masked keys are exactly zero. Fully
    differentiable: the VJP runs dedicated Pallas dq and dk/dv kernels that
    recompute probability tiles in VMEM from the saved logsumexp — no [T, S]
    matrix in HBM in either direction. With ``causal`` (a square: S == T)
    all three kernels visit only what lies at or below the diagonal (the
    walk is described above ``_mask_tile``).

    ``window`` (with ``causal``): sliding-window attention — row p sees the
    keys ``p - window < j <= p``. The forward kernel walks the BAND: a
    q-block starts at the k-block that holds its first row's first key and
    masks the blocks the band's left edge crosses on the left
    (:func:`_walk_k`); its custom call is named
    ``flash_window_attention_fwd``. Forward only — the backward kernels
    walk no band, and differentiating such a call raises.

    v may be NARROWER than q and k (``v.shape[-1]`` < D: o is as wide as
    v), and ``sink`` [H] gives every query head a learned logit that joins
    its softmax's denominator and no value (``P_j = exp(s_j) / (sum exp(s)
    + exp(sink))``). Either makes the call :func:`flash_attention_with_lse`'s
    o, forward-only; a call with neither is the program it was.

    Short sequences (max(T, S) < SHORT_SEQ_DENSE, no explicit blocks given)
    auto-route to a masked dense einsum: below that point the kernels'
    per-program overhead exceeds their HBM saving (measured — the NMT
    len-64 shapes; docs/design/nmt_roofline.md), and XLA's fusion keeps the
    small score tensor out of HBM anyway.
    """
    D = q.shape[-1]
    scale_v = scale if scale is not None else D ** -0.5
    valid = None
    if kv_lens is not None:
        # a fully-masked sample (kv_lens == 0) has no softmax support: both
        # paths would emit garbage rows. Attend key 0 (finite everywhere),
        # then zero those samples' outputs — the multiply also zeroes their
        # incoming cotangent, so no gradient reaches any key of theirs.
        valid = (kv_lens > 0)
        kv_lens = jnp.maximum(kv_lens, 1)
    if window is not None and not causal:
        raise ValueError("flash_attention: a window is a causal band "
                         "(causal=True)")
    if q.shape[2] % k.shape[2]:
        raise ValueError(
            f"flash_attention: {q.shape[2]} query heads are not "
            f"whole groups over {k.shape[2]} KV heads")
    dense = _short_dense(q, k, block_q, block_k)
    if sink is not None or v.shape[-1] != D:
        o, _ = flash_attention_with_lse(
            q, k, v, causal=causal, scale=scale_v, kv_lens=kv_lens,
            window=window, sink=sink, block_q=block_q, block_k=block_k,
            interpret=interpret, short_dense=True)
    elif window is not None:
        _count_window_route(dense)
        blocks = (None, None) if dense else _default_blocks(block_q, block_k)
        o = _flash_window(q, k, v, kv_lens, (window, scale_v) + blocks
                          + (_interpret(interpret),))
    elif dense:
        o = _dense_attention(q, k, v, causal, scale_v, kv_lens)
    else:
        block_q, block_k = _default_blocks(block_q, block_k)
        interpret = _interpret(interpret)
        if k.shape[2] != q.shape[2]:
            o, _ = _fa_fwd_call(q, k, v, causal, scale_v, block_q, block_k,
                                interpret, kv_lens=kv_lens)
        else:
            o = _flash(q, k, v, kv_lens, causal, scale_v, block_q, block_k,
                       interpret)
    if valid is not None:
        o = o * valid[:, None, None, None].astype(o.dtype)
    return o


def _short_dense(q, k, block_q, block_k) -> bool:
    """flash_attention's rule for the dense route: no blocks asked for and
    both sequences under SHORT_SEQ_DENSE."""
    return (block_q is None and block_k is None
            and max(q.shape[1], k.shape[1]) < SHORT_SEQ_DENSE)


def _count_window_route(dense: bool):
    from .. import obs
    obs.count("kernels.routes_total", kernel="flash_window_attention_fwd",
              route="dense" if dense else "kernel")


def flash_attention_with_lse(q: jax.Array, k: jax.Array, v: jax.Array, *,
                             causal: bool = False,
                             scale: Optional[float] = None,
                             kv_lens: Optional[jax.Array] = None,
                             window: Optional[int] = None,
                             sink: Optional[jax.Array] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             interpret: Optional[bool] = None,
                             short_dense: bool = False):
    """Forward-only attention returning ``(o, lse)`` with lse: [B, T, H] f32.

    Building block for what merges partial reads: results over disjoint sets
    of keys merge exactly via logaddexp (parallel/ring_attention.py's KV
    shards; models/mimo_v2.py's blocks of a row). Not differentiable — ring
    attention installs its own VJP that reuses the Pallas backward kernels
    per ring step.

    ``kv_lens``, ``window`` (with ``causal``), values narrower than keys
    and ``sink`` [H] as :func:`flash_attention` has them: o is as wide as
    v, and the sink is inside lse. ``short_dense``: take flash_attention's
    dense route for short sequences (the default keeps every call a kernel:
    ring attention's backward recomputes from the kernel's lse).
    """
    D = q.shape[-1]
    scale_v = scale if scale is not None else D ** -0.5
    if window is not None and not causal:
        raise ValueError("flash_attention_with_lse: a window is a causal "
                         "band (causal=True)")
    dense = short_dense and _short_dense(q, k, block_q, block_k)
    if window is not None:
        _count_window_route(dense)
    if dense:
        return _dense_attention(q, k, v, causal, scale_v, kv_lens, window,
                                sink, with_lse=True)
    block_q, block_k = _default_blocks(block_q, block_k)
    return _fa_fwd_call(q, k, v, causal, scale_v, block_q, block_k,
                        _interpret(interpret), kv_lens=kv_lens,
                        window=window, sink=sink)


def flash_block_grads(q, k, v, o, lse, do, *, causal: bool = False,
                      scale: Optional[float] = None,
                      block_q: Optional[int] = None,
                      block_k: Optional[int] = None,
                      interpret: Optional[bool] = None,
                      delta=None):
    """Raw (dq, dk, dv) for one attention block given saved (o, lse).

    Used by ring attention's hand-written backward, where each ring step is
    one such block with externally-merged softmax statistics. Pass ``delta``
    (= rowsum(dO·O), [B,T,H] f32) to avoid recomputing it per step — it is
    loop-invariant across ring steps.
    """
    D = q.shape[-1]
    scale_v = scale if scale is not None else D ** -0.5
    block_q, block_k = _default_blocks(block_q, block_k)
    interpret = _interpret(interpret)
    return _fa_bwd_call(q, k, v, o, lse, do, causal, scale_v, block_q,
                        block_k, interpret, delta=delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, kv_lens, causal, scale, block_q, block_k, interpret):
    o, _ = _fa_fwd_call(q, k, v, causal, scale, block_q, block_k, interpret,
                        kv_lens=kv_lens)
    return o


def _flash_fwd(q, k, v, kv_lens, causal, scale, block_q, block_k, interpret):
    o, lse = _fa_fwd_call(q, k, v, causal, scale, block_q, block_k, interpret,
                          kv_lens=kv_lens)
    return o, (q, k, v, kv_lens, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, kv_lens, o, lse = res
    dq, dk, dv = _fa_bwd_call(q, k, v, o, lse, g, causal, scale, block_q,
                              block_k, interpret, kv_lens=kv_lens)
    return dq, dk, dv, None                  # int lens: no cotangent


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Fused decode-step attention — the KV-cache read is the bytes term that
# dominates incremental decode. One (sample, chunk) program streams a chunk
# of that sample's cache rows through VMEM in the cache's natural [L, H, D]
# layout (no head transpose in HBM), masks rows past the write position and
# folds the chunk into a running f32 softmax (m, l, acc scratch) — the
# flash-decode recurrence, so VMEM holds one chunk, never the whole cache
# row. Chunks wholly past ``pos`` skip their compute. The int8 path
# dequantizes rows in VMEM from per-(row, head) scales, so the HBM cache
# term halves (2 bytes -> 1 + scale overhead) while the math stays f32 — the
# quantized-KV numerics contract of docs/design/kernels.md.
#
# The read is written for the VPU, not the MXU: with (H, D) on the tile's
# (sublane, lane) axes, s[l, h] = sum_d q[h, d] k[l, h, d] is a broadcast
# multiply and a lane reduction, and o[h, d] = sum_l p[l, h] v[l, h, d] a
# lane broadcast and adds along the untiled L axis — no operand ever changes
# layout. (A head-batched dot_general over the MIDDLE axis of [L, H, D] with
# no free lhs dimension is refused by Mosaic: "failed to parse
# TPU_DotDimensionNumbersAttr parameter 'lhs_non_contracting_dims'".)
# tests/test_chip_compile.py compiles every variant for a described v5e.
#
# That holds for a group of ONE (a KV head a query head). Grouped-query pools
# (fewer KV heads than query heads) take a body of their own, on the MXU:
# _grouped_decode_attn_kernel, below.
# ---------------------------------------------------------------------------

#: rows of cache one decode-attention program holds in VMEM: a [256, 12, 64]
#: bf16 block pads to [256, 16, 128] = 1 MiB, so K + V double-buffered plus
#: the f32 working copies stay well inside the 16 MiB scoped-VMEM default
DECODE_CHUNK = 256


def _decode_chunk(L: int) -> int:
    """Chunk rows for a dense-row read of L: the largest power of two
    <= DECODE_CHUNK that divides L (>= 8, the scale block's sublane tile),
    else the whole read as one block."""
    c = DECODE_CHUNK
    while c >= 8:
        if L % c == 0:
            return c
        c //= 2
    return L


def _decode_attn_kernel(*refs, scale: float, chunk: int, quantized: bool,
                        work_list: bool):
    """One program of the decode read at a KV head a query head, dense-row
    or paged, float or int8 — one body so every variant shares the softmax.
    A program reads chunk ``c`` (rows c*chunk..) of sample ``b``'s cache.

    Dense rows (grid (B, chunks)): scalar-prefetched pos [B]; b and c are
    the program ids. Paged (grid (n_work,), ``work_list``): scalar-
    prefetched slot / page / ordinal / last [B*NB] (:func:`paged_work_list`;
    ``page`` is read by the index maps alone) then pos [B]; program i
    reads the page of ordinal c = ordinal[i] of slot b = slot[i].

    pos: rows j <= pos[b] are live (row pos holds THIS step's k/v,
    appended before the read). Blocks: q [1, H, D]; k/v [1, chunk, H, D]
    (+ ks/vs [1, chunk, H] f32 when int8); o [1, H, D] f32, written by
    sample b's last chunk. Scratch m/l [H, 1], acc [H, D] f32 carry the
    running softmax across one sample's chunks, which run back to back."""
    if quantized:
        (pos_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
         m_ref, l_ref, acc_ref) = refs[-10:]
    else:
        pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs[-8:]
    if work_list:
        slot_ref, _, ord_ref, last_ref = refs[:4]
        i = pl.program_id(0)
        b, c, last = slot_ref[i], ord_ref[i], last_ref[i] == 1
    else:
        b, c = pl.program_id(0), pl.program_id(1)
        last = c == pl.num_programs(1) - 1

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[b]

    @pl.when(c * chunk <= pos)           # a dead chunk adds exactly nothing
    def _live():
        k = k_ref[0].astype(jnp.float32)                    # [chunk, H, D]
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            k = k * ks_ref[0][..., None]
            v = v * vs_ref[0][..., None]
        j = c * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1, 1), 0)
        q = q_ref[0].astype(jnp.float32) * scale            # [H, D]
        s = jnp.sum(k * q[None], axis=-1, keepdims=True)    # [chunk, H, 1]
        s = jnp.where(j <= pos, s, _NEG)
        m_prev = m_ref[...]                                 # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        p = jnp.exp(s - m_new[None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=0)
        acc_ref[...] = acc_ref[...] * corr + jnp.sum(p * v, axis=0)
        m_ref[...] = m_new

    @pl.when(last)
    def _finish():
        o_ref[0] = acc_ref[...] / l_ref[...]


def _mxu_hi_lo(x, w, dims):
    """x [H, .] (f32) against a matrix of cache rows, f32 accumulation: a
    bf16 matrix goes to the MXU as it is and x as the sum of two bf16
    halves, hi + lo, as ONE [2 H, .] operand, so the matrix is pushed to
    the MXU once; any other matrix multiplies in its own dtype."""
    dot = functools.partial(jax.lax.dot_general, dimension_numbers=(
        dims, ((), ())), preferred_element_type=jnp.float32)
    if w.dtype != jnp.bfloat16:
        return dot(x, w)
    H = x.shape[0]
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    y = dot(jnp.concatenate([hi, lo], axis=0), w)
    return y[:H] + y[H:]


def _grouped_decode_attn_kernel(slot_ref, _, ord_ref, last_ref, pos_ref,
                                q_ref, *refs, scale: float, chunk: int,
                                quantized: bool, groups: int,
                                window: Optional[int] = None,
                                has_sink: bool = False):
    """One program of the PAGED read over grouped-query heads: H query
    heads over Hkv = H // ``groups`` KV heads, query head h reading KV head
    ``h // groups``. A sibling of :func:`_decode_attn_kernel` with the
    same programs round the body (the work list, one page a program, the
    running softmax in scratch, the finish on a slot's last page) and both
    products on the MXU, where that body would do a query head's arithmetic
    at a time on the VPU for one KV head's bytes.

    Blocks: q / o [1, H, D] in the caller's head order; k/v [1, chunk, Hkv,
    D] (+ ks/vs [1, chunk, Hkv] f32 when int8); scratch m/l [H, 1], acc
    [H, D]. The page collapses to a [chunk * Hkv, D] matrix, row r holding
    position r // Hkv of KV head r % Hkv — the block's own bytes in the
    block's own order — and ONE product of all H queries against it gives
    scores [H, chunk * Hkv]: the columns of the other groups' KV heads are
    masked like rows past ``pos``, so their weights are exactly 0 and one
    more product against V's matrix accumulates every head's values. The
    MXU does Hkv times the flops the groups need and has them idle.

    ``window``: the read of a sliding-window layer through a RING
    (:func:`paged_work_list` with the same window). A slot's programs then
    start at the page that holds position ``pos - window + 1``, not at page
    0: ``ord_ref`` holds each page's ABSOLUTE number, the first of a slot
    (where the running softmax starts) is worked out from ``pos``, and rows
    at ``pos - window`` and before are masked like rows past ``pos``.

    v may be narrower than k (``Dv``: o and acc are), and ``has_sink`` puts
    a ``sink_ref`` [H, 1] f32 after v: a logit a head that joins the
    finished softmax's denominator (under a max that includes it).

    Precision: bf16 pools go to the MXU as they are, and the f32 operands
    beside them (q, the softmax weights) as the sum of two bf16 halves,
    hi + lo, so a product keeps ~16 bits of them; f32 and int8 pools
    (dequantized here) multiply in f32. Accumulation and softmax are f32."""
    sink_ref = None
    if quantized:
        k_ref, ks_ref, v_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    elif has_sink:
        k_ref, v_ref, sink_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    i = pl.program_id(0)
    b, c, last = slot_ref[i], ord_ref[i], last_ref[i] == 1
    H, D = q_ref.shape[1:]
    Hkv = H // groups
    R = chunk * Hkv
    pos = pos_ref[b]
    first = 0 if window is None else \
        jnp.maximum(pos - window + 1, 0) // chunk

    @pl.when(c == first)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(c * chunk <= pos)           # a dead page adds exactly nothing
    def _live():
        k, v = k_ref[0], v_ref[0]                           # [chunk, Hkv, D]
        if quantized:
            k = k.astype(jnp.float32) * ks_ref[0][..., None]
            v = v.astype(jnp.float32) * vs_ref[0][..., None]
        elif k.dtype != jnp.bfloat16 or Hkv % 2:
            # (bf16 rows are packed in pairs: an odd head count does not
            # collapse, Mosaic's "unsupported shape cast", so it goes f32)
            k, v = k.astype(jnp.float32), v.astype(jnp.float32)
        k, v = k.reshape(R, D), v.reshape(R, v.shape[-1])

        mxu = _mxu_hi_lo
        q = q_ref[0].astype(jnp.float32) * scale            # [H, D]
        s = mxu(q, k, ((1,), (1,)))                         # [H, R]
        col = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0) // groups
        row = c * chunk + col // Hkv
        seen = row <= pos if window is None else \
            (row <= pos) & (row > pos - window)
        s = jnp.where(seen & (col % Hkv == head), s, _NEG)
        m_prev = m_ref[...]                                 # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)          # a live page holds a row every head
        corr = jnp.exp(m_prev - m_new)  # sees: m_new is a real score
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + mxu(p, v, ((1,), (0,)))
        m_ref[...] = m_new

    @pl.when(last)
    def _finish():
        if sink_ref is None:
            o_ref[0] = acc_ref[...] / l_ref[...]
        else:
            m, b = m_ref[...], sink_ref[...]
            m_new = jnp.maximum(m, b)
            corr = jnp.exp(m - m_new)
            o_ref[0] = acc_ref[...] * corr / (l_ref[...] * corr
                                              + jnp.exp(b - m_new))


def _decode_attn_call(prefetch, q, k, v, k_scale, v_scale, qo_spec, kv_spec,
                      sc_spec, *, grid, scale, chunk, interpret, name,
                      groups=1, window=None, v_spec=None, sink=None):
    """The one pallas_call behind decode_attention and
    paged_decode_attention: ``prefetch`` scalars (pos last; five of them
    = the paged work list), then q [B, H, D], then k/v — each followed by
    its scale operand when the cache is int8. ``groups`` > 1 (the paged
    read alone): k/v hold H // groups heads and the grouped body runs.
    ``name`` is the caller's: what a device trace shows the kernel as.
    ``v_spec`` (the grouped body alone): v's own block where its rows are
    narrower than k's — o is then as wide as v; ``sink`` [H] f32: the
    heads' sink logits, an operand after v."""
    from jax.experimental.pallas import tpu as pltpu
    H, D = q.shape[1:]
    o_spec = qo_spec
    if k_scale is not None:
        kv_args, kv_specs = ((k, k_scale, v, v_scale),
                             [kv_spec, sc_spec, kv_spec, sc_spec])
    else:
        kv_args, kv_specs = (k, v), [kv_spec, v_spec or kv_spec]
    if v_spec is not None:
        D = v.shape[-1]
        o_spec = pl.BlockSpec((1, H, D), qo_spec.index_map)
    if sink is not None:
        kv_args += (sink.astype(jnp.float32)[:, None],)
        kv_specs.append(pl.BlockSpec((H, 1), lambda *_: (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch), grid=grid,
        in_specs=[qo_spec] + kv_specs, out_specs=o_spec,
        scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, D), jnp.float32)])
    if groups > 1:
        kernel = functools.partial(_grouped_decode_attn_kernel, scale=scale,
                                   chunk=chunk, quantized=k_scale is not None,
                                   groups=groups, window=window,
                                   has_sink=sink is not None)
    else:
        kernel = functools.partial(_decode_attn_kernel, scale=scale,
                                   chunk=chunk, quantized=k_scale is not None,
                                   work_list=len(grid) == 1)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape[:2] + (D,), jnp.float32),
        interpret=interpret, name=name,
    )(*prefetch, q, *kv_args)


def quantize_kv(x: jax.Array):
    """Symmetric int8 rows for the KV cache: x [..., D] ->
    (q int8 [..., D], scale f32 [...]) with x ~= q * scale per row.

    Per-(position, head) scales: one f32 per D-vector — 2 extra bytes per
    64-element bf16 row vs the 64 saved, so the cache read genuinely
    halves. scale = amax/127 keeps the codebook symmetric (no zero-point),
    matching the in-kernel dequant ``q.astype(f32) * scale``."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _dense_decode_attention(q, k, v, pos, scale, k_scale, v_scale,
                            window=None, sink=None):
    """Reference-math route (short caches / off-TPU): same masked-softmax
    formulation as the kernel, ordinary XLA ops. Quantized caches
    dequantize up front — numerically the kernel's contract, but the f32
    cache materializes, so this route only makes sense where the cache is
    small anyway. ``window`` = (window, ring): the rows are a RING's,
    gathered in the ring's order — entry e holds the newest page ``a <=
    pos // bs`` with ``a % ring == e`` — and the rows of ``(pos - window,
    pos]`` are live. v may be narrower than k; ``sink`` [H]: a logit a
    head in the denominator alone."""
    if k_scale is not None:
        k = k.astype(jnp.float32) * k_scale[..., None]
        v = v.astype(jnp.float32) * v_scale[..., None]
    L = k.shape[1]
    if k.shape[2] != q.shape[1]:        # grouped-query heads: h reads h // G
        k = jnp.repeat(k, q.shape[1] // k.shape[2], axis=2)
        v = jnp.repeat(v, q.shape[1] // v.shape[2], axis=2)
    s = jnp.einsum("bhd,bjhd->bhj", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    if window is None:
        valid = (jnp.arange(L)[None, :] <= pos[:, None])[:, None, :]
    else:
        ring, bs = window[1], L // window[1]
        entry, row = jnp.arange(L) // bs, jnp.arange(L) % bs
        top = (pos // bs)[:, None]
        j = (top - (top - entry[None, :]) % ring) * bs + row[None, :]
        valid = ((j >= 0) & (j <= pos[:, None])
                 & (j > pos[:, None] - window[0]))[:, None, :]
    s = jnp.where(valid, s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    if sink is not None:
        b = sink.astype(jnp.float32)[None, :, None]
        m = jnp.maximum(m, b)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if sink is not None:
        l = l + jnp.exp(b - m)
    return jnp.einsum("bhj,bjhd->bhd", p / l, v.astype(jnp.float32))


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     pos: jax.Array, *, scale: Optional[float] = None,
                     k_scale: Optional[jax.Array] = None,
                     v_scale: Optional[jax.Array] = None,
                     route: Optional[str] = None,
                     interpret: Optional[bool] = None) -> jax.Array:
    """Single-token KV-cache attention read — THE auto-routing entry for
    the decode step (models/transformer.py decode_step and everything
    above it: generate_cached/generate_fused, serving.ContinuousBatcher,
    speculative verify).

    q: [B, H, D] (this step's query); k/v: [B, L, H, D] cache slices
    already bounded to the live read length L (callers slice ``[:, :L]``
    per their cache bucket); pos: [B] int32 — rows j <= pos[b] are live.
    k_scale/v_scale: [B, L, H] f32 per-row dequant scales when k/v are
    int8 (see :func:`quantize_kv`). Returns o [B, H, D] f32.

    Routing (``route=None``): the Pallas kernel streams the cache once
    per sample and wins exactly where decode is cache-bytes-bound — long
    reads on the TPU; short reads (L < SHORT_SEQ_DENSE) and off-TPU hosts
    take the dense reference math, where XLA's fusion already keeps the
    small score tensor out of HBM. Both routes share one masked-softmax
    formulation, so route choice never changes greedy tokens
    (tests/test_decode_fused.py asserts this bit-for-bit on CPU via
    ``route="kernel", interpret=True``)."""
    B, L, H, D = k.shape
    scale_v = scale if scale is not None else D ** -0.5
    route = decode_route(L, route)
    from .. import obs
    obs.count("kernels.routes_total", kernel="decode_attention", route=route)
    if route == "dense":
        return _dense_decode_attention(q, k, v, pos, scale_v, k_scale,
                                       v_scale)
    if route != "kernel":
        raise ValueError(f"unknown decode_attention route {route!r}")
    chunk = _decode_chunk(L)
    qo_spec = pl.BlockSpec((1, H, D), lambda b, c, p: (b, 0, 0))
    kv_spec = pl.BlockSpec((1, chunk, H, D), lambda b, c, p: (b, c, 0, 0))
    sc_spec = pl.BlockSpec((1, chunk, H), lambda b, c, p: (b, c, 0))
    return _decode_attn_call(
        (pos.astype(jnp.int32),), q, k, v, k_scale, v_scale, qo_spec,
        kv_spec, sc_spec, grid=(B, L // chunk), scale=scale_v, chunk=chunk,
        interpret=_interpret(interpret), name="decode_attention")


# ---------------------------------------------------------------------------
# Paged decode attention — the KV cache as a shared BLOCK POOL instead of one
# max_len-padded row per slot: pools [P, bs, H, D] hold fixed-size pages, a
# per-request block table [B, NB] names which pages hold positions
# j*bs..(j+1)*bs-1, and only LIVE pages move. HBM then holds tokens, not
# padding — the serving plane's mixed-length sessions share one pool and
# freed requests return pages immediately (paddle_tpu/serving/paged.py).
# The kernel's grid is the list of LIVE (slot, page) pairs, not the table:
# one program per page a request has started, built in the graph from the
# table and ``pos`` (paged_work_list) and scalar-prefetched, so the index maps
# read slot and page from it and a dead table cell costs nothing — no program,
# no fetch. Each program streams its page through VMEM once and runs the SAME
# masked-softmax body as decode_attention — so the paged read and the
# dense-row read agree to the bit on the same cache contents.
# ---------------------------------------------------------------------------

def gather_pages(pool: jax.Array, tables: jax.Array) -> jax.Array:
    """Materialize the dense per-sample view of a page pool: pool
    [P, bs, ...] gathered by tables [B, NB] -> [B, NB*bs, ...]. The dense
    reference route (and tests) read through this; the kernel route never
    materializes it in HBM."""
    B, NB = tables.shape
    g = pool[tables]                       # [B, NB, bs, ...]
    return g.reshape((B, NB * pool.shape[1]) + pool.shape[2:])


def pool_rows(pool: jax.Array, shape) -> jax.Array:
    """A page pool as its rows are STATED, [P, bs, *shape]. A pool may be
    HELD wider: the serving pool pads a row narrower than the chip's (8,
    128) tile up to it, so that the array lies row-major on the chip the
    way these kernels take it (serving/paged.py ``_held_shape``), and the
    stated rows are the leading corner of the held ones — the same bytes
    at the same offsets, a bitcast in the compiled program. A pool held as
    stated comes back as it is."""
    if pool.shape[2:] == tuple(shape):
        return pool
    return pool[(slice(None), slice(None))
                + tuple(slice(0, n) for n in shape)]


def put_rows(pool: jax.Array, page: jax.Array, row: jax.Array,
             new: jax.Array):
    """Write cache rows ``new`` [*lead, *shape] at ``(page, row)`` (each
    [*lead]) of a pool held [P, bs, *shape] or wider (:func:`pool_rows`):
    -> (the pool, its rows as stated — what the paged read takes). Rows
    for a wider pool are padded to its width first and written WHOLE (the
    padding takes zeros, which nothing reads): a scatter of whole rows is
    one instruction on the chip, a scatter into the rows' leading corner
    the compiler expands into a loop over ``lead`` — 16 small writes an
    array a decode step, which doubled the GPT-2 cells' step (PERF.md
    section 6, PR 40)."""
    shape = new.shape[page.ndim:]
    held = pool.shape[2:]
    if held != shape:
        new = jnp.pad(new, ((0, 0),) * page.ndim
                      + tuple((0, h - n) for h, n in zip(held, shape)))
    pool = pool.at[page, row].set(new)
    return pool, pool_rows(pool, shape)


def paged_work_list(tables: jax.Array, pos: jax.Array, page_block: int,
                    window: Optional[int] = None):
    """The paged read's launch geometry: the live (slot, page) pairs of a
    block table, slot by slot with each slot's pages in order.

    tables [B, NB] int32, pos [B] int32 -> (slot, page, ordinal, last)
    each [B * NB] int32, and n_work [1] int32. Slot b holds pages
    ``0 .. pos[b] // page_block`` (at most NB; an empty slot — pos 0, a
    null table — holds one, so every row of the output is written). Item
    i < n_work reads pool page ``page[i]`` = ``tables[slot[i],
    ordinal[i]]``; ``last[i]`` is 1 on a slot's final page. Items past
    n_work are padding no program reads. Depends on ``tables`` and ``pos``
    alone: one list serves every layer of a decode step that reads the
    same table to the same reach.

    ``window``: the list of the layers that see ``window`` positions back
    and keep their rows in a RING — ``tables`` [B, ring] is then the ring's
    table, position p living in entry ``(p // page_block) % ring``. Slot b
    holds the pages of ``(pos[b] - window, pos[b]]`` alone (``ring`` must
    hold them: at most ``ceil(window / page_block) + 1``), ``ordinal`` is a
    page's ABSOLUTE number ``p // page_block`` (the kernel's mask needs the
    positions) and ``page`` the ring entry it lives in. A decode step of a
    model with both kinds of layer builds two lists."""
    B, NB = tables.shape
    tables, pos = tables.astype(jnp.int32), pos.astype(jnp.int32)
    if window is None:
        n_pages = jnp.clip(pos // page_block + 1, 1, NB)          # [B]
        ends = jnp.cumsum(n_pages)
        item = jnp.arange(B * NB, dtype=jnp.int32)
        slot = jnp.minimum(
            jnp.sum(item[:, None] >= ends[None, :], axis=1, dtype=jnp.int32),
            B - 1)
        ordinal = jnp.minimum(item - (ends - n_pages)[slot], NB - 1)
        last = (ordinal == n_pages[slot] - 1).astype(jnp.int32)
        return slot, tables[slot, ordinal], ordinal, last, ends[-1:]
    if NB * page_block < window + page_block:
        raise ValueError(f"a ring of {NB} pages of {page_block} cannot hold "
                         f"a window of {window} positions")
    first = jnp.maximum(pos - window + 1, 0) // page_block        # [B]
    n_pages = pos // page_block - first + 1
    ends = jnp.cumsum(n_pages)
    item = jnp.arange(B * NB, dtype=jnp.int32)
    slot = jnp.minimum(
        jnp.sum(item[:, None] >= ends[None, :], axis=1, dtype=jnp.int32),
        B - 1)
    ordinal = jnp.minimum(first[slot] + item - (ends - n_pages)[slot],
                          (pos // page_block)[slot])
    last = (ordinal == (pos // page_block)[slot]).astype(jnp.int32)
    return slot, tables[slot, ordinal % NB], ordinal, last, ends[-1:]


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, tables: jax.Array,
                           pos: jax.Array, *, scale: Optional[float] = None,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           work=None, window: Optional[int] = None,
                           sink: Optional[jax.Array] = None,
                           route: Optional[str] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Single-token attention read through a block table — the paged twin
    of :func:`decode_attention`.

    q: [B, H, D]; k_pool/v_pool: [P, bs, Hkv, D] page pools (bf16/f32, or
    int8 with k_scale/v_scale [P, bs, Hkv] f32 pools), ``Hkv`` = H or a
    divisor of it (grouped-query attention: query head h reads KV head
    ``h // (H // Hkv)``; the kernel streams a page ONCE for all its groups
    and multiplies it by every query head at once on the MXU,
    :func:`_grouped_decode_attn_kernel`, where Hkv = H keeps the VPU body
    of :func:`decode_attention`); tables: [B, NB] int32
    page indices covering positions 0..NB*bs-1 (entries past a request's
    live pages point at the reserved null page — rows there sit past
    ``pos`` and are masked exactly like dense padding); pos: [B] int32,
    rows j <= pos[b] are live. ``work``: :func:`paged_work_list` of
    (tables, pos, bs) when the caller already holds it (a decode step
    builds one for all its layers). Returns o [B, H, D] f32.

    Routing matches decode_attention: the Pallas kernel for long on-TPU
    reads (one program per live page, driven by the scalar-prefetched work
    list), the dense gather + reference math for short reads / off-TPU.
    Both routes share one masked-softmax formulation over the SAME
    assembled row order, so route choice never changes greedy tokens.
    ``kernels.paged_decode_plan_total{plan, group}`` says, once a trace of
    a kernel-route call, which body its programs run.

    ``window``: the read of a sliding-window layer — rows ``pos - window <
    j <= pos`` — through a RING: ``tables`` [B, ring] names the ring's
    pages, position p in entry ``(p // bs) % ring``, and ``work`` is
    ``paged_work_list(tables, pos, bs, window)``. The kernel is the
    grouped body (one KV head a query head is not asked of it) and its
    custom call is NAMED ``paged_window_attention``, as are its counters:
    what reads the trace counts a full read's bytes by every live row and
    this one's by the window's.

    ``v_pool`` may hold rows NARROWER than ``k_pool``'s ([P, bs, Hkv, Dv]:
    o is [B, H, Dv]) and ``sink`` [H] gives every query head a logit that
    joins its softmax's denominator and no value — both the grouped body's
    (and the dense route's); a call with neither is the program it was."""
    B, NB = tables.shape
    P, bs, H, D = k_pool.shape
    Hq = q.shape[1]
    if Hq % H:
        raise ValueError(f"paged_decode_attention: {Hq} query heads are "
                         f"not whole groups over {H} KV heads")
    L = NB * bs
    scale_v = scale if scale is not None else D ** -0.5
    route = decode_route(L, route)
    name = "paged_decode_attention" if window is None \
        else "paged_window_attention"
    from .. import obs
    obs.count("kernels.routes_total", kernel=name, route=route)
    if route == "dense":
        k = gather_pages(k_pool, tables)
        v = gather_pages(v_pool, tables)
        ks = None if k_scale is None else gather_pages(k_scale, tables)
        vs = None if v_scale is None else gather_pages(v_scale, tables)
        return _dense_decode_attention(
            q, k, v, pos, scale_v, ks, vs,
            None if window is None else (window, NB), sink)
    if route != "kernel":
        raise ValueError(f"unknown paged_decode_attention route {route!r}")
    G = Hq // H
    Dv = v_pool.shape[3]
    if window is not None and G == 1:
        raise ValueError("paged_decode_attention: the windowed read is the "
                         "grouped body's (fewer KV heads than query heads)")
    if (sink is not None or Dv != D) and (G == 1 or k_scale is not None):
        raise ValueError("paged_decode_attention: a sink, or values "
                         "narrower than keys, is the grouped body's over "
                         "unquantised pools")
    if work is None:
        work = paged_work_list(tables, pos, bs, window)
    *work, n_work = work
    obs.count("kernels.paged_decode_plan_total",
              plan="group_mxu" if G > 1 else "head_vpu", group=str(G))
    qo_spec = pl.BlockSpec((1, Hq, D), lambda i, slot, *_: (slot[i], 0, 0))
    page_spec = pl.BlockSpec((1, bs, H, D),
                             lambda i, slot, page, *_: (page[i], 0, 0, 0))
    sc_spec = pl.BlockSpec((1, bs, H),
                           lambda i, slot, page, *_: (page[i], 0, 0))
    return _decode_attn_call(
        (*work, pos.astype(jnp.int32)), q, k_pool, v_pool, k_scale, v_scale,
        qo_spec, page_spec, sc_spec, grid=(n_work[0],), scale=scale_v,
        chunk=bs, interpret=_interpret(interpret), name=name, groups=G,
        window=window, sink=sink, v_spec=None if Dv == D else pl.BlockSpec(
            (1, bs, H, Dv), lambda i, slot, page, *_: (page[i], 0, 0, 0)))


# ---------------------------------------------------------------------------
# Paged LATENT attention — the absorbed read of multi-head latent attention
# (DeepSeek-V2/V3): the cache holds ONE row a token a layer, the normed
# latent (d_value wide) followed by the shared rotary key, and every query
# head reads that same row: keys are the whole row, values its first d_value
# entries. A sibling of paged_decode_attention, not a widening of it: that
# kernel forms q * k elementwise per (row, head) on the VPU, which for 64
# heads over one 576-wide row would be a [rows, 64, 576] f32 temporary; here
# the two products are matrix products on the MXU ([H, Dk] x [rows, Dk]^T and
# [H, rows] x [rows, d_value]). They share everything around the body: the
# work list (paged_work_list), one program per live (slot, page), the
# scalar-prefetched index maps, the running softmax in m/l/acc scratch, the
# null page and the ``j <= pos`` mask, and the dense route off-TPU.
# ---------------------------------------------------------------------------

def _latent_attn_kernel(slot_ref, _, ord_ref, last_ref, pos_ref, q_ref,
                        kv_ref, o_ref, m_ref, l_ref, acc_ref, *,
                        scale: float, chunk: int, d_value: int):
    """Program i reads page ordinal c = ordinal[i] of slot b = slot[i].
    Blocks: q [1, H, Dk]; kv [1, chunk, Dk]; o [1, H, d_value] f32, written
    by slot b's last page. Operands stay in the cache's dtype, both products
    accumulate in f32, the softmax is f32."""
    i = pl.program_id(0)
    b, c, last = slot_ref[i], ord_ref[i], last_ref[i] == 1

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[b]

    @pl.when(c * chunk <= pos)
    def _live():
        rows = kv_ref[0]                                    # [chunk, Dk]
        s = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [H, chunk]
        j = c * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
        s = jnp.where(j <= pos, s, _NEG)
        m_prev = m_ref[...]                                 # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(rows.dtype), rows[:, :d_value],
            preferred_element_type=jnp.float32)             # [H, d_value]
        m_ref[...] = m_new

    @pl.when(last)
    def _finish():
        o_ref[0] = acc_ref[...] / l_ref[...]


def _dense_latent_attention(q, rows, pos, scale, d_value):
    """Reference-math route: q [B, H, Dk], rows [B, L, Dk] (the gathered
    per-sample view), rows j <= pos[b] live."""
    L = rows.shape[1]
    rows = rows.astype(jnp.float32)
    s = jnp.einsum("bhd,bjd->bhj", q.astype(jnp.float32), rows) * scale
    valid = (jnp.arange(L)[None, :] <= pos[:, None])[:, None, :]
    s = jnp.where(valid, s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("bhj,bjd->bhd", p / l, rows[..., :d_value])


def paged_latent_attention(q: jax.Array, pool: jax.Array, tables: jax.Array,
                           pos: jax.Array, *, d_value: int, scale: float,
                           work=None, route: Optional[str] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Single-token absorbed latent-attention read through a block table.

    q: [B, H, Dk] — each head's query already carried into the latent space
    (``[q_nope W_UK^T | q_rope]``); pool: [P, bs, Dk] latent rows (``Dk`` =
    d_value + the rotary key's width), ONE row a token shared by all heads;
    tables [B, NB], pos [B] and ``work`` as for
    :func:`paged_decode_attention`. Keys are whole rows, values their first
    ``d_value`` entries. Returns o [B, H, d_value] f32 — the caller applies
    W_UV. Routing follows decode_attention's rule (kernel for long on-TPU
    reads, gathered dense math otherwise)."""
    B, NB = tables.shape
    P, bs, Dk = pool.shape
    H = q.shape[1]
    route = decode_route(NB * bs, route)
    from .. import obs
    obs.count("kernels.routes_total", kernel="paged_latent_attention",
              route=route)
    if route == "dense":
        return _dense_latent_attention(q, gather_pages(pool, tables), pos,
                                       scale, d_value)
    if route != "kernel":
        raise ValueError(f"unknown paged_latent_attention route {route!r}")
    from jax.experimental.pallas import tpu as pltpu
    if work is None:
        work = paged_work_list(tables, pos, bs)
    *work, n_work = work
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(n_work[0],),
        in_specs=[pl.BlockSpec((1, H, Dk),
                               lambda i, slot, *_: (slot[i], 0, 0)),
                  pl.BlockSpec((1, bs, Dk),
                               lambda i, slot, page, *_: (page[i], 0, 0))],
        out_specs=pl.BlockSpec((1, H, d_value),
                               lambda i, slot, *_: (slot[i], 0, 0)),
        scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, d_value), jnp.float32)])
    kernel = functools.partial(_latent_attn_kernel, scale=scale, chunk=bs,
                               d_value=d_value)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, d_value), jnp.float32),
        interpret=_interpret(interpret), name="paged_latent_attention",
    )(*work, pos.astype(jnp.int32), q.astype(pool.dtype), pool)


# ---------------------------------------------------------------------------
# Grouped matrix product — the expert layer's three products. The rows of
# ``lhs`` are laid out in TILES of ``tm`` rows, every tile belonging to ONE
# group (parallel/expert_share.py builds that layout: each held expert's
# tokens, padded up to a whole tile), and ``tile_group`` names each tile's
# group. The grid walks only the ``n_tiles`` tiles in use, so an expert no
# token chose costs nothing: no program, no weight fetch. (The technique is
# megablox's — group metadata scalar-prefetched into the index maps; tiles
# aligned to groups make the store mask unnecessary.)
#
# The tiles of one group are ADJACENT (tile_layout sorts them by group), and
# a group's matrix should cross HBM once a visit however many tiles the group
# has. Two plans, one owner (grouped_matmul_blocks):
# - K-split (_grouped_matmul_kernel): weight blocks (tk, tn) on a
#   (N // tn, n_tiles, K // tk) grid. The block changes on every step, so a
#   group with k tiles streams its matrix k times — right where k is 1 (a
#   decode step's short tiles) or the whole matrix does not fit VMEM twice.
# - resident (_grouped_matmul_resident_kernel): tall tiles, one grid step a
#   tile, the group's WHOLE matrix in one of two VMEM buffers. The first tile
#   of a group waits for its matrix and at once starts the fetch of the next
#   group's into the other buffer; the group's further tiles find the
#   matrix where it is and fetch nothing. (BlockSpec pipelining would skip
#   the unchanged block too, but fetches only ONE step ahead: the next
#   group's matrix would hide behind one tile's product, not behind all.)
# ---------------------------------------------------------------------------

def _tile_dot(lhs, w, transposed: bool):
    """lhs [tm, K] x w -> [tm, N] f32: w [K, N], or [N, K] where the
    group's matrix is held with K minor (``transposed``)."""
    if not transposed:
        return jnp.dot(lhs, w, preferred_element_type=jnp.float32)
    return jax.lax.dot_general(lhs, w, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _grouped_matmul_kernel(group_ref, lhs_ref, rhs_ref, o_ref, acc_ref, *,
                           n_k: int, transposed: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _tile_dot(lhs_ref[...], rhs_ref[0], transposed)

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _grouped_matmul_resident_kernel(group_ref, lhs_ref, rhs_hbm, o_ref,
                                    w_ref, sem, slot_ref, *,
                                    transposed: bool):
    """One tile a step; ``rhs_hbm`` [G, K, N] ([G, N, K] ``transposed``)
    stays in HBM, ``w_ref`` [2, ...] holds this group's matrix and receives
    the next group's, ``slot_ref`` (SMEM) remembers which half is this
    group's."""
    from jax.experimental.pallas import tpu as pltpu
    t, n_t = pl.program_id(0), pl.num_programs(0)
    last = group_ref.shape[0] - 1
    g = group_ref[t]

    def fetch(group, slot):
        return pltpu.make_async_copy(rhs_hbm.at[group], w_ref.at[slot],
                                     sem.at[slot])

    @pl.when(t == 0)
    def _prime():
        slot_ref[0] = 1                     # _arrive flips it to 0
        fetch(g, 0).start()

    @pl.when((t == 0) | (group_ref[jnp.maximum(t - 1, 0)] != g))
    def _arrive():
        slot = 1 - slot_ref[0]
        slot_ref[0] = slot
        fetch(g, slot).wait()
        nxt = jax.lax.while_loop(
            lambda j: (j < n_t) & (group_ref[jnp.minimum(j, last)] == g),
            lambda j: j + 1, t + 1)

        @pl.when(nxt < n_t)
        def _ahead():
            fetch(group_ref[nxt], 1 - slot).start()

    o_ref[...] = _tile_dot(lhs_ref[...], w_ref[slot_ref[0]],
                           transposed).astype(o_ref.dtype)


def _dense_grouped_matmul(lhs, rhs, tile_group, tm, out_dtype,
                          transposed=False):
    """Reference-math route: every tile against its own group's matrix."""
    M, K = lhs.shape
    w = rhs[tile_group]                     # [tiles, K, N] (or [.., N, K])
    out = jnp.einsum("tmk,tnk->tmn" if transposed else "tmk,tkn->tmn",
                     lhs.reshape(M // tm, tm, K), w,
                     preferred_element_type=jnp.float32)
    return out.reshape(M, -1).astype(out_dtype)


#: VMEM the resident plan may ask for (a v5e core has 128 MiB; Mosaic's
#: default scoped limit is 16 MiB, so the plan states its own)
_RESIDENT_VMEM = 40 << 20


def _resident_vmem_bytes(tm: int, K: int, N: int, itemsize: int) -> int:
    """The resident plan's VMEM: the matrix twice, the pipeline's two lhs
    and two f32 out blocks, and the product before it is stored."""
    return 2 * K * N * itemsize + 2 * tm * K * itemsize + 3 * tm * N * 4


def _multiples_of_128(n: int):
    """The multiples of 128 that divide ``n``, largest first."""
    return [t for t in range(n - n % 128, 0, -128) if n % t == 0]


def grouped_matmul_blocks(tm: int, K: int, N: int,
                          itemsize: int = 2) -> Tuple[int, int, bool]:
    """(tk, tn, resident): the weight block a program holds and the plan
    that holds it — the single owner of "which plan", from what it can
    see. RESIDENT (the block is the whole matrix): tiles are tall (``tm``
    >= 128, the admission layouts, where a group has several adjacent
    tiles) and the matrix fits VMEM twice, so a group's matrix is fetched
    once a visit and the fetch of the next group's hides behind all of
    this group's tiles. Otherwise K-SPLIT: whole rows of the matrix where
    they fit (one contiguous fetch), streamed once a TILE — a decode
    step's short tiles (one a group: nothing to reuse) and matrices too
    large to hold twice (``K`` 7168). ``tn`` is ``N`` up to 2048, else its
    largest divisor that is a multiple of 128 and at most 3584; ``tk`` the
    largest such divisor of ``K`` that keeps the block at 2 MiB of bf16 or
    under (at most 512 rows: as deep as the accumulator's tile is worth).
    A ``K`` with no such divisor (1856 = 29 x 64) is taken whole, and
    ``tn`` then narrows until the block is 4 MiB or under — never to
    strips of a lane tile's width, whose rows would be 256-byte
    fetches."""
    if tm >= 128 and _resident_vmem_bytes(tm, K, N, itemsize) \
            <= _RESIDENT_VMEM:
        return K, N, True
    wide = [t for t in _multiples_of_128(N) if t <= 3584]
    tn = N if N <= 2048 or not wide else wide[0]
    tk = next((t for t in _multiples_of_128(K)
               if t <= 512 and t * tn <= (1 << 20)), K)
    if tk == K and K * tn > (2 << 20):
        tn = next((t for t in wide if K * t <= (2 << 20)), tn)
    return tk, tn, False


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, tile_group: jax.Array,
                   n_tiles: jax.Array, *, tm: int,
                   out_dtype=jnp.float32, transposed: bool = False,
                   route: Optional[str] = None,
                   interpret: Optional[bool] = None) -> jax.Array:
    """lhs [M, K] (M a multiple of ``tm``) x rhs [G, K, N] -> [M, N]: tile
    t (rows t*tm ..) is multiplied by ``rhs[tile_group[t]]``.
    ``transposed``: rhs is [G, N, K], every group's matrix held with K
    minor (a matrix whose N is no multiple of the lane width — 1856 — is
    held so: the device stores [K, N] of such an N with K minor anyway,
    and hands a kernel that wants it otherwise a COPY of all of it every
    program); the same products, the contraction over both last
    dimensions. Only tiles
    t < n_tiles[0] are computed; the rows of later tiles are UNDEFINED on
    the kernel route (the caller masks them), so a group without rows costs
    nothing. The tiles of one group must be ADJACENT in ``tile_group``
    (tile_layout's order): the resident plan fetches a group's matrix when
    the walk arrives at the group and never again, so a group that came
    back later would be fetched again, and streamed once a visit only if
    its tiles lie together. Operands in their own dtype, f32 accumulation.
    ``route``: "kernel" on the TPU (ONE custom call name,
    ``expert_grouped_matmul``, whichever plan :func:`grouped_matmul_blocks`
    gives the shape), the dense einsum elsewhere (``None`` decides)."""
    M = lhs.shape[0]
    if M % tm:
        raise ValueError(f"grouped_matmul: {M} rows are not whole tiles "
                         f"of {tm}")
    if route is None:
        route = "kernel" if _on_tpu() else "dense"
    from .. import obs
    obs.count("kernels.routes_total", kernel="expert_grouped_matmul",
              route=route)
    if route == "dense":
        return _dense_grouped_matmul(lhs, rhs, tile_group, tm, out_dtype,
                                     transposed)
    if route != "kernel":
        raise ValueError(f"unknown grouped_matmul route {route!r}")
    return _grouped_matmul_call(tile_group.astype(jnp.int32), lhs, rhs,
                                n_tiles, tm, jnp.dtype(out_dtype),
                                _interpret(interpret), transposed)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _grouped_matmul_call(tile_group, lhs, rhs, n_tiles, tm, out_dtype,
                         interpret, transposed=False):
    """The kernel route of :func:`grouped_matmul`. Jitted: a program calls
    it with the same shapes a layer (12 layers x gate / up / down), and a
    jitted wrapper is traced and lowered once a distinct call, not once a
    call site."""
    from jax.experimental.pallas import tpu as pltpu
    (M, K), N = lhs.shape, rhs.shape[1 if transposed else 2]
    itemsize = jnp.dtype(rhs.dtype).itemsize
    tk, tn, resident = grouped_matmul_blocks(tm, K, N, itemsize)
    if resident:
        kernel = functools.partial(_grouped_matmul_resident_kernel,
                                   transposed=transposed)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_tiles[0],),
            in_specs=[pl.BlockSpec((tm, K), lambda t, g: (t, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tm, N), lambda t, g: (t, 0)),
            scratch_shapes=[pltpu.VMEM((2,) + rhs.shape[1:], rhs.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)])
        params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_resident_vmem_bytes(tm, K, N, itemsize)
            + (8 << 20))
    else:
        kernel = functools.partial(_grouped_matmul_kernel, n_k=K // tk,
                                   transposed=transposed)
        w_spec = pl.BlockSpec((1, tn, tk), lambda n, t, k, g: (g[t], n, k)) \
            if transposed else \
            pl.BlockSpec((1, tk, tn), lambda n, t, k, g: (g[t], k, n))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(N // tn, n_tiles[0], K // tk),
            in_specs=[pl.BlockSpec((tm, tk), lambda n, t, k, g: (t, k)),
                      w_spec],
            out_specs=pl.BlockSpec((tm, tn), lambda n, t, k, g: (t, n)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)])
        params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=params, interpret=interpret,
        name="expert_grouped_matmul",
    )(tile_group, lhs, rhs)


# ---------------------------------------------------------------------------
# Fused LSTM sequence kernel — the hl_cuda_lstm.cu analog: the entire T-step
# recurrence runs inside ONE kernel with the recurrent weights and the h/c
# state resident in VMEM, so the per-step state never round-trips HBM the way
# a lax.scan's carry does. The input projection x@W stays outside (one big
# MXU matmul); the kernel consumes the precomputed gates input [B, T, 4H].
# ---------------------------------------------------------------------------

def _lstm_seq_kernel(xw_ref, len_ref, u_ref, b_ref, h0_ref, c0_ref,
                     out_ref, ht_ref, ct_ref, *, T: int, H: int,
                     forget_bias: float):
    """One batch-tile program: xw [T, Bb, 4H] (TIME-MAJOR — dynamic indexing
    is only legal on the leading, untiled dim), lengths [Bb, 1] f32 (mask
    computed in-kernel: no dynamic lane loads), u [H, 4H], b [1, 4H],
    h0/c0 [Bb, H] -> out [T, Bb, H], hT/cT [Bb, H]."""
    u = u_ref[...].astype(jnp.float32)
    bias = b_ref[...].astype(jnp.float32)
    lens = len_ref[...].astype(jnp.float32)          # [Bb, 1]
    h0 = h0_ref[...].astype(jnp.float32)
    c0 = c0_ref[...].astype(jnp.float32)

    def step(t, carry):
        h, c = carry
        xw_t = xw_ref[t].astype(jnp.float32)
        gates = xw_t + jax.lax.dot(h, u,
                                   preferred_element_type=jnp.float32) + bias
        i = jax.nn.sigmoid(gates[:, :H])
        f = jax.nn.sigmoid(gates[:, H:2 * H] + forget_bias)
        g = jnp.tanh(gates[:, 2 * H:3 * H])
        o = jax.nn.sigmoid(gates[:, 3 * H:])
        c_new = f * c + i * g
        h_new = o * jnp.tanh(c_new)
        m = (t.astype(jnp.float32) < lens).astype(jnp.float32)   # [Bb, 1]
        h = m * h_new + (1.0 - m) * h
        c = m * c_new + (1.0 - m) * c
        out_ref[t] = (m * h).astype(out_ref.dtype)
        return h, c

    h, c = jax.lax.fori_loop(0, T, step, (h0, c0))
    ht_ref[...] = h.astype(ht_ref.dtype)
    ct_ref[...] = c.astype(ct_ref.dtype)


def _lstm_seq_train_kernel(xw_ref, len_ref, u_ref, b_ref, h0_ref, c0_ref,
                           out_ref, ht_ref, ct_ref, cseq_ref, *, T: int,
                           H: int, forget_bias: float):
    """Training-mode forward: identical math to _lstm_seq_kernel, plus the
    post-mask cell sequence saved for the hand-written backward (the
    reference's fused hl_lstm likewise saved per-step cell state)."""
    u = u_ref[...].astype(jnp.float32)
    bias = b_ref[...].astype(jnp.float32)
    lens = len_ref[...].astype(jnp.float32)
    h0 = h0_ref[...].astype(jnp.float32)
    c0 = c0_ref[...].astype(jnp.float32)

    def step(t, carry):
        h, c = carry
        xw_t = xw_ref[t].astype(jnp.float32)
        gates = xw_t + jax.lax.dot(h, u,
                                   preferred_element_type=jnp.float32) + bias
        i = jax.nn.sigmoid(gates[:, :H])
        f = jax.nn.sigmoid(gates[:, H:2 * H] + forget_bias)
        g = jnp.tanh(gates[:, 2 * H:3 * H])
        o = jax.nn.sigmoid(gates[:, 3 * H:])
        c_new = f * c + i * g
        h_new = o * jnp.tanh(c_new)
        m = (t.astype(jnp.float32) < lens).astype(jnp.float32)
        h = m * h_new + (1.0 - m) * h
        c = m * c_new + (1.0 - m) * c
        out_ref[t] = (m * h).astype(out_ref.dtype)
        cseq_ref[t] = c.astype(cseq_ref.dtype)
        return h, c

    h, c = jax.lax.fori_loop(0, T, step, (h0, c0))
    ht_ref[...] = h.astype(ht_ref.dtype)
    ct_ref[...] = c.astype(ct_ref.dtype)


def lstm_sequence_fused(xw: jax.Array, lengths: jax.Array, u: jax.Array,
                        b: Optional[jax.Array] = None,
                        h0: Optional[jax.Array] = None,
                        c0: Optional[jax.Array] = None, *,
                        forget_bias: float = 0.0, block_b: int = 8,
                        chunk_t: Optional[int] = None,
                        save_cell: bool = False,
                        interpret: Optional[bool] = None):
    """Masked LSTM over a whole sequence in one Pallas kernel.

    xw: precomputed x@W [B, T, 4H]; lengths: [B] int; u: [H, 4H];
    returns (out [B, T, H], hT [B, H], cT [B, H]), plus the post-mask cell
    sequence c_seq [B, T, H] when ``save_cell`` (the residual the
    hand-written backward kernel consumes — ops/rnn.py wires the custom
    VJP, so training uses this kernel in BOTH directions, matching the
    reference's training-mode fused hl_lstm kernels).

    ``chunk_t`` splits time into chunk-sized kernel launches threading
    (h, c) between them — all inside one traced graph, so the cost is one
    h/c HBM round-trip per boundary, not a dispatch. This is what lets
    ``block_b`` grow past 8 on long sequences: the resident tile is
    [chunk_t, block_b, •] instead of [T, block_b, •], and a 32/64-row
    batch tile feeds the MXU where the old whole-sequence 8-row tile
    starved it (ops/rnn.py _fused_plan picks the pair).
    """
    B, T, G = xw.shape
    if G % 4:
        raise ValueError(f"xw last dim {G} must be 4*H (i/f/g/o gates)")
    H = G // 4
    if chunk_t is not None and chunk_t < T:
        h = h0 if h0 is not None else jnp.zeros((B, H), xw.dtype)
        c = c0 if c0 is not None else jnp.zeros((B, H), xw.dtype)
        outs, cells = [], []
        for s in range(0, T, chunk_t):
            e = min(T, s + chunk_t)
            res = lstm_sequence_fused(
                xw[:, s:e], lengths - s, u, b, h, c,
                forget_bias=forget_bias, block_b=block_b,
                save_cell=save_cell, interpret=interpret)
            if save_cell:
                o, h, c, cs = res
                cells.append(cs)
            else:
                o, h, c = res
            outs.append(o)
        out = jnp.concatenate(outs, axis=1)
        if save_cell:
            return out, h, c, jnp.concatenate(cells, axis=1)
        return out, h, c
    interpret = _interpret(interpret)
    if b is None:
        b = jnp.zeros((G,), xw.dtype)
    if h0 is None:
        h0 = jnp.zeros((B, H), xw.dtype)
    if c0 is None:
        c0 = jnp.zeros((B, H), xw.dtype)
    blk = min(block_b, B)
    Bp = -(-B // blk) * blk
    lens = lengths.astype(jnp.float32).reshape(B, 1)
    if Bp > B:
        pad = Bp - B
        xw = jnp.pad(xw, ((0, pad), (0, 0), (0, 0)))
        lens = jnp.pad(lens, ((0, pad), (0, 0)))
        h0 = jnp.pad(h0, ((0, pad), (0, 0)))
        c0 = jnp.pad(c0, ((0, pad), (0, 0)))
    xw_tm = jnp.swapaxes(xw, 0, 1)               # time-major [T, Bp, 4H]
    b2 = b.reshape(1, G)

    in_specs = [
        pl.BlockSpec((T, blk, G), lambda i: (0, i, 0)),
        pl.BlockSpec((blk, 1), lambda i: (i, 0)),
        pl.BlockSpec((H, G), lambda i: (0, 0)),
        pl.BlockSpec((1, G), lambda i: (0, 0)),
        pl.BlockSpec((blk, H), lambda i: (i, 0)),
        pl.BlockSpec((blk, H), lambda i: (i, 0)),
    ]
    out_specs = [
        pl.BlockSpec((T, blk, H), lambda i: (0, i, 0)),
        pl.BlockSpec((blk, H), lambda i: (i, 0)),
        pl.BlockSpec((blk, H), lambda i: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((T, Bp, H), xw.dtype),
        jax.ShapeDtypeStruct((Bp, H), xw.dtype),
        jax.ShapeDtypeStruct((Bp, H), xw.dtype),
    ]
    if save_cell:
        out_specs.append(pl.BlockSpec((T, blk, H), lambda i: (0, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((T, Bp, H), xw.dtype))
        kernel = functools.partial(_lstm_seq_train_kernel, T=T, H=H,
                                   forget_bias=forget_bias)
        out, ht, ct, cseq = pl.pallas_call(
            kernel, grid=(Bp // blk,), in_specs=in_specs,
            out_specs=out_specs, out_shape=out_shape,
            interpret=interpret, name="lstm_sequence_fwd_train",
        )(xw_tm, lens, u, b2, h0, c0)
        return (jnp.swapaxes(out, 0, 1)[:B], ht[:B], ct[:B],
                jnp.swapaxes(cseq, 0, 1)[:B])

    kernel = functools.partial(_lstm_seq_kernel, T=T, H=H,
                               forget_bias=forget_bias)
    out, ht, ct = pl.pallas_call(
        kernel,
        grid=(Bp // blk,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="lstm_sequence_fwd",
    )(xw_tm, lens, u, b2, h0, c0)
    return jnp.swapaxes(out, 0, 1)[:B], ht[:B], ct[:B]


def _lstm_seq_bwd_kernel(xw_ref, len_ref, u_ref, b_ref, h0_ref, c0_ref,
                         out_ref, cseq_ref, gout_ref, ght_ref, gct_ref,
                         dxw_ref, dh0_ref, dc0_ref, du_ref, *, T: int,
                         H: int, forget_bias: float):
    """Hand-written whole-sequence LSTM backward — the
    hl_lstm_parallel_backward_data/_weight analog: the reverse-time gate
    recurrence runs entirely in VMEM, recomputing gate activations from the
    saved (h, c) sequences instead of storing [T, B, 4H] activations.

    Per reverse step: recompute gates from xw_t + h_{t-1}·u + b (h_{t-1} is
    the saved masked output — identical to the true carry on every live
    step, and irrelevant on dead steps where the mask zeroes all grads),
    then the standard LSTM adjoints. dW/dx/db are large batched matmuls
    left to XLA outside (ops/rnn.py); dU accumulates in VMEM here because
    it needs the per-step h_{t-1}·dgates products.
    """
    u = u_ref[...].astype(jnp.float32)
    bias = b_ref[...].astype(jnp.float32)
    lens = len_ref[...].astype(jnp.float32)
    h0 = h0_ref[...].astype(jnp.float32)
    c0 = c0_ref[...].astype(jnp.float32)

    def step(s, carry):
        dh, dc, du = carry
        t = T - 1 - s
        tm1 = jnp.maximum(t - 1, 0)
        live_prev = (t > 0).astype(jnp.float32)
        h_prev = (live_prev * out_ref[tm1].astype(jnp.float32)
                  + (1.0 - live_prev) * h0)
        c_prev = (live_prev * cseq_ref[tm1].astype(jnp.float32)
                  + (1.0 - live_prev) * c0)
        xw_t = xw_ref[t].astype(jnp.float32)
        gates = xw_t + jax.lax.dot(h_prev, u,
                                   preferred_element_type=jnp.float32) + bias
        i = jax.nn.sigmoid(gates[:, :H])
        f = jax.nn.sigmoid(gates[:, H:2 * H] + forget_bias)
        g = jnp.tanh(gates[:, 2 * H:3 * H])
        o = jax.nn.sigmoid(gates[:, 3 * H:])
        c_cur = f * c_prev + i * g
        tc = jnp.tanh(c_cur)

        m = (t.astype(jnp.float32) < lens).astype(jnp.float32)   # [Bb, 1]
        dh_t = dh + m * gout_ref[t].astype(jnp.float32)
        dhp = m * dh_t
        dct = m * dc + dhp * o * (1.0 - tc * tc)
        do_ = dhp * tc
        dgi = (dct * g) * i * (1.0 - i)
        dgf = (dct * c_prev) * f * (1.0 - f)
        dgg = (dct * i) * (1.0 - g * g)
        dgo = do_ * o * (1.0 - o)
        dgates = jnp.concatenate([dgi, dgf, dgg, dgo], axis=1)   # [Bb, 4H]
        dxw_ref[t] = dgates.astype(dxw_ref.dtype)
        du = du + jax.lax.dot_general(
            h_prev, dgates, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                  # [H, 4H]
        dh_prev = (1.0 - m) * dh_t + jax.lax.dot_general(
            dgates, u, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dc_prev = (1.0 - m) * dc + dct * f
        return dh_prev, dc_prev, du

    dh0_i = ght_ref[...].astype(jnp.float32)
    dc0_i = gct_ref[...].astype(jnp.float32)
    du0 = jnp.zeros((H, 4 * H), jnp.float32)
    dh, dc, du = jax.lax.fori_loop(0, T, step, (dh0_i, dc0_i, du0))
    dh0_ref[...] = dh.astype(dh0_ref.dtype)
    dc0_ref[...] = dc.astype(dc0_ref.dtype)

    # the du output block is shared by every grid program; the TPU grid is
    # sequential, so accumulate across batch tiles in place
    @pl.when(pl.program_id(0) == 0)
    def _init():
        du_ref[...] = jnp.zeros_like(du_ref)

    du_ref[...] += du.astype(du_ref.dtype)


def lstm_sequence_fused_bwd(xw, lengths, u, b, h0, c0, out_seq, c_seq,
                            g_out, g_ht, g_ct, *, forget_bias: float = 0.0,
                            block_b: int = 8,
                            interpret: Optional[bool] = None):
    """Backward of :func:`lstm_sequence_fused` (save_cell residuals).

    Returns (dxw [B,T,4H], dh0 [B,H], dc0 [B,H], du [H,4H] f32).
    """
    B, T, G = xw.shape
    H = G // 4
    interpret = _interpret(interpret)
    blk = min(block_b, B)
    Bp = -(-B // blk) * blk
    lens = lengths.astype(jnp.float32).reshape(B, 1)
    if Bp > B:
        pad = Bp - B
        pad3 = ((0, pad), (0, 0), (0, 0))
        pad2 = ((0, pad), (0, 0))
        xw = jnp.pad(xw, pad3)
        out_seq = jnp.pad(out_seq, pad3)
        c_seq = jnp.pad(c_seq, pad3)
        g_out = jnp.pad(g_out, pad3)
        lens = jnp.pad(lens, pad2)
        h0 = jnp.pad(h0, pad2)
        c0 = jnp.pad(c0, pad2)
        g_ht = jnp.pad(g_ht, pad2)
        g_ct = jnp.pad(g_ct, pad2)
    tm = lambda a: jnp.swapaxes(a, 0, 1)
    b2 = b.reshape(1, G)

    kernel = functools.partial(_lstm_seq_bwd_kernel, T=T, H=H,
                               forget_bias=forget_bias)
    seq_spec = lambda width: pl.BlockSpec((T, blk, width), lambda i: (0, i, 0))
    vec_spec = pl.BlockSpec((blk, H), lambda i: (i, 0))
    dxw, dh0, dc0, du = pl.pallas_call(
        kernel,
        grid=(Bp // blk,),
        in_specs=[
            seq_spec(G),
            pl.BlockSpec((blk, 1), lambda i: (i, 0)),
            pl.BlockSpec((H, G), lambda i: (0, 0)),
            pl.BlockSpec((1, G), lambda i: (0, 0)),
            vec_spec, vec_spec,
            seq_spec(H), seq_spec(H), seq_spec(H),
            vec_spec, vec_spec,
        ],
        out_specs=[
            seq_spec(G),
            vec_spec, vec_spec,
            pl.BlockSpec((H, G), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, Bp, G), xw.dtype),
            jax.ShapeDtypeStruct((Bp, H), xw.dtype),
            jax.ShapeDtypeStruct((Bp, H), xw.dtype),
            jax.ShapeDtypeStruct((H, G), jnp.float32),
        ],
        interpret=interpret,
        name="lstm_sequence_bwd",
    )(tm(xw), lens, u, b2, h0, c0, tm(out_seq), tm(c_seq), tm(g_out),
      g_ht, g_ct)
    return jnp.swapaxes(dxw, 0, 1)[:B], dh0[:B], dc0[:B], du


def _gru_seq_kernel(xw_ref, len_ref, u_ref, h0_ref, out_ref, ht_ref,
                    *, T: int, H: int):
    """Fused whole-sequence GRU (hl_gpu_gru.cuh analog) — one batch-tile
    program, time-major xw [T, Bb, 3H] with the BIAS PRE-ADDED (Mosaic
    rejects sliced-bias broadcasts; the bias is a per-gate constant, so it
    folds into the input projection), u [H, 3H] packed [u_z | u_r | u_c],
    gate order z, r, candidate (the reference's layout)."""
    u = u_ref[...].astype(jnp.float32)
    uz, ur, uc = u[:, :H], u[:, H:2 * H], u[:, 2 * H:]
    lens = len_ref[...].astype(jnp.float32)
    h0 = h0_ref[...].astype(jnp.float32)

    def step(t, h):
        xw_t = xw_ref[t].astype(jnp.float32)
        xz, xr, xc = xw_t[:, :H], xw_t[:, H:2 * H], xw_t[:, 2 * H:]
        z = jax.nn.sigmoid(
            xz + jax.lax.dot(h, uz, preferred_element_type=jnp.float32))
        r = jax.nn.sigmoid(
            xr + jax.lax.dot(h, ur, preferred_element_type=jnp.float32))
        c = jnp.tanh(
            xc + jax.lax.dot(r * h, uc,
                             preferred_element_type=jnp.float32))
        h_new = (1.0 - z) * h + z * c
        m = (t.astype(jnp.float32) < lens).astype(jnp.float32)
        h = m * h_new + (1.0 - m) * h
        out_ref[t] = (m * h).astype(out_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, T, step, h0)
    ht_ref[...] = h.astype(ht_ref.dtype)


def _gru_seq_bwd_kernel(xw_ref, len_ref, u_ref, h0_ref, out_ref, gout_ref,
                        ght_ref, dxw_ref, dh0_ref, du_ref, *, T: int, H: int):
    """Hand-written whole-sequence GRU backward (hl_gpu_gru.cuh backward
    analog). Everything is recomputable from xw (bias pre-added) and the
    saved masked output sequence, so no extra residuals are stored; the
    reverse recurrence and dU accumulation stay in VMEM."""
    u = u_ref[...].astype(jnp.float32)
    uz, ur, uc = u[:, :H], u[:, H:2 * H], u[:, 2 * H:]
    lens = len_ref[...].astype(jnp.float32)
    h0 = h0_ref[...].astype(jnp.float32)

    def step(s, carry):
        dh, du = carry
        t = T - 1 - s
        tm1 = jnp.maximum(t - 1, 0)
        live_prev = (t > 0).astype(jnp.float32)
        h_prev = (live_prev * out_ref[tm1].astype(jnp.float32)
                  + (1.0 - live_prev) * h0)
        xw_t = xw_ref[t].astype(jnp.float32)
        xz, xr, xc = xw_t[:, :H], xw_t[:, H:2 * H], xw_t[:, 2 * H:]
        z = jax.nn.sigmoid(
            xz + jax.lax.dot(h_prev, uz, preferred_element_type=jnp.float32))
        r = jax.nn.sigmoid(
            xr + jax.lax.dot(h_prev, ur, preferred_element_type=jnp.float32))
        rh = r * h_prev
        c = jnp.tanh(
            xc + jax.lax.dot(rh, uc, preferred_element_type=jnp.float32))

        m = (t.astype(jnp.float32) < lens).astype(jnp.float32)
        dh_t = dh + m * gout_ref[t].astype(jnp.float32)
        dhp = m * dh_t                              # grad wrt h'_t
        # h' = (1-z) h_prev + z c
        dgz = (dhp * (c - h_prev)) * z * (1.0 - z)
        dgc = (dhp * z) * (1.0 - c * c)
        drh = jax.lax.dot_general(dgc, uc, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dgr = (drh * h_prev) * r * (1.0 - r)
        dh_prev = ((1.0 - m) * dh_t + dhp * (1.0 - z) + drh * r
                   + jax.lax.dot_general(dgz, uz, (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                   + jax.lax.dot_general(dgr, ur, (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32))
        dxw_ref[t] = jnp.concatenate([dgz, dgr, dgc],
                                     axis=1).astype(dxw_ref.dtype)
        ha = lambda lhs, rhs: jax.lax.dot_general(
            lhs, rhs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        du = du + jnp.concatenate([ha(h_prev, dgz), ha(h_prev, dgr),
                                   ha(rh, dgc)], axis=1)
        return dh_prev, du

    dh0_i = ght_ref[...].astype(jnp.float32)
    du0 = jnp.zeros((H, 3 * H), jnp.float32)
    dh, du = jax.lax.fori_loop(0, T, step, (dh0_i, du0))
    dh0_ref[...] = dh.astype(dh0_ref.dtype)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        du_ref[...] = jnp.zeros_like(du_ref)

    du_ref[...] += du.astype(du_ref.dtype)


def gru_sequence_fused_bwd(xw, lengths, u, h0, out_seq, g_out, g_ht, *,
                           block_b: int = 8,
                           interpret: Optional[bool] = None):
    """Backward of :func:`gru_sequence_fused` (xw carries the pre-added
    bias, so its grad is also the bias grad summed outside).

    Returns (dxw [B,T,3H], dh0 [B,H], du [H,3H] f32).
    """
    B, T, G = xw.shape
    H = G // 3
    interpret = _interpret(interpret)
    blk = min(block_b, B)
    Bp = -(-B // blk) * blk
    lens = lengths.astype(jnp.float32).reshape(B, 1)
    if Bp > B:
        pad = Bp - B
        pad3 = ((0, pad), (0, 0), (0, 0))
        pad2 = ((0, pad), (0, 0))
        xw = jnp.pad(xw, pad3)
        out_seq = jnp.pad(out_seq, pad3)
        g_out = jnp.pad(g_out, pad3)
        lens = jnp.pad(lens, pad2)
        h0 = jnp.pad(h0, pad2)
        g_ht = jnp.pad(g_ht, pad2)
    tm = lambda a: jnp.swapaxes(a, 0, 1)

    kernel = functools.partial(_gru_seq_bwd_kernel, T=T, H=H)
    seq_spec = lambda width: pl.BlockSpec((T, blk, width), lambda i: (0, i, 0))
    vec_spec = pl.BlockSpec((blk, H), lambda i: (i, 0))
    dxw, dh0, du = pl.pallas_call(
        kernel,
        grid=(Bp // blk,),
        in_specs=[
            seq_spec(G),
            pl.BlockSpec((blk, 1), lambda i: (i, 0)),
            pl.BlockSpec((H, G), lambda i: (0, 0)),
            vec_spec,
            seq_spec(H), seq_spec(H),
            vec_spec,
        ],
        out_specs=[
            seq_spec(G),
            vec_spec,
            pl.BlockSpec((H, G), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, Bp, G), xw.dtype),
            jax.ShapeDtypeStruct((Bp, H), xw.dtype),
            jax.ShapeDtypeStruct((H, G), jnp.float32),
        ],
        interpret=interpret,
        name="gru_sequence_bwd",
    )(tm(xw), lens, u, h0, tm(out_seq), tm(g_out), g_ht)
    return jnp.swapaxes(dxw, 0, 1)[:B], dh0[:B], du


def gru_sequence_fused(xw: jax.Array, lengths: jax.Array, u: jax.Array,
                       b: Optional[jax.Array] = None,
                       h0: Optional[jax.Array] = None, *,
                       block_b: int = 8, chunk_t: Optional[int] = None,
                       interpret: Optional[bool] = None):
    """Masked GRU over a whole sequence in one Pallas kernel; see
    lstm_sequence_fused for the design notes (including ``chunk_t`` time
    chunking, which buys the wide MXU-feeding batch tiles). xw: x@W
    [B, T, 3H]; returns (out [B, T, H], hT [B, H])."""
    B, T, G = xw.shape
    if G % 3:
        raise ValueError(f"xw last dim {G} must be 3*H (z/r/candidate gates)")
    H = G // 3
    if chunk_t is not None and chunk_t < T:
        if b is not None:
            xw = xw + b
            b = None
        h = h0 if h0 is not None else jnp.zeros((B, H), xw.dtype)
        outs = []
        for s in range(0, T, chunk_t):
            e = min(T, s + chunk_t)
            o, h = gru_sequence_fused(xw[:, s:e], lengths - s, u, None, h,
                                      block_b=block_b, interpret=interpret)
            outs.append(o)
        return jnp.concatenate(outs, axis=1), h
    interpret = _interpret(interpret)
    if b is not None:
        xw = xw + b                       # bias folds into the projection
    if h0 is None:
        h0 = jnp.zeros((B, H), xw.dtype)
    blk = min(block_b, B)
    Bp = -(-B // blk) * blk
    lens = lengths.astype(jnp.float32).reshape(B, 1)
    if Bp > B:
        pad = Bp - B
        xw = jnp.pad(xw, ((0, pad), (0, 0), (0, 0)))
        lens = jnp.pad(lens, ((0, pad), (0, 0)))
        h0 = jnp.pad(h0, ((0, pad), (0, 0)))
    xw_tm = jnp.swapaxes(xw, 0, 1)

    kernel = functools.partial(_gru_seq_kernel, T=T, H=H)
    out, ht = pl.pallas_call(
        kernel,
        grid=(Bp // blk,),
        in_specs=[
            pl.BlockSpec((T, blk, G), lambda i: (0, i, 0)),
            pl.BlockSpec((blk, 1), lambda i: (i, 0)),
            pl.BlockSpec((H, G), lambda i: (0, 0)),
            pl.BlockSpec((blk, H), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((T, blk, H), lambda i: (0, i, 0)),
            pl.BlockSpec((blk, H), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, Bp, H), xw.dtype),
            jax.ShapeDtypeStruct((Bp, H), xw.dtype),
        ],
        interpret=interpret,
        name="gru_sequence_fwd",
    )(xw_tm, lens, u, h0)
    return jnp.swapaxes(out, 0, 1)[:B], ht[:B]


# ---------------------------------------------------------------------------
# Mamba-2 state-space kernels: the recurrence of a head h of group g,
#
#     S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t^g      S in R^{P x N}
#     y_t = S_t C_t^g                                 (+ D_h x_t outside)
#
# as a single step against per-slot state (ssm_state_update: a decode step)
# and as the chunked (SSD) form over a whole prompt (ssd_chunk_scan: an
# admission). Both keep a row's state PACKED (ssm_pack): two heads side by
# side on the lanes, [H // 2, N, 2 P] — at P = 64 a [N, 128] tile a pair, all
# 128 lanes in use, x and y as natural rows [1, 2 P], B and C as columns
# [N, 1], so the step is multiply-adds with sublane and lane broadcasts and
# one sublane reduction, no relayout of the state. The two heads of a pair
# share a group (heads a group is even), so B and C are the pair's.
# ---------------------------------------------------------------------------

def ssm_pack(state: jax.Array) -> jax.Array:
    """[..., H, P, N] (a head's state as the equations write it) ->
    [..., H // 2, N, 2 P], the layout both state-space kernels keep."""
    *lead, H, P, N = state.shape
    s = jnp.moveaxis(state.reshape(*lead, H // 2, 2, P, N), -1, -3)
    return s.reshape(*lead, H // 2, N, 2 * P)


def ssm_unpack(packed: jax.Array) -> jax.Array:
    """:func:`ssm_pack`'s inverse: [..., H // 2, N, 2 P] -> [..., H, P, N]."""
    *lead, Hp, N, PP = packed.shape
    s = jnp.moveaxis(packed.reshape(*lead, Hp, N, 2, PP // 2), -3, -1)
    return s.reshape(*lead, 2 * Hp, PP // 2, N)


def _ssm_route(kernel: str, route: Optional[str], heads: int, groups: int):
    if route is None:
        route = "kernel" if _on_tpu() else "dense"
    if route not in ("kernel", "dense"):
        raise ValueError(f"unknown {kernel} route {route!r}")
    if heads % groups or (heads // groups) % 2:
        raise ValueError(f"{kernel}: {heads} heads over {groups} groups "
                         "are not whole pairs of heads a group")
    from .. import obs
    obs.count("kernels.routes_total", kernel=kernel, route=route)
    return route


def _ssm_update_kernel(order_ref, n_ref, s_ref, da_ref, dtx_ref, b_ref,
                       c_ref, y_ref, s_out, *, pairs_per_group: int):
    """Program i: the slot ``order[i]``, all of it. Blocks: s [1, H/2, N,
    2P] f32 (read once, written once, in place); da, dtx, y [1, H/2, 2P]
    f32 (``exp(dt A)``, ``dt x`` and the result, a row a pair); b, c [1, N,
    G] f32 (a column a group)."""
    i = pl.program_id(0)
    n_pairs = s_ref.shape[1]

    @pl.when(i < n_ref[0])
    def _live():
        for j in range(n_pairs):
            g = j // pairs_per_group
            s = s_ref[0, j].astype(jnp.float32) * da_ref[0, j:j + 1, :] \
                + b_ref[0, :, g:g + 1] * dtx_ref[0, j:j + 1, :]
            s_out[0, j] = s.astype(s_out.dtype)
            y_ref[0, j:j + 1, :] = jnp.sum(s * c_ref[0, :, g:g + 1], axis=0,
                                           keepdims=True)

    @pl.when(i >= n_ref[0])
    def _idle():                 # no live slot at all: the one walked stays
        s_out[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def _dense_ssm_state_update(state, x, dt, a, bm, cm, live):
    nat = ssm_unpack(state).astype(jnp.float32)             # [S, H, P, N]
    rep = x.shape[1] // bm.shape[1]
    new = jnp.exp(dt * a)[..., None, None] * nat \
        + (dt[..., None] * x)[..., None] \
        * jnp.repeat(bm, rep, axis=1)[:, :, None, :]
    y = jnp.sum(new * jnp.repeat(cm, rep, axis=1)[:, :, None, :], axis=-1)
    keep = live[:, None, None]
    return (jnp.where(keep, y, 0.0),
            ssm_pack(jnp.where(keep[..., None], new, nat)).astype(
                state.dtype))


def ssm_state_update(state: jax.Array, x: jax.Array, dt: jax.Array,
                     a: jax.Array, bm: jax.Array, cm: jax.Array,
                     live: Optional[jax.Array] = None, *,
                     route: Optional[str] = None,
                     interpret: Optional[bool] = None):
    """One position of the recurrence for every LIVE slot, in place.

    state [S, H // 2, N, 2 P] (:func:`ssm_pack`; float32, or a narrower
    dtype the arithmetic is rounded to on the way out); x [S, H, P] f32; dt
    [S, H] f32 (after its softplus); a [H] f32 (``-exp(A_log)``); bm, cm
    [S, G, N] f32; live [S] bool (None: all). Returns (y [S, H, P] f32 =
    ``S_new C`` — zero for a slot that is not live — and the new state, a
    slot that is not live keeping what it had).

    The kernel route (custom call ``ssm_state_update``) walks the live
    slots alone — a scalar-prefetched list of them is the grid — reads a
    slot's state once and writes it once over itself
    (``input_output_aliases``: inside a program whose state buffer is
    donated or carried, no second copy of it exists); the dense route is
    the same arithmetic over the unpacked state."""
    S, H, P = x.shape
    G, N = bm.shape[1:]
    route = _ssm_route("ssm_state_update", route, H, G)
    live = jnp.ones((S,), bool) if live is None else live.astype(bool)
    f32 = jnp.float32
    x, dt, a = x.astype(f32), dt.astype(f32), a.astype(f32)
    bm, cm = bm.astype(f32), cm.astype(f32)
    if route == "dense":
        return _dense_ssm_state_update(state, x, dt, a, bm, cm, live)
    from jax.experimental.pallas import tpu as pltpu
    da = jnp.repeat(jnp.exp(dt * a), P, axis=1).reshape(S, H // 2, 2 * P)
    dtx = (dt[..., None] * x).reshape(S, H // 2, 2 * P)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    n_live = jnp.sum(live, dtype=jnp.int32).reshape(1)
    row = pl.BlockSpec((1, H // 2, 2 * P),
                       lambda i, order, n: (order[i], 0, 0))
    col = pl.BlockSpec((1, N, G), lambda i, order, n: (order[i], 0, 0))
    st = pl.BlockSpec((1, H // 2, N, 2 * P),
                      lambda i, order, n: (order[i], 0, 0, 0))
    y, new = pl.pallas_call(
        functools.partial(_ssm_update_kernel,
                          pairs_per_group=H // G // 2),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(jnp.maximum(n_live[0], 1),),
            in_specs=[st, row, row, col, col], out_specs=[row, st]),
        out_shape=[jax.ShapeDtypeStruct((S, H // 2, 2 * P), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 * (H // 2) * N * 2 * P + (8 << 20)),
        interpret=_interpret(interpret), name="ssm_state_update",
    )(order, n_live, state, da, dtx, jnp.swapaxes(bm, 1, 2),
      jnp.swapaxes(cm, 1, 2))
    y = jnp.where(live[:, None, None], y.reshape(S, H, P), 0.0)
    return y, new


def _ssd_chunk_kernel(len_ref, x_ref, b_ref, c_ref, acol_ref, arow_ref,
                      y_ref, s_ref, *, chunk: int, half: int):
    """Program (r, g, c): chunk c of row r for the heads of group g. Blocks:
    x [1, L, hpg P] (already ``dt x``, the operands' dtype) and y (f32) a
    pair of heads every 2P lanes; b, c [1, L, N]; acol [1, 1, L, hpg] and
    arow [1, 1, hpg, L] f32: the inclusive running sum of ``dt A`` inside
    the chunk, a head a column / a row; s [1, hpg / 2, N, 2P] f32 — the
    group's packed state, resident across the row's chunks (its block
    does not move with c) and written back after the last."""
    r, c = pl.program_id(0), pl.program_id(2)
    L, lanes = chunk, 2 * half
    n_pairs = s_ref.shape[1]

    @pl.when(c == 0)
    def _start():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(c * L >= len_ref[r])
    def _past():                    # nothing of the row in this chunk
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(c * L < len_ref[r])
    def _chunk():
        bm, cm = b_ref[0], c_ref[0]
        cd = bm.dtype
        cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        below = jax.lax.broadcasted_iota(jnp.int32, (1, L), 1) \
            <= jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0)
        first = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1) < half

        def by_head(lo, hi):        # a value a head -> the pair's lanes
            return jnp.where(first, lo, hi)
        for j in range(n_pairs):
            xp = x_ref[0, :, j * lanes:(j + 1) * lanes]         # [L, 2P]
            col = [acol_ref[0, 0, :, h:h + 1] for h in (2 * j, 2 * j + 1)]
            row = [arow_ref[0, 0, h:h + 1, :] for h in (2 * j, 2 * j + 1)]
            y = jnp.zeros((L, lanes), jnp.float32)
            for k in (0, 1):        # inside the chunk, a head at a time
                m = cb * jnp.exp(jnp.where(below, col[k] - row[k], _NEG))
                mine = first if k == 0 else jnp.logical_not(first)
                y = y + jnp.dot(m.astype(cd),
                                jnp.where(mine, xp, jnp.zeros_like(xp)),
                                preferred_element_type=jnp.float32)
            s = s_ref[0, j]                                     # [N, 2P]
            y = y + by_head(jnp.exp(col[0]), jnp.exp(col[1])) * jnp.dot(
                cm, s.astype(cd), preferred_element_type=jnp.float32)
            end = [a[L - 1:L, :] for a in col]                  # [1, 1]
            left = by_head(jnp.exp(end[0] - col[0]),
                           jnp.exp(end[1] - col[1]))            # [L, 2P]
            s_ref[0, j] = by_head(jnp.exp(end[0]), jnp.exp(end[1])) * s \
                + jax.lax.dot_general(
                    bm, (xp.astype(jnp.float32) * left).astype(cd),
                    (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            y_ref[0, :, j * lanes:(j + 1) * lanes] = y


def _dense_ssd_chunk_scan(xdt, a, bm, cm, chunk):
    """The same chunked algebra in jax.numpy, heads unpacked. xdt [R, T, H,
    P] and bm, cm [R, T, G, N] in the operands' dtype; a [R, T, H] f32."""
    R, T, H, P = xdt.shape
    G, N = bm.shape[2:]
    nc, rep, cd = T // chunk, H // G, xdt.dtype
    x = xdt.reshape(R, nc, chunk, H, P)
    bh = jnp.repeat(bm.reshape(R, nc, chunk, G, N), rep, axis=3)
    ch = jnp.repeat(cm.reshape(R, nc, chunk, G, N), rep, axis=3)
    cum = jnp.cumsum(a.reshape(R, nc, chunk, H), axis=2)
    ein = functools.partial(jnp.einsum,
                            preferred_element_type=jnp.float32)
    cb = ein("rclhn,rcshn->rchls", ch, bh)
    seg = jnp.moveaxis(cum, 2, 3)[..., :, None] \
        - jnp.moveaxis(cum, 2, 3)[..., None, :]                # [R,nc,H,L,L]
    below = jnp.tril(jnp.ones((chunk, chunk), bool))
    m = cb * jnp.exp(jnp.where(below, seg, _NEG))
    y = ein("rchls,rcshp->rclhp", m.astype(cd), x)
    left = jnp.exp(cum[:, :, -1:, :] - cum)                    # [R,nc,L,H]
    add = ein("rclhp,rclhn->rchpn",
              (x.astype(jnp.float32) * left[..., None]).astype(cd), bh)

    def carry(s, inp):
        add_c, end_c = inp
        return jnp.exp(end_c)[..., None, None] * s + add_c, s
    final, before = jax.lax.scan(
        carry, jnp.zeros((R, H, P, N), jnp.float32),
        (jnp.moveaxis(add, 1, 0), jnp.moveaxis(cum[:, :, -1, :], 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                        # [R,nc,H,P,N]
    y = y + jnp.exp(cum)[..., None] * ein("rclhn,rchpn->rclhp", ch,
                                          before.astype(cd))
    return y.reshape(R, T, H, P), ssm_pack(final)


def ssd_chunk_scan(x: jax.Array, dt: jax.Array, a: jax.Array,
                   bm: jax.Array, cm: jax.Array,
                   lengths: Optional[jax.Array] = None, *, chunk: int = 128,
                   dtype=None, route: Optional[str] = None,
                   interpret: Optional[bool] = None):
    """The recurrence over whole rows from a zero state, in the chunked
    (state-space-duality) form: inside a chunk of ``chunk`` positions
    ``Y = ((C B^T) * L) (dt X)`` with ``L[t, s] = exp(sum_{s < r <= t} dt_r
    A)`` for ``s <= t`` — matrix products — and across chunks the state
    is carried: ``y_t += exp(cum_t) C_t S_before``, ``S = exp(cum_end)
    S_before + sum_s exp(cum_end - cum_s) dt_s x_s (x) B_s``.

    x [R, T, H, P] f32; dt [R, T, H] f32 (after its softplus); a [H] f32;
    bm, cm [R, T, G, N]; lengths [R]: each row's OWN length (None: T).
    ``dt`` is taken as zero past a row's length — decay 1, no input — so
    the state returned is the state AT that length, whatever the padding
    holds. ``dtype``: the dtype the four products' operands are rounded to
    (accumulation, the decays and the carried state are float32). Returns
    (y [R, T, H, P] f32 = ``S_t C_t``, the final state packed [R, H // 2,
    N, 2 P] f32 (:func:`ssm_pack`)).

    The kernel route (custom call ``ssd_chunk_scan``): one program a (row,
    group, chunk), the chunks of a row in order with the group's state in
    VMEM between them; a chunk wholly past its row's length does
    nothing."""
    R, T, H, P = x.shape
    G, N = bm.shape[2:]
    route = _ssm_route("ssd_chunk_scan", route, H, G)
    cd = jnp.dtype(x.dtype if dtype is None else dtype)
    lengths = jnp.full((R,), T, jnp.int32) if lengths is None \
        else jnp.asarray(lengths, jnp.int32)
    pad = -T % chunk
    if pad:
        x, dt, bm, cm = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),)
                                 * (v.ndim - 2)) for v in (x, dt, bm, cm))
    Tp = T + pad
    dt = jnp.where(jnp.arange(Tp)[None, :, None] < lengths[:, None, None],
                   dt.astype(jnp.float32), 0.0)
    da = dt * a.astype(jnp.float32)
    xdt = (x.astype(jnp.float32) * dt[..., None]).astype(cd)
    bm, cm = bm.astype(cd), cm.astype(cd)
    if route == "dense":
        y, final = _dense_ssd_chunk_scan(xdt, da, bm, cm, chunk)
        return y[:, :T], final
    from jax.experimental.pallas import tpu as pltpu
    hpg = H // G
    cum = jnp.cumsum(da.reshape(R, Tp // chunk, chunk, G, hpg), axis=2)
    acol = jnp.moveaxis(cum, 3, 1).reshape(R, G, Tp, hpg)
    arow = jnp.swapaxes(acol, 2, 3)
    wide = pl.BlockSpec((1, chunk, hpg * P), lambda r, g, c, n: (r, c, g))
    bc = pl.BlockSpec((1, chunk, N), lambda r, g, c, n: (r, c, g))
    y, final = pl.pallas_call(
        functools.partial(_ssd_chunk_kernel, chunk=chunk, half=P),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R, G, Tp // chunk),
            in_specs=[wide, bc, bc,
                      pl.BlockSpec((1, 1, chunk, hpg),
                                   lambda r, g, c, n: (r, g, c, 0)),
                      pl.BlockSpec((1, 1, hpg, chunk),
                                   lambda r, g, c, n: (r, g, 0, c))],
            out_specs=[wide,
                       pl.BlockSpec((1, hpg // 2, N, 2 * P),
                                    lambda r, g, c, n: (r, g, 0, 0))]),
        out_shape=[jax.ShapeDtypeStruct((R, Tp, H * P), jnp.float32),
                   jax.ShapeDtypeStruct((R, H // 2, N, 2 * P),
                                        jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(interpret), name="ssd_chunk_scan",
    )(lengths, xdt.reshape(R, Tp, H * P), bm.reshape(R, Tp, G * N),
      cm.reshape(R, Tp, G * N), acol, arow)
    return y.reshape(R, Tp, H, P)[:, :T], final


# ---------------------------------------------------------------------------
# Attention that SELECTS what it reads (DeepSeek Sparse Attention's lightning
# indexer; models/keye_vl2.py). A layer keeps a third row a token beside its
# key and value: the INDEXER'S key, ``Di`` wide, one head. A query scores
# every cached key with it, ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] .
# kI[s])`` over ``Hi`` small heads, the ``topk`` best are kept, and the
# softmax runs over those alone. Five pieces, each with a dense route of the
# same arithmetic for hosts that are not the TPU:
#
#   index_scores            an admission's scores, a block of queries against
#                           the row's keys so far            [Q, L] f32
#   index_scores_paged      a decode step's scores, a slot's pages of ``ik``
#                           streamed a group of pages at a time   [B, L] f32
#   select_topk             EXACT selection, no sort: the k-th largest score
#                           of a row found by bisection over the scores' bit
#                           patterns (32 counting passes over a row held in
#                           VMEM), ties to the lower index by a second
#                           bisection over the columns; gives the ADDITIVE
#                           mask (0 selected, _NEG not) and the count
#   selected_flash_attention  the admission's attention under that mask
#   sparse_decode_attention   a decode step's read of the selected rows under
#                           that mask: a page's aligned RUN of rows fetched
#                           by one descriptor where it holds a selected row,
#                           skipped where it holds none, so what the read
#                           streams follows the pages the selection touches
#                           and not the context
# ---------------------------------------------------------------------------

_INT_MIN = -2 ** 31


def _dense_index_scores(qi, w, ki):
    s = jnp.einsum("hqd,ld->hql", qi, ki,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jnp.moveaxis(w, 1, 0)[:, :, None] * jax.nn.relu(s),
                   axis=0)


def _index_scores_kernel(info_ref, q_ref, w_ref, k_ref, o_ref, *,
                         block_q: int, block_k: int):
    """Program (qi, ki): queries ``qi * block_q ..`` (the first at absolute
    position ``info[0]``) against keys ``ki * block_k ..``; a tile wholly
    past the last query's position is not computed (nor fetched, nor
    written: the index maps stop at the last tile that is)."""
    qi, ki = pl.program_id(0), pl.program_id(1)

    @pl.when(ki * block_k <= info_ref[0] + (qi + 1) * block_q - 1)
    def _live():
        k = k_ref[...]                                      # [block_k, Di]
        acc = jnp.zeros((block_q, block_k), jnp.float32)
        for j in range(q_ref.shape[0]):
            s = jax.lax.dot_general(q_ref[j], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            acc = acc + w_ref[:, j:j + 1] * jnp.maximum(s, 0.0)
        o_ref[...] = acc


def index_scores(qi: jax.Array, w: jax.Array, ki: jax.Array, q0, *,
                 block_q: int = 256, block_k: int = 512,
                 route: Optional[str] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """The indexer's scores of a block of queries: qi [Hi, Q, Di] (a head's
    queries together), w [Q, Hi] f32, ki [L, Di] (the row's indexer keys,
    the block's own among them), ``q0`` the absolute position of the first
    query -> I [Q, L] f32, ``I[t, s] = sum_j w[t, j] relu(qi[j, t] .
    ki[s])``. Entries with ``s`` past the block's last position are
    UNDEFINED on the kernel route (:func:`select_topk` reads none of them).
    Operands in their own dtype, float32 accumulation."""
    Hi, Q, Di = qi.shape
    L = ki.shape[0]
    if route is None:
        route = "kernel" if _on_tpu() else "dense"
    from .. import obs
    obs.count("kernels.routes_total", kernel="index_scores", route=route)
    if route == "dense":
        return _dense_index_scores(qi, w, ki)
    from jax.experimental.pallas import tpu as pltpu
    bq, bk = min(block_q, Q), min(block_k, L)
    if Q % bq or L % bk:
        raise ValueError(f"index_scores: {Q} queries x {L} keys are not "
                         f"whole tiles of {bq} x {bk}")

    def upto(qi_, ki_, info):
        return jnp.minimum(ki_, (info[0] + (qi_ + 1) * bq - 1) // bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(Q // bq, L // bk),
        in_specs=[pl.BlockSpec((Hi, bq, Di), lambda a, b, i: (0, a, 0)),
                  pl.BlockSpec((bq, Hi), lambda a, b, i: (a, 0)),
                  pl.BlockSpec((bk, Di), lambda a, b, i: (upto(a, b, i), 0))],
        out_specs=pl.BlockSpec((bq, bk),
                               lambda a, b, i: (a, upto(a, b, i))))
    return pl.pallas_call(
        functools.partial(_index_scores_kernel, block_q=bq, block_k=bk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Q, L), jnp.float32),
        interpret=_interpret(interpret), name="index_scores",
    )(jnp.asarray(q0, jnp.int32).reshape(1), qi, w.astype(jnp.float32), ki)


#: pages of indexer keys one step of the decode scoring fetches together
INDEX_PAGE_GROUP = 8


def _index_scores_paged_kernel(tables_ref, pos_ref, q_ref, w_ref, ik_hbm,
                               o_ref, buf, sem, *, bs: int, group: int):
    """Program b: slot b's scores. The slot's pages of ``ik_hbm`` [P, bs,
    Di] arrive ``group`` at a time (one DMA a page, the next group's in
    flight while this one's product runs); a group's scores are one
    product ``[Hi, Di] x [group * bs, Di]^T``. o [1, NG, group * bs]."""
    from jax.experimental.pallas import tpu as pltpu
    b = pl.program_id(0)
    pos = pos_ref[b]
    gw = group * bs
    n_groups = (pos // bs) // group + 1

    def copies(g, slot):
        return [pltpu.make_async_copy(
            ik_hbm.at[tables_ref[b, g * group + i]],
            buf.at[slot, pl.ds(i * bs, bs)], sem.at[slot])
            for i in range(group)]

    o_ref[...] = jnp.full_like(o_ref, _NEG)
    for c in copies(0, 0):
        c.start()

    def step(g, carry):
        slot = jax.lax.rem(g, 2)

        @pl.when(g + 1 < n_groups)
        def _ahead():
            for c in copies(g + 1, 1 - slot):
                c.start()
        for c in copies(g, slot):
            c.wait()
        s = jax.lax.dot_general(q_ref[0], buf[slot][:, :q_ref.shape[2]],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        sc = jnp.sum(w_ref[0] * jnp.maximum(s, 0.0), axis=0, keepdims=True)
        col = g * gw + jax.lax.broadcasted_iota(jnp.int32, (1, gw), 1)
        o_ref[0, pl.ds(g, 1), :] = jnp.where(col <= pos, sc, _NEG)
        return carry
    jax.lax.fori_loop(0, n_groups, step, 0)


def index_scores_paged(qi: jax.Array, w: jax.Array, ik_pool: jax.Array,
                       tables: jax.Array, pos: jax.Array, *,
                       route: Optional[str] = None,
                       interpret: Optional[bool] = None) -> jax.Array:
    """A decode step's indexer scores through a block table: qi [B, Hi, Di],
    w [B, Hi] f32, ik_pool [P, bs, W] with the keys in a row's first ``Di``
    entries, tables [B, NB], pos [B] (the step's own key already written
    at ``pos``) -> I [B, NB * bs] f32, ``_NEG`` past ``pos``. The read
    streams the pages a slot has started and no others. ``W``: the chip
    holds a row narrower than its 128 lanes in 128 lanes anyway, and a
    page of such rows cannot be cut out of its tiles by a DMA (Mosaic:
    "slice shape must be aligned to tiling"), so the serving pool holds
    the indexer's row 128 wide (``CacheRow.held``), zeros past ``Di``; a
    pool as narrow as its keys is widened here, by a copy (the solo
    decode's, models/paged_lm.py ``paged_greedy``)."""
    B, NB = tables.shape
    P, bs, W = ik_pool.shape
    Di = qi.shape[-1]
    L = NB * bs
    if route is None:
        route = "kernel" if _on_tpu() else "dense"
    from .. import obs
    obs.count("kernels.routes_total", kernel="index_scores_paged",
              route=route)
    pos = pos.astype(jnp.int32)
    if route == "dense":
        k = gather_pages(ik_pool[:, :, :Di], tables)        # [B, L, Di]
        s = jnp.einsum("bhd,bld->bhl", qi.astype(k.dtype), k,
                       preferred_element_type=jnp.float32)
        sc = jnp.sum(w.astype(jnp.float32)[:, :, None] * jax.nn.relu(s),
                     axis=1)
        return jnp.where(jnp.arange(L)[None, :] <= pos[:, None], sc, _NEG)
    from jax.experimental.pallas import tpu as pltpu
    if W % 128:
        W = -(-W // 128) * 128
        ik_pool = jnp.pad(ik_pool, ((0, 0), (0, 0), (0, W - ik_pool.shape[2])))
    group = INDEX_PAGE_GROUP
    ng = -(-NB // group)
    tables = jnp.pad(tables.astype(jnp.int32), ((0, 0), (0, ng * group - NB)))
    Hi = qi.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B,),
        in_specs=[pl.BlockSpec((1, Hi, Di), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec((1, Hi, 1), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, ng, group * bs), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, group * bs, W), ik_pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))])
    out = pl.pallas_call(
        functools.partial(_index_scores_paged_kernel, bs=bs, group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, ng, group * bs), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(interpret), name="index_scores_paged",
    )(tables, pos, qi.astype(ik_pool.dtype),
      w.astype(jnp.float32)[:, :, None], ik_pool)
    return out.reshape(B, ng * group * bs)[:, :L]


def _dense_select_topk(scores, extent, k):
    R, L = scores.shape
    valid = jnp.arange(L)[None, :] < extent[:, None]
    vals, idx = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf),
                              min(k, L))
    hit = jnp.zeros((R, L), bool).at[
        jnp.arange(R)[:, None], idx].set(vals > -jnp.inf)
    hit = hit & valid
    return (jnp.where(hit, 0.0, _NEG).astype(jnp.float32),
            jnp.sum(hit, axis=1, dtype=jnp.int32))


def _select_kernel(top_ref, s_ref, ext_ref, bias_ref, cnt_ref, key_ref, *,
                   k: int, chunk: int):
    """Program i: ``rows`` rows of scores [rows, L] held whole in VMEM.
    A score's bit pattern, sign-folded, orders as the score does; the k-th
    largest key of a row is built a bit at a time, each bit one pass that
    counts the keys at or above a candidate. Only the chunks under the
    tile's largest extent (``top_ref[i]``) are read."""
    i = pl.program_id(0)
    rows, L = s_ref.shape
    n_chunks = (top_ref[i] + chunk - 1) // chunk
    ext = ext_ref[...]                                      # [rows, 1]

    def cols(c):
        return c * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)

    def at(c):
        return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)

    def keys(c, carry):
        bits = jax.lax.bitcast_convert_type(s_ref[:, at(c)], jnp.int32)
        key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
        key_ref[:, at(c)] = jnp.where(cols(c) < ext, key, _INT_MIN)
        return carry
    jax.lax.fori_loop(0, n_chunks, keys, 0)

    def count(hit):
        """[rows, 1]: how many of a row's keys ``hit(key, cols)`` holds."""
        acc = jax.lax.fori_loop(
            0, n_chunks,
            lambda c, a: a + hit(key_ref[:, at(c)], cols(c)).astype(
                jnp.int32), jnp.zeros((rows, chunk), jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)

    zero = jnp.zeros((rows, 1), jnp.int32)
    thr = jnp.where(count(lambda key, _: key >= 0) >= k, zero,
                    zero + _INT_MIN)

    def value_bit(j, thr):
        cand = thr | jnp.left_shift(jnp.int32(1), 30 - j)
        return jnp.where(count(lambda key, _: key >= cand) >= k, cand, thr)
    thr = jax.lax.fori_loop(0, 31, value_bit, thr)
    # the ties at the threshold go to the lowest columns: ``need`` of them.
    # Where no row of the tile has more keys at its threshold than it
    # needs (nearly always: float32 sums seldom tie), every tie is taken
    # and the second bisection is not run.
    need = k - count(lambda key, _: key > thr)
    n_bits = max(1, (L - 1).bit_length())
    at_thr = count(lambda key, col: (key == thr) & (col < ext))

    def column_bit(j, cut):
        cand = cut | jnp.left_shift(jnp.int32(1), n_bits - 1 - j)
        below = count(lambda key, col: (key == thr) & (col < cand))
        return jnp.where(below < need, cand, cut)
    cut = jax.lax.cond(
        jnp.max(at_thr - need) > 0,
        lambda: jax.lax.fori_loop(0, n_bits, column_bit, zero),
        lambda: zero + (L - 1))

    def write(c, acc):
        key, col = key_ref[:, at(c)], cols(c)
        hit = (col < ext) & ((key > thr) | ((key == thr) & (col <= cut)))
        bias_ref[:, at(c)] = jnp.where(hit, 0.0, _NEG)
        return acc + hit.astype(jnp.int32)
    acc = jax.lax.fori_loop(0, n_chunks, write,
                            jnp.zeros((rows, chunk), jnp.int32))
    cnt_ref[...] = jnp.sum(acc, axis=1, keepdims=True)

    def blank(c, carry):
        bias_ref[:, at(c)] = jnp.full((rows, chunk), _NEG, jnp.float32)
        return carry
    jax.lax.fori_loop(n_chunks, L // chunk, blank, 0)


def select_topk(scores: jax.Array, extent: jax.Array, k: int, *,
                rows: int = 8, chunk: Optional[int] = None,
                route: Optional[str] = None,
                interpret: Optional[bool] = None):
    """EXACT top-``k`` of every row: scores [R, L] f32, extent [R] int32
    (row r's keys are columns ``0 .. extent[r] - 1``; what lies past them
    is never read) -> (bias [R, L] f32: 0 at the ``min(k, extent)``
    largest scores of the row, ties to the lower column — jax.lax.top_k's
    order — and ``_NEG`` everywhere else; count [R] int32 of the zeros).
    No sort: see :func:`_select_kernel`."""
    R, L = scores.shape
    if route is None:
        route = "kernel" if _on_tpu() else "dense"
    from .. import obs
    obs.count("kernels.routes_total", kernel="select_topk", route=route)
    extent = jnp.clip(extent.astype(jnp.int32), 0, L)
    if route == "dense":
        return _dense_select_topk(scores, extent, k)
    from jax.experimental.pallas import tpu as pltpu
    # the widest chunk a pass walks a row in: fewer, longer loop bodies
    chunk = chunk or next((c for c in (2048, 1024, 512, 256, 128)
                           if L % c == 0), L)
    rows, chunk = min(rows, R), min(chunk, L)
    if R % rows or L % chunk:
        raise ValueError(f"select_topk: [{R}, {L}] scores are not whole "
                         f"tiles of {rows} rows and chunks of {chunk}")
    top = jnp.max(extent.reshape(R // rows, rows), axis=1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(R // rows,),
        in_specs=[pl.BlockSpec((rows, L), lambda i, t: (i, 0)),
                  pl.BlockSpec((rows, 1), lambda i, t: (i, 0))],
        out_specs=[pl.BlockSpec((rows, L), lambda i, t: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i, t: (i, 0))],
        scratch_shapes=[pltpu.VMEM((rows, L), jnp.int32)])
    bias, cnt = pl.pallas_call(
        functools.partial(_select_kernel, k=k, chunk=chunk),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, L), jnp.float32),
                   jax.ShapeDtypeStruct((R, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=6 * rows * L * 4 + (8 << 20)),
        interpret=_interpret(interpret), name="select_topk",
    )(top, scores.astype(jnp.float32), extent[:, None])
    return bias, cnt[:, 0]


#: positions of a slot's context one step of the selected read covers
SPARSE_ROWS = 1024
#: rows of ONE page a descriptor of the selected read fetches, at most
SPARSE_RUN = 64


def sparse_run(page_block: int) -> int:
    """The unit the selected read fetches: an aligned run of this many
    rows of one page — the longest that divides both ``SPARSE_RUN`` and the
    page, so a run never leaves its page (a 64-row page is one run)."""
    return math.gcd(SPARSE_RUN, page_block)


def _sparse_decode_kernel(src_ref, pos_ref, q_ref, b_ref, k_hbm, v_hbm, o_ref,
                          kbuf, vbuf, sem, *, scale: float, rows: int,
                          run: int, bs: int, groups: int):
    """Program b: slot b's read of its selected rows, a chunk of ``rows``
    positions of the context at a time. ``src_ref[b, r]`` places run r of
    the context — ``run`` consecutive rows of one page — in the pools [P,
    bs * Hkv, D]: ``page * bs + first row`` where the run holds a selected
    row, -1 where it holds none. A run that holds one is fetched WHOLE by
    ONE descriptor (k and v: two) into its place in the chunk's ``[rows *
    Hkv, D]`` buffer — the matrix the two products take, row ``p * Hkv + g``
    position p of KV head g —, the next chunk's runs in flight while this
    one's are multiplied; a run with -1 is not fetched, and a wait is made
    for every run started and no other. The mask ``b_ref`` [1, L / 128,
    128] (0 selected) says which of a chunk's rows count: a position's bit
    is spread over its Hkv columns by a 0/1 product, and every other
    column — a fetched run's unselected rows, whatever an unfetched run
    left in the buffer, the other groups' KV heads — is masked as padding
    is (``_NEG`` before the max, weight 0 after), so the softmax is over
    the selected rows alone. The value buffer is zeroed once a call: a
    weight of 0 meets what the pool holds or zeros, never what the chip
    left in VMEM. The arithmetic is :func:`_grouped_decode_attn_kernel`'s:
    one product of all H queries against the chunk."""
    from jax.experimental.pallas import tpu as pltpu
    b = pl.program_id(0)
    H, D = q_ref.shape[1:]
    Hkv = H // groups
    R, rr, per, tiles = rows * Hkv, run * Hkv, rows // run, rows // 128
    n_chunks = pos_ref[b] // rows + 1

    @pl.when(b == 0)
    def _once():
        vbuf[...] = jnp.zeros_like(vbuf)

    def fetch(c, slot, wait: bool):
        def one(i, carry):
            src = src_ref[b, c * per + i]

            @pl.when(src >= 0)
            def _held():
                at = pl.ds(pl.multiple_of(jax.lax.rem(src, bs) * Hkv, rr), rr)
                to = pl.ds(pl.multiple_of(i * rr, rr), rr)
                for s, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                    cp = pltpu.make_async_copy(hbm.at[src // bs, at],
                                               buf.at[slot, to],
                                               sem.at[s, slot])
                    cp.wait() if wait else cp.start()
            return carry
        jax.lax.fori_loop(0, per, one, 0)

    fetch(0, 0, False)
    q = q_ref[0].astype(jnp.float32) * scale                # [H, D]
    wide = 128 * Hkv
    spread = (jax.lax.broadcasted_iota(jnp.int32, (128, wide), 1) // Hkv
              == jax.lax.broadcasted_iota(jnp.int32, (128, wide), 0)
              ).astype(jnp.float32)
    own = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1) % Hkv \
        == jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0) // groups

    mxu = _mxu_hi_lo

    def step(c, carry):
        m_prev, l_prev, acc = carry
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < n_chunks)
        def _ahead():
            fetch(c + 1, 1 - slot, False)
        fetch(c, slot, True)
        hit = (b_ref[0, pl.ds(pl.multiple_of(c * tiles, tiles), tiles), :]
               == 0.0).astype(jnp.float32)                  # [tiles, 128]
        cols = jax.lax.dot_general(hit, spread, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        seen = own & (jnp.concatenate(
            [jnp.broadcast_to(cols[j:j + 1], (H, wide))
             for j in range(tiles)], axis=1) > 0.5)         # [H, R]
        s = jnp.where(seen, mxu(q, kbuf[slot], ((1,), (1,))), _NEG)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        return (m_new, l_prev * corr + jnp.sum(p, axis=1, keepdims=True),
                acc * corr + mxu(p, vbuf[slot], ((1,), (0,))))
    _, l, acc = jax.lax.fori_loop(
        0, n_chunks, step,
        (jnp.full((H, 1), _NEG, jnp.float32), jnp.zeros((H, 1), jnp.float32),
         jnp.zeros((H, D), jnp.float32)))
    o_ref[0] = acc / jnp.where(l == 0.0, 1.0, l)


def sparse_decode_attention(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, tables: jax.Array,
                            bias: jax.Array, pos: jax.Array, *,
                            scale: Optional[float] = None,
                            route: Optional[str] = None,
                            interpret: Optional[bool] = None):
    """Single-token attention over SELECTED rows of a paged cache: q [B, H,
    D]; k_pool / v_pool [P, bs, Hkv, D]; tables [B, NB]; bias [B, NB * bs]
    :func:`select_topk`'s additive mask (0 at a slot's selected positions,
    all of them at or before ``pos`` [B]) -> (o [B, H, D] f32, the softmax
    over those rows alone; runs [B] int32).

    The unit the read fetches is a RUN: :func:`sparse_run` consecutive
    rows of one page, one descriptor for k and one for v, where the run
    holds a selected row; a run that holds none is not fetched. ``runs``
    counts a slot's fetched runs (both routes: it is the mask's own
    property), so the read moved ``runs * sparse_run`` rows with ``2 *
    runs`` descriptors, whatever the context's length. A fetched run's
    unselected rows take a weight of exactly 0 — as the dense paged read
    gives a page's rows past ``pos`` — so what they hold must be finite,
    as a pool's rows are."""
    B, L = bias.shape
    P, bs, Hkv, D = k_pool.shape
    H = q.shape[1]
    if tables.shape != (B, L // bs) or L % bs:
        raise ValueError(f"sparse_decode_attention: a mask of {L} positions "
                         f"against tables {tables.shape} of {bs}-row pages")
    scale_v = scale if scale is not None else D ** -0.5
    if route is None:
        route = "kernel" if _on_tpu() else "dense"
    from .. import obs
    obs.count("kernels.routes_total", kernel="sparse_decode_attention",
              route=route)
    run = sparse_run(bs)
    hit = bias == 0.0
    held = jnp.any(hit.reshape(B, L // run, run), axis=2)   # [B, runs]
    runs = jnp.sum(held, axis=1, dtype=jnp.int32)
    if route == "dense":
        k = jnp.repeat(gather_pages(k_pool, tables), H // Hkv, axis=2)
        v = jnp.repeat(gather_pages(v_pool, tables), H // Hkv, axis=2)
        s = jnp.einsum("bhd,bjhd->bhj", q.astype(jnp.float32) * scale_v,
                       k.astype(jnp.float32))
        w = jax.nn.softmax(jnp.where(hit[:, None, :], s, _NEG), axis=-1)
        return jnp.einsum("bhj,bjhd->bhd", w, v.astype(jnp.float32)), runs
    from jax.experimental.pallas import tpu as pltpu
    # a run's place in the pool: its page's number x bs + its first row
    first = (tables.astype(jnp.int32)[:, :, None] * bs
             + jnp.arange(0, bs, run, dtype=jnp.int32)).reshape(B, L // run)
    rows = min(SPARSE_ROWS, -(-L // 128) * 128)
    more = -L % rows                    # whole chunks: no run, no hit
    src = jnp.pad(jnp.where(held, first, -1), ((0, 0), (0, more // run)),
                  constant_values=-1)
    mask = jnp.pad(bias.astype(jnp.float32), ((0, 0), (0, more)),
                   constant_values=_NEG).reshape(B, (L + more) // 128, 128)
    buf = pltpu.VMEM((2, rows * Hkv, D), k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B,),
        in_specs=[pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec((1,) + mask.shape[1:], lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2))])
    o = pl.pallas_call(
        functools.partial(_sparse_decode_kernel, scale=scale_v, rows=rows,
                          run=run, bs=bs, groups=H // Hkv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(interpret), name="sparse_decode_attention",
    )(src, pos.astype(jnp.int32), q, mask,
      k_pool.reshape(P, bs * Hkv, D), v_pool.reshape(P, bs * Hkv, D))
    return o, runs


def _selected_flash_kernel(info_ref, q_ref, k_ref, v_ref, b_ref, o_ref,
                           m_ref, l_ref, acc_ref, *, scale: float,
                           block_q: int, block_k: int):
    """Program (g, qi, ki): KV head g's whole GROUP of query heads (their
    ``block_q`` rows stacked: [G * block_q, D]) against key tile ki under
    the additive mask tile [block_q, block_k], the same for every head.
    Tiles past the q-block's last position are not visited."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    G, _, D = q_ref.shape[1:]
    n_live = (info_ref[0] + (qi + 1) * block_q - 1) // block_k + 1

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ki < n_live)
    def _live():
        q = q_ref[0].reshape(G * block_q, D)
        s = jax.lax.dot_general(q, k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = (s.reshape(G, block_q, block_k) + b_ref[...][None]).reshape(
            G * block_q, block_k)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).reshape(
            G, block_q, D).astype(o_ref.dtype)


def selected_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                             bias: jax.Array, q0, *,
                             scale: Optional[float] = None,
                             block_q: int = 128, block_k: int = 512,
                             route: Optional[str] = None,
                             interpret: Optional[bool] = None) -> jax.Array:
    """An admission's attention under a per-query selection: q [Q, H, D] (a
    block of queries, the first at absolute position ``q0``), k / v [L,
    Hkv, D] (the row's keys, the block's own among them), bias [Q, L] f32
    (:func:`select_topk`'s: 0 at the keys a query attends, ``_NEG``
    elsewhere, the causal limit included) -> o [Q, H, D] in q's dtype.
    Dense flash tiles under the mask; key tiles past the block's last
    position are neither fetched nor multiplied."""
    Q, H, D = q.shape
    L, Hkv, _ = k.shape
    G = H // Hkv
    scale_v = scale if scale is not None else D ** -0.5
    if route is None:
        route = "kernel" if _on_tpu() else "dense"
    from .. import obs
    obs.count("kernels.routes_total", kernel="selected_flash_attention",
              route=route)
    if route == "dense":
        s = jnp.einsum("qkgd,lkd->kgql",
                       q.reshape(Q, Hkv, G, D).astype(jnp.float32),
                       k.astype(jnp.float32)) * scale_v + bias[None, None]
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgql,lkd->qkgd", p, v.astype(jnp.float32))
        return o.reshape(Q, H, D).astype(q.dtype)
    from jax.experimental.pallas import tpu as pltpu
    bq, bk = min(block_q, Q), min(block_k, L)
    if Q % bq or L % bk:
        raise ValueError(f"selected_flash_attention: {Q} queries x {L} keys "
                         f"are not whole tiles of {bq} x {bk}")
    qg = jnp.moveaxis(q, 1, 0).reshape(Hkv, G, Q, D)
    kg, vg = jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)   # [Hkv, L, D]

    def upto(a, b, i):
        return jnp.minimum(b, (i[0] + (a + 1) * bq - 1) // bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(Hkv, Q // bq, L // bk),
        in_specs=[
            pl.BlockSpec((1, G, bq, D), lambda g, a, b, i: (g, 0, a, 0)),
            pl.BlockSpec((1, bk, D), lambda g, a, b, i: (g, upto(a, b, i), 0)),
            pl.BlockSpec((1, bk, D), lambda g, a, b, i: (g, upto(a, b, i), 0)),
            pl.BlockSpec((bq, bk), lambda g, a, b, i: (a, upto(a, b, i)))],
        out_specs=pl.BlockSpec((1, G, bq, D), lambda g, a, b, i: (g, 0, a, 0)),
        scratch_shapes=[pltpu.VMEM((G * bq, 1), jnp.float32),
                        pltpu.VMEM((G * bq, 1), jnp.float32),
                        pltpu.VMEM((G * bq, D), jnp.float32)])
    out = pl.pallas_call(
        functools.partial(_selected_flash_kernel, scale=scale_v, block_q=bq,
                          block_k=bk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hkv, G, Q, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=_interpret(interpret), name="selected_flash_attention",
    )(jnp.asarray(q0, jnp.int32).reshape(1), qg, kg, vg, bias)
    return jnp.moveaxis(out.reshape(H, Q, D), 0, 1)


# ---------------------------------------------------------------------------
# Roofline cost models — Pallas custom calls report ZERO FLOPs/bytes to XLA's
# cost analysis, so each kernel registers the analytic HBM bytes of one
# dispatch with the obs cost ledger (obs/roofline.py register_kernel_cost).
# Every consumer — the live fluid.device_bytes_total accounting, the
# kernels.bytes_total counters at dispatch sites, and the bench rows'
# hbm_bw_util columns (benchmarks/serving_decode.py) — resolves through
# roofline.kernel_cost, so the modeled number has exactly one owner and the
# bench rows and live gauges can never disagree on methodology.
# ---------------------------------------------------------------------------

def _decode_attention_bytes(*, batch, read, n_heads, d_head, layers=1,
                            kv_dtype=None, itemsize=2, steps=1,
                            kv_heads=None, d_value=None):
    """HBM bytes of ``steps`` decode_attention dispatches: k+v live cache
    rows stream once per step (int8 rows read 1 byte/element plus one f32
    scale per (row, head) — the quantized-KV numerics contract,
    docs/design/kernels.md). ``kv_heads``: the heads a cache row holds
    where they are fewer than the query heads (grouped-query attention);
    the read is charged those. ``d_value``: a value row's width where it
    is not a key row's ``d_head``; k and v are charged each its own."""
    heads = kv_heads or n_heads
    if d_value is not None:
        return float(batch * read * heads * (d_head + d_value) * itemsize
                     * layers * steps)
    row = heads * (d_head + 4 if kv_dtype == "int8" else d_head * itemsize)
    return 2.0 * batch * read * row * layers * steps


def _paged_decode_attention_bytes(*, pages, page_block, n_heads, d_head,
                                  batch=1, layers=1, kv_dtype=None,
                                  itemsize=2, steps=1, kv_heads=None,
                                  d_value=None):
    """HBM bytes of paged reads: ``pages`` pages of ``page_block`` rows,
    k and v. The decode kernel streams exactly the pages it walks (one
    program each: ``PagePool.run_segment`` passes that count, summed over
    the segment's steps and layers); a gathered read streams ``batch``
    samples' ``pages`` each."""
    return _decode_attention_bytes(batch=batch, read=pages * page_block,
                                   n_heads=n_heads, d_head=d_head,
                                   layers=layers, kv_dtype=kv_dtype,
                                   itemsize=itemsize, steps=steps,
                                   kv_heads=kv_heads, d_value=d_value)


def _paged_prefill_attention_bytes(*, batch, pages, page_block, n_heads,
                                   d_head, layers=1, kv_dtype=None,
                                   itemsize=2):
    """HBM bytes of one prefix-HIT admission dispatch
    (TransformerLM.prefill_paged): each sample's read view gathers its
    ``pages`` table-named pages once per layer — the shared-prefix read
    that replaces re-prefilling those positions. The suffix k/v WRITES
    ride the executable's own XLA byte analysis; only the gathered cache
    read needs a hand model (same shape as the paged decode read at
    steps=1)."""
    return _paged_decode_attention_bytes(batch=batch, pages=pages,
                                         page_block=page_block,
                                         n_heads=n_heads, d_head=d_head,
                                         layers=layers, kv_dtype=kv_dtype,
                                         itemsize=itemsize, steps=1)


def _flash_window_attention_bytes(*, positions, n_heads, kv_heads, d_head,
                                  itemsize=2, d_value=None):
    """HBM bytes of banded flash forwards over ``positions`` (position,
    layer) pairs, padding included: q and o of the query heads, k and v of
    the KV heads, once each (a group's programs share a K/V block); o and
    v ``d_value`` wide where that is not ``d_head``."""
    return float(positions * (n_heads + kv_heads)
                 * (d_head + (d_head if d_value is None else d_value))
                 * itemsize)


def _paged_latent_attention_bytes(*, pages, page_block, row, itemsize=2):
    """HBM bytes of paged latent reads: ``pages`` pages of ``page_block``
    rows of ``row`` values, each read ONCE (keys and values are the same
    row) — the count PagePool.run_segment passes, summed over steps and
    layers."""
    return float(pages) * page_block * row * itemsize


def _lstm_sequence_fused_bytes(*, batch, seq_len, hidden, itemsize=4,
                               gates=4):
    """HBM bytes of one fused-RNN forward launch: the [B, T, G*H] gate
    input streams in once, [B, T, H] outputs stream out, the recurrent
    [H, G*H] weights load once (VMEM-resident across steps — the whole
    point of the kernel)."""
    return float(itemsize) * (batch * seq_len * hidden * gates      # xw in
                              + batch * seq_len * hidden            # out
                              + hidden * hidden * gates)            # U


def _ssm_state_update_bytes(*, updates, heads, head_dim, state, groups,
                            state_itemsize=4):
    """HBM bytes of ``updates`` single-position state updates (one a live
    slot a Mamba layer a step — the count a program returns beside its
    tokens): the packed state read once and written once, and the step's
    own ``x, B, C, dt, y`` in float32."""
    return float(updates) * (
        2.0 * heads * head_dim * state * state_itemsize
        + (2 * heads * head_dim + 2 * groups * state + heads) * 4.0)


def _ssd_chunk_scan_bytes(*, tokens, heads, head_dim, state, groups,
                          itemsize=2):
    """HBM bytes of the chunked scan over ``tokens`` (position, Mamba
    layer) pairs, padding included (the kernel streams it): ``x, B, C`` in
    the operands' dtype, ``dt`` and ``y`` in float32, once each."""
    return float(tokens) * (
        (heads * head_dim + 2 * groups * state) * itemsize
        + (heads + heads * head_dim) * 4.0)


def _index_scores_paged_bytes(*, keys, index_dim, itemsize=2):
    """HBM bytes of decode steps' indexer reads over ``keys`` cached keys
    (live slot x step x layer x context, the count a program returns): a
    key's ``index_dim`` values once each — the WORK's bytes; the pool
    holds the row at the lane width and the kernel streams that."""
    return float(keys) * index_dim * itemsize


def _select_topk_bytes(*, keys):
    """HBM bytes of selections over ``keys`` scores: each read once (f32)
    and its mask entry written once (f32)."""
    return float(keys) * 8.0


def _sparse_decode_attention_bytes(*, rows, kv_heads, d_head, itemsize=2):
    """HBM bytes of selected reads of ``rows`` cache rows (the count the
    selection returns): a row of k and a row of v, each once."""
    return 2.0 * rows * kv_heads * d_head * itemsize


def _index_scores_bytes(*, pairs):
    """HBM bytes of admissions' indexer scores over ``pairs`` causal
    (query, key) pairs: a float32 score written for each."""
    return float(pairs) * 4.0


def _selected_flash_attention_bytes(*, pairs, kv_heads):
    """HBM bytes of admissions' attention under the selection over
    ``pairs`` causal (query, key) pairs: the float32 mask entry of each,
    read once a KV head (a group's heads share the tile)."""
    return float(pairs) * 4.0 * kv_heads


def _register_cost_models():
    from ..obs import roofline
    for name, fn in (
            ("index_scores_paged", _index_scores_paged_bytes),
            ("select_topk", _select_topk_bytes),
            ("sparse_decode_attention", _sparse_decode_attention_bytes),
            ("index_scores", _index_scores_bytes),
            ("selected_flash_attention", _selected_flash_attention_bytes)):
        roofline.register_kernel_cost(name, fn)
    roofline.register_kernel_cost("decode_attention",
                                  _decode_attention_bytes)
    roofline.register_kernel_cost("paged_decode_attention",
                                  _paged_decode_attention_bytes)
    roofline.register_kernel_cost("paged_window_attention",
                                  _paged_decode_attention_bytes)
    roofline.register_kernel_cost("flash_window_attention_fwd",
                                  _flash_window_attention_bytes)
    roofline.register_kernel_cost("paged_prefill_attention",
                                  _paged_prefill_attention_bytes)
    roofline.register_kernel_cost("paged_latent_attention",
                                  _paged_latent_attention_bytes)
    roofline.register_kernel_cost("ssm_state_update",
                                  _ssm_state_update_bytes)
    roofline.register_kernel_cost("ssd_chunk_scan", _ssd_chunk_scan_bytes)
    roofline.register_kernel_cost("lstm_sequence_fused",
                                  _lstm_sequence_fused_bytes)
    roofline.register_kernel_cost(
        "gru_sequence_fused",
        functools.partial(_lstm_sequence_fused_bytes, gates=3))


_register_cost_models()
