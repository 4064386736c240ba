"""Recurrent ops: LSTM/GRU cells + masked scans.

Replaces the reference's fused recurrent kernels (paddle/cuda/src/hl_cuda_lstm.cu,
hl_gpu_gru.cuh, operators/math/lstm_compute.cc, gru_compute.cc) and the dynamic-RNN
engine (gserver/gradientmachines/RecurrentGradientMachine.cpp, operators/recurrent_op.cc,
dynamic_recurrent_op.cc). TPU-first design:

* The input projection x @ W for ALL timesteps is one big [B*T, 4H] matmul (MXU-
  friendly) done before the scan; only the recurrent h @ U matmul lives inside
  ``lax.scan`` — the same restructuring SequenceToBatch did for step-parallelism,
  expressed at the compiler level.
* Variable lengths: every step is masked (state frozen once t >= length), replacing
  shrink-live-batch (lod_rank_table + shrink_rnn_memory_op) with branch-free masking.
* Gate order: i, f, c(candidate/g), o — matching the reference's hl_lstm layout
  (input/forget/cell/output).
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import functools

import jax
import numpy as np
import jax.numpy as jnp
from jax import lax

from ..core.lod import sequence_mask


class LSTMState(NamedTuple):
    h: jax.Array
    c: jax.Array


def lstm_cell(xw: jax.Array, state: LSTMState, u: jax.Array, b: Optional[jax.Array],
              forget_bias: float = 0.0) -> LSTMState:
    """One LSTM step. xw: precomputed x@W [B, 4H]; u: [H, 4H]."""
    h, c = state
    gates = xw + jnp.matmul(h, u)
    if b is not None:
        gates = gates + b
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    i = jax.nn.sigmoid(i)
    f = jax.nn.sigmoid(f + forget_bias)
    g = jnp.tanh(g)
    o = jax.nn.sigmoid(o)
    c_new = f * c + i * g
    h_new = o * jnp.tanh(c_new)
    return LSTMState(h_new, c_new)


def gru_cell(xw: jax.Array, h: jax.Array, u: jax.Array,
             b: Optional[jax.Array]) -> jax.Array:
    """One GRU step (ref gate order: update z, reset r, candidate).

    xw: x@W [B, 3H]; u: [H, 3H] packed [u_zr | u_c]."""
    H = h.shape[-1]
    xz, xr, xc = jnp.split(xw, 3, axis=-1)
    uz, ur, uc = jnp.split(u, 3, axis=-1)
    bz = br = bc = 0.0
    if b is not None:
        bz, br, bc = jnp.split(b, 3, axis=-1)
    z = jax.nn.sigmoid(xz + jnp.matmul(h, uz) + bz)
    r = jax.nn.sigmoid(xr + jnp.matmul(h, ur) + br)
    c = jnp.tanh(xc + jnp.matmul(r * h, uc) + bc)
    return (1.0 - z) * h + z * c


def lstm(x: jax.Array, lengths: Optional[jax.Array], w: jax.Array, u: jax.Array,
         b: Optional[jax.Array] = None, h0: Optional[jax.Array] = None,
         c0: Optional[jax.Array] = None, reverse: bool = False,
         forget_bias: float = 0.0,
         fused: Optional[bool] = None) -> Tuple[jax.Array, LSTMState]:
    """Full-sequence LSTM. x: [B, T, D]; w: [D, 4H]; u: [H, 4H].

    Returns (outputs [B, T, H], final LSTMState). Masked: for t >= length the state
    carries through unchanged and the output is zero (LoD semantics — downstream
    sequence pooling then ignores padding for free).

    ``fused=True`` routes through the Pallas whole-sequence kernels in BOTH
    directions (hl_cuda_lstm.cu analog: u and h/c resident in VMEM for all
    T steps; the backward is the hand-written reverse-recurrence kernel,
    hl_lstm_parallel_backward_data/_weight analog). Both paths compute
    identical math, so fused training == scan training numerically (see
    tests/test_pallas.py).

    ``fused=None`` (default) auto-selects: the kernel whenever a legal
    (batch-tile, time-chunk) plan fits VMEM on the TPU (see
    :func:`_fused_plan`), the scan otherwise. The original narrow-tile
    kernel lost MXU-bound large batches (B=64 train 2.2x slower — VMEM
    capped the whole-sequence batch tile at 8 rows, starving the 128-row
    MXU; docs/design/fused_rnn_bench.md); time-chunked launches lift that
    cap to 32/64-row tiles, which is what routes the textcls (h256,
    len 30-100, B>=64) and NMT-encoder shape families onto the kernel.
    benchmarks/fused_rnn.py re-measures the crossover on-chip.
    """
    B, T, _ = x.shape
    H = u.shape[0]
    if fused is None:
        fused = True                 # auto: plan + backend decide below
    if fused:
        from . import pallas_kernels as _pk
        from .. import obs
        plan = _fused_plan(T, H, seq_h_units=6, batch=B)
        obs.count("kernels.routes_total", kernel="lstm_sequence_fused",
                  route=("fused" if _pk._on_tpu() and plan is not None
                         else "scan"))
        if _pk._on_tpu() and plan is not None:
            # modeled launch bytes through the ONE registered model
            # (pallas_kernels._lstm_sequence_fused_bytes): under an
            # executor/instrumented-jit trace the collector re-emits them
            # PER DISPATCH; eagerly this counts kernels.bytes_total now
            obs.roofline.note_kernel_bytes(
                "lstm_sequence_fused",
                obs.roofline.kernel_cost(
                    "lstm_sequence_fused", batch=B, seq_len=T,
                    hidden=H, itemsize=jnp.dtype(x.dtype).itemsize))
            blk, chunk = plan
            lens = (lengths if lengths is not None
                    else jnp.full((B,), T, jnp.int32))
            b_ = b if b is not None else jnp.zeros((4 * H,), x.dtype)
            h0_ = h0 if h0 is not None else jnp.zeros((B, H), x.dtype)
            c0_ = c0 if c0 is not None else jnp.zeros((B, H), x.dtype)
            xk = _reverse_within_length(x, lens) if reverse else x
            out, ht, ct = _lstm_fused(xk, lens, w, u, b_, h0_, c0_,
                                      forget_bias, blk, chunk)
            if reverse:
                out = _reverse_within_length(out, lens)
            return out, LSTMState(ht, ct)
        # off-TPU, or no VMEM-legal plan — the scan handles any shape
    return _lstm_scan(x, lengths, w, u, b, h0, c0, reverse, forget_bias)


#: minimum resident time-chunk for a wide batch tile: below this the
#: chunk-boundary h/c round-trips start to rival the per-step work
_CHUNK_MIN_WIDE = 16


def _fused_plan(T: int, H: int, gates: int = 4,
                seq_h_units: Optional[int] = None,
                batch: Optional[int] = None,
                budget_bytes: int = 15_500_000,
                double_buffer_always: bool = False
                ) -> Optional[Tuple[int, int]]:
    """(block_b, chunk_t) for the fused whole-sequence kernels, or None
    for the scan. ``gates``: 4 for LSTM, 3 for GRU (sizes the [H, gates*H]
    u and the [chunk, blk, gates*H] xw tile); ``seq_h_units``: total width
    of the per-step sequence buffers in multiples of H (default xw + out =
    gates + 1; the train forward adds the saved cell sequence, the
    backward roughly doubles it).

    Preference order: the WIDEST batch tile whose resident time-chunk
    still fits VMEM — the recurrent matmul is [blk, H] @ [H, gates*H] per
    step, so blk is the MXU row dimension and an 8-row tile starves the
    128-row systolic array (the measured 2.2x large-batch loss of the old
    whole-sequence-resident kernel). chunk_t < T costs one h/c HBM
    round-trip per boundary inside the same traced graph — cheap next to
    feeding the MXU 4-8x more rows.

    Mosaic tiling: the batch tile is the second-to-last block dim, so it
    must be a multiple of 8 — or equal the whole (padded) batch, i.e. a
    single grid program, which is how sub-8 batches run. Cost model
    calibrated against the chip's 16 MB scoped VMEM (measured on v5e):
    with more than one grid program Pallas double-buffers every
    batch-varying block, so the tile costs 2x; a single-program grid is
    single-buffered (which is why tiny-batch probes fit shapes that OOM
    at full batch)."""
    if seq_h_units is None:
        seq_h_units = gates + 1
    u_bytes = H * gates * H * 4          # u resident + du accumulator
    avail = budget_bytes - 2 * u_bytes
    if avail <= 0:
        return None

    def chunk_for(blk, grid_is_1):
        per_step = blk * seq_h_units * H * 4
        if double_buffer_always or not grid_is_1:
            per_step *= 2                # double-buffered batch tiles
        return avail // per_step

    if batch is not None and batch < 8:
        chunk = chunk_for(batch, True)
        return (batch, min(T, chunk)) if chunk >= min(T, 8) else None
    for blk in (64, 32, 16):
        if batch is not None and blk > batch:
            continue
        chunk = chunk_for(blk, batch is not None and blk == batch)
        if chunk >= min(T, _CHUNK_MIN_WIDE):
            return blk, min(T, chunk)
    chunk = chunk_for(8, batch == 8)
    if chunk >= min(T, 8):
        return 8, min(T, chunk)
    return None


def _fused_bwd_plan(T: int, H: int, gates: int, seq_h_units: int,
                    batch: int,
                    budget_bytes: int = 15_500_000
                    ) -> Optional[Tuple[int, int]]:
    """(block_b, chunk_t) for the hand-written backward kernels — the SAME
    planner as :func:`_fused_plan` (one place owns the VMEM cost model and
    tile preference), always double-buffer-costed. The reverse recurrence
    splits cleanly at chunk boundaries: the saved (out, c) sequences
    provide each chunk's initial state, so the wrapper runs a few kernel
    launches instead of one."""
    return _fused_plan(T, H, gates, seq_h_units, batch, budget_bytes,
                       double_buffer_always=True)


def _reverse_within_length(x: jax.Array, lengths: jax.Array) -> jax.Array:
    """Flip each sample's FIRST ``length`` steps along time; positions at
    or past length become zero. x: [B, T, ...].

    This is how ``reverse=True`` rides the forward-only fused kernels: a
    masked reverse scan over a right-padded batch is exactly a forward
    scan over the within-length-flipped input — state updates visit the
    original steps length-1..0 and frozen (t >= length) steps stay
    frozen — with the output flipped back on the way out (outputs at
    padding are zero on both sides, so the round trip is lossless).
    Ordinary gather/where, so autodiff flows through it around the fused
    kernel's custom VJP."""
    T = x.shape[1]
    idx = lengths.astype(jnp.int32)[:, None] - 1 - jnp.arange(T)[None, :]
    ok = idx >= 0                                     # [B, T]
    idx = jnp.clip(idx, 0, T - 1)
    tail = (1,) * (x.ndim - 2)
    out = jnp.take_along_axis(x, idx.reshape(idx.shape + tail), axis=1)
    return jnp.where(ok.reshape(ok.shape + tail), out,
                     jnp.zeros((), x.dtype))


def _lstm_scan(x, lengths, w, u, b, h0, c0, reverse, forget_bias):
    B, T, D = x.shape
    H = u.shape[0]
    xw = jnp.matmul(x.reshape(B * T, D), w).reshape(B, T, -1)  # one MXU pass
    mask = (sequence_mask(lengths, T, x.dtype) if lengths is not None
            else jnp.ones((B, T), x.dtype))
    h = h0 if h0 is not None else jnp.zeros((B, H), x.dtype)
    c = c0 if c0 is not None else jnp.zeros((B, H), x.dtype)

    def step(carry, inp):
        state = LSTMState(*carry)
        xw_t, m_t = inp
        new = lstm_cell(xw_t, state, u, b, forget_bias)
        m = m_t[:, None]
        h_n = m * new.h + (1.0 - m) * state.h
        c_n = m * new.c + (1.0 - m) * state.c
        return (h_n, c_n), m * h_n

    xs = (jnp.swapaxes(xw, 0, 1), jnp.swapaxes(mask, 0, 1))  # [T, B, ...]
    (h, c), ys = lax.scan(step, (h, c), xs, reverse=reverse)
    return jnp.swapaxes(ys, 0, 1), LSTMState(h, c)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _lstm_fused(x, lens, w, u, b, h0, c0, forget_bias, block_b, chunk_t):
    """Forward through the Pallas fused kernel; under autodiff the VJP pairs
    it with the hand-written reverse-recurrence kernel
    (pallas_kernels.lstm_sequence_fused_bwd) — fused in BOTH directions,
    the training-mode discipline of the reference's hl_lstm kernels
    (hl_cuda_lstm.cu hl_lstm_parallel_backward_data/_weight)."""
    from .pallas_kernels import lstm_sequence_fused
    B, T, D = x.shape
    xw = jnp.matmul(x.reshape(B * T, D), w).reshape(B, T, -1)
    return lstm_sequence_fused(xw, lens, u, b, h0=h0, c0=c0,
                               forget_bias=forget_bias, block_b=block_b,
                               chunk_t=chunk_t)


def _lstm_fused_fwd(x, lens, w, u, b, h0, c0, forget_bias, block_b, chunk_t):
    from .pallas_kernels import lstm_sequence_fused
    B, T, D = x.shape
    xw = jnp.matmul(x.reshape(B * T, D), w).reshape(B, T, -1)
    out, ht, ct, c_seq = lstm_sequence_fused(
        xw, lens, u, b, h0=h0, c0=c0, forget_bias=forget_bias,
        block_b=block_b, chunk_t=chunk_t, save_cell=True)
    return (out, ht, ct), (x, lens, w, u, b, h0, c0, xw, out, c_seq)


def _lstm_fused_bwd(forget_bias, block_b, chunk_t, res, g):
    x, lens, w, u, b, h0, c0, xw, out, c_seq = res
    zero_lens = np.zeros(lens.shape, jax.dtypes.float0)
    B, T, D = x.shape
    H = u.shape[0]
    plan = _fused_bwd_plan(T, H, 4, 11, B)   # 2*(xw+dxw) + 3 H-wide seqs
    if plan is None:
        # VMEM won't hold even an 8-step backward tile: replay the
        # (bit-identical) scan under autodiff instead
        def replay(x, w, u, b, h0, c0):
            out, state = _lstm_scan(x, lens, w, u, b, h0, c0, False,
                                    forget_bias)
            return out, state.h, state.c

        _, vjp = jax.vjp(replay, x, w, u, b, h0, c0)
        dx, dw, du, db, dh0, dc0 = vjp(g)
        return dx, zero_lens, dw, du, db, dh0, dc0

    from .pallas_kernels import lstm_sequence_fused_bwd
    g_out, g_ht, g_ct = g
    blk, chunk = plan
    dh, dc = g_ht, g_ct
    du = jnp.zeros((H, 4 * H), jnp.float32)
    parts = []
    starts = list(range(0, T, chunk))
    for s in reversed(starts):
        e = min(T, s + chunk)
        h0_k = h0 if s == 0 else out[:, s - 1]
        c0_k = c0 if s == 0 else c_seq[:, s - 1]
        dxw_k, dh, dc, du_k = lstm_sequence_fused_bwd(
            xw[:, s:e], lens - s, u, b, h0_k, c0_k, out[:, s:e],
            c_seq[:, s:e], g_out[:, s:e], dh, dc,
            forget_bias=forget_bias, block_b=blk)
        du = du + du_k
        parts.append(dxw_k)
    dxw = parts[0] if len(parts) == 1 else jnp.concatenate(parts[::-1],
                                                           axis=1)
    dh0, dc0 = dh, dc
    G = 4 * H
    dxw2 = dxw.reshape(B * T, G).astype(jnp.float32)
    dx = jnp.matmul(dxw2, w.T.astype(jnp.float32)).reshape(x.shape)\
        .astype(x.dtype)
    dw = jnp.matmul(x.reshape(B * T, D).T.astype(jnp.float32), dxw2)\
        .astype(w.dtype)
    db = dxw2.sum(0).astype(b.dtype)
    return (dx, zero_lens, dw, du.astype(u.dtype), db, dh0.astype(h0.dtype),
            dc0.astype(c0.dtype))


_lstm_fused.defvjp(_lstm_fused_fwd, _lstm_fused_bwd)


def gru(x: jax.Array, lengths: Optional[jax.Array], w: jax.Array, u: jax.Array,
        b: Optional[jax.Array] = None, h0: Optional[jax.Array] = None,
        reverse: bool = False,
        fused: Optional[bool] = None) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence GRU. x: [B, T, D]; w: [D, 3H]; u: [H, 3H].

    ``fused=True`` runs both directions through the Pallas whole-sequence
    kernels (hl_gpu_gru.cuh analog) — same contract as lstm(fused=True):
    identical math to the scan, hand-written backward kernel;
    ``fused=None`` auto-selects the kernel whenever a VMEM-legal
    (batch-tile, time-chunk) plan exists on the TPU — including
    ``reverse=True`` (the bidirectional NMT encoder), which rides the
    forward kernel via the within-length flip (see lstm())."""
    B, T, D = x.shape
    H = u.shape[0]
    if fused is None:
        fused = True
    if fused:
        from . import pallas_kernels as _pk
        from .. import obs
        plan = _fused_plan(T, H, gates=3, batch=B)
        obs.count("kernels.routes_total", kernel="gru_sequence_fused",
                  route=("fused" if _pk._on_tpu() and plan is not None
                         else "scan"))
        if _pk._on_tpu() and plan is not None:
            obs.roofline.note_kernel_bytes(
                "gru_sequence_fused",
                obs.roofline.kernel_cost(
                    "gru_sequence_fused", batch=B, seq_len=T,
                    hidden=H, itemsize=jnp.dtype(x.dtype).itemsize))
            blk, chunk = plan
            lens = (lengths if lengths is not None
                    else jnp.full((B,), T, jnp.int32))
            b_ = b if b is not None else jnp.zeros((3 * H,), x.dtype)
            h0_ = h0 if h0 is not None else jnp.zeros((B, H), x.dtype)
            xk = _reverse_within_length(x, lens) if reverse else x
            out, ht = _gru_fused(xk, lens, w, u, b_, h0_, blk, chunk)
            if reverse:
                out = _reverse_within_length(out, lens)
            return out, ht
    xw = jnp.matmul(x.reshape(B * T, D), w).reshape(B, T, -1)
    mask = (sequence_mask(lengths, T, x.dtype) if lengths is not None
            else jnp.ones((B, T), x.dtype))
    h = h0 if h0 is not None else jnp.zeros((B, H), x.dtype)

    def step(h_prev, inp):
        xw_t, m_t = inp
        h_new = gru_cell(xw_t, h_prev, u, b)
        m = m_t[:, None]
        h_n = m * h_new + (1.0 - m) * h_prev
        return h_n, m * h_n

    xs = (jnp.swapaxes(xw, 0, 1), jnp.swapaxes(mask, 0, 1))
    h, ys = lax.scan(step, h, xs, reverse=reverse)
    return jnp.swapaxes(ys, 0, 1), h


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _gru_fused(x, lens, w, u, b, h0, block_b, chunk_t):
    from .pallas_kernels import gru_sequence_fused
    B, T, D = x.shape
    xw = jnp.matmul(x.reshape(B * T, D), w).reshape(B, T, -1)
    return gru_sequence_fused(xw, lens, u, b, h0=h0, block_b=block_b,
                              chunk_t=chunk_t)


def _gru_fused_fwd(x, lens, w, u, b, h0, block_b, chunk_t):
    from .pallas_kernels import gru_sequence_fused
    B, T, D = x.shape
    xw = jnp.matmul(x.reshape(B * T, D), w).reshape(B, T, -1)
    if b is not None:
        xw = xw + b                        # kernel expects bias pre-added
    out, ht = gru_sequence_fused(xw, lens, u, None, h0=h0, block_b=block_b,
                                 chunk_t=chunk_t)
    return (out, ht), (x, lens, w, u, b, h0, xw, out)


def _gru_fused_bwd(block_b, chunk_t, res, g):
    x, lens, w, u, b, h0, xw, out = res
    zero_lens = np.zeros(lens.shape, jax.dtypes.float0)
    B, T, D = x.shape
    H = u.shape[0]
    plan = _fused_bwd_plan(T, H, 3, 8, B)    # 2*(xw+dxw) + 2 H-wide seqs
    if plan is None:
        def replay(x, w, u, b, h0):
            return gru(x, lens, w, u, b, h0, fused=False)

        _, vjp = jax.vjp(replay, x, w, u, b, h0)
        dx, dw, du, db, dh0 = vjp(g)
        return dx, zero_lens, dw, du, db, dh0

    from .pallas_kernels import gru_sequence_fused_bwd
    g_out, g_ht = g
    blk, chunk = plan
    dh = g_ht
    du = jnp.zeros((H, 3 * H), jnp.float32)
    parts = []
    for s in reversed(range(0, T, chunk)):
        e = min(T, s + chunk)
        h0_k = h0 if s == 0 else out[:, s - 1]
        dxw_k, dh, du_k = gru_sequence_fused_bwd(
            xw[:, s:e], lens - s, u, h0_k, out[:, s:e], g_out[:, s:e], dh,
            block_b=blk)
        du = du + du_k
        parts.append(dxw_k)
    dxw = parts[0] if len(parts) == 1 else jnp.concatenate(parts[::-1],
                                                           axis=1)
    dh0 = dh
    G = 3 * H
    dxw2 = dxw.reshape(B * T, G).astype(jnp.float32)
    dx = jnp.matmul(dxw2, w.T.astype(jnp.float32)).reshape(x.shape)\
        .astype(x.dtype)
    dw = jnp.matmul(x.reshape(B * T, D).T.astype(jnp.float32), dxw2)\
        .astype(w.dtype)
    db = None if b is None else dxw2.sum(0).astype(b.dtype)
    return dx, zero_lens, dw, du.astype(u.dtype), db, dh0.astype(h0.dtype)


_gru_fused.defvjp(_gru_fused_fwd, _gru_fused_bwd)


def bidirectional(rnn_fn: Callable, x, lengths, fwd_params: dict, bwd_params: dict,
                  merge: str = "concat"):
    """Bidirectional wrapper (ref: networks.py bidirectional_lstm:553ff).

    For the reverse direction the mask-aware scan runs with reverse=True, which on
    padded-right batches is equivalent to the reference's sequence-reverse layers
    because masked steps carry state through unchanged."""
    out_f, _ = rnn_fn(x, lengths, reverse=False, **fwd_params)
    out_b, _ = rnn_fn(x, lengths, reverse=True, **bwd_params)
    if merge == "concat":
        return jnp.concatenate([out_f, out_b], axis=-1)
    if merge == "sum":
        return out_f + out_b
    raise ValueError(f"unknown merge '{merge}'")


def simple_rnn(x: jax.Array, lengths: Optional[jax.Array],
               w: Optional[jax.Array], u: jax.Array,
               b: Optional[jax.Array] = None,
               act: Callable = jnp.tanh, h0: Optional[jax.Array] = None,
               reverse: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Vanilla RNN (ref: gserver/layers/RecurrentLayer.cpp). ``w=None`` is
    the reference's recurrent_layer contract — x is already projected to
    the hidden width and only the recurrent transform U applies."""
    B, T, D = x.shape
    H = u.shape[0]
    xw = (x if w is None
          else jnp.matmul(x.reshape(B * T, D), w).reshape(B, T, -1))
    mask = (sequence_mask(lengths, T, x.dtype) if lengths is not None
            else jnp.ones((B, T), x.dtype))
    h = h0 if h0 is not None else jnp.zeros((B, H), x.dtype)

    def step(h_prev, inp):
        xw_t, m_t = inp
        h_new = act(xw_t + jnp.matmul(h_prev, u) + (b if b is not None else 0.0))
        m = m_t[:, None]
        h_n = m * h_new + (1.0 - m) * h_prev
        return h_n, m * h_n

    xs = (jnp.swapaxes(xw, 0, 1), jnp.swapaxes(mask, 0, 1))
    h, ys = lax.scan(step, h, xs, reverse=reverse)
    return jnp.swapaxes(ys, 0, 1), h


def lstm_peephole_step(xw: jax.Array, c_prev: jax.Array, w_peep: jax.Array,
                       b: Optional[jax.Array] = None,
                       forget_bias: float = 0.0) -> Tuple[jax.Array, jax.Array]:
    """One LSTM step with PRE-PROJECTED gates and peephole connections —
    the reference's LstmStepLayer (gserver/layers/LstmStepLayer.cpp,
    trainer_config_helpers/layers.py:3544 lstm_step_layer): the user's
    mixed_layer computes Wx_t + Wh_{t-1}; this step only adds the
    c_{t-1}/c_t peephole terms, bias, and the cell recurrence.

        i = sigmoid(g_i + w_ci * c_prev + b_i)
        f = sigmoid(g_f + w_cf * c_prev + b_f [+ forget_bias])
        c = f * c_prev + i * tanh(g_c + b_c)
        o = sigmoid(g_o + w_co * c + b_o)      # peeps at the NEW cell
        h = o * tanh(c)

    xw: [B, 4H] packed (i, f, c, o); w_peep: [3, H] packed (ci, cf, co).
    Returns (h, c).
    """
    H = c_prev.shape[-1]
    gi, gf, gc, go = (xw[..., :H], xw[..., H:2 * H], xw[..., 2 * H:3 * H],
                      xw[..., 3 * H:])
    if b is not None:
        bi, bf, bc, bo = (b[..., :H], b[..., H:2 * H], b[..., 2 * H:3 * H],
                          b[..., 3 * H:])
        gi, gf, gc, go = gi + bi, gf + bf, gc + bc, go + bo
    i = jax.nn.sigmoid(gi + c_prev * w_peep[0])
    f = jax.nn.sigmoid(gf + c_prev * w_peep[1] + forget_bias)
    c = f * c_prev + i * jnp.tanh(gc)
    o = jax.nn.sigmoid(go + c * w_peep[2])
    return o * jnp.tanh(c), c
