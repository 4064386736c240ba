"""Sequence/context parallelism: ring attention over a ``seq`` mesh axis.

The 2017 reference's longest-sequence story is padding-free LoD batching
(SURVEY.md §5 long-context) — there is no sequence-dim sharding to port. This
module provides the modern first-class capability the TPU build is required to
have: sequences sharded over a mesh axis, attention computed exactly via a ring
of ``ppermute`` steps with online-softmax (flash-style) accumulation, so each
chip only ever holds 1/N of the KV cache and the KV blocks ride the ICI ring.

Per-step compute runs the Pallas flash kernel (ops/pallas_kernels.py), so the
[T_local, T_local] score tile lives only in VMEM. The backward pass is
hand-written: because flash-attention block gradients factor over key blocks
given the *global* logsumexp and delta = rowsum(dO·O), each ring step computes
one block's (dq, dk, dv) with the Pallas backward kernels while the dk/dv
accumulators ride the ring alongside their KV block — after n steps every
accumulator is back home with contributions from all devices.

Layout: q/k/v are [batch, time_local, heads, head_dim] inside ``shard_map`` over
the ``seq`` axis; time_local = T_global / n_shards.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import pallas_kernels as pk

_NEG = -1e30


def _online_update(o, l, m, scores, v):
    """One flash-attention accumulation step.

    o [B,T,H,D] running numerator; l [B,H,T] running denominator; m [B,H,T]
    running max; scores [B,H,T,S]; v [B,S,H,D].
    """
    m_new = jnp.maximum(m, scores.max(axis=-1))
    p = jnp.exp(scores - m_new[..., None])             # [B,H,T,S]
    corr = jnp.exp(m - m_new)                          # [B,H,T]
    l = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bhts,bshd->bthd", p, v)
    o = o * corr.transpose(0, 2, 1)[..., None] + pv
    return o, l, m_new


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        block_size: int = 512, causal: bool = False,
                        scale: Optional[float] = None) -> jax.Array:
    """Single-device memory-efficient attention: scan over KV blocks.

    Never materialises the [T, S] score matrix beyond one [T, block] tile —
    the host-memory analog of what the Pallas flash kernel does in VMEM.
    q,k,v: [B, T, H, D] -> [B, T, H, D].
    """
    B, T, H, D = q.shape
    S = k.shape[1]
    scale = scale if scale is not None else D ** -0.5
    nblk = -(-S // block_size)
    pad = nblk * block_size - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nblk, block_size, H, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nblk, block_size, H, D).transpose(1, 0, 2, 3, 4)
    q_pos = jnp.arange(T)

    def body(carry, blk):
        o, l, m, i = carry
        kblk, vblk = blk
        scores = jnp.einsum("bthd,bshd->bhts", q, kblk) * scale
        k_pos = i * block_size + jnp.arange(block_size)
        valid = k_pos < S
        mask = valid[None, :]
        if causal:
            mask = mask & (q_pos[:, None] >= k_pos[None, :])
        scores = jnp.where(mask[None, None], scores, _NEG)
        o, l, m = _online_update(o, l, m, scores, vblk)
        return (o, l, m, i + 1), None

    # derive accumulator initials from q so they carry q's device-varying type
    # (required for the scan carry when running inside shard_map)
    o0 = (q * 0).astype(jnp.float32)
    l0 = (q[..., 0] * 0).astype(jnp.float32).transpose(0, 2, 1)
    m0 = l0 + _NEG
    (o, l, m, _), _ = lax.scan(body, (o0, l0, m0, 0), (kb, vb))
    l = jnp.where(l == 0.0, 1.0, l)
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def _merge_partials(o1, lse1, o2, lse2):
    """Exactly combine two attention partials over disjoint key sets.

    o_i are softmax-normalised within their key set, lse_i the corresponding
    logsumexp [B,T,H]. Returns the merged (o, lse).
    """
    m = jnp.maximum(lse1, lse2)
    w1 = jnp.exp(lse1 - m)
    w2 = jnp.exp(lse2 - m)
    denom = w1 + w2
    safe = jnp.where(denom == 0.0, 1.0, denom)
    o = (o1 * w1[..., None] + o2 * w2[..., None]) / safe[..., None]
    return o, m + jnp.log(safe)


def _step_attention(q, k, v, diag, causal, scale, interpret):
    """One ring step's partial attention: Pallas flash kernel, (o_f32, lse).

    ``diag`` (traced bool) selects the causally-masked kernel when this step
    holds the device's own KV block.
    """
    if not causal:
        o, lse = pk.flash_attention_with_lse(q, k, v, causal=False,
                                             scale=scale, interpret=interpret)
        return o.astype(jnp.float32), lse
    o, lse = lax.cond(
        diag,
        lambda args: pk.flash_attention_with_lse(*args, causal=True,
                                                 scale=scale,
                                                 interpret=interpret),
        lambda args: pk.flash_attention_with_lse(*args, causal=False,
                                                 scale=scale,
                                                 interpret=interpret),
        (q, k, v))
    return o.astype(jnp.float32), lse


# ---------------------------------------------------------------------------
# zigzag (load-balanced causal) layout
#
# The contiguous layout wastes ~(n-1)/2n of causal ring FLOPs: whole KV
# blocks from the future are computed then discarded. The zigzag layout
# (llama3-style: split the sequence into 2n chunks, device d holds chunks
# (d, 2n-1-d)) makes every step do the same ~half-block of useful work:
#
#   * src == my (diagonal): the local 2c-causal mask is EXACTLY right for
#     the (d, 2n-1-d) chunk pair — chunk d attends itself causally and never
#     reaches chunk 2n-1-d's keys; chunk 2n-1-d attends chunk d fully and
#     itself causally. One plain causal flash call, nothing wasted.
#   * src < my (block from the past): both local q chunks attend only the
#     held block's FIRST chunk (its second chunk 2n-1-src is in both q
#     chunks' future) -> one half-width kernel call.
#   * src > my (block from the future): only the local SECOND q chunk
#     attends (the held block is entirely in chunk 2n-1-my's past) -> one
#     half-height kernel call.
#
# _zigzag_step_pairs() is the work accounting used by the balance test.
# ---------------------------------------------------------------------------

def zigzag_order(T: int, n: int):
    """Global position order such that contiguous equal shards of the
    REORDERED sequence give device d chunks (d, 2n-1-d) of the original."""
    if T % (2 * n):
        raise ValueError(f"T={T} must divide into 2*{n} zigzag chunks")
    c = T // (2 * n)
    idx = []
    for d in range(n):
        idx.extend(range(d * c, (d + 1) * c))
        idx.extend(range((2 * n - 1 - d) * c, (2 * n - d) * c))
    return jnp.asarray(idx, jnp.int32)


def zigzag_inverse(T: int, n: int):
    order = zigzag_order(T, n)
    inv = jnp.zeros((T,), jnp.int32).at[order].set(jnp.arange(T, dtype=jnp.int32))
    return inv


def _zigzag_step_pairs(c: int):
    """(diagonal, off-diagonal) attended (q, key) pair counts per ring step
    per device — the layout's work model. Diagonal: the 2c-causal triangle
    (= 2c^2 + c pairs); every off-diagonal step: exactly half the 2c x 2c
    block (2c^2), whichever direction the held block came from."""
    diag = 2 * c * (2 * c + 1) // 2
    off = 2 * c * c
    return diag, off


def _zigzag_step(q, k, v, case, scale, interpret):
    """One zigzag ring step: lax.switch over diagonal/past/future shapes.

    Returns (o [B,2c,H,D] f32, lse [B,2c,H]) with -inf lse on rows that
    attend nothing this step (only q chunk 1 on future steps)."""
    B, T2, H, D = q.shape
    c = T2 // 2

    def diag(_):
        o, lse = pk.flash_attention_with_lse(q, k, v, causal=True,
                                             scale=scale, interpret=interpret)
        return o.astype(jnp.float32), lse

    def past(_):
        # all q rows vs the held block's first chunk
        o, lse = pk.flash_attention_with_lse(q, k[:, :c], v[:, :c],
                                             causal=False, scale=scale,
                                             interpret=interpret)
        return o.astype(jnp.float32), lse

    def future(_):
        # only the local second q chunk vs the whole held block; padding
        # rows derive from q so they carry its device-varying type under
        # shard_map
        o2, lse2 = pk.flash_attention_with_lse(q[:, c:], k, v, causal=False,
                                               scale=scale,
                                               interpret=interpret)
        zo = (q[:, :c] * 0).astype(jnp.float32)
        zl = (q[:, :c, :, 0] * 0).astype(jnp.float32) + _NEG
        o = jnp.concatenate([zo, o2.astype(jnp.float32)], axis=1)
        lse = jnp.concatenate([zl, lse2], axis=1)
        return o, lse

    return lax.switch(case, (diag, past, future), None)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, axis_name: str,
                   causal: bool = False, scale: Optional[float] = None,
                   interpret: Optional[bool] = None,
                   zigzag: bool = False) -> jax.Array:
    """Exact attention with KV rotating around the ``axis_name`` ring.

    Call inside shard_map with q/k/v time-sharded: [B, T_local, H, D]. Each of
    the n ring steps runs the Pallas flash kernel on the local Q block against
    the currently-held KV block, then passes KV to the neighbour (ppermute
    over ICI); partials merge exactly via logaddexp.

    ``zigzag`` (causal only): the local block must hold chunks
    (d, 2n-1-d) of the zigzag-reordered sequence (zigzag_order();
    ring_self_attention does the reordering) — every ring step then does
    ~half-block useful work instead of discarding whole future blocks,
    recovering the ~(n-1)/2n of FLOPs the contiguous layout wastes.
    """
    o, _ = _ring_forward(q, k, v, axis_name, causal, scale, interpret, zigzag)
    return o


def _ring_forward(q, k, v, axis_name, causal, scale, interpret, zigzag=False):
    B, T, H, D = q.shape
    scale_v = scale if scale is not None else D ** -0.5
    if interpret is None:
        interpret = not pk._on_tpu()
    if zigzag and not causal:
        raise ValueError("zigzag layout only applies to causal attention")
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)

    # derive accumulator initials from q so the fori_loop carry keeps q's
    # device-varying type under shard_map's varying-axes check
    o = (q * 0).astype(jnp.float32)
    lse = (q[..., 0] * 0).astype(jnp.float32) + _NEG    # [B,T,H]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(i, carry):
        o, lse, k, v = carry
        src = (my - i) % n                   # whose KV block we hold now
        if zigzag:
            case = jnp.where(src == my, 0, jnp.where(src < my, 1, 2))
            o_i, lse_i = _zigzag_step(q, k, v, case, scale_v, interpret)
        else:
            o_i, lse_i = _step_attention(q, k, v, src == my, causal, scale_v,
                                         interpret)
            if causal:
                # blocks strictly in the future contribute nothing
                skip = src > my
                lse_i = jnp.where(skip, _NEG, lse_i)
        o, lse = _merge_partials(o, lse, o_i, lse_i)
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        return o, lse, k, v

    o, lse, k, v = lax.fori_loop(0, n, body, (o, lse, k, v))
    return o.astype(q.dtype), lse


def _ring_fwd(q, k, v, axis_name, causal, scale, interpret, zigzag=False):
    o, lse = _ring_forward(q, k, v, axis_name, causal, scale, interpret,
                           zigzag)
    return o, (q, k, v, o, lse)


def _ring_bwd(axis_name, causal, scale, interpret, zigzag, res, g):
    q, k, v, o, lse = res
    B, T, H, D = q.shape
    c = T // 2
    scale_v = scale if scale is not None else D ** -0.5
    if interpret is None:
        interpret = not pk._on_tpu()
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # loop-invariant across ring steps: compute once, pass into each block
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    def block_grads(k_blk, v_blk, diag):
        """(dq, dk, dv) for the local Q against one KV block, using the
        global lse/delta (flash block gradients factor over key blocks)."""
        if not causal:
            return pk.flash_block_grads(q, k_blk, v_blk, o, lse, g,
                                        causal=False, scale=scale_v,
                                        interpret=interpret, delta=delta)
        return lax.cond(
            diag,
            lambda args: pk.flash_block_grads(q, *args, o, lse, g,
                                              causal=True, scale=scale_v,
                                              interpret=interpret,
                                              delta=delta),
            lambda args: pk.flash_block_grads(q, *args, o, lse, g,
                                              causal=False, scale=scale_v,
                                              interpret=interpret,
                                              delta=delta),
            (k_blk, v_blk))

    def zz_block_grads(k_blk, v_blk, case):
        """Zigzag block gradients — the same three work shapes as
        _zigzag_step, zero-padded to full-block accumulators."""
        f32 = lambda *ts: tuple(t.astype(jnp.float32) for t in ts)

        def diag(_):
            return f32(*pk.flash_block_grads(
                q, k_blk, v_blk, o, lse, g, causal=True, scale=scale_v,
                interpret=interpret, delta=delta))

        def past(_):
            dq, dk1, dv1 = pk.flash_block_grads(
                q, k_blk[:, :c], v_blk[:, :c], o, lse, g, causal=False,
                scale=scale_v, interpret=interpret, delta=delta)
            z = (q[:, :c] * 0).astype(jnp.float32)   # device-varying zeros
            return (dq.astype(jnp.float32),
                    jnp.concatenate([dk1.astype(jnp.float32), z], axis=1),
                    jnp.concatenate([dv1.astype(jnp.float32), z], axis=1))

        def future(_):
            dq2, dk, dv = pk.flash_block_grads(
                q[:, c:], k_blk, v_blk, o[:, c:], lse[:, c:], g[:, c:],
                causal=False, scale=scale_v, interpret=interpret,
                delta=delta[:, c:])
            z = (q[:, :c] * 0).astype(jnp.float32)   # device-varying zeros
            return (jnp.concatenate([z, dq2.astype(jnp.float32)], axis=1),
                    dk.astype(jnp.float32), dv.astype(jnp.float32))

        return lax.switch(case, (diag, past, future), None)

    dq0 = (q * 0).astype(jnp.float32)
    dk0 = (k * 0).astype(jnp.float32)
    dv0 = (v * 0).astype(jnp.float32)

    def body(i, carry):
        dq, k_blk, v_blk, dk, dv = carry
        src = (my - i) % n
        if zigzag:
            case = jnp.where(src == my, 0, jnp.where(src < my, 1, 2))
            dq_i, dk_i, dv_i = zz_block_grads(k_blk, v_blk, case)
        else:
            dq_i, dk_i, dv_i = block_grads(k_blk, v_blk, src == my)
            if causal:
                skip = src > my
                dq_i = jnp.where(skip, 0.0, dq_i.astype(jnp.float32))
                dk_i = jnp.where(skip, 0.0, dk_i.astype(jnp.float32))
                dv_i = jnp.where(skip, 0.0, dv_i.astype(jnp.float32))
        dq = dq + dq_i.astype(jnp.float32)
        dk = dk + dk_i.astype(jnp.float32)
        dv = dv + dv_i.astype(jnp.float32)
        # dk/dv accumulators travel WITH their KV block; after n hops each is
        # back at its home device having collected every device's contribution
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        dk = lax.ppermute(dk, axis_name, perm)
        dv = lax.ppermute(dv, axis_name, perm)
        return dq, k_blk, v_blk, dk, dv

    dq, _, _, dk, dv = lax.fori_loop(0, n, body, (dq0, k, v, dk0, dv0))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


ring_attention.defvjp(_ring_fwd, _ring_bwd)


def ring_self_attention(mesh: Mesh, q, k, v, seq_axis: str = "seq",
                        causal: bool = False,
                        layout: Optional[str] = None):
    """Host-level wrapper: shard_map ring_attention over the mesh's seq axis.

    q/k/v: [B, T_global, H, D] in ORIGINAL sequence order (replicated or
    already seq-sharded on dim 1). ``layout``: "zigzag" (default for
    causal — load-balanced, no discarded future blocks) or "contiguous".
    The zigzag permutation and its inverse are applied here, so callers
    always see original-order tensors.
    """
    if layout is None:
        layout = "zigzag" if causal else "contiguous"
    zigzag = layout == "zigzag" and causal
    spec = P(None, seq_axis, None, None)
    n = mesh.shape[seq_axis]
    T = q.shape[1]
    if zigzag and T % (2 * n):
        # the contiguous causal layout computes-and-discards roughly half the
        # ring's K/V blocks (device i skips blocks from devices > i), so the
        # fallback costs ~2x the balanced zigzag FLOPs — never take it
        # silently
        import warnings
        warnings.warn(
            f"ring_self_attention: T={T} is not divisible by 2*n_shards"
            f"={2 * n}; falling back to the CONTIGUOUS causal layout, which "
            "wastes ~half the attention FLOPs vs zigzag. Pad the sequence "
            f"to a multiple of {2 * n} to keep the load-balanced layout.",
            stacklevel=2)
        zigzag = False                       # shape can't chunk: fall back
    if zigzag:
        order = zigzag_order(T, n)
        q, k, v = (jnp.take(x, order, axis=1) for x in (q, k, v))
    # check_vma=False: pallas_call out_shapes carry no varying-mesh-axes info
    fn = jax.shard_map(
        partial(ring_attention, axis_name=seq_axis, causal=causal,
                zigzag=zigzag),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    o = fn(q, k, v)
    if zigzag:
        o = jnp.take(o, zigzag_inverse(T, n), axis=1)
    return o


def ulysses_attention(mesh: Mesh, q, k, v, seq_axis: str = "seq",
                      causal: bool = False):
    """DeepSpeed-Ulysses-style sequence parallelism: all_to_all re-shards
    time-sharded q/k/v to head-sharded, runs the Pallas flash kernel locally
    over the whole sequence, then all_to_alls back. Complements ring attention
    when heads >= shards: two a2a's instead of n ppermute steps.
    """
    spec = P(None, seq_axis, None, None)

    def local(q, k, v):
        # [B, T/n, H, D] -> a2a -> [B, T, H/n, D]
        q = lax.all_to_all(q, seq_axis, split_axis=2, concat_axis=1, tiled=True)
        k = lax.all_to_all(k, seq_axis, split_axis=2, concat_axis=1, tiled=True)
        v = lax.all_to_all(v, seq_axis, split_axis=2, concat_axis=1, tiled=True)
        o = pk.flash_attention(q, k, v, causal=causal)
        return lax.all_to_all(o, seq_axis, split_axis=1, concat_axis=2, tiled=True)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)


def sharded_flash_attention(mesh: Mesh, q, k, v, *, causal: bool = False,
                            kv_lens=None):
    """The Pallas flash kernel inside a GSPMD-partitioned program.

    A Mosaic kernel cannot be partitioned automatically — on more than one
    real chip jit refuses it ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map"); the CPU mesh never
    shows this because the interpreter lowers to plain HLO. Attention is
    independent per (sample, head), so the wrap needs no communication:
    the batch splits over the mesh's ``data``/``fsdp`` axes and the heads
    over ``tp``/``model`` — each only where it divides — and every shard
    runs the kernel on its local [B/n, T, H/m, D] block. Axes the split
    does not use see replicated operands.
    """
    B, H = q.shape[0], q.shape[2]

    def fit(names, extent):
        axes = []
        for a in names:
            n = mesh.shape.get(a, 1)
            if n > 1 and extent % n == 0:
                axes.append(a)
                extent //= n
        return tuple(axes) or None

    b_axes, h_axes = fit(("data", "fsdp"), B), fit(("tp", "model"), H)
    spec = P(b_axes, None, h_axes, None)
    if kv_lens is None:
        local = partial(pk.flash_attention, causal=causal)
        args, in_specs = (q, k, v), (spec, spec, spec)
    else:
        def local(q, k, v, lens):
            return pk.flash_attention(q, k, v, causal=causal, kv_lens=lens)
        args, in_specs = (q, k, v, kv_lens), (spec, spec, spec, P(b_axes))
    # check_vma=False: pallas_call out_shapes carry no varying-mesh-axes info
    return jax.shard_map(local, mesh=mesh, in_specs=in_specs, out_specs=spec,
                         check_vma=False)(*args)
