"""DataFeeder: row-tuples -> device-ready arrays under the slot-type classification.

The reference's canonical feature types (SURVEY.md §8.2: proto/DataFormat.proto
SlotType; PyDataProvider2.py input_types; LayerGradUtil.h:23-34):
dense / index / sparse-binary / sparse-value, each optionally (nested) sequence.
The converter to engine buffers is DataProviderConverter
(py_paddle/dataprovider_converter.py:247) + DataFeeder (v2/data_feeder.py:112).

TPU-native: the target layout is static-shaped —
* DenseSlot  -> float [B, dim]
* IndexSlot  -> int32 [B]
* SeqSlot    -> SeqBatch (padded [B, T(bucketed), ...] + lengths)  — LoD analog
* SparseSlot -> padded COO per row: (ids [B, K], vals [B, K], mask) with K the
  bucketed max-nnz; embedding-sum consumes it directly (SelectedRows analog).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..core.lod import (NestedSeqBatch, SeqBatch, bucket_length,
                        pack_nested_sequences, pack_sequences)


# -- shape bucketing (Executor feed policy) ------------------------------------

def next_bucket(n: int, buckets: Sequence[int] = ()) -> int:
    """Smallest listed bucket >= n (``buckets`` ascending); beyond the
    largest (or with no list), the next power of two — so an unforeseen
    length still lands in a bounded shape family instead of minting its
    own compile.  Thin alias: :func:`~paddle_tpu.core.lod.bucket_length`
    owns the rounding policy."""
    return bucket_length(n, tuple(buckets), overflow="pow2")


def pad_to_bucket(arr, axis: int, buckets: Sequence[int] = ()):
    """Zero-pad ``arr`` along ``axis`` up to :func:`next_bucket`.

    Returns ``(padded, true_len)`` — the caller feeds the true length
    alongside so masked ops can ignore the tail. Host (numpy) inputs pad on
    the host; device (jax) arrays pad on device (no round-trip).
    """
    if not hasattr(arr, "shape"):
        arr = np.asarray(arr)
    n = int(arr.shape[axis])
    b = next_bucket(n, buckets)
    if b == n:
        return arr, n
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, b - n)
    if isinstance(arr, np.ndarray):
        return np.pad(arr, widths), n
    return jnp.pad(arr, widths), n


class BucketSpec:
    """Per-feed shape-bucketing policy for :class:`~paddle_tpu.fluid.Executor`.

    ``spec`` maps a feed name to its bucket boundaries::

        BucketSpec({"words": (32, 64, 128)})                  # axis inferred
        BucketSpec({"words": {"axis": 2, "buckets": (8, 16)}})  # pinned axis

    A feed axis is padded up to the next listed bucket (falling back to the
    next power of two past the largest), the true length is fed alongside
    as ``<name>@LEN`` (int32 scalar), and the executor's compiled-fn cache
    keys on the *bucketed* shape — a varied-length workload compiles at
    most ``len(buckets) + 1`` times per feed instead of once per distinct
    length. The axis defaults to the feed Variable's declared
    ``bucket_axis``, else its first dynamic (``-1``) non-batch dim, else
    axis 1 (axis 0 for rank-1 feeds).
    """

    def __init__(self, spec: Dict[str, Any]):
        self.spec: Dict[str, Tuple[Optional[int], Tuple[int, ...]]] = {}
        for name, v in dict(spec).items():
            axis: Optional[int] = None
            if isinstance(v, dict):
                axis = v.get("axis")
                buckets = v.get("buckets", ())
            else:
                buckets = v
            self.spec[name] = (axis, tuple(sorted(int(b) for b in buckets)))

    def names(self):
        return self.spec.keys()

    def pinned_axis(self, name: str) -> Optional[int]:
        """The axis the spec pins for ``name`` (None = caller infers)."""
        return self.spec[name][0]

    def pad(self, name: str, arr, default_axis: Optional[int] = None):
        """(padded, true_len) for one feed; see :func:`pad_to_bucket`."""
        axis, buckets = self.spec[name]
        if axis is None:
            axis = (default_axis if default_axis is not None
                    else (1 if getattr(arr, "ndim", 1) >= 2 else 0))
        return pad_to_bucket(arr, axis, buckets)


@dataclass
class DenseSlot:
    dim: int
    dtype: Any = np.float32


@dataclass
class IndexSlot:
    dtype: Any = np.int32


@dataclass
class SeqSlot:
    """A variable-length sequence of scalars (ids) or vectors.

    elem_dim None -> id sequence (int32); else vector sequence [len, elem_dim].
    nested=True accepts list-of-list-of-elem and produces a NestedSeqBatch
    ([B, S, T] + sub/seq lengths — the 2-level-LoD analog).
    """
    elem_dim: Optional[int] = None
    nested: bool = False
    dtype: Any = None

    @property
    def np_dtype(self):
        if self.dtype is not None:
            return self.dtype
        return np.int32 if self.elem_dim is None else np.float32


@dataclass
class SparseSlot:
    """Sparse row features: sample = list of ids or list of (id, value)."""
    dim: int
    with_values: bool = False


class DataFeeder:
    """feed(rows) -> tuple of arrays, one per slot.

    rows: list of sample tuples, sample[i] belongs to slots[i].
    """

    def __init__(self, slots: Sequence[Any]):
        self.slots = list(slots)

    def __call__(self, rows: Sequence[Tuple]) -> Tuple:
        return self.feed(rows)

    def feed(self, rows: Sequence[Tuple]) -> Tuple:
        cols = list(zip(*rows))
        if len(cols) != len(self.slots):
            raise ValueError(f"sample width {len(cols)} != #slots {len(self.slots)}")
        return tuple(self._convert(slot, col) for slot, col in zip(self.slots, cols))

    # ------------------------------------------------------------------
    def _convert(self, slot, col):
        if isinstance(slot, DenseSlot):
            arr = np.asarray(col, dtype=slot.dtype).reshape(len(col), slot.dim)
            return jnp.asarray(arr)
        if isinstance(slot, IndexSlot):
            return jnp.asarray(np.asarray(col, dtype=slot.dtype).reshape(len(col)))
        if isinstance(slot, SeqSlot):
            return self._convert_seq(slot, col)
        if isinstance(slot, SparseSlot):
            return self._convert_sparse(slot, col)
        raise TypeError(f"unknown slot {slot!r}")

    def _convert_seq(self, slot: SeqSlot, col):
        if slot.nested:
            # 2-level LoD: padded [B, S, T] + per-subseq and per-seq lengths
            # (subSequenceStartPositions analog, Argument.h:84-90)
            nested = [[np.asarray(sub, dtype=slot.np_dtype) for sub in sample]
                      for sample in col]
            return pack_nested_sequences(nested)
        seqs = [np.asarray(s, dtype=slot.np_dtype) for s in col]
        return pack_sequences(seqs)

    def _convert_sparse(self, slot: SparseSlot, col):
        if slot.with_values:
            ids_list = [[int(i) for i, _ in s] for s in col]
            val_list = [[float(v) for _, v in s] for s in col]
        else:
            ids_list = [[int(i) for i in s] for s in col]
            val_list = [[1.0] * len(s) for s in col]
        k = bucket_length(max(1, max((len(s) for s in ids_list), default=1)),
                          buckets=(4, 8, 16, 32, 64, 128, 256))
        B = len(col)
        ids = np.zeros((B, k), np.int32)
        vals = np.zeros((B, k), np.float32)
        for r, (ii, vv) in enumerate(zip(ids_list, val_list)):
            n = min(len(ii), k)
            ids[r, :n] = ii[:n]
            vals[r, :n] = vv[:n]
        return jnp.asarray(ids), jnp.asarray(vals)


def to_lod_batch(seqs, max_len: Optional[int] = None) -> SeqBatch:
    """Convenience: list of sequences -> SeqBatch (bucketed padding)."""
    return pack_sequences([np.asarray(s) for s in seqs], max_len=max_len)
