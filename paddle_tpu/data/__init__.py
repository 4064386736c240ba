"""Data layer: readers, decorators, datasets, feeder, prefetch.

Re-provides the reference's data stack (SURVEY.md §2.4):
* reader protocol + decorators  (python/paddle/v2/reader/decorator.py:26-233)
* ``batch``                     (python/paddle/v2/minibatch.py)
* dataset zoo                   (python/paddle/v2/dataset/*) — synthetic generators
  here (no network egress); same shapes/vocab semantics as the originals.
* DataFeeder                    (python/paddle/v2/data_feeder.py + py_paddle
  DataProviderConverter) — converts row batches into device-ready arrays under the
  feature-type classification of SURVEY §8.2 (dense / index / sparse / sequence).
* DoubleBuffer prefetch         (gserver/dataproviders/DataProvider.h:249) — a
  background-thread pipeline overlapping host batch prep with device steps.
"""

from .reader import (map_readers, shuffle, chain, compose, buffered, firstn,
                     xmap_readers, cache, batch, mix)
from .feeder import (DataFeeder, DenseSlot, IndexSlot, SeqSlot, SparseSlot,
                     to_lod_batch)
from .prefetch import DoubleBuffer
from . import dataset, format, parsers
from .provider import CacheType, provider

__all__ = ["parsers", "provider", "CacheType", "map_readers", "shuffle", "chain", "compose", "buffered", "firstn",
           "xmap_readers", "cache", "batch", "mix",
           "DataFeeder", "DenseSlot", "IndexSlot", "SeqSlot", "SparseSlot",
           "to_lod_batch", "DoubleBuffer", "dataset", "format"]
