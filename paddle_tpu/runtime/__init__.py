"""Native host runtime bindings (ctypes over native/libpaddle_tpu_host.so).

The C++ components the TPU build re-provides natively (SURVEY.md §2 'every
C++/CUDA/Go row'):
* :mod:`master`   — task-queue data master (go/master/service.go semantics)
* :mod:`recordio` — CRC-checked chunked record files (recordio / DataFormat)
* :mod:`arena`    — host buddy allocator (paddle/memory BuddyAllocator)

The library builds from source on first use (``make -C native`` under a file
lock, see :mod:`lib`), mirroring how the reference builds vendored externals
at configure time; a failed build raises :class:`NativeLibraryError` with the
compiler's output.
"""

from .lib import NativeLibraryError, load_library
from .master import TaskMaster
from .recordio import RecordReader, RecordWriter
from .arena import HostArena
from .optimizer import HostOptimizer
from .lease import FileLease, LeaseKeeper
from .coord import CoordServer, NetworkFencedStore, NetworkLease
from .master_service import StaleMemberError
from .membership import (HeartbeatKeeper, MembershipClient,
                         MembershipService, autoscale_recommendation)
from .host_embedding import (HostEmbedBatch, HostEmbeddingTable,
                             HostEmbedPrefetcher)

__all__ = ["load_library", "NativeLibraryError", "TaskMaster",
           "FileLease", "LeaseKeeper",
           "CoordServer", "NetworkLease", "NetworkFencedStore",
           "MembershipService", "MembershipClient", "HeartbeatKeeper",
           "StaleMemberError", "autoscale_recommendation",
           "HostEmbeddingTable", "HostEmbedBatch", "HostEmbedPrefetcher",
           "RecordReader", "RecordWriter", "HostArena", "HostOptimizer"]
