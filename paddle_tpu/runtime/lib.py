"""Load (building if needed) the native host-runtime library.

The library is git-ignored and built on first use from ``native/*.cc``.
First use is concurrent by nature — six xdist workers importing their
test files, a launcher's children — so the build runs ONCE, under a file
lock, and the Makefile publishes each ``.so`` by atomic rename: a process
either sees no library and waits for the lock, or sees a whole one.
(Unlocked, concurrent ``make -j4`` runs rewrote each other's ``.o`` files
mid-link and the losers silently got "no library".) The library is
REQUIRED by everything that loads it, so a failed build or load raises
:class:`NativeLibraryError` carrying the compiler's output — never None.
"""

from __future__ import annotations

import ctypes
import fcntl
import os
import subprocess
import threading
from typing import Optional

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_error: Optional["NativeLibraryError"] = None

#: signature of the unknown-op fallback (master_server.cc ptms_set_fallback):
#: (request bytes, length, opaque reply handle) -> None; the callback
#: answers via ptms_reply(handle, data, len) before returning. Callers must
#: keep the CFUNCTYPE instance alive while the server runs.
PTMS_FALLBACK_FN = ctypes.CFUNCTYPE(None, ctypes.POINTER(ctypes.c_char),
                                    ctypes.c_int, ctypes.c_void_p)

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")
_SO = os.path.join(_NATIVE_DIR, "libpaddle_tpu_host.so")
# wheel installs ship the .so inside the package (setup.py copies it here;
# the repo-relative path above covers source checkouts)
_PKG_SO = os.path.join(os.path.dirname(os.path.dirname(__file__)), "_native",
                       "libpaddle_tpu_host.so")

#: a cold build takes ~15 s idle, a minute on a loaded host; the limit only
#: bounds a hung toolchain
_BUILD_TIMEOUT_S = 600


class NativeLibraryError(RuntimeError):
    """libpaddle_tpu_host.so could not be built or loaded; the message
    carries the toolchain's own output."""


def load_library() -> ctypes.CDLL:
    """The native library, built first if a source is newer than it.
    Raises :class:`NativeLibraryError` (once computed, re-raised on every
    later call — the build is not retried within a process)."""
    global _lib, _error
    with _lock:
        if _error is not None:
            raise _error
        if _lib is None:
            try:
                in_checkout = os.path.isdir(_NATIVE_DIR)
                if in_checkout and _needs_build():
                    _build()
                lib = ctypes.CDLL(_SO if in_checkout else _PKG_SO)
            except (OSError, subprocess.SubprocessError) as e:
                _error = NativeLibraryError(
                    f"native host runtime unavailable: {e}")
                raise _error from e
            except NativeLibraryError as e:
                _error = e
                raise
            _configure(lib)
            _lib = lib
        return _lib


def _build() -> None:
    """``make -C native`` under an exclusive file lock: one build at a
    time across processes, and a process that waited re-checks before
    building again."""
    with open(os.path.join(_NATIVE_DIR, ".build.lock"), "w") as lk:
        fcntl.flock(lk, fcntl.LOCK_EX)
        if not _needs_build():
            return
        r = subprocess.run(["make", "-C", _NATIVE_DIR, "-j4"],
                           capture_output=True, text=True,
                           timeout=_BUILD_TIMEOUT_S)
        if r.returncode != 0:
            raise NativeLibraryError(
                f"make -C native failed (exit {r.returncode}):\n"
                f"{r.stdout[-2000:]}{r.stderr[-4000:]}")


def _needs_build() -> bool:
    """Rebuild when any source is newer than the .so — a stale binary with an
    old C ABI would be silently called with the new signature otherwise."""
    if not os.path.exists(_SO):
        return True
    so_mtime = os.path.getmtime(_SO)
    return any(os.path.getmtime(os.path.join(_NATIVE_DIR, n)) > so_mtime
               for n in os.listdir(_NATIVE_DIR)
               if n.endswith((".cc", ".h")) or n == "Makefile")


def _configure(lib: ctypes.CDLL):
    c = ctypes
    # task master
    lib.ptm_create.restype = c.c_void_p
    lib.ptm_create.argtypes = [c.c_double, c.c_int]
    lib.ptm_destroy.argtypes = [c.c_void_p]
    lib.ptm_set_dataset.argtypes = [c.c_void_p, c.POINTER(c.c_char_p), c.c_int]
    lib.ptm_get_task.restype = c.c_int
    lib.ptm_get_task.argtypes = [c.c_void_p, c.c_double, c.c_char_p, c.c_int,
                                 c.POINTER(c.c_int)]
    lib.ptm_task_finished.argtypes = [c.c_void_p, c.c_int]
    lib.ptm_new_pass.restype = c.c_int
    lib.ptm_new_pass.argtypes = [c.c_void_p]
    lib.ptm_task_failed.argtypes = [c.c_void_p, c.c_int]
    lib.ptm_tick.restype = c.c_int
    lib.ptm_tick.argtypes = [c.c_void_p, c.c_double]
    lib.ptm_stats.argtypes = [c.c_void_p] + [c.POINTER(c.c_int)] * 5
    lib.ptm_snapshot.restype = c.c_int
    lib.ptm_snapshot.argtypes = [c.c_void_p, c.c_char_p]
    lib.ptm_restore.restype = c.c_int
    lib.ptm_restore.argtypes = [c.c_void_p, c.c_char_p]
    # master RPC server (master_server.cc — ProtoServer-analog data plane)
    lib.ptms_start.restype = c.c_void_p
    lib.ptms_start.argtypes = [c.c_void_p, c.c_char_p, c.c_int,
                               c.POINTER(c.c_int)]
    lib.ptms_port.restype = c.c_int
    lib.ptms_port.argtypes = [c.c_void_p]
    if hasattr(lib, "ptms_active_conns"):   # absent in a stale packaged .so
        lib.ptms_active_conns.restype = c.c_int
        lib.ptms_active_conns.argtypes = [c.c_void_p]
    lib.ptms_set_fenced.argtypes = [c.c_void_p, c.c_int]
    lib.ptms_set_fallback.argtypes = [c.c_void_p, PTMS_FALLBACK_FN]
    lib.ptms_reply.argtypes = [c.c_void_p, c.POINTER(c.c_char), c.c_int]
    lib.ptms_stop.argtypes = [c.c_void_p]
    # recordio
    lib.ptr_writer_open.restype = c.c_void_p
    lib.ptr_writer_open.argtypes = [c.c_char_p]
    lib.ptr_writer_write.restype = c.c_int
    lib.ptr_writer_write.argtypes = [c.c_void_p, c.c_char_p, c.c_int]
    lib.ptr_writer_close.restype = c.c_int64
    lib.ptr_writer_close.argtypes = [c.c_void_p]
    lib.ptr_reader_open.restype = c.c_void_p
    lib.ptr_reader_open.argtypes = [c.c_char_p]
    lib.ptr_reader_next.restype = c.c_int
    lib.ptr_reader_next.argtypes = [c.c_void_p, c.c_char_p, c.c_int]
    lib.ptr_reader_close.argtypes = [c.c_void_p]
    # arena
    lib.pta_create.restype = c.c_void_p
    lib.pta_create.argtypes = [c.c_uint64, c.c_uint64]
    lib.pta_destroy.argtypes = [c.c_void_p]
    lib.pta_alloc.restype = c.c_uint64
    lib.pta_alloc.argtypes = [c.c_void_p, c.c_uint64]
    lib.pta_free.restype = c.c_int
    lib.pta_free.argtypes = [c.c_void_p, c.c_uint64]
    lib.pta_stats.argtypes = [c.c_void_p] + [c.POINTER(c.c_uint64)] * 3
