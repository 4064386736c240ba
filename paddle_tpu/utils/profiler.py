"""Device profiling — the hl_profiler_start/hl_profiler_stop analog.

Reference: `hl_profiler_start/end` wrap cudaProfilerStart/Stop
(cuda/src/hl_cuda_device.cc:675-677, WITH_PROFILER gate; exercised by
math/tests/test_GpuProfiler.cpp with nvprof markers). TPU-native: the jax/XLA
profiler — traces carry XLA op timelines, HBM usage, and host annotations,
viewable in TensorBoard/xprof/Perfetto.

* :func:`start` / :func:`stop` — begin/end a trace into a log dir.
* :func:`profile` — context manager form.
* :func:`annotate` — named host-span annotation appearing on the trace
  (the REGISTER_TIMER_INFO marker analog); StatSet timers also annotate
  when a trace is active.
"""

from __future__ import annotations

import glob
import os
from contextlib import contextmanager
from typing import Optional

import jax

_active_dir: Optional[str] = None


def start(logdir: str):
    """Begin an XLA trace (cudaProfilerStart analog)."""
    global _active_dir
    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    _active_dir = logdir


def stop() -> Optional[str]:
    """End the trace; returns the logdir (traces land under
    plugins/profile/<ts>/ as .xplane.pb)."""
    global _active_dir
    jax.profiler.stop_trace()
    d, _active_dir = _active_dir, None
    return d


def is_active() -> bool:
    return _active_dir is not None


@contextmanager
def profile(logdir: str):
    start(logdir)
    try:
        yield logdir
    finally:
        stop()


def annotate(name: str):
    """Named span on the device trace (TraceAnnotation) — pairs with the
    scoped StatSet timers the way REGISTER_TIMER_INFO named GPU ranges."""
    return jax.profiler.TraceAnnotation(name)


def trace_files(logdir: str):
    """The .xplane.pb artifacts produced under ``logdir``."""
    return sorted(glob.glob(os.path.join(logdir, "plugins", "profile",
                                         "*", "*.xplane.pb")))


def device_memory_stats(device=None) -> dict:
    """Live HBM statistics for a device (the memory/ observability the
    reference exposed through its allocator counters): bytes_in_use,
    peak_bytes_in_use, bytes_limit where the backend reports them."""
    if device is None:
        device = jax.devices()[0]
    stats = getattr(device, "memory_stats", lambda: None)()
    return dict(stats) if stats else {}


def save_device_memory_profile(path: str, backend: Optional[str] = None):
    """Dump a pprof-format device memory profile (jax.profiler
    .save_device_memory_profile) — who holds HBM right now.

    ``backend`` names the platform to profile (default: the default
    backend)."""
    jax.profiler.save_device_memory_profile(path, backend=backend)
    return path
