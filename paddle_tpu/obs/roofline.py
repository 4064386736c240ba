"""The device cost ledger — chip utilization as a first-class obs signal.

Every ``mfu`` / ``hbm_bw_util`` figure the bench harness ever printed was
an offline artifact: ``benchmarks/mfu.py`` cost-analyzed a step in a side
script and the serving rows hand-modeled their bytes. This module is the
ONE resolution path both the bench rows and the live gauges go through,
so the two can never disagree on methodology:

* **Peak tables** — dense-peak TFLOP/s *and* HBM GB/s per jax
  ``device_kind`` (the HBM table is new; the TFLOP table is shared with
  ``benchmarks/mfu.py``, which now delegates here). ``None`` peaks (CPU,
  unknown chips) make every derived utilization an honest null, never a
  fabricated number. Override with ``PADDLE_TPU_PEAK_TFLOPS`` /
  ``PADDLE_TPU_PEAK_HBM_GBPS``.
* **Per-executable costs** — :func:`compiled_cost` reads
  ``compiled.cost_analysis()`` (FLOPs, bytes accessed) and
  ``memory_analysis()`` (peak temp/argument HBM) off an AOT-compiled
  executable; :class:`CostInstrumentedJit` wraps a ``jax.jit`` callable
  so its first call per argument signature lowers + compiles AOT,
  records the :class:`Cost`, and every call *accounts* it.
* **Kernel cost models** — Pallas custom calls report ZERO FLOPs/bytes
  to XLA, so the routes that dispatch them (:func:`register_kernel_cost`
  / :func:`kernel_cost`) contribute their modeled bytes instead:
  ``ops/pallas_kernels.py`` registers ``decode_attention`` /
  ``paged_decode_attention``; the model/serving call sites and
  ``benchmarks/serving_decode.py`` resolve through the same entry.
* **Accounting** — :func:`account` accumulates
  ``fluid.device_flops_total`` / ``fluid.device_bytes_total`` on the
  installed session and derives the live ``roofline.mfu`` /
  ``roofline.hbm_bw_util`` gauges from the counter deltas over a short
  window — visible in ``paddle_tpu obs serve`` and the cluster
  aggregator exactly like any other series.

Failure is loud but bounded: a broken cost analysis warns ONCE per
process, counts ``roofline.cost_analysis_failures_total``, and resolves
to ``None`` — an honest unknown, not a quiet null
(docs/design/observability.md "Device timelines & roofline").
"""

from __future__ import annotations

import os
import threading
import time
import warnings
import weakref
from typing import Any, Callable, Dict, Optional, Tuple

# -- peak tables (the roofline's two ceilings) ---------------------------------

#: dense bf16 peak TFLOP/s by jax device_kind (f32 shares the MXU peak via
#: XLA's 3-pass bf16 decomposition; the convention is noted in bench JSON)
PEAK_TFLOPS: Dict[str, Optional[float]] = {
    "TPU v5 lite": 197.0,       # v5e
    "TPU v5e": 197.0,
    "TPU v5": 459.0,            # v5p
    "TPU v4": 275.0,
    "TPU v6 lite": 918.0,       # v6e / Trillium
    "cpu": None,
}

#: HBM bandwidth GB/s by device_kind — the table benchmarks/serving_decode
#: hard-coded as a module constant before this existed
PEAK_HBM_GBPS: Dict[str, Optional[float]] = {
    "TPU v5 lite": 819.0,       # v5e
    "TPU v5e": 819.0,
    "TPU v5": 2765.0,           # v5p
    "TPU v4": 1228.0,
    "TPU v6 lite": 1640.0,      # v6e / Trillium
    "cpu": None,
}


def _device_kind() -> str:
    """The default device's kind as JAX reports it ("cpu" on the CPU
    backend, by JAX's own naming). A backend that cannot start raises: a
    failure never reads as "cpu" and with it as "no peak known"."""
    import jax
    return jax.devices()[0].device_kind


_warned_env_vars: set = set()


def _env_peak(var: str) -> Optional[float]:
    """``float(os.environ[var])`` with a malformed value demoted to a
    once-per-process warning and a fall-through to the device table —
    these run inside ``account()`` on the dispatch hot path, and
    telemetry must never destroy a successful run."""
    env = os.environ.get(var)
    if not env:
        return None
    try:
        return float(env)
    except ValueError:
        with _warn_lock:
            if var in _warned_env_vars:
                return None
            _warned_env_vars.add(var)
        warnings.warn(
            f"ignoring malformed {var}={env!r} (expected a number); peak "
            "resolves from the built-in device table instead",
            RuntimeWarning, stacklevel=3)
        return None


def peak_flops_per_sec() -> Optional[float]:
    """Chip dense peak in FLOP/s, or None when unknown (derived MFU is
    then omitted/null)."""
    env = _env_peak("PADDLE_TPU_PEAK_TFLOPS")
    if env is not None:
        return env * 1e12
    tf = PEAK_TFLOPS.get(_device_kind())
    return None if tf is None else tf * 1e12


def peak_hbm_bytes_per_sec() -> Optional[float]:
    """Chip HBM bandwidth in bytes/s, or None when unknown."""
    env = _env_peak("PADDLE_TPU_PEAK_HBM_GBPS")
    if env is not None:
        return env * 1e9
    gb = PEAK_HBM_GBPS.get(_device_kind())
    return None if gb is None else gb * 1e9


# -- failure path (shared with benchmarks/mfu.py) ------------------------------

_warned_cost_failure = False
_warn_lock = threading.Lock()


def cost_failure(where: str, exc: Optional[BaseException] = None) -> None:
    """A cost analysis failed: count it and warn ONCE per process — the
    old ``benchmarks/mfu.step_flops`` swallowed every exception into a
    silent None, and a broken methodology read as a legit unknown."""
    from . import count
    count("roofline.cost_analysis_failures_total")
    global _warned_cost_failure
    with _warn_lock:
        if _warned_cost_failure:
            return
        _warned_cost_failure = True
    detail = f": {type(exc).__name__}: {exc}" if exc is not None else ""
    warnings.warn(
        f"XLA cost analysis failed at {where}{detail} — derived "
        "FLOPs/bytes resolve to null for this executable (counted in "
        "roofline.cost_analysis_failures_total; further failures this "
        "process are counted silently)",
        RuntimeWarning, stacklevel=3)


# -- the per-executable cost record --------------------------------------------

class Cost:
    """FLOPs + HBM bytes of ONE dispatch of a compiled executable (plus
    its compile-time peak-memory estimate). ``None`` fields mean the
    analysis could not resolve them — honest unknowns."""

    __slots__ = ("flops", "bytes", "peak_hbm_bytes")

    def __init__(self, flops: Optional[float] = None,
                 bytes: Optional[float] = None,
                 peak_hbm_bytes: Optional[int] = None):
        self.flops = flops
        self.bytes = bytes
        self.peak_hbm_bytes = peak_hbm_bytes

    def __repr__(self):
        return (f"Cost(flops={self.flops}, bytes={self.bytes}, "
                f"peak_hbm_bytes={self.peak_hbm_bytes})")


def compiled_cost(compiled, where: str = "compiled") -> Optional[Cost]:
    """Resolve one executable's :class:`Cost` from XLA's own analyses.

    ``cost_analysis()`` yields ``flops`` and ``bytes accessed``;
    ``memory_analysis()`` the argument/output/temp footprint whose sum
    approximates peak HBM for the dispatch. Pallas custom calls report
    zero to both — callers whose executables route through hand kernels
    add the registered :func:`kernel_cost` on top (see
    :class:`CostInstrumentedJit`'s ``extra_bytes``)."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = float(ca.get("flops", 0.0)) if hasattr(ca, "get") else 0.0
        nbytes = (float(ca.get("bytes accessed", 0.0))
                  if hasattr(ca, "get") else 0.0)
    except Exception as e:
        cost_failure(where, e)
        return None
    peak = None
    try:
        ma = compiled.memory_analysis()
        peak = int(getattr(ma, "argument_size_in_bytes", 0)
                   + getattr(ma, "output_size_in_bytes", 0)
                   + getattr(ma, "temp_size_in_bytes", 0)
                   - getattr(ma, "alias_size_in_bytes", 0))
    except Exception:
        pass          # memory_analysis is optional on some backends
    return Cost(flops=flops if flops > 0 else None,
                bytes=nbytes if nbytes > 0 else None,
                peak_hbm_bytes=peak)


def analyze_fn(fn, *args, where: str = "analyze_fn",
               **kwargs) -> Optional[Cost]:
    """Lower + compile ``fn(*args)`` and resolve its :class:`Cost` — the
    shared resolution path behind ``benchmarks/mfu.step_flops`` and the
    executor's ledger."""
    import jax
    try:
        jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
        compiled = jitted.lower(*args, **kwargs).compile()
    except Exception as e:
        cost_failure(where, e)
        return None
    return compiled_cost(compiled, where)


# -- kernel cost models (the Pallas zero-FLOP override) ------------------------

#: kernel name -> callable(**meta) -> modeled HBM bytes per dispatch
_KERNEL_COSTS: Dict[str, Callable[..., float]] = {}


def register_kernel_cost(kernel: str, fn: Callable[..., float]) -> None:
    """Register the modeled HBM bytes of one dispatch of a hand kernel.

    Pallas custom calls are opaque to XLA's cost analysis (zero FLOPs,
    zero bytes); the kernel's own module registers an analytic bytes
    model here at import, and every consumer — live accounting, bench
    rows, the profile CLI — resolves through :func:`kernel_cost`, so the
    modeled number has exactly one owner."""
    _KERNEL_COSTS[kernel] = fn


def kernel_cost(kernel: str, **meta) -> Optional[float]:
    """Modeled HBM bytes for one dispatch of ``kernel`` under ``meta``
    (shape/dtype facts the call site knows); None when no model is
    registered."""
    fn = _KERNEL_COSTS.get(kernel)
    if fn is None:
        return None
    return float(fn(**meta))


def registered_kernels() -> Tuple[str, ...]:
    return tuple(sorted(_KERNEL_COSTS))


# -- trace-time kernel-byte collection -----------------------------------------
# A Pallas launch site runs ONCE per trace, but the compiled executable
# dispatches many times. The executor / instrumented-jit wraps its trace
# in collect_kernel_bytes(); launch sites call note_kernel_bytes(), the
# collector absorbs the modeled bytes, and the owner re-emits them PER
# DISPATCH (kernels.bytes_total + the account() extra) — so the counter
# keeps one semantic everywhere. Outside any collector (eager execution)
# the site counts directly: one call == one dispatch there.

_trace_collect = threading.local()


class collect_kernel_bytes:
    """Context manager around one trace/lower: collects the kernel bytes
    recorded by launch sites inside. ``per_kernel`` (kernel -> bytes of
    one dispatch) is set at exit."""

    def __init__(self):
        self.per_kernel: Dict[str, float] = {}

    def __enter__(self):
        stack = getattr(_trace_collect, "stack", None)
        if stack is None:
            stack = _trace_collect.stack = []
        stack.append({})
        return self

    def __exit__(self, *exc):
        self.per_kernel = _trace_collect.stack.pop()
        return False


def record_kernel_bytes(kernel: str, nbytes: Optional[float]) -> bool:
    """Record one launch's modeled bytes with the innermost collector.
    Returns False when no collector is active (the caller is executing
    eagerly and owns its own counting)."""
    stack = getattr(_trace_collect, "stack", None)
    if not stack:
        return False
    if nbytes:
        d = stack[-1]
        d[kernel] = d.get(kernel, 0.0) + float(nbytes)
    return True


def note_kernel_bytes(kernel: str, nbytes: Optional[float]) -> None:
    """What a kernel launch site calls with one dispatch's modeled bytes:
    under a trace collector they are absorbed (re-emitted per dispatch by
    the owner); eagerly they count straight into ``kernels.bytes_total``.

    Boundary: a launch traced under a plain user-owned ``jax.jit`` (no
    Executor/:func:`instrument` owner, no collector) counts its trace
    exactly once, so N compiled dispatches contribute one increment —
    wrap such callables in :func:`instrument` to get per-dispatch
    re-emission."""
    if record_kernel_bytes(kernel, nbytes):
        return
    if nbytes:
        from . import count
        count("kernels.bytes_total", nbytes, kernel=kernel)


def emit_kernel_bytes(kb: Optional[Dict[str, float]]) -> float:
    """Re-emit one dispatch's collected kernel bytes into
    ``kernels.bytes_total`` and return their sum (the ``account()``
    extra) — the ONE owner of the per-dispatch re-emission both the
    fluid Executor and :class:`CostInstrumentedJit` call."""
    if not kb:
        return 0.0
    from . import count
    extra = 0.0
    for k, v in kb.items():
        if v:
            extra += v
            count("kernels.bytes_total", v, kernel=k)
    return extra


# -- accounting + derived gauges -----------------------------------------------

#: minimum window between derived-gauge recomputes (seconds): utilization
#: over sub-millisecond deltas is noise
_GAUGE_WINDOW_S = 0.25


class _Deriver:
    """Per-registry derivation state: turns counter deltas into the live
    roofline gauges."""

    __slots__ = ("t0", "flops0", "bytes0")

    def __init__(self, now: float):
        self.t0 = now
        self.flops0 = 0.0
        self.bytes0 = 0.0


# weak-keyed on the registry object: a gc'd registry drops its derivation
# state with it (an id()-keyed dict would leak an entry per registry AND
# let a recycled id inherit a dead registry's t0/counter baselines)
_derivers: "weakref.WeakKeyDictionary[Any, _Deriver]" = \
    weakref.WeakKeyDictionary()
_derive_lock = threading.Lock()


def account(cost: Optional[Cost], extra_bytes: float = 0.0,
            registry=None, now: Optional[float] = None) -> None:
    """Accumulate one dispatch's cost into the device counters and
    refresh the derived roofline gauges.

    No-op without an installed session (the obs zero-cost discipline).
    ``extra_bytes`` carries kernel-modeled bytes the executable's own
    analysis cannot see (see :func:`kernel_cost`)."""
    from . import session
    s = session()
    if s is None and registry is None:
        return
    reg = registry if registry is not None else s.registry
    flops = (cost.flops or 0.0) if cost is not None else 0.0
    nbytes = ((cost.bytes or 0.0) if cost is not None else 0.0) + extra_bytes
    if flops:
        reg.counter("fluid.device_flops_total").inc(flops)
    if nbytes:
        reg.counter("fluid.device_bytes_total").inc(nbytes)
    derive_gauges(reg, now=now)


def derive_gauges(registry, now: Optional[float] = None,
                  min_window: float = _GAUGE_WINDOW_S) -> None:
    """Set ``roofline.mfu`` / ``roofline.hbm_bw_util`` from the device
    counters' deltas since the last derivation (rate-limited). Peaks
    unknown (off-TPU, no env override) -> the gauge is never set: a
    dashboard reads absence, not a made-up zero."""
    if now is None:
        now = time.monotonic()
    d = _derivers.get(registry)
    if d is not None and now - d.t0 < min_window:
        return          # steady-state fast path: no global lock per token
    with _derive_lock:
        d = _derivers.get(registry)
        if d is None:
            _derivers[registry] = d = _Deriver(now)
            d.flops0 = registry.counter("fluid.device_flops_total").get()
            d.bytes0 = registry.counter("fluid.device_bytes_total").get()
            return
        dt = now - d.t0
        if dt < min_window:
            return
        flops = registry.counter("fluid.device_flops_total").get()
        nbytes = registry.counter("fluid.device_bytes_total").get()
        dflops, dbytes = flops - d.flops0, nbytes - d.bytes0
        d.t0, d.flops0, d.bytes0 = now, flops, nbytes
    # >1.0 is physically impossible — a collapsed window or an
    # over-counting byte model. attach_mfu/attach_hbm_bw null the bench
    # column in that case; the gauge twin SKIPS the set (the last honest
    # reading stands) rather than fabricate a saturated chip.
    peak_f = peak_flops_per_sec()
    if peak_f and dflops >= 0:
        mfu = dflops / dt / peak_f
        if mfu <= 1.0:
            registry.gauge("roofline.mfu").set(mfu)
    peak_b = peak_hbm_bytes_per_sec()
    if peak_b and dbytes >= 0:
        util = dbytes / dt / peak_b
        if util <= 1.0:
            registry.gauge("roofline.hbm_bw_util").set(util)


def _reset_derivers() -> None:
    """Test hook: forget derivation state between injected registries."""
    with _derive_lock:
        _derivers.clear()


# -- the instrumented-jit wrapper ----------------------------------------------

def _signature(args, kwargs=None) -> Tuple:
    """Hashable aval signature of a pytree of arrays (shape/dtype per
    leaf) — the wrapper's AOT entries are keyed on it exactly like jit's
    internal cache, so shape-polymorphic callers (a trailing partial
    batch) compile one AOT executable per shape family. Keyword
    arguments ride the same tree (dicts are pytrees), so wrapped
    callables keep jit's full calling convention."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs or {}))
    # dtype objects hash/compare directly — no per-leaf str() on a path
    # a decode loop hits every token
    return (treedef,
            tuple((getattr(x, "shape", ()), getattr(x, "dtype", type(x)))
                  for x in leaves))


class CostInstrumentedJit:
    """Wrap a ``jax.jit`` callable so the cost ledger sees every dispatch.

    First call per argument signature AOT-compiles
    (``jitted.lower(...).compile()``) — paying the compile ONCE, exactly
    where jit would — records the executable's :class:`Cost` in
    :attr:`ledger`, and executes through the compiled object from then
    on. A signature that warmed up on the plain jit path while the
    plane was OFF re-pays one compile at its first plane-on call (jit's
    internal executable is unreachable for cost analysis; the
    persistent XLA compile cache makes it a deserialize). Any lowering/compile/argument mismatch falls back to the plain
    jitted callable for that signature (counted via
    :func:`cost_failure`), so instrumentation can never break a step.

    ``extra_bytes`` (optional ``fn(*args) -> float``) models the HBM
    bytes of hand kernels inside the executable (zero to XLA's own
    analysis); it is resolved per call and added at accounting time.
    """

    def __init__(self, jitted, label: str,
                 extra_bytes: Optional[Callable[..., float]] = None):
        self._jitted = jitted
        self._label = label
        self._extra_bytes = extra_bytes
        #: signature -> (callable, Cost|None); public for the ledger tests
        self.ledger: Dict[Tuple, Tuple[Any, Optional[Cost]]] = {}
        #: signature -> {kernel: bytes/dispatch} collected at trace time
        #: from note_kernel_bytes sites inside the traced function
        self.kernel_bytes: Dict[Tuple, Dict[str, float]] = {}
        self._lock = threading.Lock()

    def cost_of(self, *args, **kwargs) -> Optional[Cost]:
        entry = self.ledger.get(_signature(args, kwargs))
        return entry[1] if entry is not None else None

    def _entry(self, args, kwargs):
        key = _signature(args, kwargs)
        entry = self.ledger.get(key)
        if entry is not None:
            return entry[0], entry[1], self.kernel_bytes.get(key)
        with self._lock:
            entry = self.ledger.get(key)
            if entry is not None:
                return entry[0], entry[1], self.kernel_bytes.get(key)
            try:
                with collect_kernel_bytes() as col:
                    lowered = self._jitted.lower(*args, **kwargs)
                if col.per_kernel:
                    self.kernel_bytes[key] = col.per_kernel
                compiled = lowered.compile()
                entry = (compiled, compiled_cost(compiled, self._label))
            except Exception as e:
                cost_failure(self._label, e)
                entry = (self._jitted, None)
            self.ledger[key] = entry
            return entry[0], entry[1], self.kernel_bytes.get(key)

    def __call__(self, *args, **kwargs):
        from . import is_active
        active = is_active()
        if not active:
            # plane off: reuse an executable the ledger already holds, but
            # NEVER pay a new signature's AOT compile while off (the
            # zero-cost discipline _CompiledEntry enforces the same way)
            entry = (self.ledger.get(_signature(args, kwargs))
                     if self.ledger else None)
            if entry is None:
                return self._jitted(*args, **kwargs)
            call, cost = entry
            kb = None
        else:
            call, cost, kb = self._entry(args, kwargs)
        try:
            out = call(*args, **kwargs)
        except TypeError as e:
            if call is self._jitted:
                raise
            # AOT argument strictness (weak types, committed devices) the
            # signature key cannot see: fall back to jit for this
            # signature — the error fires BEFORE dispatch, so donated
            # buffers are still intact and the retry is safe
            cost_failure(f"{self._label} (aot call)", e)
            self.ledger[_signature(args, kwargs)] = (self._jitted, cost)
            out = self._jitted(*args, **kwargs)
        if active:
            extra = (self._extra_bytes(*args, **kwargs)
                     if self._extra_bytes is not None else 0.0) or 0.0
            account(cost, extra_bytes=extra + emit_kernel_bytes(kb))
        return out


def instrument(fn, label: str, *,
               extra_bytes: Optional[Callable[..., float]] = None,
               **jit_kwargs) -> CostInstrumentedJit:
    """``jax.jit`` + cost ledger in one call: jit ``fn`` (unless already
    jitted) and wrap it in :class:`CostInstrumentedJit`."""
    import jax
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn, **jit_kwargs)
    return CostInstrumentedJit(jitted, label, extra_bytes=extra_bytes)
