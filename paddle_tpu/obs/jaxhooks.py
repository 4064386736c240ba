"""Bridge jax.monitoring compilation events into the metrics plane.

XLA compilation is the dominant hidden cost of a jit-first framework: a
shape change in the train loop silently recompiles and a step that should
take milliseconds takes seconds. jax reports these through
``jax.monitoring`` duration events (e.g. ``.../backend_compile_time``);
this module registers ONE process-wide listener that forwards the three
stages of building a program into the installed session: the backend
compile as ``jax.compiles_total`` / ``jax.compile_seconds`` — the
compile-vs-execute split the trainer's step histograms can't see from the
host side — and the jaxpr trace and the lowering to MLIR, which a
persistent compile cache does NOT save a later run, as
``jax.trace_seconds`` / ``jax.lower_seconds``. Each also leaves an instant
(``jax.compile`` / ``jax.trace`` / ``jax.lower``) that names the function
where jax passes one: which program was built, and when.

The listener is registered lazily on the first session install and checks
``obs.is_active()`` per event, so an uninstalled process pays nothing and
jax's listener list is never cleared (other packages may have their own).
The jax.monitoring surface is semi-public and varies across versions, so
registration is best-effort: on any API mismatch the bridge degrades to a
no-op and the rest of the plane works unchanged.
"""

from __future__ import annotations

import threading

_registered = False
_lock = threading.Lock()

#: event-name marker for "one XLA backend compile". One jit call emits
#: SEVERAL duration events (jaxpr trace, mlir lowering, backend compile);
#: counting anything broader than backend_compile would tally one compile
#: 3x and mix unrelated distributions into one histogram.
_COMPILE_MARKER = "backend_compile"

#: the two stages before the compile, by the event name's last part. A
#: traced function that calls jitted ones reports their traces too, inside
#: its own: a reader that wants seconds takes the union of the instants'
#: [ts - duration_secs, ts] on one thread, not a sum.
_TRACE_EVENT = "jaxpr_trace_duration"
_LOWER_EVENT = "jaxpr_to_mlir_module_duration"
#: a stage shorter than this leaves no instant (the histograms count it):
#: tracing one 24-layer train step reports ~9,000 traces of jnp's own small
#: jitted helpers, 8 us at the median and 1.5 % of the seconds together
_INSTANT_MIN_S = 1e-3


def _on_duration(event: str, duration_secs: float = 0.0, **kw) -> None:
    # late import: this module must stay importable before obs/__init__
    # finishes (it registers us during _install)
    from . import _SESSION
    s = _SESSION
    if s is None:
        return
    fun = {"fun_name": str(kw["fun_name"])[:80]} if "fun_name" in kw else {}
    try:
        if _COMPILE_MARKER not in event:
            stage = event.rsplit("/", 1)[-1]
            if stage == _TRACE_EVENT:
                s.registry.histogram("jax.trace_seconds").observe(
                    duration_secs)
                instant = "jax.trace"
            elif stage == _LOWER_EVENT:
                s.registry.histogram("jax.lower_seconds").observe(
                    duration_secs)
                instant = "jax.lower"
            else:
                return
            if duration_secs >= _INSTANT_MIN_S:
                s.tracer.instant(instant, duration_secs=duration_secs, **fun)
            return
        s.registry.counter("jax.compiles_total").inc()
        s.registry.histogram("jax.compile_seconds").observe(duration_secs)
        s.tracer.instant("jax.compile", event=event,
                         duration_secs=duration_secs, **fun)
        # goodput ledger: the compile second is hiding inside whatever
        # bucket the compiling thread has open — move it to `compile`
        from . import goodput
        goodput.note_compile(duration_secs)
    except Exception:
        # a telemetry bridge must never take down a compile
        pass


def ensure_registered() -> bool:
    """Idempotently hook jax.monitoring; True when the bridge is live."""
    global _registered
    with _lock:
        if _registered:
            return True
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(_on_duration)
        except Exception:
            return False
        _registered = True
        return True
