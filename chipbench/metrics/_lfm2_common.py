"""Shared by the readers the ``lfm2_moe`` cells brought (a leading
underscore keeps it out of the metric listing): the window's spans of one
name that lie wholly inside the trace, and the kernel events inside them."""

from chipbench import trace_reduce
from chipbench.metrics._serve_common import window_spans


def spans_inside(ctx, tr, name):
    """[(start, end, args)] — trace clock — of the window's spans ``name``
    that lie wholly between the trace's first and last device operation.
    An admission (``serving.prefill``) is dispatched and fetched inside its
    span, as a segment is, so every device event of the program lies
    inside it too."""
    if not tr["raw_ops"] or tr.get("shift") is None:
        return []
    lo = min(s for _, s, _ in tr["raw_ops"])
    hi = max(s + d for _, s, d in tr["raw_ops"])
    out = []
    for s, d, args in window_spans(ctx, name):
        s -= tr["shift"]
        if lo <= s and s + d <= hi:
            out.append((s, s + d, args))
    return out


def events_inside(tr, kernel, spans):
    """[(name, start, dur)] of the device events of the Pallas kernel
    ``kernel`` that lie inside one of ``spans``."""
    return [(n, s, d) for n, s, d in tr["raw_ops"]
            if " custom-call(" in n and trace_reduce.stable_name(n) == kernel
            and any(a <= s and s + d <= b for a, b, _ in spans)]
