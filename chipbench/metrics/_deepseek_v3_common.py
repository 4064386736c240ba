"""Shared by the readers of the ``deepseek_v3`` cells (a leading underscore
keeps it out of the metric listing): the trace's events of one named
kernel, and the decode segments that lie wholly inside the trace."""

from chipbench import trace_reduce
from chipbench.metrics._serve_common import window_spans


def kernel_events(tr, kernel):
    """[(start_s, dur_s)] of the device events of the Pallas kernel
    ``kernel`` (its ``name=``: the custom call's instruction name)."""
    return [(s, d) for name, s, d in tr["raw_ops"]
            if " custom-call(" in name
            and trace_reduce.stable_name(name) == kernel]


def segments_inside(ctx, tr):
    """[(start, end, args)] — trace clock — of the window's
    ``serving.segment`` spans that carry the expert layer's counts and lie
    wholly between the trace's first and last device operation. A segment
    is dispatched and fetched inside its span, so every device event of
    the program lies inside it too, and counts and device time can be
    taken over exactly the same programs."""
    if not tr["raw_ops"] or tr.get("shift") is None:
        return []
    lo = min(s for _, s, _ in tr["raw_ops"])
    hi = max(s + d for _, s, d in tr["raw_ops"])
    out = []
    for s, d, args in window_spans(ctx, "serving.segment"):
        s -= tr["shift"]
        if "routed_here" in args and lo <= s and s + d <= hi:
            out.append((s, s + d, args))
    return out
