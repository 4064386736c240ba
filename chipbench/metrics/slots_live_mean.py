"""Slots decoding, averaged over the window's decode segments (the ``live``
count on every ``serving.segment`` span, weighted by the span's length)."""

from chipbench.metrics._serve_common import window_spans


def read(ctx):
    spans = window_spans(ctx, "serving.segment")
    total = sum(d for _, d, _ in spans)
    if not total:
        return None
    return sum(d * float(a.get("live", 0)) for _, d, a in spans) / total
