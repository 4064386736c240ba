"""Median time of one decode step: a ``serving.segment`` span (one dispatch
of ``segment`` steps over every slot, plus the fetch of its tokens) over the
steps in it."""

from chipbench.metrics._serve_common import median, window_spans


def read(ctx):
    spans = window_spans(ctx, "serving.segment")
    steps = ctx["cell"]["flags"]["segment"]
    return median([d * 1e3 / steps for _, d, _ in spans]) if spans else None
