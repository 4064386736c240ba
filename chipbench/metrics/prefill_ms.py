"""Median length of the window's ``serving.prefill`` spans: one admission
wave — page accounting, the full-pool-width prefill dispatch and the fetch of
the first tokens."""

from chipbench.metrics._serve_common import median, window_spans


def read(ctx):
    spans = window_spans(ctx, "serving.prefill")
    return median([d * 1e3 for _, d, _ in spans]) if spans else None
