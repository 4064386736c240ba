"""Median length of the window's ``trainer.input`` spans: one pull of the
next batch through the reader and the feeder, as the train loop waits for
it (a part of ``step_host_ms``)."""

from chipbench.metrics._serve_common import median, window_spans


def read(ctx):
    pulls = [d * 1e3 for _, d, _ in window_spans(ctx, "trainer.input")]
    return median(pulls) if pulls else None
