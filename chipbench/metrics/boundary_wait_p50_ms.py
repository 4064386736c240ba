"""Median, over the window's requests, of the wait for the scheduler to
look at the queue at all: the ``queued`` record's duration less its
``blocked_s`` — arrival to the first admission round, which comes when the
segment (or prefill) in flight ends, whatever the pool holds."""

from chipbench.metrics._span_tree import median, queue_split


def read(ctx):
    waits = [b * 1e3 for b, _, _ in queue_split(ctx)]
    return median(waits) if waits else None
