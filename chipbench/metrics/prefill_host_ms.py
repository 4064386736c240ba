"""Host milliseconds of one admission wave: a ``serving.prefill`` span less
its ``serving.fetch`` children (the device wait and the copy back) — page
bookkeeping, staging, the dispatch call and the prefix-index insertion.
Median over the window's waves. ``prefill_ms`` times the same envelope from
outside, device and all; a program without the leaf spans reports nothing
here."""

from chipbench.metrics._span_tree import host_parts, median


def read(ctx):
    host = [s * 1e3 for _, s in host_parts(ctx, "serving.prefill")]
    return median(host) if host else None
