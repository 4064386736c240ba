"""95th percentile of the time a request waited in the engine's queue for a
slot and pages (request ledger: the ``queued`` record's telescoped duration,
admitted -> scheduled), over the window's requests."""

from chipbench import harness
from chipbench.metrics._serve_common import window_timelines


def read(ctx):
    waits = [float(tl["queued"]["dur"]) * 1e3
             for tl in window_timelines(ctx).values() if "queued" in tl]
    return harness.percentile(waits, 95) if waits else None
