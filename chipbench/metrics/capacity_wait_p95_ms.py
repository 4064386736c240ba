"""95th percentile, over the window's requests, of the wait for capacity:
``blocked_s`` of the ``queued`` record — from the first admission round that
passed the request over, for want of a slot or of pages, to its admission;
0 for a request taken at the first boundary after it arrived. A note says
what share was passed over, and for what."""

from chipbench import harness
from chipbench.metrics._span_tree import queue_split


def read(ctx):
    rows = queue_split(ctx)
    if not rows:
        return None
    by = {}
    for _, _, why in rows:
        if why:
            by[why] = by.get(why, 0) + 1
    waits = [blocked * 1e3 for _, blocked, _ in rows]
    ctx.setdefault("notes", []).append(
        f"capacity wait: {sum(by.values())} of {len(rows)} requests were "
        f"passed over by an admission round ({by or 'none'}); median "
        f"{harness.percentile(waits, 50):.1f} ms")
    return harness.percentile(waits, 95)
