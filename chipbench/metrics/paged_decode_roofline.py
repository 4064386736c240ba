"""Share of its (memory) roofline the paged decode-attention kernel reached
in the traced seconds: the bytes the algorithm needs — K and V of every LIVE
cache row, once per call (chipbench/flops.py) — over the chip's peak
bandwidth, against the summed device time of the kernel's events.

The kernel has no name of its own in the trace (a Pallas call shows as
``custom-call``), so it is told by structure: the custom calls that take a
page pool, an array ``[pages, page_block, heads, d_head]``. The live rows are
worked out from the daemon's request ledger (each request's prompt length,
when it was prefilled and done, how many tokens it made), averaged over the
traced span. No such call in the trace, or no ledger: nothing is reported."""

import re

from chipbench import flops, harness
from chipbench.metrics._serve_common import window_timelines


def live_rows(ctx, t_a, t_b, samples=200):
    """Mean over [t_a, t_b] (unix) of the cache rows of the live requests."""
    plen = {r["key"]: r["plen"] for r in ctx["records"]}
    spans = []
    for key, tl in window_timelines(ctx).items():
        if "first_token" not in tl or "done" not in tl:
            continue
        t0, t1 = float(tl["first_token"]["t"]), float(tl["done"]["t"])
        spans.append((t0, max(t1, t0 + 1e-6), plen[key],
                      int(tl["done"].get("tokens", 0))))
    total = 0.0
    for i in range(samples):
        t = t_a + (t_b - t_a) * (i + 0.5) / samples
        total += sum(p + n * (t - t0) / (t1 - t0)
                     for t0, t1, p, n in spans if t0 <= t <= t1)
    return total / samples


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or tr.get("shift") is None:
        return None
    cfg, flags = ctx["config"], ctx["cell"]["flags"]
    heads, d_head = cfg["n_head"], cfg["n_embd"] // cfg["n_head"]
    pool = re.compile(rf"\[{flags['pages']},{flags['page_block']},"
                      rf"{heads},{d_head}\]")
    hits = [(s, d) for name, s, d in tr["raw_ops"]
            if " custom-call(" in name and pool.search(name)]
    if not hits:
        return None
    seconds = sum(d for _, d in hits) / tr["chips"]
    t_a = min(s for s, _ in hits) + tr["shift"]
    t_b = max(s + d for s, d in hits) + tr["shift"]
    rows = live_rows(ctx, t_a, t_b)
    if rows <= 0:
        return None
    f, b = flops.paged_decode_cost(rows, heads, d_head, 4)
    n = len(hits) / tr["chips"]
    peaks = harness.peaks_for(ctx["device"]["kind"], ctx["base"])
    share, bound = flops.roofline_share(n * f, n * b, seconds, peaks)
    ctx.setdefault("notes", []).append(
        f"paged decode attention: {len(hits)} kernel events, "
        f"{seconds * 1e3:.1f} ms ({100 * seconds / tr['busy_s']:.1f}% of "
        f"busy time), {rows:.0f} live rows on average, {bound}-bound")
    return share
