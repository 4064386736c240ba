"""Share of its (memory) roofline the grouped-query paged decode read
reached in the traced seconds: the bytes the algorithm needs — K and V of
the KV heads (fewer than the query heads) of every LIVE cache row, once per
call, whatever the size of a group (chipbench/flops_lfm2.py) — over the
chip's peak bandwidth, against the summed device time of the kernel's
events.

The kernel is found by its own name in the trace
(``paged_decode_attention``); the live rows come from the daemon's request
ledger as for ``paged_decode_roofline`` (whose ``live_rows`` this reads
through). No such event in the trace (the parent names no such kernel for
this configuration: it cannot run it), or no ledger: nothing is reported."""

from chipbench import flops, flops_lfm2, harness
from chipbench.metrics._deepseek_v3_common import kernel_events


def read(ctx):
    tr = ctx.get("trace")
    cfg = ctx["config"]
    if tr is None or tr.get("shift") is None \
            or "num_key_value_heads" not in cfg:
        return None
    hits = kernel_events(tr, "paged_decode_attention")
    if not hits:
        return None
    seconds = sum(d for _, d in hits) / tr["chips"]
    t_a = min(s for s, _ in hits) + tr["shift"]
    t_b = max(s + d for s, d in hits) + tr["shift"]
    rows = harness.load_module("metrics", "paged_decode_roofline",
                               ctx["base"]).live_rows(ctx, t_a, t_b)
    if rows <= 0:
        return None
    heads = cfg["num_attention_heads"]
    f, b = flops_lfm2.gqa_decode_cost(
        rows, heads, cfg["num_key_value_heads"], cfg["hidden_size"] // heads,
        2)
    n = len(hits) / tr["chips"]
    peaks = harness.peaks_for(ctx["device"]["kind"], ctx["base"])
    share, bound = flops.roofline_share(n * f, n * b, seconds, peaks)
    ctx.setdefault("notes", []).append(
        f"grouped-query decode read: {len(hits)} kernel events, "
        f"{seconds * 1e3:.1f} ms ({100 * seconds / tr['busy_s']:.1f}% of "
        f"busy time), {rows:.0f} live rows on average, {bound}-bound")
    return share
