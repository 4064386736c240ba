"""Share of its roofline the held experts' grouped products reached in the
decode segments of the traced seconds: 6 x d_model x d_expert operations
for every (token, choice) pair computed, and the bytes of the three
matrices of every held expert a token reached in a step (an expert no token
chose is not read) plus the activations (chipbench/flops_deepseek_v3.py),
against the summed device time of the kernel's events
(``expert_grouped_matmul``: gate, up and down).

Counts and time are taken over the same programs: the ``serving.segment``
spans that lie wholly inside the trace give ``routed_here`` and
``experts_touched`` (the program's own count, returned beside its tokens),
and only the kernel events inside those spans are summed; admissions and
segments cut by the trace's edges are left out of both. No such event or
no such span argument (the parent has neither): nothing is reported."""

from chipbench import flops, flops_deepseek_v3, harness
from chipbench.metrics._deepseek_v3_common import (kernel_events,
                                                   segments_inside)


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    hits = kernel_events(tr, "expert_grouped_matmul")
    segs = segments_inside(ctx, tr)
    if not hits or not segs:
        return None
    inside = [d for s, d in hits if any(a <= s and s + d <= b
                                        for a, b, _ in segs)]
    routed = sum(float(args["routed_here"]) for _, _, args in segs)
    touched = sum(float(args["experts_touched"]) for _, _, args in segs)
    if not inside or not routed:
        return None
    seconds = sum(inside) / tr["chips"]
    cfg = ctx["config"]
    f, b = flops_deepseek_v3.expert_matmul_cost(
        routed, touched, cfg["hidden_size"], cfg["moe_intermediate_size"], 2)
    peaks = harness.peaks_for(ctx["device"]["kind"], ctx["base"])
    share, bound = flops.roofline_share(f, b, seconds, peaks)
    ctx.setdefault("notes", []).append(
        f"expert grouped products: {len(inside)} kernel events in "
        f"{len(segs)} whole segments, {seconds * 1e3:.1f} ms, {routed:.0f} "
        f"pairs over {touched:.0f} expert visits "
        f"({b / seconds / 1e9:.0f} GB/s), {bound}-bound")
    return share
