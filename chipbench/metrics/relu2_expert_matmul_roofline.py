"""Share of its roofline the held experts' grouped products reached in the
decode segments of the traced seconds, for experts that are NOT gated
(``down(relu(up(y))^2)``: the ``nemotron_h`` family): 4 x d_model x
d_expert operations for every (token, choice) pair computed, and the bytes
of the TWO matrices of every held expert a token reached in a step plus the
activations (chipbench/flops_nemotron_h.py), against the summed device time
of the kernel's events (``expert_grouped_matmul``: up and down).
``expert_matmul_roofline`` counts three matrices a visit and would read
1.5x this cell's share.

Counts and time are taken over the same programs, as there: the
``serving.segment`` spans that lie wholly inside the trace give
``routed_here`` and ``experts_touched``, and only the kernel events inside
those spans are summed. A configuration whose experts are gated, no such
event or no such span argument: nothing is reported."""

from chipbench import flops, flops_nemotron_h, harness
from chipbench.metrics._deepseek_v3_common import (kernel_events,
                                                   segments_inside)


def read(ctx):
    tr = ctx.get("trace")
    cfg = ctx["config"]
    if tr is None or cfg.get("mlp_hidden_act") != "relu2":
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
    f, b = flops_nemotron_h.relu2_expert_matmul_cost(
        routed, touched, cfg["hidden_size"], cfg["moe_intermediate_size"], 2)
    peaks = harness.peaks_for(ctx["device"]["kind"], ctx["base"])
    share, bound = flops.roofline_share(f, b, seconds, peaks)
    ctx.setdefault("notes", []).append(
        f"relu2 expert grouped products: {len(inside)} kernel events in "
        f"{len(segs)} whole segments, {seconds * 1e3:.1f} ms "
        f"({100 * seconds / tr['busy_s']:.1f}% of busy time), {routed:.0f} "
        f"pairs over {touched:.0f} expert visits "
        f"({b / seconds / 1e9:.0f} GB/s), {bound}-bound")
    return share
