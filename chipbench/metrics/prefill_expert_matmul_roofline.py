"""Share of its roofline the experts' grouped products reached in the
ADMISSIONS of the traced seconds — hundreds of tokens an expert, where
``expert_matmul_roofline`` reads the decode segments' few: 6 x d_model x
d_expert operations for every (token, choice) pair computed, and the bytes
of the three matrices of every expert a prompt token reached in a prefill
chunk plus the activations (chipbench/flops_lfm2.py), against the summed
device time of the kernel's events (``expert_grouped_matmul``).

Counts and time are taken over the same programs: the ``serving.prefill``
spans that lie wholly inside the trace give ``routed_here`` and
``experts_touched`` (the admit program's own count, returned beside its
first tokens), and only the kernel events inside those spans are summed.
No such event or no such span argument (a parent whose admissions carry no
counts): nothing is reported."""

from chipbench import flops, flops_lfm2, harness
from chipbench.metrics._lfm2_common import events_inside, spans_inside


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    spans = [s for s in spans_inside(ctx, tr, "serving.prefill")
             if "routed_here" in s[2]]
    inside = events_inside(tr, "expert_grouped_matmul", spans)
    routed = sum(float(args["routed_here"]) for _, _, args in spans)
    touched = sum(float(args["experts_touched"]) for _, _, args in spans)
    if not inside or not routed:
        return None
    seconds = sum(d for _, _, d in inside) / tr["chips"]
    cfg = ctx["config"]
    f, b = flops_lfm2.expert_matmul_cost(
        routed, touched, cfg["hidden_size"], cfg["moe_intermediate_size"], 2)
    peaks = harness.peaks_for(ctx["device"]["kind"], ctx["base"])
    share, bound = flops.roofline_share(f, b, seconds, peaks)
    ctx.setdefault("notes", []).append(
        f"expert grouped products in admissions: {len(inside)} kernel "
        f"events in {len(spans)} whole admissions, {seconds * 1e3:.1f} ms, "
        f"{routed:.0f} pairs over {touched:.0f} expert visits "
        f"({f / seconds / 1e12:.1f} TFLOP/s), {bound}-bound")
    return share
