"""Shared by the readers the ``keye_vl2`` cell brought (a leading underscore
keeps it out of the metric listing): the kernels the selection added, by
the names a device trace shows them under, and a roofline share over the
spans that carry a count."""

from chipbench import flops, harness
from chipbench.metrics._lfm2_common import events_inside, spans_inside

#: the kernels the selection added, in both programs
DECODE_KERNELS = ("index_scores_paged", "select_topk",
                  "sparse_decode_attention")
ADMIT_KERNELS = ("index_scores", "select_topk", "selected_flash_attention")


def share_over(ctx, span, needs, kernel, cost, what):
    """Roofline share of ``kernel``'s events inside the whole ``span``s of
    the trace that carry the span arguments ``needs``; ``cost(spans) ->
    (flops, bytes)``. None where the program has no such span argument or
    the trace no such event (the parent has neither)."""
    tr = ctx.get("trace")
    if tr is None or "sa_config" not in ctx["config"]:
        return None
    spans = [s for s in spans_inside(ctx, tr, span)
             if all(k in s[2] for k in needs)]
    inside = events_inside(tr, kernel, spans)
    if not inside or not spans:
        return None
    f, b = cost(spans)
    if not f and not b:
        return None
    seconds = sum(d for _, _, d in inside) / tr["chips"]
    peaks = harness.peaks_for(ctx["device"]["kind"], ctx["base"])
    share, bound = flops.roofline_share(f, b, seconds, peaks)
    ctx.setdefault("notes", []).append(
        f"{what}: {len(inside)} {kernel} events in {len(spans)} whole "
        f"{span} spans, {seconds * 1e3:.1f} ms "
        f"({100 * seconds / tr['busy_s']:.1f}% of busy time), "
        f"{f / seconds / 1e12:.2f} TFLOP/s, {b / seconds / 1e9:.1f} GB/s, "
        f"{bound}-bound")
    return share


def total(spans, key):
    return sum(float(args[key]) for _, _, args in spans)
