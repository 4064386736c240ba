"""Shared by the readers the ``mimo_v2_flash`` cell brought (a leading
underscore keeps it out of the metric listing): the four attention kernels
by the names a device trace shows them under, and a roofline share of one of
them over the whole spans that carry a count. A configuration of another
family (no ``v_head_dim``), a program without the span argument or a trace
without the event (the parent has none of them): nothing is reported.
(``share_over`` is ``_keye_vl2_common``'s but for the family it knows a
configuration by; that file is the benchmark's and may not be edited.)"""

from chipbench import flops, harness
from chipbench.metrics._keye_vl2_common import total  # noqa: F401
from chipbench.metrics._lfm2_common import events_inside, spans_inside

#: the four reads: (kernel, the program's span it runs in)
KERNELS = (("paged_window_attention", "serving.segment"),
           ("paged_decode_attention", "serving.segment"),
           ("flash_window_attention_fwd", "serving.prefill"),
           ("flash_attention_fwd", "serving.prefill"))


def share_over(ctx, span, needs, kernel, cost, what):
    """Roofline share of ``kernel``'s events inside the whole ``span``s of
    the trace that carry the span arguments ``needs``; ``cost(spans) ->
    (flops, bytes)``."""
    tr = ctx.get("trace")
    if tr is None or "v_head_dim" not in ctx["config"]:
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
