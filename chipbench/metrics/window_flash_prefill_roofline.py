"""Share of its roofline the BANDED flash-attention forward reached in the
ADMISSIONS of the traced seconds: the operations of the band at each call's
own length — ``sum_i min(i + 1, window)`` keys a query — over all query
heads, and q, o of the query heads and k, v of the KV heads once each
(chipbench/flops_afmoe.py), against the summed device time of the kernel's
events.

The kernel is found by its own name (``flash_window_attention_fwd``) among
the device events inside whole ``serving.prefill`` spans; each call's rows
x heads and length are read off its own result in the event's text
(``[rows x heads, T, d_head]``). No such event (the parent names no such
kernel): nothing is reported."""

import re

from chipbench import flops, flops_afmoe, harness
from chipbench.metrics._lfm2_common import events_inside, spans_inside

_SHAPE = re.compile(r"\[(\d+),(\d+),(\d+)\]")


def read(ctx):
    tr = ctx.get("trace")
    cfg = ctx["config"]
    if tr is None or "sliding_window" not in cfg:
        return None
    spans = spans_inside(ctx, tr, "serving.prefill")
    inside = events_inside(tr, "flash_window_attention_fwd", spans)
    if not inside:
        return None
    heads, kv, d_head = (cfg["num_attention_heads"],
                         cfg["num_key_value_heads"], cfg["head_dim"])
    f_all = b_all = 0.0
    for name, _, _ in inside:
        m = _SHAPE.search(name)
        if m is None or int(m.group(3)) != d_head or int(m.group(1)) % heads:
            return None
        f, b = flops_afmoe.window_flash_cost(
            int(m.group(1)) // heads, heads, kv, int(m.group(2)),
            cfg["sliding_window"], d_head, 2)
        f_all, b_all = f_all + f, b_all + b
    seconds = sum(d for _, _, d in inside) / tr["chips"]
    peaks = harness.peaks_for(ctx["device"]["kind"], ctx["base"])
    share, bound = flops.roofline_share(f_all, b_all, seconds, peaks)
    ctx.setdefault("notes", []).append(
        f"banded flash attention in admissions: {len(inside)} kernel events "
        f"in {len(spans)} whole admissions, {seconds * 1e3:.1f} ms "
        f"({f_all / seconds / 1e12:.1f} TFLOP/s of the band), "
        f"{bound}-bound")
    return share
