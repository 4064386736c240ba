"""Share of its roofline the flash-attention forward kernel reached in the
ADMISSIONS of the traced seconds: the operations of a causal square at each
call's own length over all query heads, and q, o of the query heads and k,
v of the KV heads once each (chipbench/flops_lfm2.py), against the summed
device time of the kernel's events.

The kernel is found by its own name (``flash_attention_fwd``) among the
device events inside whole ``serving.prefill`` spans; each call's rows x
heads and length are read off its own result in the event's text
(``[rows x heads, T, d_head]``), so prompt buckets of any length are
counted at their own size. No such event (a configuration without grouped
heads is not this reader's: it reads ``num_key_value_heads``): nothing is
reported."""

import re

from chipbench import flops, flops_lfm2, harness
from chipbench.metrics._lfm2_common import events_inside, spans_inside

_SHAPE = re.compile(r"\[(\d+),(\d+),(\d+)\]")


def read(ctx):
    tr = ctx.get("trace")
    cfg = ctx["config"]
    if tr is None or "num_key_value_heads" not in cfg:
        return None
    spans = spans_inside(ctx, tr, "serving.prefill")
    inside = events_inside(tr, "flash_attention_fwd", spans)
    if not inside:
        return None
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d_head = cfg["hidden_size"] // heads
    f_all = b_all = 0.0
    for name, _, _ in inside:
        m = _SHAPE.search(name)
        if m is None or int(m.group(3)) != d_head or int(m.group(1)) % heads:
            return None
        f, b = flops_lfm2.flash_prefill_cost(
            int(m.group(1)) // heads, heads, kv, int(m.group(2)), d_head, 2)
        f_all, b_all = f_all + f, b_all + b
    seconds = sum(d for _, _, d in inside) / tr["chips"]
    peaks = harness.peaks_for(ctx["device"]["kind"], ctx["base"])
    share, bound = flops.roofline_share(f_all, b_all, seconds, peaks)
    ctx.setdefault("notes", []).append(
        f"flash attention in admissions: {len(inside)} kernel events in "
        f"{len(spans)} whole admissions, {seconds * 1e3:.1f} ms "
        f"({f_all / seconds / 1e12:.1f} TFLOP/s), {bound}-bound")
    return share
