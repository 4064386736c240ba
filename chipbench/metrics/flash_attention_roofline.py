"""Share of its roofline the flash-attention kernels (forward and backward
together) reached in the traced steps: the least time the chip could take for
the operations and bytes the algorithm needs (chipbench/flops.py, from the
cell's shapes) over the summed device time of the kernels' events.

The kernels carry no name of their own in the trace (a Pallas call shows as
``custom-call`` under its autodiff name stack, ``jvp``/``transpose_jvp``), so
they are told by structure: the custom calls of the step program one of
whose arrays is ``[rows x heads, T, d_head]``. A step whose trace shows no
such call reports nothing."""

import re

from chipbench import flops, harness


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    cfg, traffic = ctx["config"], ctx["traffic"]
    heads, d_head = cfg["n_head"], cfg["n_embd"] // cfg["n_head"]
    rows, T = traffic["rows"], traffic["seq_len"] - 1
    shape = re.compile(rf"\[{rows * heads},({T}|{T + 1}),{d_head}\]")
    hits = [d for name, _, d in tr["raw_ops"]
            if " custom-call(" in name and shape.search(name)]
    steps = len([m for m in tr["modules"] if "step" in m[0]])
    if not hits or not steps:
        return None
    seconds = sum(hits) / tr["chips"]
    f1, b1 = flops.flash_attention_cost(rows, heads, T, T, d_head, 2)
    f2, b2 = flops.flash_attention_cost(rows, heads, T, T, d_head, 2,
                                        backward=True)
    n = steps * cfg["n_layer"]
    peaks = harness.peaks_for(ctx["device"]["kind"], ctx["base"])
    share, bound = flops.roofline_share(n * (f1 + f2), n * (b1 + b2),
                                        seconds, peaks)
    ctx.setdefault("notes", []).append(
        f"flash attention: {len(hits)} kernel events, {seconds * 1e3:.1f} ms "
        f"over {steps} steps ({100 * seconds / tr['busy_s']:.1f}% of busy "
        f"time), {bound}-bound")
    return share
