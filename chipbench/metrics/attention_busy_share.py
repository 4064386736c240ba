"""Device time of the four attention reads — the sinked windowed decode
read and the global one (``paged_window_attention``,
``paged_decode_attention``), the banded sinked flash and the causal one
(``flash_window_attention_fwd``, ``flash_attention_fwd``) — over the
device's busy time in the traced seconds, in %: whether the mechanism does
the work, with each kernel's own share in a note. What it leaves out: the
projections (2.0 GB of q / k / v / o matrices a decode step), the writes of
the rows, the merges of an admission's partial reads and the dense read of
the 128 keys before a block — XLA fusions, no kernel of their own. A
configuration of another family or no such event: nothing is reported."""

from chipbench import trace_reduce
from chipbench.metrics._mimo_v2_common import KERNELS


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not tr.get("raw_ops") or not tr.get("busy_s") \
            or "v_head_dim" not in ctx["config"]:
        return None
    names = {k for k, _ in KERNELS}
    took = {}
    for n, _, d in tr["raw_ops"]:
        if " custom-call(" in n and trace_reduce.stable_name(n) in names:
            k = trace_reduce.stable_name(n)
            took[k] = took.get(k, 0.0) + d
    if not took:
        return None
    busy = tr["busy_s"] * tr["chips"]
    ctx.setdefault("notes", []).append(
        "the attention reads, % of busy time: " + ", ".join(
            f"{k} {100 * v / busy:.1f}" for k, v in sorted(took.items())))
    return 100.0 * sum(took.values()) / busy
