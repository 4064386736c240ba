"""Share of its (memory) roofline the global layers' paged decode read —
keys 192 wide, values 128 — reached in the decode segments of the traced
seconds: k and v of the 4 KV heads of every LIVE cache row (``pos + 1`` a
live slot a step, the program's own count: ``full_rows`` on
``serving.segment``) once a call for the whole group of 16, for each of the
configuration's global layers (chipbench/flops_mimo_v2.py: 2,560 B a row a
layer), against the summed device time of the events named
``paged_decode_attention`` inside whole segments."""

from chipbench import flops_mimo_v2
from chipbench.metrics._mimo_v2_common import share_over, total


def read(ctx):
    cfg = ctx["config"]
    return share_over(
        ctx, "serving.segment", ("full_rows",), "paged_decode_attention",
        lambda spans: flops_mimo_v2.decode_read_cost(
            total(spans, "full_rows")
            * flops_mimo_v2.layer_counts(cfg)["full"], cfg, "full"),
        "split-width global decode read")
