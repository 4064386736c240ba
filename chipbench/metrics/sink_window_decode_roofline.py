"""Share of its (memory) roofline the SINKED windowed paged decode read
reached in the decode segments of the traced seconds: k (192 wide) and v
(128) of the sliding layers' 8 KV heads of the rows a window covers —
``min(pos + 1, 128)`` a live slot a step, the program's own count
(``window_rows`` on ``serving.segment``) — once a call whatever the size of
a group, for each of the configuration's sliding layers
(chipbench/flops_mimo_v2.py: 5,120 B a row a layer), against the summed
device time of the events named ``paged_window_attention`` inside whole
segments. The window is two pages, so a call is a few microseconds of
transfer under a program's fixed cost: expect a low share."""

from chipbench import flops_mimo_v2
from chipbench.metrics._mimo_v2_common import share_over, total


def read(ctx):
    cfg = ctx["config"]
    return share_over(
        ctx, "serving.segment", ("window_rows",), "paged_window_attention",
        lambda spans: flops_mimo_v2.decode_read_cost(
            total(spans, "window_rows")
            * flops_mimo_v2.layer_counts(cfg)["sliding"], cfg, "sliding"),
        "sinked windowed decode read")
