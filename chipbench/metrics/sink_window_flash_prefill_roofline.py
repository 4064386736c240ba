"""Share of its roofline the BANDED, sinked flash forward reached in the
ADMISSIONS of the traced seconds: the operations of the band over the
admitted rows' REAL positions — ``sum_i min(i + 1, 128)`` keys a query, the
program's own count (``pairs_band`` on ``serving.prefill``) — x 64 query
heads x 2 x (192 + 128), for each of the sliding layers, and q, o, k, v once
a position the walk ran (chipbench/flops_mimo_v2.py), against the summed
device time of the events named ``flash_window_attention_fwd`` inside whole
admissions. At a band of 128 under blocks of 512 x 1,024 the walk multiplies
far more than the band (``kernels.flash_block_pairs_total``): expect a low
share, which is the finding."""

from chipbench import flops_mimo_v2
from chipbench.metrics._mimo_v2_common import share_over, total


def read(ctx):
    cfg = ctx["config"]
    n = flops_mimo_v2.layer_counts(cfg)["sliding"] if "v_head_dim" in cfg \
        else 0
    return share_over(
        ctx, "serving.prefill", ("pairs_band", "positions"),
        "flash_window_attention_fwd",
        lambda spans: flops_mimo_v2.flash_cost(
            total(spans, "pairs_band") * n, total(spans, "positions") * n,
            cfg, "sliding"), "banded sinked flash in admissions")
