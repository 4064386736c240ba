"""Share of its roofline the global layers' flash forward — q, k 192 wide,
v, o 128 — reached in the ADMISSIONS of the traced seconds: the operations
of the causal triangle over the admitted rows' REAL positions (the
program's own count: ``pairs_causal`` on ``serving.prefill``) x 64 query
heads x 2 x (192 + 128), for each of the global layers, and q, o, k, v once
a position the walk ran (chipbench/flops_mimo_v2.py), against the summed
device time of the events named ``flash_attention_fwd`` inside whole
admissions — a block's own causal square and the plain calls over every
earlier block's keys alike (models/mimo_v2.py); the merges of their
partial reads run in XLA fusions and are not in the time."""

from chipbench import flops_mimo_v2
from chipbench.metrics._mimo_v2_common import share_over, total


def read(ctx):
    cfg = ctx["config"]
    n = flops_mimo_v2.layer_counts(cfg)["full"] if "v_head_dim" in cfg else 0
    return share_over(
        ctx, "serving.prefill", ("pairs_causal", "positions"),
        "flash_attention_fwd",
        lambda spans: flops_mimo_v2.flash_cost(
            total(spans, "pairs_causal") * n, total(spans, "positions") * n,
            cfg, "full"), "split-width causal flash in admissions")
