"""Share of its (memory) roofline the decode step's INDEXER SCORES reached in
the decode segments of the traced seconds: a cached indexer key's 64 values
(128 B) once for every key scored — ``keys_scored`` on ``serving.segment``,
the program's own count: live slot x step x layer x context — and a product
with each of the 16 small queries (chipbench/flops_keye_vl2.py), against
the summed device time of the kernel's events (``index_scores_paged``).
The pool holds the row 256 B wide and the kernel streams that: the share
counts the WORK's 128 B, so the width shows as a lower share. No such event
or span argument (the parent has neither): nothing is reported."""

from chipbench import flops_keye_vl2
from chipbench.metrics._keye_vl2_common import share_over, total


def read(ctx):
    sa = ctx["config"].get("sa_config", {})
    return share_over(
        ctx, "serving.segment", ("keys_scored",), "index_scores_paged",
        lambda spans: flops_keye_vl2.index_score_cost(
            total(spans, "keys_scored"), sa["indexer_num_heads"],
            sa["indexer_head_dim"], 2), "indexer scores")
