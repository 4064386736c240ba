"""Device time of the kernels the selection added — the scores, the
selection and the selected read, in both programs (``index_scores_paged``,
``select_topk``, ``sparse_decode_attention``; ``index_scores``,
``selected_flash_attention``) — over the device's busy time in the traced
seconds, in %: whether the mechanism does most of the work. What it leaves
out: the listing of the selected rows (``pk.selected_rows``: compares, sums
and one small product in XLA fusions, no kernel of its own), the writes of
the three rows and the projections of the indexer's queries, which hide in
fusions. No such event (the parent has none): nothing is reported."""

from chipbench import trace_reduce
from chipbench.metrics._keye_vl2_common import ADMIT_KERNELS, DECODE_KERNELS


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not tr.get("raw_ops") or not tr.get("busy_s"):
        return None
    names = set(DECODE_KERNELS) | set(ADMIT_KERNELS)
    took = {}
    for n, _, d in tr["raw_ops"]:
        if " custom-call(" in n and trace_reduce.stable_name(n) in names:
            k = trace_reduce.stable_name(n)
            took[k] = took.get(k, 0.0) + d
    if not took:
        return None
    busy = tr["busy_s"] * tr["chips"]
    ctx.setdefault("notes", []).append(
        "the selection's kernels, % of busy time: " + ", ".join(
            f"{k} {100 * v / busy:.1f}" for k, v in sorted(took.items())))
    return 100.0 * sum(took.values()) / busy
