"""Share of its (memory) roofline the decode step's SELECTED-ROW READ reached
in the decode segments of the traced seconds: a row of k and a row of v
(2,048 B) for every row selected — ``keys_selected`` less ``dense_rows`` on
``serving.segment``, the selection's own count on the device — plus the q
and o of every (slot, step, layer) (chipbench/flops_keye_vl2.py), against
the summed device time of the kernel's events
(``sparse_decode_attention``). No such event or span argument (the parent
has neither): nothing is reported."""

from chipbench import flops_keye_vl2
from chipbench.metrics._keye_vl2_common import share_over, total


def read(ctx):
    cfg = ctx["config"]
    return share_over(
        ctx, "serving.segment", ("keys_selected", "sparse_steps"),
        "sparse_decode_attention",
        lambda spans: flops_keye_vl2.sparse_decode_cost(
            total(spans, "keys_selected") - total(spans, "dense_rows"),
            total(spans, "sparse_steps") * cfg["num_hidden_layers"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], 2), "selected-row read")
