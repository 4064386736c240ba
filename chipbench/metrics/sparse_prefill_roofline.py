"""Share of the chip's bfloat16 peak the ADMISSION'S ATTENTION UNDER THE
SELECTION reached in the traced seconds: 4 x 32 x 128 operations for every
SELECTED (query, key) pair — ``pairs_selected`` on ``serving.prefill``, the
selection's own count on the device, all layers — against the summed
device time of the kernel's events (``selected_flash_attention``). The
kernel multiplies every tile of the causal triangle and masks what was not
selected, so at 32k, where 1 pair in 16 is selected, the share reads low:
that is the finding, not a fault. No such event or span argument (the
parent has neither): nothing is reported."""

from chipbench import flops_keye_vl2
from chipbench.metrics._keye_vl2_common import share_over, total


def read(ctx):
    cfg = ctx["config"]
    return share_over(
        ctx, "serving.prefill", ("pairs_selected",),
        "selected_flash_attention",
        lambda spans: flops_keye_vl2.sparse_prefill_cost(
            total(spans, "pairs_selected"), cfg["num_attention_heads"],
            cfg["head_dim"]), "attention under the selection")
