"""Model FLOP/s utilisation of the training window: the operations forward
and backward need per token (chipbench/flops.py: 6 x matmul parameters plus
causal attention, nothing recomputed counted) times the tokens a second the
window completed, over the chip's bf16 peak (peaks.json)."""

from chipbench import flops, harness


def read(ctx):
    rate = ctx["values"].get("train_tokens_per_s")
    if not rate or ctx["device"]["platform"] == "cpu":
        return None
    peaks = harness.peaks_for(ctx["device"]["kind"], ctx["base"])
    per_token = flops.train_flops_per_token(ctx["config"],
                                            ctx["traffic"]["seq_len"] - 1)
    return 100.0 * per_token * rate / (
        peaks["bf16_flops_per_s"] * ctx["device"]["count"])
