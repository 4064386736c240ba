"""Share of its (memory) roofline the absorbed latent-attention read reached
in the traced seconds: the bytes the algorithm needs — the latent row of
every LIVE cache position, once per call, whatever the number of heads
(chipbench/flops_deepseek_v3.py) — over the chip's peak bandwidth, against
the summed device time of the kernel's events.

The kernel is found by its own name in the trace
(``paged_latent_attention``); the live rows come from the daemon's request
ledger as for ``paged_decode_roofline`` (whose ``live_rows`` this reads
through). No such event in the trace (the parent has no such kernel), or no
ledger: nothing is reported."""

from chipbench import flops, flops_deepseek_v3, harness
from chipbench.metrics._deepseek_v3_common import kernel_events


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or tr.get("shift") is None:
        return None
    hits = kernel_events(tr, "paged_latent_attention")
    if not hits:
        return None
    cfg = ctx["config"]
    seconds = sum(d for _, d in hits) / tr["chips"]
    t_a = min(s for s, _ in hits) + tr["shift"]
    t_b = max(s + d for s, d in hits) + tr["shift"]
    rows = harness.load_module("metrics", "paged_decode_roofline",
                               ctx["base"]).live_rows(ctx, t_a, t_b)
    if rows <= 0:
        return None
    f, b = flops_deepseek_v3.mla_decode_cost(
        rows, cfg["num_attention_heads"],
        cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"], cfg["kv_lora_rank"],
        2)
    n = len(hits) / tr["chips"]
    peaks = harness.peaks_for(ctx["device"]["kind"], ctx["base"])
    share, bound = flops.roofline_share(n * f, n * b, seconds, peaks)
    ctx.setdefault("notes", []).append(
        f"latent attention read: {len(hits)} kernel events, "
        f"{seconds * 1e3:.1f} ms ({100 * seconds / tr['busy_s']:.1f}% of "
        f"busy time), {rows:.0f} live rows on average, {bound}-bound")
    return share
