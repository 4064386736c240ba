"""Share of its (memory) roofline the single-position state update of the
Mamba-2 layers reached in the decode segments of the traced seconds: the
bytes the algorithm needs — the state of every LIVE slot read once and
written once a Mamba layer a step, float32, plus the step's ``x, B, C, dt,
y`` (chipbench/flops_nemotron_h.py) — over the chip's peak bandwidth,
against the summed device time of the kernel's events
(``ssm_state_update``). A kernel that walks dead slots reads low, as
``paged_decode_roofline`` does for dead pages.

Counts and time are taken over the same programs: the ``serving.segment``
spans that lie wholly inside the trace give ``live`` (the slots the program
was told are live, for all of its ``segment`` steps), and only the kernel
events inside those spans are summed. No such event (a parent that has no
such kernel): nothing is reported."""

from chipbench import flops, flops_nemotron_h, harness
from chipbench.metrics._lfm2_common import events_inside, spans_inside


def read(ctx):
    tr = ctx.get("trace")
    cfg = ctx["config"]
    if tr is None or "hybrid_override_pattern" not in cfg:
        return None
    spans = [s for s in spans_inside(ctx, tr, "serving.segment")
             if "live" in s[2]]
    inside = events_inside(tr, "ssm_state_update", spans)
    if not inside:
        return None
    layers = flops_nemotron_h.layer_counts(cfg)["M"]
    updates = sum(float(args["live"]) for _, _, args in spans) \
        * ctx["cell"]["flags"]["segment"] * layers
    seconds = sum(d for _, _, d in inside) / tr["chips"]
    f, b = flops_nemotron_h.ssm_update_cost(
        updates, *flops_nemotron_h.mamba_shape(cfg))
    peaks = harness.peaks_for(ctx["device"]["kind"], ctx["base"])
    share, bound = flops.roofline_share(f, b, seconds, peaks)
    ctx.setdefault("notes", []).append(
        f"state-space decode updates: {len(inside)} kernel events in "
        f"{len(spans)} whole segments, {seconds * 1e3:.1f} ms "
        f"({100 * seconds / tr['busy_s']:.1f}% of busy time), "
        f"{updates:.0f} (slot, layer) updates ({b / seconds / 1e9:.0f} "
        f"GB/s), {bound}-bound")
    return share
