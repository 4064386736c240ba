"""Share of its roofline the chunked (state-space-duality) scan of the
Mamba-2 layers reached in the ADMISSIONS of the traced seconds: the
operations and bytes of every REAL prompt token a Mamba layer
(chipbench/flops_nemotron_h.py: a token's row of ``C B^T``, the masked
product with ``X``, the chunk's state and the state to the output; ``x, B,
C, dt, y`` once) — padding is not work — against the summed device time of
the kernel's events (``ssd_chunk_scan``), by the tighter of the two bounds.

Counts and time are taken over the same programs: the ``serving.prefill``
spans that lie wholly inside the trace give ``prompt_tokens`` (the admit
program's own count of the positions inside their row's length, returned
beside its first tokens), and only the kernel events inside those spans
are summed. No such event or no such span argument (a parent that has
neither): nothing is reported."""

from chipbench import flops, flops_nemotron_h, harness
from chipbench.metrics._lfm2_common import events_inside, spans_inside


def read(ctx):
    tr = ctx.get("trace")
    cfg = ctx["config"]
    if tr is None or "hybrid_override_pattern" not in cfg:
        return None
    spans = [s for s in spans_inside(ctx, tr, "serving.prefill")
             if "prompt_tokens" in s[2]]
    inside = events_inside(tr, "ssd_chunk_scan", spans)
    tokens = sum(float(args["prompt_tokens"]) for _, _, args in spans)
    if not inside or not tokens:
        return None
    layers = flops_nemotron_h.layer_counts(cfg)["M"]
    seconds = sum(d for _, _, d in inside) / tr["chips"]
    f, b = flops_nemotron_h.ssd_scan_cost(
        tokens * layers, *flops_nemotron_h.mamba_shape(cfg),
        cfg["chunk_size"], 2)
    peaks = harness.peaks_for(ctx["device"]["kind"], ctx["base"])
    share, bound = flops.roofline_share(f, b, seconds, peaks)
    ctx.setdefault("notes", []).append(
        f"chunked scan in admissions: {len(inside)} kernel events in "
        f"{len(spans)} whole admissions, {seconds * 1e3:.1f} ms, "
        f"{tokens:.0f} prompt tokens x {layers} layers "
        f"({f / seconds / 1e12:.1f} TFLOP/s), {bound}-bound")
    return share
