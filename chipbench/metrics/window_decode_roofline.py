"""Share of its (memory) roofline the WINDOWED paged decode read reached in
the decode segments of the traced seconds: K and V of the KV heads of the
rows a sliding layer's window covers — ``min(pos + 1, window)`` a live slot
a step, the program's own count (``window_rows`` on ``serving.segment``) —
once a call whatever the size of a group, for each of the configuration's
sliding layers (chipbench/flops_afmoe.py), against the summed device time
of the kernel's events.

The kernel is found by its own name (``paged_window_attention``: the read
through a ring, which stops at the window — ``paged_decode_attention``, the
full layers' read, is ``gqa_head_dim_decode_roofline``'s). Counts and time
are taken over the same programs: the segments that lie wholly inside the
trace, and only the events inside them. No such event or no such span
argument (the parent has neither): nothing is reported."""

from chipbench import flops, flops_afmoe, harness
from chipbench.metrics._lfm2_common import events_inside, spans_inside


def read(ctx):
    tr = ctx.get("trace")
    cfg = ctx["config"]
    if tr is None or "sliding_window" not in cfg:
        return None
    segs = [s for s in spans_inside(ctx, tr, "serving.segment")
            if "window_rows" in s[2]]
    inside = events_inside(tr, "paged_window_attention", segs)
    rows = sum(float(args["window_rows"]) for _, _, args in segs)
    if not inside or not rows:
        return None
    layers = flops_afmoe.layer_counts(cfg)["sliding"]
    f, b = flops_afmoe.window_decode_cost(
        rows * layers, cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"], 2)
    seconds = sum(d for _, _, d in inside) / tr["chips"]
    peaks = harness.peaks_for(ctx["device"]["kind"], ctx["base"])
    share, bound = flops.roofline_share(f, b, seconds, peaks)
    ctx.setdefault("notes", []).append(
        f"windowed decode read: {len(inside)} kernel events in {len(segs)} "
        f"whole segments, {seconds * 1e3:.1f} ms "
        f"({100 * seconds / tr['busy_s']:.1f}% of busy time), {rows:.0f} "
        f"window rows a layer ({b / seconds / 1e9:.0f} GB/s), {bound}-bound")
    return share
