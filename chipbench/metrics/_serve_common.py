"""Shared by the serve cells' readers (a leading underscore keeps it out of
the metric listing): the window's spans and request timelines."""

from chipbench import harness


def window_spans(ctx, name):
    """(start_unix, dur_s, args) of the daemon's spans ``name`` that began
    inside the measured window."""
    origin = float(ctx["obs"]["meta"].get("clock_origin_unix", 0.0))
    t0, t1 = ctx["window"]
    return [(origin + float(e["ts"]), float(e["dur"]), e.get("args", {}))
            for e in ctx["obs"]["events"]
            if e.get("name") == name and "dur" in e
            and t0 <= origin + float(e["ts"]) <= t1]


def window_timelines(ctx):
    """{key: {phase: event}} of the request ledger's timelines for the
    window's own requests (their keys are the client's)."""
    keys = {r["key"] for r in ctx["records"]}
    out = {}
    for tl in ctx["obs"]["requests"]:
        if tl.get("key") in keys:
            out[tl["key"]] = {ev["phase"]: ev for ev in tl["events"]}
    return out


median = lambda xs: harness.percentile(xs, 50)
