"""Milliseconds a step the device has no step queued: from the end of step
n's ``trainer.device_wait`` (the device has finished, the host knows) to the
end of step n+1's ``trainer.dispatch`` (the next program is handed over) —
the loss fetch, the event handler, the reader and feeder, and the dispatch
itself. Median over the window's steps; over the step time it is the share
of the device's idle time the train loop's host code is answerable for."""

from chipbench.metrics._span_tree import in_window, median, spans


def read(ctx):
    waits = spans(ctx, "trainer.device_wait")
    gaps, i = [], 0
    for d in spans(ctx, "trainer.dispatch"):
        while i + 1 < len(waits) and waits[i + 1]["t1"] <= d["t0"]:
            i += 1
        if not waits or waits[i]["t1"] > d["t0"] or not in_window(ctx, d):
            continue
        gaps.append((d["t1"] - waits[i]["t1"]) * 1e3)
    return median(gaps) if gaps else None
