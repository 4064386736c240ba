"""Host milliseconds of one decode segment: a ``serving.segment`` span less
its ``serving.fetch`` child (the device wait and the copy back), plus the
``serving.emit`` span that follows it on the scheduler's thread (the locked
token hand-out) — what stands between two segments besides admissions.
Median over the window's segments."""

from chipbench.metrics._span_tree import (host_parts, median, next_after,
                                          spans)


def read(ctx):
    emits = [e for e in spans(ctx, "serving.emit")
             if e.get("args", {}).get("after") == "segment"]
    host = []
    for e, seconds in host_parts(ctx, "serving.segment"):
        emit = next_after(emits, e["t1"], e.get("tid"))
        host.append((seconds + (emit["t1"] - emit["t0"] if emit else 0.0))
                    * 1e3)
    return median(host) if host else None
