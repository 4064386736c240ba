"""Shared by the readers of the program's leaf spans and admission stamps
(a leading underscore keeps it out of the metric listing): the obs dump's
spans on the unix clock, children by ``parent`` id, the window filter, the
union of intervals, and the request ledger's queue wait split in two.

A dump from a program that has no such span or stamp (the parent of the PR
that added them) gives empty lists, and the readers then report nothing."""

import bisect

from chipbench.metrics._serve_common import median  # noqa: F401
from chipbench.metrics._serve_common import window_timelines


def origin(ctx):
    return float(ctx["obs"]["meta"].get("clock_origin_unix", 0.0))


def spans(ctx, name=None):
    """The dump's spans as dicts with ``t0``/``t1`` (unix seconds) added,
    in order of start; ``name`` keeps those of one name."""
    o = origin(ctx)
    out = []
    for e in ctx["obs"].get("events", []):
        if "dur" not in e or (name is not None and e.get("name") != name):
            continue
        t0 = o + float(e["ts"])
        out.append(dict(e, t0=t0, t1=t0 + float(e["dur"])))
    out.sort(key=lambda e: e["t0"])
    return out


def instants(ctx, names):
    """(tid, unix time, args) of the dump's instants called one of
    ``names``."""
    o = origin(ctx)
    return [(e.get("tid"), o + float(e["ts"]), e.get("args", {}))
            for e in ctx["obs"].get("events", [])
            if "dur" not in e and e.get("name") in names]


def in_window(ctx, e):
    t0, t1 = ctx["window"]
    return t0 <= e["t0"] <= t1


def children(ctx):
    """{span id: [child spans]} over the whole dump."""
    out = {}
    for e in spans(ctx):
        if e.get("parent") is not None:
            out.setdefault(e["parent"], []).append(e)
    return out


def host_parts(ctx, envelope, waited="serving.fetch"):
    """[(span, seconds of it outside its ``waited`` children)] of the
    window's ``envelope`` spans; an envelope with no such child (a program
    without the leaf spans) is left out."""
    kids, out = children(ctx), []
    for e in spans(ctx, envelope):
        waits = [k["t1"] - k["t0"] for k in kids.get(e.get("id"), ())
                 if k["name"] == waited]
        if waits and in_window(ctx, e):
            out.append((e, (e["t1"] - e["t0"]) - sum(waits)))
    return out


def next_after(sorted_spans, t, same_tid=None):
    """The first of ``sorted_spans`` (by start) that starts at or after
    ``t`` (on thread ``same_tid`` when given); None when there is none."""
    i = bisect.bisect_left([e["t0"] for e in sorted_spans], t)
    for e in sorted_spans[i:]:
        if same_tid is None or e.get("tid") == same_tid:
            return e
    return None


def union_seconds(intervals):
    """Summed length of the union of (start, end) intervals."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def queue_split(ctx):
    """[(seconds from arrival to the first admission round, seconds from
    that round to admission, what that round lacked or None)] of the
    window's requests (request ledger, the ``queued`` record); requests
    whose record carries no ``blocked_s`` are left out."""
    out = []
    for tl in window_timelines(ctx).values():
        q = tl.get("queued")
        if q is None or "blocked_s" not in q:
            continue
        blocked = float(q["blocked_s"])
        out.append((max(0.0, float(q["dur"]) - blocked), blocked,
                    q.get("blocked_by")))
    return out
