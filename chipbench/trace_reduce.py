"""From a profiler trace (``*.xplane.pb``) to device busy/idle time, time by
operation, and idle gaps named by what the host was doing — the benchmark's
own reduction, read with ``jax.profiler.ProfileData`` and nothing of the
program's.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed HLO operation (a Pallas kernel is a custom call whose
event carries the kernel's name), ``XLA Modules`` one per program run.
A trace's timestamps count from the start of the profiling session, the obs
dump's spans are on the unix clock (``meta.clock_origin_unix``): ``align``
finds the shift between them from the programs themselves — every run of an
XLA module lies inside the host span that dispatched it and waited for it —
and a gap on the device is then named after the innermost host span that
covers it.
"""

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: operations that only contain others (a scan's ``while`` spans its whole
#: loop): left out, so that busy time is the union of the operations that
#: did the work and a loop's time is not counted twice
CONTAINERS = ("while", "conditional", "call")


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def device_events(path):
    """{plane name: {"ops": [(name, start_s, dur_s)], "modules": [...]}} for
    every device plane that executed something."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {}
        for line in plane.lines:
            lines[line.name] = [(e.name, e.start_ns * 1e-9,
                                 e.duration_ns * 1e-9) for e in line.events]
        ops = [op for op in lines.get(OPS_LINE, ())
               if stable_name(op[0]) not in CONTAINERS]
        if ops:
            out[plane.name] = {"ops": ops,
                               "modules": lines.get(MODULES_LINE, [])}
    return out


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def stable_name(name):
    """An operation's name without the numbering the compiler gives it:
    ``fusion.123`` -> ``fusion``, ``%convolution.4 = ...`` -> ``convolution``;
    what follows a ``/`` (the fixture's op path) is kept."""
    name = name.split(" = ")[0].lstrip("%")
    return re.sub(r"\.\d+", "", name)


def host_spans(obs_dump):
    """(name, start_unix_s, end_unix_s) of every span in an obs dump."""
    if not obs_dump:
        return []
    origin = float(obs_dump.get("meta", {}).get("clock_origin_unix", 0.0))
    out = []
    for ev in obs_dump.get("events", []):
        if ev.get("ph", "X") != "X" or "dur" not in ev:
            continue
        ts, dur = float(ev["ts"]), float(ev["dur"])
        out.append((ev["name"], origin + ts, origin + ts + dur))
    return out


#: idle gaps shorter than this are the device's own pauses between two
#: operations of one program; they are summed under one name, not looked up
SHORT_GAP_S = 20e-6
SHORT_GAP_NAME = "between operations (<20us each)"


class SpanIndex:
    """Host spans by time bin, so that naming a gap looks at the few spans
    near it and not at every poll the daemon ever answered."""

    def __init__(self, spans, lo, hi, width=0.01):
        self.lo, self.width, self.bins = lo, width, {}
        for sp in spans:
            if sp[2] < lo or sp[1] > hi:
                continue
            for b in range(self._bin(max(sp[1], lo)),
                           self._bin(min(sp[2], hi)) + 1):
                self.bins.setdefault(b, []).append(sp)

    def _bin(self, t):
        return int((t - self.lo) / self.width)

    def name(self, gap):
        """The innermost host span that covers an idle gap: of the spans
        that cover at least half of it the shortest; failing that the one
        that covers most; ``host:none`` when nothing overlaps it."""
        near = {sp for b in range(self._bin(gap[0]), self._bin(gap[1]) + 1)
                for sp in self.bins.get(b, ())}
        length = gap[1] - gap[0]
        half, most = None, None
        for name, s, e in near:
            cover = min(e, gap[1]) - max(s, gap[0])
            if cover <= 0:
                continue
            if cover >= 0.5 * length and (half is None or e - s < half[0]):
                half = (e - s, name)
            if most is None or cover > most[0]:
                most = (cover, name)
        return (half or most or (0, "host:none"))[1]


def align(modules, spans, guess, slack=2.0):
    """Seconds to add to trace time to get unix time: the shift under which
    the most module runs lie wholly inside a host span; of the shifts that
    tie, the largest (a program starts right after its span does, so the
    true shift is the tightest lower bound). ``guess`` is the unix time at
    which the profiler was started; the answer lies within ``slack`` of it.
    Only spans long enough to hold a module run are looked at. None when
    there is nothing to align."""
    mods = [(s, s + d) for _, s, d in modules]
    if not mods or not spans:
        return None
    shortest = min(m1 - m0 for m0, m1 in mods)
    t_lo = guess + min(m0 for m0, _ in mods) - slack
    t_hi = guess + max(m1 for _, m1 in mods) + slack
    big = [(a, b) for _, a, b in spans
           if b - a >= shortest and b >= t_lo and a <= t_hi]
    if not big:
        return None

    def score(shift):
        return sum(any(a <= m0 + shift and m1 + shift <= b for a, b in big)
                   for m0, m1 in mods)
    cands = sorted({a - m0 + 1e-6 for m0, m1 in mods for a, b in big
                    if b - a >= m1 - m0 and abs(a - m0 - guess) <= slack})
    if not cands:
        return None
    scored = [(score(c), c) for c in cands]
    best = max(sc for sc, _ in scored)
    return max(c for sc, c in scored if sc == best) if best else None


def reduce(path, obs_dump=None, top=10, started_unix=None):
    """The whole reduction of one trace file; None when no device plane
    executed anything (a CPU rehearsal). ``started_unix``: when the
    profiler was started, for ``align``; without it (or without spans to
    align with) gaps stay unnamed."""
    planes = device_events(path)
    if not planes:
        return None
    spans = host_spans(obs_dump)
    shift = None
    if started_unix is not None:
        shift = align([m for p in planes.values() for m in p["modules"]],
                      spans, started_unix)
    spans = [] if shift is None else [(n, a - shift, b - shift)
                                      for n, a, b in spans]
    busy_each, window_each = [], []
    by_op, gaps_by_name = {}, {}
    for plane in planes.values():
        ops = plane["ops"]
        merged = union((s, s + d) for _, s, d in ops)
        t0, t1 = merged[0][0], merged[-1][1]
        index = SpanIndex(spans, t0, t1)
        busy_each.append(sum(e - s for s, e in merged))
        window_each.append(t1 - t0)
        for name, _, d in ops:
            key = stable_name(name)
            by_op[key] = by_op.get(key, 0.0) + d
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            who = (SHORT_GAP_NAME if s1 - e0 < SHORT_GAP_S
                   else index.name((e0, s1)))
            gaps_by_name[who] = gaps_by_name.get(who, 0.0) + (s1 - e0)
    n = len(planes)
    busy, window = sum(busy_each) / n, sum(window_each) / n
    rank = lambda d: [[k, v / n] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": busy, "window_s": window,
            "idle_pct": 100.0 * (1.0 - busy / window),
            "raw_ops": [op for p in planes.values() for op in p["ops"]],
            "modules": [m for p in planes.values() for m in p["modules"]],
            "chips": n, "shift": shift,
            "breakdown": {"device_ops": rank(by_op),
                          "idle_gaps": rank(gaps_by_name)},
            "summary": f"{n} device plane(s), busy {busy:.4f}s of "
                       f"{window:.4f}s ({100 * busy / window:.1f}%), "
                       f"{len(by_op)} distinct operations; clock shift "
                       f"{'not found' if shift is None else round(shift, 4)}"}


def reduce_dir(trace_dir, obs_dump=None, started_unix=None):
    path = find_xplane(trace_dir)
    return reduce(path, obs_dump, started_unix=started_unix) if path else None
