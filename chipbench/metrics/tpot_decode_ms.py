"""Milliseconds a token (after the first) a request spent in its own decode
segments: the median, over the window's finished requests with more than one
token, of ``decode_s / (tokens - 1)`` from the request ledger's ``done``
record. A note sets a token's time out in full: decode + admissions + host
(the scheduler's ``serving.schedule`` and ``serving.emit`` spans inside the
request's decode life) against the daemon's own TPOT (done - first token on
its clock) and what no span covers — as medians, and as means, which add
where medians of skewed terms do not — beside the client's ``tpot_p50_ms`` —
the difference is delivery: polling, and tokens that arrive a segment at a
time."""

from chipbench.metrics._iteration_account import token_costs
from chipbench.metrics._serve_common import median


def read(ctx):
    rows = token_costs(ctx)
    if not rows:
        return None
    mid = {k: median([r[k] for r in rows]) for k in rows[0]}
    parts = mid["decode"] + mid["admissions"] + mid["host"]
    loose = median([r["life"] - r["decode"] - r["admissions"] - r["host"]
                    for r in rows])
    # medians of skewed terms do not add (a burst's admissions fall on few
    # requests); means do, so they show what the account leaves out
    mean = {k: sum(r[k] for r in rows) / len(rows) for k in rows[0]}
    covered = mean["decode"] + mean["admissions"] + mean["host"]
    client = ctx.get("values", {}).get("tpot_p50_ms")
    ctx.setdefault("notes", []).append(
        f"a token's time, medians over {len(rows)} requests, ms: decode "
        f"{mid['decode']:.3f} + admissions {mid['admissions']:.3f} + host "
        f"{mid['host']:.3f} = {parts:.3f}; the daemon's own TPOT "
        f"{mid['life']:.3f} ({100 * (parts / mid['life'] - 1):+.1f} %; a "
        f"request's time under no span or account: median {loose:.3f}); "
        f"means: {mean['decode']:.3f} + {mean['admissions']:.3f} + "
        f"{mean['host']:.3f} = {covered:.3f} of {mean['life']:.3f} "
        f"({100 * (covered / mean['life'] - 1):+.1f} %)"
        + ("" if client is None else
           f"; the client's tpot_p50_ms {client:.3f}: delivery "
           f"{client - mid['life']:+.3f}"))
    return mid["decode"]
