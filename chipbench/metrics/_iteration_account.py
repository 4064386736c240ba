"""Shared by the four readers of the serve iteration's accounts (a leading
underscore keeps it out of the metric listing): what an admission ran against
what it was asked for (``rows`` / ``prompt_tokens`` / ``positions`` on the
``serving.prefill`` span), what a segment ran against what it delivered
(``slot_steps`` / ``live_steps`` / ``emitted`` on the ``serving.emit`` span
behind it), and every finished request's decode life split into its own
segments and other requests' admissions (``decode_s`` / ``stalled_s`` on the
request ledger's ``done`` record).

All of it is read from the window's own spans and the window's own requests,
never from a counter's running total, which holds the warm-up too. A dump
from a program that keeps no such account (the parent of the PR that added
it) gives empty lists, and the readers then report nothing."""

from chipbench.metrics._serve_common import window_spans
from chipbench.metrics._span_tree import spans

ADMISSION = ("rows", "prompt_tokens", "positions")
SEGMENT = ("slot_steps", "live_steps", "emitted")
LIFE = ("decode_s", "stalled_s")
#: the scheduler's own spans: what stands between a request's segments and
#: the admissions it waits behind
HOST_SPANS = ("serving.schedule", "serving.emit")


def admissions(ctx):
    """[(rows, prompt_tokens, positions)] of the window's admissions that
    ran a program (an adoption of shipped pages runs none)."""
    return [tuple(int(a[k]) for k in ADMISSION)
            for _, _, a in window_spans(ctx, "serving.prefill")
            if all(k in a for k in ADMISSION) and a["positions"]]


def segments(ctx):
    """[(slot_steps, live_steps, emitted)] of the window's segments."""
    return [tuple(int(a[k]) for k in SEGMENT)
            for _, _, a in window_spans(ctx, "serving.emit")
            if a.get("after") == "segment" and all(k in a for k in SEGMENT)]


def share(part, whole):
    return 100.0 * part / whole


def token_costs(ctx):
    """One row a finished request of the window with more than one token,
    in ms a token after the first: {"life": done - first token on the
    daemon's clock, "decode": ``decode_s``, "admissions": ``stalled_s``,
    "host": the scheduler's ``HOST_SPANS`` inside that life}. What the
    three parts leave of ``life`` is time no span and no account covers."""
    keys = {r["key"] for r in ctx["records"]}
    host = [(e["t0"], e["t1"]) for name in HOST_SPANS
            for e in spans(ctx, name)]
    rows = []
    for tl in ctx["obs"].get("requests", []):
        if tl.get("key") not in keys:
            continue
        at = {ev["phase"]: ev for ev in tl["events"]}
        first, done = at.get("first_token"), at.get("done")
        if first is None or done is None \
                or not all(k in done for k in LIFE):
            continue
        gaps = int(done.get("tokens", 0)) - 1
        if gaps < 1:
            continue
        origin = float(tl.get("origin", 0.0))
        t0, t1 = origin + float(first["t"]), origin + float(done["t"])
        inside = sum(max(0.0, min(b, t1) - max(a, t0)) for a, b in host)
        per = 1e3 / gaps
        rows.append({"life": (t1 - t0) * per,
                     "decode": float(done["decode_s"]) * per,
                     "admissions": float(done["stalled_s"]) * per,
                     "host": inside * per})
    return rows

