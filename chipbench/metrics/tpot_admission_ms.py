"""Milliseconds a token (after the first) a request spent behind OTHER
requests' admissions: the median, over the window's finished requests with
more than one token, of ``stalled_s / (tokens - 1)`` from the request
ledger's ``done`` record — the ``serving.prefill`` spans that ran while the
request was already live (its own admission is TTFT's)."""

from chipbench.metrics._iteration_account import token_costs
from chipbench.metrics._serve_common import median


def read(ctx):
    rows = token_costs(ctx)
    return median([r["admissions"] for r in rows]) if rows else None
