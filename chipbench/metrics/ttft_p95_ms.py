"""95th percentile over the window's requests of first token seen by the client minus the time the request was DUE;
a failed, refused or unfinished request is a miss (infinite). Recorded, not
judged: on the same work its run-to-run spread (5-17 % for TTFT, PERF.md
PR 23) is wider than any bound the contract allows, so the median stands as
the end-to-end metric and the tail beside it here."""


def read(ctx):
    value = ctx["summary"]["ttft_p95_ms"]
    return None if value == float("inf") else value
