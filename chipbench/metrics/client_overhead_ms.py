"""What the wire and the poll add to a request's first token: the median,
over the window's requests, of the client's time from submit to first token
minus the daemon's own TTFT for the SAME request (request ledger,
``first_token.ttft_s``; matched by the client's submit key)."""

from chipbench.metrics._serve_common import median, window_timelines


def read(ctx):
    tls = window_timelines(ctx)
    gaps = []
    for r in ctx["records"]:
        ft = tls.get(r["key"], {}).get("first_token")
        if ft is None or r["first"] is None or r["sent"] is None:
            continue
        gaps.append((r["first"] - r["sent"] - float(ft["ttft_s"])) * 1e3)
    return median(gaps) if gaps else None
