"""Keys the value reads took over keys the indexer scored, in the window's
decode segments that read through the selection: ``(keys_selected -
dense_rows) / keys_scored`` summed over the ``serving.segment`` spans (the
program's own counts, the selected one counted on the device by the
selection itself), in %. About ``2048 / mean context``; 100 means nothing
was selected. No span carries the counts (the parent): nothing is
reported."""

from chipbench.metrics._serve_common import window_spans


def read(ctx):
    spans = [a for _, _, a in window_spans(ctx, "serving.segment")
             if a.get("keys_scored")]
    scored = sum(float(a["keys_scored"]) for a in spans)
    if not scored:
        return None
    took = sum(float(a["keys_selected"]) - float(a.get("dense_rows", 0))
               for a in spans)
    return 100.0 * took / scored
