"""How unevenly the router loaded the held experts over the window's decode
segments: the busiest (layer, held expert) cell's (token, choice) pairs over
the mean cell's, summed over the ``serving.segment`` spans (``load_max``
against ``routed_here`` / cells). 1.0 is an even load; the grouped products
pay for the busiest expert's tiles. No such span argument (the parent has
no expert layer): nothing is reported."""

from chipbench.metrics._serve_common import window_spans


def read(ctx):
    cfg = ctx["config"]
    cells = (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]) \
        * len(cfg.get("experts_held", []))
    top = mean = 0.0
    for _, _, args in window_spans(ctx, "serving.segment"):
        if "load_max" in args:
            top += float(args["load_max"])
            mean += float(args["routed_here"]) / max(cells, 1)
    return top / mean if mean > 0 else None
