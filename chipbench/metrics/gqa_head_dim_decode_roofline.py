"""``gqa_decode_roofline`` for a configuration that STATES its head size
(``head_dim``: ``nemotron_h``'s 32 query heads of 128 over 2 KV heads under
a hidden size of 2688). That reader takes the head as ``hidden_size //
heads`` (84 here) and is not asked of such a cell; this one hands it the
same run with the hidden size the stated head implies, so the count is its
own: K and V of the KV heads of every LIVE cache row once a call, whatever
the size of a group, against the summed device time of the events named
``paged_decode_attention``. A group of 16 reads low: the kernel forms q k a
QUERY head on the VPU, 16 heads' arithmetic a KV head's bytes (PERF.md
section 7). A configuration without ``head_dim``, no such event in the
trace, or no ledger: nothing is reported."""

from chipbench import harness


def read(ctx):
    cfg = ctx["config"]
    if not {"head_dim", "num_attention_heads"} <= set(cfg):
        return None
    stated = dict(cfg, hidden_size=cfg["num_attention_heads"]
                  * cfg["head_dim"])
    ctx.setdefault("notes", [])     # the copy below shares this list
    return harness.load_module("metrics", "gqa_decode_roofline",
                               ctx["base"]).read(dict(ctx, config=stated))
