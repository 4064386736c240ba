"""``flash_prefill_roofline`` for a configuration that STATES its head size
(``head_dim``: ``afmoe``'s 32 query heads of 128 under a hidden size of
2048), as ``gqa_head_dim_decode_roofline`` is to ``gqa_decode_roofline``.
That reader takes the head as ``hidden_size // heads`` (64 here), finds
events of another width and reports nothing; this one hands it the same run
with the hidden size the stated head implies, so the count is its own: the
causal squares of the events named ``flash_attention_fwd`` inside whole
admissions — here the FULL layers' (the sliding layers' band runs under
``flash_window_attention_fwd``: ``window_flash_prefill_roofline``). A
configuration without ``head_dim``, or no such event: nothing is
reported."""

from chipbench import harness


def read(ctx):
    cfg = ctx["config"]
    if not {"head_dim", "num_attention_heads"} <= set(cfg):
        return None
    stated = dict(cfg, hidden_size=cfg["num_attention_heads"]
                  * cfg["head_dim"])
    ctx.setdefault("notes", [])     # the copy below shares this list
    return harness.load_module("metrics", "flash_prefill_roofline",
                               ctx["base"]).read(dict(ctx, config=stated))
