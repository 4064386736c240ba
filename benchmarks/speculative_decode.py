"""Speculative decoding throughput + acceptance rate — the bench-visible
scenario for paddle_tpu.serving.SpeculativeDecoder (ROADMAP item 3).

Scenario: GPT-2-small target, SELF-speculation draft — the target's own
weights reading an int8-quantized KV cache. The draft's per-token cache
read halves while its argmax agrees with the full-precision target on
most steps (quantization noise rarely flips a greedy choice), so the
target's weights stream once per ROUND instead of once per token and the
emitted stream stays EXACTLY the full-precision greedy one (the verify
pass guarantees it for any acceptance pattern — tests/test_serving.py).

Headline columns: delivered tokens/sec, acceptance_rate, and
``hbm_bw_util`` for the modeled bytes actually streamed per emitted token
(draft cache reads + one target verify per round, amortized over
1 + accepted tokens). A separate tiny-draft row (2-layer d256) shows the
classic small-draft trade: cheaper proposals, lower acceptance.

Timing note: each draft proposal is its own dispatch here (k-1 per
round), so the HOST-side rate includes k-1 dispatch latencies per round;
the acceptance rate and bytes model do not depend on it.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.mfu import attach_hbm_bw
from benchmarks.serving_decode import (MAX_LEN, N_HEADS, N_LAYERS,
                                       D_MODEL, PROMPT, VOCAB, build,
                                       _param_bytes)

STEPS = 192      # leaves 2k rollback margin under max_len (k <= 16)
K = 4


def _spec_row(tag, model, p16, draft_model, draft_params, draft_kv, prompt,
              note_extra=""):
    from paddle_tpu.serving import SpeculativeDecoder

    batch = prompt.shape[0]
    sd = SpeculativeDecoder(model, p16, draft_model, draft_params, k=K,
                            draft_kv_dtype=draft_kv)
    out, _ = sd.generate(np.asarray(prompt), 8)          # compile + warm
    t0 = time.perf_counter()
    out, stats = sd.generate(np.asarray(prompt), STEPS)
    dt = time.perf_counter() - t0
    delivered = out.size
    toks_sec = delivered / dt

    # modeled HBM bytes per EMITTED token (batch-wide tokens, consistent
    # with toks_sec): every round streams the draft's weights + cache k
    # times (k-1 proposals + the cache-fill step) and the target's weights
    # + cache once (the verify), then yields batch*(1 + accepted) tokens.
    # Cache terms resolve through the ONE registered kernel byte model
    # (obs/roofline.py) — same resolution as the live gauges
    from paddle_tpu.obs import roofline

    d_head = D_MODEL // N_HEADS
    read = MAX_LEN                                        # unbucketed reads
    t_bytes = _param_bytes(p16) + roofline.kernel_cost(
        "decode_attention", batch=batch, read=read, n_heads=N_HEADS,
        d_head=d_head, layers=N_LAYERS, kv_dtype=None, itemsize=2)
    dm = draft_model.blocks[0]
    d_bytes = _param_bytes(draft_params) + roofline.kernel_cost(
        "decode_attention", batch=batch, read=read, n_heads=dm.n_heads,
        d_head=dm.d_head, layers=len(draft_model.blocks),
        kv_dtype=draft_kv, itemsize=2)
    per_round = (K if K > 1 else 0) * d_bytes + t_bytes
    toks_per_round = delivered / max(stats["rounds"], 1)  # batch-wide
    bytes_per_tok = per_round / toks_per_round
    # plain greedy: one target stream per dispatch, which emits `batch`
    # tokens — so per emitted token it costs t_bytes / batch
    plain_per_tok = t_bytes / batch
    bw = bytes_per_tok * toks_sec / 1e9                   # total bytes/sec
    row = {"metric": f"transformer_lm_decode_speculative_tokens_per_sec_"
                     f"{tag}_k{K}_bs{batch}_prompt{PROMPT}_gen{STEPS}",
           "value": round(toks_sec, 1), "unit": "tokens/sec",
           "vs_baseline": None,
           "acceptance_rate": round(stats["acceptance_rate"], 3),
           "rounds": stats["rounds"],
           "tokens_per_round": round(toks_per_round / batch, 2),
           "bytes_per_token_mb": round(bytes_per_tok / 1e6, 2),
           "projected_bytes_reduction": round(plain_per_tok
                                              / bytes_per_tok, 3),
           "hbm_bw_gbps": round(bw, 1),
           "note": "greedy speculative decode, output exactly equals "
                   "plain greedy (verify pass, tests/test_serving.py); "
                   "bytes model: k draft streams (k-1 proposals + cache "
                   "fill) + 1 target verify per round, amortized over "
                   "emitted tokens" + note_extra}
    # per-token bytes over per-token time: same utilization ratio as the
    # whole-run totals, but gbytes_per_step stays an honest per-token figure
    return attach_hbm_bw(row, bytes_per_tok, dt / max(delivered, 1),
                         methodology="modeled")


def run(batch: int = 8) -> dict:
    """Driver row: int8-KV self-speculation (same weights, quantized cache
    draft)."""
    model, p16, prompt = build(batch)
    return _spec_row("int8self", model, p16, model, p16, "int8", prompt,
                     "; draft = target reading int8 KV (self-speculation)")


def run_tiny_draft(batch: int = 8) -> dict:
    """2-layer d256 random-init draft: the cheap-draft/low-acceptance end
    of the trade (a TRAINED small draft would sit between the two rows)."""
    from paddle_tpu.models import TransformerLM

    model, p16, prompt = build(batch)
    draft = TransformerLM(VOCAB, d_model=256, n_heads=4, n_layers=2,
                          max_len=MAX_LEN)
    dparams = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a,
        draft.init(jax.random.PRNGKey(1)))
    return _spec_row("draft2x256", model, p16, draft, dparams, None, prompt,
                     "; draft = untrained 2-layer d256 (acceptance floor)")


if __name__ == "__main__":
    import json
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    print(json.dumps(run()), flush=True)
    print(json.dumps(run_tiny_draft()), flush=True)
