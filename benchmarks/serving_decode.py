"""KV-cache incremental decode throughput WITH roofline accounting — the
serving path (feeds the C inference ABI, capi/gradient_machine.h:73).

Decode is memory-bound: every token streams the bf16 weights plus the live
KV-cache rows from HBM. So next to ms/token this prints what MFU is to
training rows: bytes moved per step and the achieved fraction of the v5e's
~819 GB/s HBM bandwidth. Bucketed cache reads (generate_cached's ``bucket``)
keep the cache term proportional to the CURRENT position instead of the
max_len padding.

Timing: whole decode is one (or few, bucketed) jitted scans — a single
dispatch per segment, so the per-call dispatch latency amortizes; the
reported rate divides by the total generated tokens.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

VOCAB = 50257
D_MODEL, N_HEADS, N_LAYERS, MAX_LEN = 768, 12, 12, 1024
PROMPT, STEPS = 128, 256


def _param_bytes(params) -> int:
    return sum(a.size * 2 for a in jax.tree_util.tree_leaves(params)
               if hasattr(a, "size"))            # bf16 on the wire


def build(batch: int):
    from paddle_tpu.models import TransformerLM

    model = TransformerLM(VOCAB, d_model=D_MODEL, n_heads=N_HEADS,
                          n_layers=N_LAYERS, max_len=MAX_LEN)
    params = model.init(jax.random.PRNGKey(0))
    p16 = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16)
        if a.dtype == jnp.float32 else a, params)
    rs = np.random.RandomState(0)
    prompt = jnp.asarray(rs.randint(0, VOCAB, (batch, PROMPT)), jnp.int32)
    return model, p16, prompt


def _avg_step_bytes(model, params, batch: int, bucket,
                    kv_dtype=None) -> float:
    """Average HBM bytes per decode step: weights + live cache rows.

    The cache term resolves through the ONE registered kernel byte model
    (obs/roofline.py, registered by ops/pallas_kernels.py) — the same
    resolution the live ``fluid.device_bytes_total`` accounting and the
    ``kernels.bytes_total`` dispatch counters use, so this row and the
    live ``roofline.hbm_bw_util`` gauge can never disagree on the bytes
    side of the formula."""
    from paddle_tpu.obs import roofline

    w = _param_bytes(params)
    d_head = D_MODEL // N_HEADS
    total_cache = 0.0
    for i in range(STEPS):
        pos = PROMPT + i
        read = (MAX_LEN if bucket is None
                else min(-(-(pos + 1) // bucket) * bucket, MAX_LEN))
        total_cache += roofline.kernel_cost(
            "decode_attention", batch=batch, read=read, n_heads=N_HEADS,
            d_head=d_head, layers=N_LAYERS, kv_dtype=kv_dtype, itemsize=2)
    return w + total_cache / STEPS


def run_config(batch: int, bucket=256, kv_dtype=None) -> dict:
    model, p16, prompt = build(batch)

    # ONE jitted program for prefill + every bucketed segment scan: an
    # unjitted generate_cached runs the prefill eagerly, one dispatch per
    # op
    decode = jax.jit(lambda p, ids: model.generate_cached(
        p, ids, steps=STEPS, bucket=bucket, kv_dtype=kv_dtype))

    out = decode(p16, prompt)          # compile + warm
    int(out[0, -1])                    # fetch a token: forces completion
    t0 = time.perf_counter()
    out = decode(p16, prompt)
    int(out[0, -1])
    dt = time.perf_counter() - t0
    ms_tok = dt / STEPS * 1e3
    toks_sec = batch * STEPS / dt
    from benchmarks.mfu import attach_hbm_bw

    step_bytes = _avg_step_bytes(model, p16, batch, bucket, kv_dtype)
    bw = step_bytes / (ms_tok / 1e3) / 1e9
    note = ("GPT-2-small KV-cache greedy decode; bytes/step = bf16 "
            "weights + live cache rows (bucketed reads, shared kernel "
            "byte model); util vs the chip HBM peak "
            "(obs/roofline.PEAK_HBM_GBPS — null off-TPU)")
    row = {"metric": f"transformer_lm_decode_tokens_per_sec_bs{batch}"
                     f"_prompt{PROMPT}_gen{STEPS}"
                     + ("" if bucket is None else f"_bucket{bucket}")
                     + ("" if kv_dtype is None else f"_kv{kv_dtype}"),
           "value": round(toks_sec, 1), "unit": "tokens/sec",
           "vs_baseline": None,
           "ms_per_token": round(ms_tok, 3),
           "step_bytes_mb": round(step_bytes / 1e6, 1),
           "hbm_bw_gbps": round(bw, 1),
           "note": note}
    # bytes are an analytic model (Pallas cache reads are invisible to
    # XLA), so the row is honest about it: methodology="modeled"
    attach_hbm_bw(row, step_bytes, ms_tok / 1e3, methodology="modeled")
    if kv_dtype is not None:
        full = _avg_step_bytes(model, p16, batch, bucket, None)
        row["projected_bytes_reduction"] = round(full / step_bytes, 3)
        row["note"] = (note + f"; {kv_dtype} KV cache — bytes/step "
                       f"{step_bytes / 1e6:.1f} MB vs {full / 1e6:.1f} MB "
                       "full-precision (the projected reduction; tokens "
                       "follow the quantized-KV numerics contract, "
                       "docs/design/kernels.md)")
    return row


def run() -> dict:
    """Driver row: the strongest static config, bs64 bucketed (bs8/bs32 in
    __main__)."""
    return run_config(64)


def run_quantized() -> dict:
    """The int8-KV decode row: same workload as run(), cache read halved —
    the decode-roofline lever of ROADMAP item 3 (target >= 0.30 HBM-bw
    util; on bytes-bound decode the tokens/sec gain tracks the bytes
    reduction)."""
    return run_config(64, kv_dtype="int8")


def run_continuous(n_requests: int = 128, slots: int = 64,
                   segment: int = 64) -> dict:
    """Continuous (in-flight) batching over a MIXED workload: prompts and
    generation budgets each uniform in [32, 256], requests admitted into
    freed slots at segment boundaries (paddle_tpu/serving/batcher.py). Shapes are
    bucketed so the whole run compiles a handful of programs (prompt pad
    256; cache reads 512/1024). Exactness vs solo decode is proven in
    tests/test_serving.py; this row measures delivered tokens/sec."""
    from paddle_tpu.serving import ContinuousBatcher, Request

    model, p16, _ = build(slots)
    rs = np.random.RandomState(0)
    reqs = [Request(i, rs.randint(0, VOCAB, int(rs.randint(32, 257))),
                    int(rs.randint(32, 257)))
            for i in range(n_requests)]
    total_new = sum(r.max_new for r in reqs)

    b = ContinuousBatcher(model, p16, slots=slots, segment=segment,
                          cache_bucket=512, prompt_buckets=(256,))
    # warm EVERY program the measured pass will hit (compile is ~20-40 s
    # each and amortizes away in a long-running server): prompt 256 + gen 256 pushes positions past 512, compiling
    # both the cache_len=512 and =1024 segment scans plus the tpad-256
    # prefill and the merge
    warm = [Request(-1 - i, rs.randint(0, VOCAB, 256), 256)
            for i in range(slots)]
    b.serve(warm)

    t0 = time.perf_counter()
    got = b.serve(reqs)
    dt = time.perf_counter() - t0
    delivered = sum(len(v) for v in got.values())
    return {"metric": f"transformer_lm_continuous_batching_tokens_per_sec_"
                      f"slots{slots}_seg{segment}_mixed32-256",
            "value": round(delivered / dt, 1), "unit": "tokens/sec",
            "vs_baseline": None,
            "requests": n_requests, "delivered_tokens": delivered,
            "budget_tokens": total_new,
            "note": "in-flight batching, mixed prompt/gen lengths "
                    "U[32,256], longest-first admission, slot refill at "
                    "segment boundaries via ragged prefill + masked merge; "
                    "greedy tokens exactly equal solo decode "
                    "(tests/test_serving.py)"}


def run_paged(n_requests: int = 128, slots: int = 64,
              segment: int = 64) -> dict:
    """Paged-vs-pinned continuous batching: the SAME mixed U[32,256]
    workload as :func:`run_continuous`, served through the paged KV-cache
    (block pool + per-request block tables, serving/paged.py) instead of
    per-slot max_len rows. Reports delivered tokens/sec, the modeled
    HBM-bandwidth utilization of the decode segments, and the residency
    story: peak pool pages + mean page occupancy vs the pinned pool's
    slots*max_len rows — the 'HBM holds live tokens, not padding' claim,
    measured."""
    from paddle_tpu.serving import PagedBatcher, Request

    model, p16, _ = build(slots)
    block = 64
    rs = np.random.RandomState(0)
    reqs = [Request(i, rs.randint(0, VOCAB, int(rs.randint(32, 257))),
                    int(rs.randint(32, 257)))
            for i in range(n_requests)]

    b = PagedBatcher(model, p16, slots=slots, segment=segment,
                     page_block=block, cache_bucket=512,
                     prompt_buckets=(256,))
    # warm every program the measured pass hits: tpad-256 admission and
    # both cache-read buckets (nb=8 and nb=16)
    warm = [Request(-1 - i, rs.randint(0, VOCAB, 256), 256)
            for i in range(slots)]
    b.serve(warm)
    pool = b.pool
    pool.reset_tallies()

    t0 = time.perf_counter()
    got = b.serve(reqs)
    dt = time.perf_counter() - t0
    delivered = sum(len(v) for v in got.values())
    from benchmarks.mfu import attach_hbm_bw

    w = _param_bytes(p16)
    total_bytes = (pool.segments_total * segment * w
                   + pool.read_bytes_total)
    bw = total_bytes / dt / 1e9
    occupancy = (pool.occupancy_num / pool.occupancy_den
                 if pool.occupancy_den else 0.0)
    pinned_rows = slots * MAX_LEN
    peak_rows = max(pool.peak_pages_used, 1) * block
    row = {"metric": f"transformer_lm_continuous_batching_paged_tokens_"
                     f"per_sec_slots{slots}_seg{segment}_mixed32-256",
            "value": round(delivered / dt, 1), "unit": "tokens/sec",
            "vs_baseline": None,
            "requests": n_requests, "delivered_tokens": delivered,
            "hbm_bw_gbps": round(bw, 1),
            "page_occupancy": round(occupancy, 3),
            "peak_pages": pool.peak_pages_used,
            "cache_rows_pinned": pinned_rows,
            "cache_rows_paged_peak": peak_rows,
            "residency_ratio": round(pinned_rows / peak_rows, 2),
            "note": "paged KV-cache (block 64, shared pool, per-request "
                    "block tables) vs the pinned slots*max_len pool of "
                    "transformer_lm_continuous_batching_*: greedy tokens "
                    "exactly equal solo decode "
                    "(tests/test_serving_paged.py); residency_ratio = "
                    "pinned cache rows / paged peak rows — cache bytes "
                    "per resident token shrink by that factor, the "
                    "headroom for bigger live batches"}
    # per-delivered-token bytes/time (ratio-invariant vs the run totals) so
    # gbytes_per_step is comparable with run_config's per-token figure
    return attach_hbm_bw(row, total_bytes / max(delivered, 1),
                         dt / max(delivered, 1), methodology="modeled")


if __name__ == "__main__":
    import json
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    for bs in (8, 32, 64):
        print(json.dumps(run_config(bs)), flush=True)
    print(json.dumps(run_config(8, bucket=None)), flush=True)
    print(json.dumps(run_quantized()), flush=True)
    print(json.dumps(run_continuous()), flush=True)
    print(json.dumps(run_paged()), flush=True)
