"""Roofline accounting for the benchmark harness — a thin veneer over
``paddle_tpu.obs.roofline``, the ONE resolution path for FLOPs / HBM
bytes / chip peaks.

Every bench metric reports ``mfu`` (model FLOPs utilization): the training
step's FLOPs — XLA's own cost analysis of the compiled step HLO — divided by
measured step time and the chip's peak. Decode/serving rows report
``hbm_bw_util`` the same way against the chip's HBM ceiling. The reference
never measured either (its README reports raw ms/batch); on TPU they are
the numbers that say whether a throughput is actually good, so the harness
carries them next to every throughput figure.

Notes on methodology:
* FLOPs/bytes come from ``compiled.cost_analysis()`` of ONE step
  (fwd + bwd + optimizer). Pallas custom calls report zero to XLA, so
  benches that route through hand kernels resolve the kernel's modeled
  bytes through ``roofline.kernel_cost`` — the same registry the live
  ``fluid.device_bytes_total`` accounting uses, so bench rows and live
  gauges can never disagree on methodology.
* Peaks come from ``roofline.PEAK_TFLOPS`` / ``roofline.PEAK_HBM_GBPS``
  by jax device_kind (bf16 and f32 share the MXU peak via XLA's
  f32-as-3-bf16-passes; the convention is noted in the JSON). Override
  with PADDLE_TPU_PEAK_TFLOPS / PADDLE_TPU_PEAK_HBM_GBPS for new chips.
* A broken cost analysis warns once per process and counts
  ``roofline.cost_analysis_failures_total`` (an installed obs session
  sees it); the derived column is an explicit null, never a silent one.
"""

from __future__ import annotations

from typing import Optional

from paddle_tpu.obs import roofline

# the peak tables live in ONE place now; these aliases keep the bench
# modules' historical import surface working
peak_flops_per_sec = roofline.peak_flops_per_sec
peak_hbm_bytes_per_sec = roofline.peak_hbm_bytes_per_sec
_PEAK_TFLOPS = roofline.PEAK_TFLOPS


def step_flops(fn, *args, **kwargs) -> Optional[float]:
    """FLOPs of one call of ``fn(*args)`` per XLA cost analysis — None is
    an honest unknown (the failure warned once and was counted, see
    roofline.cost_failure; the old version swallowed every exception into
    a silent None)."""
    cost = roofline.analyze_fn(fn, *args, where="benchmarks.mfu.step_flops",
                               **kwargs)
    return cost.flops if cost is not None else None


def step_bytes(fn, *args, **kwargs) -> Optional[float]:
    """HBM bytes accessed by one call of ``fn(*args)`` per XLA cost
    analysis — the numerator of a measured ``hbm_bw_util``. Kernel-routed
    steps add ``roofline.kernel_cost(...)`` on top (XLA sees zero bytes
    for Pallas custom calls)."""
    cost = roofline.analyze_fn(fn, *args, where="benchmarks.mfu.step_bytes",
                               **kwargs)
    return cost.bytes if cost is not None else None


def attach_mfu(result: dict, flops_per_step: Optional[float],
               sec_per_step: float) -> dict:
    """Add mfu + gflops_per_step fields to a bench JSON record.

    ``mfu`` is ALWAYS present — null when the chip peak or the step FLOPs
    are unknown (off-TPU hosts) — per the bench-row schema
    (benchmarks/schema.py): a missing roofline column reads as a tooling
    bug, an explicit null as an honest unknown.

    ``methodology`` defaults to "measured" — attach_mfu's FLOPs come from
    XLA's cost analysis of the real compiled step over a real timing;
    pre-set the key to "modeled" before calling when the FLOPs are a hand
    projection."""
    result.setdefault("mfu", None)
    result.setdefault("methodology", "measured")
    if flops_per_step:
        result["gflops_per_step"] = round(flops_per_step / 1e9, 2)
        peak = peak_flops_per_sec()
        if peak:
            mfu = flops_per_step / sec_per_step / peak
            if mfu > 1.0:
                # physically impossible: the timing collapsed (window below
                # the noise floor) — flag it rather than publish nonsense
                result["mfu"] = None
                result["timing_suspect"] = round(mfu, 2)
            else:
                result["mfu"] = round(mfu, 4)
            result["peak_tflops"] = round(peak / 1e12, 1)
    return result


def attach_hbm_bw(result: dict, bytes_per_step: Optional[float],
                  sec_per_step: float, *,
                  methodology: Optional[str] = None) -> dict:
    """The ``hbm_bw_util`` twin of :func:`attach_mfu` — same null
    semantics, same one-owner derivation (bytes / time / chip HBM peak,
    ``roofline.peak_hbm_bytes_per_sec``), so a decode row's bandwidth
    figure and the live ``roofline.hbm_bw_util`` gauge can never diverge
    on formula. ``methodology`` stamps the row "measured" (on-chip
    timing) or "modeled" (projected bytes over an analytic model) — the
    bench-row schema requires the field on rows carrying roofline
    columns."""
    result.setdefault("hbm_bw_util", None)
    if methodology is not None:
        result["methodology"] = methodology
    if bytes_per_step:
        result["gbytes_per_step"] = round(bytes_per_step / 1e9, 3)
        peak = peak_hbm_bytes_per_sec()
        if peak:
            util = bytes_per_step / sec_per_step / peak
            if util > 1.0:
                result["hbm_bw_util"] = None
                result["timing_suspect"] = round(util, 2)
            else:
                result["hbm_bw_util"] = round(util, 4)
            result["peak_hbm_gbps"] = round(peak / 1e9, 1)
    return result
