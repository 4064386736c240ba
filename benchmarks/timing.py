"""Shared chained-loop timing used by every bench.

One methodology, one implementation: ``run_n(*args, n)`` executes n chained
training steps in a single on-device ``lax.fori_loop`` dispatch and returns
a carry whose last element is a scalar loss; we time a short and a long loop
(best of ``repeats``) and difference them, cancelling the fixed dispatch +
host-fetch cost of one call, which a sub-millisecond step would otherwise
be charged. Chained state (the carry
threads params) prevents XLA from hoisting loop-invariant work out of the
loop — the failure mode that invalidates naive forward-only timing loops.
"""

from __future__ import annotations

import time


def chained_ms_per_step(run_n, args, iters: int, repeats: int,
                        short: int = 1, min_window_s: float = 0.025,
                        max_iters: int = 25000) -> float:
    """ms per step via short/long on-device-loop differencing.

    The long-short window must clear the dispatch/fetch noise floor (the
    host's clock on a machine that shares its cores) or the difference can
    collapse to ~0 for sub-ms steps and report nonsense; when the measured
    window is below ``min_window_s`` the trip count grows (x4) and the row
    re-measures, so fast models are timed over enough chained steps for the
    per-step quotient to be trustworthy."""

    def timed(n):
        t0 = time.perf_counter()
        out = run_n(*args, n)
        loss = out[-1]
        float(loss)                     # force completion
        return time.perf_counter() - t0

    # warm compile once: n is a traced scalar, so every trip count reuses
    # the same executable
    timed(short)
    while True:
        # short and long runs interleave within a round so slow drift in
        # the dispatch/RTT floor cancels out of the difference; the floor's
        # own jitter (measured as the short-run spread) sets how big the
        # window must be before the quotient is trustworthy
        shorts = [timed(short) for _ in range(max(repeats, 4))]
        t_short = min(shorts)
        noise = max(shorts) - t_short
        t_long = min(timed(short + iters) for _ in range(repeats))
        window = t_long - t_short
        if window >= max(min_window_s, 6 * noise) or iters >= max_iters:
            return max(window, 1e-9) / iters * 1e3
        iters *= 4
