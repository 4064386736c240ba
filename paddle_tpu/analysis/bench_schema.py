"""Bench-row schema — the contract between benchmarks/*.py rows and every
consumer downstream (bench.py's stdout JSONL, the driver's tail parser,
saved-row trend tracking, `paddle_tpu lint --bench-rows`).

A malformed row used to fail SILENTLY: a benchmark that dropped `mfu` or
`hbm_bw_util` from its dict still printed, the trend tooling skipped the
missing column, and the regression surfaced rounds later as a "why is this
column empty" archaeology session. Rows are validated here instead — at
print time in bench.py (loud stderr + nonzero-signal) and statically in
the lint CLI.

Family rules key on the metric NAME, which is itself part of the contract
(metric keys carry methodology; see benchmarks/lstm_textcls.py):

* every row: ``metric`` (str), ``value`` (number or null), ``unit`` (str),
  ``vs_baseline`` (number or null);
* ``*_train_*`` rows: ``mfu`` — the roofline campaign's target column
  (no training row below 15% MFU, ROADMAP item 3);
* ``*_decode_*`` rows: ``hbm_bw_util`` — decode is bytes-bound, so its
  roofline column is bandwidth, not FLOPs (target >= 0.30);
* ``*_serve_*`` rows: ``ttft_p50_ms`` + ``tpot_p50_ms`` — a serving row
  without its SLO pair is throughput theater (time-to-first-token and
  time-per-output-token are what callers experience; PR 8's daemon rows);
* ``*_prefix_*`` rows additionally: ``hit_rate`` — a prefix-cache row
  whose speedup is not conditioned on its measured hit rate is
  unreproducible (a serve+prefix metric name matches BOTH families, so
  the SLO pair stays mandatory too; benchmarks/serving_prefix.py);
* ``*_route_*`` rows: the SLO pair PLUS ``n_decode_workers`` — a routed
  serving number is meaningless without the fleet size it was spread
  over (1 prefill + 2 decode pools is not comparable to a solo daemon;
  benchmarks/serving_router.py) — PLUS ``ttft_breakdown``: the
  phase-decomposed TTFT p50s (queued/prefill/ship/adopt, ms) from the
  request-timeline ledger, so a routed TTFT regression names WHICH hop
  moved instead of reopening the whole fabric;
* ``*_fleet_*`` rows: ``recovery_windows`` + ``slo_recovered`` — a
  fleet-actor recovery number is the chaos bar itself: how many alert
  windows from kill to restored SLO, and whether the SLO actually
  recovered (a recovery-time row that never re-met the SLO is a
  failure wearing a latency; benchmarks/fleet_autoscale.py).
"""

from __future__ import annotations

from typing import Dict, List

#: keys every row must carry
REQUIRED_KEYS = ("metric", "value", "unit", "vs_baseline")

#: metric-name substring -> additionally required keys. ``methodology``
#: ("measured" | "modeled") says whether the roofline/SLO columns come
#: from on-chip measurement of the real executable or from an analytic
#: projection — so on-chip vs projected numbers are distinguishable in
#: the trajectory (attach_mfu defaults it to "measured"; the decode
#: rows' hand byte models stamp "modeled")
FAMILY_REQUIRED = {
    "_train_": ("mfu", "methodology"),
    "_decode_": ("hbm_bw_util", "methodology"),
    "_serve_": ("ttft_p50_ms", "tpot_p50_ms", "methodology"),
    "_prefix_": ("hit_rate",),
    "_route_": ("ttft_p50_ms", "tpot_p50_ms", "n_decode_workers",
                "ttft_breakdown"),
    "_fleet_": ("recovery_windows", "slo_recovered"),
}

#: the only legal methodology stamps
METHODOLOGIES = ("measured", "modeled")

#: substrings exempting a row from family rules (comparative/meta rows
#: that are not themselves roofline measurements)
FAMILY_EXEMPT = ("_speedup_",)


def validate_row(row) -> List[str]:
    """Problems with one row dict; empty list == valid."""
    if not isinstance(row, dict):
        return [f"row is {type(row).__name__}, not a dict"]
    problems = []
    for key in REQUIRED_KEYS:
        if key not in row:
            problems.append(f"missing required key '{key}'")
    metric = row.get("metric")
    if metric is not None and not isinstance(metric, str):
        problems.append("'metric' must be a string")
    for key in ("value", "vs_baseline"):
        if key in row and row[key] is not None \
                and not isinstance(row[key], (int, float)):
            problems.append(f"'{key}' must be a number or null")
    if "methodology" in row and row["methodology"] not in METHODOLOGIES:
        problems.append(f"'methodology' must be one of {METHODOLOGIES}, "
                        f"got {row['methodology']!r}")
    if isinstance(metric, str) and not any(t in metric
                                           for t in FAMILY_EXEMPT):
        for tag, extra in FAMILY_REQUIRED.items():
            if tag in metric:
                for key in extra:
                    if key not in row:
                        problems.append(
                            f"'{metric}' is a {tag.strip('_')} row but "
                            f"lacks '{key}' (family rule: roofline rows "
                            "carry their utilization column)")
    return problems


def validate_rows(rows) -> Dict[int, List[str]]:
    """{row index: problems} over an iterable of row dicts (valid rows are
    omitted)."""
    out: Dict[int, List[str]] = {}
    for i, row in enumerate(rows):
        problems = validate_row(row)
        if problems:
            out[i] = problems
    return out
