"""Bench-row schema — thin re-export; the single source of truth lives in
paddle_tpu.analysis.bench_schema so the installed `paddle_tpu lint
--bench-rows` CLI shares exactly the rules bench.py enforces at print
time."""

from paddle_tpu.analysis.bench_schema import (FAMILY_EXEMPT,  # noqa: F401
                                              FAMILY_REQUIRED,
                                              METHODOLOGIES,
                                              REQUIRED_KEYS, validate_row,
                                              validate_rows)
