"""chipbench — the chip benchmark of paddle_tpu (BENCHMARK.json, PERF.md).

One run is ``python -m chipbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``; everything that belongs to one configuration, cell, traffic
mix, generator, mode or per-layer metric is a file of its own, found by name
(see README.md).
"""
