"""The serve mode end to end on the CPU at the cell's tiny sizes: the real
``serve`` daemon as a child, the open loop over the wire, the reference
child; then the same with a served token altered, and ``correct`` is false."""

from chipbench import run

QUIET = lambda m: None


def _run(**kw):
    import chipbench.harness as harness
    real = harness.load_cell

    def tiny_limits(name, root=None):
        loaded = real(name, root)
        loaded["cell"]["limits"] = {"served_gap_mean": 1e-5,
                                    "served_gap_widest": 1e-4}
        return loaded
    harness.load_cell = tiny_limits
    try:
        return run.run_cell("gpt2l-serve-longprompt", 2**31 + 9, 2.0, 1,
                            rehearsal=True, log=QUIET, **kw)
    finally:
        harness.load_cell = real


def test_sound_run_then_an_altered_token():
    line, raw = _run()
    assert all(ok for *_, ok in raw["checks"]), raw["checks"]
    assert line["correct"] is True
    assert line["attempted"] == 24 and line["failed"] == 0
    # a traced run reports the cell's per-layer metrics that found something
    assert {"prefill_ms", "decode_step_ms", "queue_wait_p95_ms",
            "client_overhead_ms", "slots_live_mean", "ttft_p95_ms",
            "tpot_p95_ms"} <= set(line["metrics"])
    assert "ttft_p50_ms" not in line["metrics"]

    def alter(records):
        for rec in records:     # whichever the seeded sample picks
            rec["tokens"][-1] = (rec["tokens"][-1] + 1) % 128
    line, raw = _run(alter=alter)
    rows = {name: ok for name, _, _, ok in raw["checks"]}
    assert rows["served_gap_widest"] is False and line["correct"] is False
