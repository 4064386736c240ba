"""The train mode end to end on the CPU at the cell's tiny sizes (the look
for a chip skipped): a sound run's comparisons all hold; with the timed path
broken underneath — a step that returns its state unchanged, a part of the
batch left out — ``correct`` comes out false."""

import pytest

from chipbench import run

QUIET = lambda m: None
LIMITS = {"loss_rel_gap": 2e-3, "grad_norm_gap": 0.05, "update_norm_gap": 0.5}


def _run(**kw):
    import chipbench.harness as harness
    real = harness.load_cell

    def tiny_limits(name, root=None):
        loaded = real(name, root)
        loaded["cell"]["limits"] = dict(LIMITS)
        return loaded
    harness.load_cell = tiny_limits
    try:
        return run.run_cell("gpt2m-train-1k", 2**31 + 3, 1.0, 0,
                            rehearsal=True, log=QUIET, **kw)
    finally:
        harness.load_cell = real


def test_sound_run_is_correct_and_counts_its_steps():
    line, raw = _run()
    assert all(ok for *_, ok in raw["checks"]), raw["checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    assert line["device"]["platform"] == "cpu"     # a rehearsal, and says so


def _frozen(res, call):
    """The step's new state thrown away: parameters stay as they were."""
    import jax
    import jax.numpy as jnp
    if call == 0:
        _frozen.params = jax.tree_util.tree_map(jnp.copy, res[0])
    return (jax.tree_util.tree_map(jnp.copy, _frozen.params),) + tuple(res[1:])


def _loss_of_half(res, call):
    """What a step that left out part of the batch would report."""
    return res[:2] + (res[2] * 1.05,) + tuple(res[3:])


@pytest.mark.parametrize("broken,failing", [(_frozen, "update_norm_gap"),
                                            (_loss_of_half, "loss_rel_gap")])
def test_broken_step_makes_correct_false(broken, failing):
    line, raw = _run(broken=broken)
    rows = {name: ok for name, _, _, ok in raw["checks"]}
    assert rows[failing] is False and line["correct"] is False
