"""Share of the Trainer's step time the host spent waiting on the step's loss
(``trainer.sync_seconds`` over ``trainer.step_seconds``, the obs dump's
histograms). Near 100 by construction while the loop fetches every loss: it
says the loop is synchronous, not that it is slow."""


def _hist_sum(obs, name):
    return sum(float(m.get("sum", 0.0)) for m in obs.get("metrics", [])
               if m.get("name") == name)


def read(ctx):
    step = _hist_sum(ctx["obs"], "trainer.step_seconds")
    if step <= 0:
        return None
    return 100.0 * _hist_sum(ctx["obs"], "trainer.sync_seconds") / step
