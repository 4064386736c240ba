"""Share of the traced steady steps in which no operation ran on the device
(1 - union of the device-op intervals over the traced window)."""


def read(ctx):
    return None if ctx.get("trace") is None else ctx["trace"]["idle_pct"]
