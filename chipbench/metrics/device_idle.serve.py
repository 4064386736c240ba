"""Share of the traced few seconds inside the window in which no operation
ran on the device."""


def read(ctx):
    return None if ctx.get("trace") is None else ctx["trace"]["idle_pct"]
