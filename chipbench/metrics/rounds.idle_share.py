"""Share of the traced window of rounds in which no operation ran on the
device: 1 - busy / window."""
UNIT = "%"


def read(ctx):
    if ctx.trace is None or "rounds" not in ctx.record or ctx.trace["window_ns"] <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_ns"] / ctx.trace["window_ns"])
