"""Device busy time per round: the union of device-operation intervals in
the traced window of rounds, over the rounds in it."""
UNIT = "ms/round"


def read(ctx):
    if ctx.trace is None or not ctx.record.get("rounds"):
        return None
    return ctx.trace["busy_ns"] * 1e-6 / ctx.record["rounds"]
