"""Median host time of one ``AlignerServer.serve`` call on a head-of-line
batch in the traced window (the harness's clock around the call, which
returns host arrays, so the device work is inside it)."""
import statistics

UNIT = "ms"


def read(ctx):
    service = ctx.record.get("service_s")
    if not service:
        return None
    return 1e3 * statistics.median(service)
