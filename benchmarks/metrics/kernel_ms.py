"""Device time a step spends in the kernels its patterns name (see the
metric's file)."""

from lib import trace as tracelib


def read(ctx, spec):
    tr = ctx.get("trace")
    if not tr or not tr.get("steps"):
        return None
    secs, count = tracelib.matched(tr["ops"], spec["patterns"], tr["t0"], tr["t1"])
    return 1e3 * secs / tr["steps"] if count else None
