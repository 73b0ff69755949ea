"""Device time of one run of the engine program whose module the metric's
file names, from the XLA Modules line of the run's own trace (see the
metric's file): the trace the run's reduction parsed, read by every metric
that shares this reader."""

from lib import decode_steps
from lib import trace as tracelib


def read(ctx, spec):
    tr = ctx.get("trace")
    if not tr or "t1" not in tr:
        return None
    raw = tr.get("xplane")
    if raw is None:
        return None
    ops, runs = decode_steps.program_runs(raw, tr, spec["module"])
    if not runs:
        return None
    busy = sum(tracelib.busy_in_runs(ops, runs))
    return 1e3 * busy / len(runs)
