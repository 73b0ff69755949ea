"""The expert kernels' share of the decode program's busy time in the traced
part (see the metric's file)."""

from lib import decode_steps
from lib import trace as tracelib


def read(ctx, spec):
    got = decode_steps.traced(ctx, spec)
    if not got:
        return None
    secs, count = decode_steps.matched_seconds(got["ops"], spec["patterns"])
    busy = sum(tracelib.busy_in_runs(got["ops"], got["runs"]))
    return 100.0 * secs / busy if count and busy else None
