"""Device time of one run of an engine program whose module the metric's file
names: `metrics/decode_program_device_ms.py`'s reading, letter for letter, over
the run's trace as `lib/decode_steps.py` keeps it (parsed once for all the
readers of a run: the accepted reader opens the file again for each)."""

import re

from lib import decode_steps
from lib import trace as tracelib


def read(ctx, spec):
    tr = ctx.get("trace")
    if not tr or "t1" not in tr:
        return None
    raw = decode_steps.xplane_of(tr)
    if raw is None:
        return None
    dev = next(iter(raw["devices"].values()))
    named = re.compile(spec["module"])
    runs = [(a, b) for name, a, b in dev["modules"]
            if named.search(name) and a >= tr["t0"] and b <= tr["t1"]]
    if not runs:
        return None
    busy = sum(tracelib.total(tracelib.busy_union(dev["ops"], a, b)) for a, b in runs)
    return 1e3 * busy / len(runs)
