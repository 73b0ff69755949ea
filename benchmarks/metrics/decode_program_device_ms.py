"""Device time of one run of the paged decode program, from the XLA Modules
line of the run's own trace (see the metric's file). `ctx["trace"]` keeps the
device's ops but not its modules, so the file is opened again."""

import glob
import os
import re

from lib import trace as tracelib
from lib.common import ROOT


def own_xplane(tr: dict):
    """The trace this run wrote: the newest .xplane.pb under .bench_work
    whose last device op ends where the run's reduction says it does."""
    paths = glob.glob(os.path.join(ROOT, ".bench_work", "*", "profile", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        raw = tracelib.read_xplane(path)
        if any(d["ops"] and max(e[2] for e in d["ops"]) == tr["t1"]
               for d in raw["devices"].values()):
            return raw
    return None


def read(ctx, spec):
    tr = ctx.get("trace")
    if not tr or "t1" not in tr:
        return None
    raw = own_xplane(tr)
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
