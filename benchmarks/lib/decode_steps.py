"""The decode steps inside the traced part of a serving window, with what the
engine's `decode` spans say of each (live rows, positions attended by layer
kind, experts touched, token-expert pairs) beside the device's ops inside the
runs of the decode program: what the kernel rooflines of a cell with two kinds
of layer and expert layers are reckoned from.

`ctx["trace"]` keeps the device's ops on the profiler's clock and
`ctx["spans"]` the engine's spans on the host's; the traced run's tracer also
wrote every span into the profiler's host plane, so a `decode` span is found
there again by its place in the sequence: the gaps between the starts of
successive steps agree on both clocks."""

from __future__ import annotations

import bisect
import re

from metrics.decode_program_device_ms import own_xplane

ALIGN_OVER = 8  # steps whose start gaps are compared
ALIGN_WITHIN_S = 1e-3


def _aligned(host, spans):
    """Index into `spans` of the span that host event 0 is, or None."""
    n = min(len(host), ALIGN_OVER)
    best, at = None, None
    for i in range(len(spans) - len(host) + 1):
        err = max(abs((host[k][0] - host[0][0]) - (spans[i + k]["t0_s"] - spans[i]["t0_s"]))
                  for k in range(n))
        if best is None or err < best:
            best, at = err, i
    return at if best is not None and best < ALIGN_WITHIN_S else None


_XPLANE: dict = {}  # the run's own trace, parsed once for every reader of a run


def xplane_of(tr):
    """`own_xplane(tr)`, kept: a run has one trace, five readers want its
    modules, and parsing the file takes a minute at this size."""
    key = tr["t1"]
    if key not in _XPLANE:
        _XPLANE.clear()
        _XPLANE[key] = own_xplane(tr)
    return _XPLANE[key]


def traced(ctx, spec):
    """{"steps": [the `decode` span of each run of the decode program inside
    the traced part], "runs": [(start, end)], "ops": [the device's ops inside
    those runs]}, or None where the context has no trace, the program no such
    module or the spans no such counts."""
    tr = ctx.get("trace")
    if not tr or "t1" not in tr:
        return None
    raw = xplane_of(tr)
    if raw is None:
        return None
    dev = next(iter(raw["devices"].values()))
    named = re.compile(spec["module"])
    runs = [(a, b) for name, a, b in dev["modules"]
            if named.search(name) and a >= tr["t0"] and b <= tr["t1"]]
    spans = sorted((s for s in ctx.get("spans", ()) if s["name"] == "decode"),
                   key=lambda s: s["t0_s"])
    host = [(a, b) for name, a, b in raw["host"] if name == "decode" and b > tr["t0"]]
    if not runs or len(host) < 2 or len(spans) < len(host):
        return None
    first = _aligned(host, spans)
    if first is None:
        return None
    starts = [a for a, _ in host]
    steps, kept = [], []
    for a, b in runs:
        k = bisect.bisect_right(starts, a) - 1  # the host span the run began in
        if k >= 0 and a <= host[k][1]:
            steps.append(spans[first + k])
            kept.append((a, b))
    if not steps:
        return None
    ops = dev["ops"]
    op_starts = [e[1] for e in ops]
    inside = []
    for a, b in kept:
        i = bisect.bisect_left(op_starts, a)
        while i < len(ops) and ops[i][1] < b:
            if ops[i][2] <= b:
                inside.append(ops[i])
            i += 1
    return {"steps": steps, "runs": kept, "ops": inside}


def matched_seconds(ops, patterns) -> tuple[float, int]:
    regs = [re.compile(p) for p in patterns]
    hits = [b - a for name, a, b in ops if any(r.search(name) for r in regs)]
    return sum(hits), len(hits)
