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

ALIGN_OVER = 8  # steps whose start gaps are compared
ALIGN_WITHIN_S = 1e-3


def program_runs(raw: dict, tr: dict, module: str):
    """(first device's ops, [(start, end)] of each run inside the traced part
    of the program whose module name matches `module`)."""
    dev = next(iter(raw["devices"].values()))
    named = re.compile(module)
    return dev["ops"], [(a, b) for name, a, b in dev["modules"]
                        if named.search(name) and a >= tr["t0"] and b <= tr["t1"]]


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


def pair(runs, host, spans) -> tuple[list, list]:
    """Each run of the decode program with the `decode` span that FETCHES
    its tokens: ([span], [run]). `host[k]` (start, end) is `spans[k]` on
    the profiler's clock. A span whose `ahead` is 0 enqueued its own step:
    the first run that begins inside it is that step. Any other run was
    enqueued ahead, in the last span begun before it: the next span fetches
    it where that span's `ahead` is 1; where it is 0 the step was dropped
    unfetched (every row had ended), and the run is left out. Where no span
    is ahead this is the span each run began in, as a step's single run
    begins inside its own span."""
    starts = [a for a, _ in host]
    taken = set()
    steps, kept = [], []
    for a, b in runs:
        k = bisect.bisect_right(starts, a) - 1  # the last span begun before the run
        if k >= 0 and a <= host[k][1] and not spans[k].get("ahead", 0) and k not in taken:
            at = k
        elif k + 1 < len(spans) and spans[k + 1].get("ahead", 0) and k + 1 not in taken:
            at = k + 1
        else:
            continue
        taken.add(at)
        steps.append(spans[at])
        kept.append((a, b))
    return steps, kept


def traced(ctx, spec):
    """{"steps": [the `decode` span that fetches each run of the decode
    program inside the traced part], "runs": [(start, end)], "ops": [the
    device's ops inside those runs]}, or None where the context has no
    parsed trace, the program no such module or the spans no such counts."""
    tr = ctx.get("trace")
    if not tr or "t1" not in tr:
        return None
    raw = tr.get("xplane")
    if raw is None:
        return None
    ops, runs = program_runs(raw, tr, spec["module"])
    spans = sorted((s for s in ctx.get("spans", ()) if s["name"] == "decode"),
                   key=lambda s: s["t0_s"])
    host = [(a, b) for name, a, b in raw["host"] if name == "decode" and b > tr["t0"]]
    if not runs or len(host) < 2 or len(spans) < len(host):
        return None
    first = _aligned(host, spans)
    if first is None:
        return None
    steps, kept = pair(runs, host, spans[first:])
    if not steps:
        return None
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
