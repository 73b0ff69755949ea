"""Reduction of a profiler trace to the device's busy union (over a window,
or inside each run of a program), the time of events whose name matches a
pattern, and the host span under each idle gap; each linear in the trace.
Times are seconds on the trace's own clock.

`read_xplane` turns the profiler's file into plain lists (with nothing but
JAX); everything below it works on those lists, so the tests run the same
arithmetic on the small recorded trace under benchmarks/recorded/.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NAME_CHARS = 200  # a device op's event name is its whole HLO instruction


def find_xplane(log_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return hits[-1]


def read_xplane(path: str, min_host_s: float = 20e-6) -> dict:
    """{"devices": {index: {"ops": [...], "modules": [...]}}, "host": [...]}
    with every event as [name, start_s, end_s]. A device op's name is the
    start of its HLO instruction ("%attn = (bf16[8,16,1024,64]...) custom-call(":
    a Pallas kernel shows as a custom-call named after the flax scope it sits
    in); host events shorter than `min_host_s` are dropped."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: dict = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = out["devices"].setdefault(int(m.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                dest = dev["ops" if line.name == OPS_LINE else "modules"]
                for ev in line.events:
                    a = ev.start_ns * 1e-9
                    dest.append([ev.name[:NAME_CHARS], a, a + ev.duration_ns * 1e-9])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns * 1e-9 >= min_host_s:
                        a = ev.start_ns * 1e-9
                        out["host"].append([ev.name, a, a + ev.duration_ns * 1e-9])
    for dev in out["devices"].values():
        dev["ops"].sort(key=lambda e: e[1])
        dev["modules"].sort(key=lambda e: e[1])
    out["host"].sort(key=lambda e: e[1])
    return out


def merge(intervals):
    """Sorted union of (start, end) intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, t0: float, t1: float):
    return [(max(a, t0), min(b, t1)) for a, b in intervals if min(b, t1) > max(a, t0)]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def busy_union(ops, t0: float, t1: float):
    """Merged intervals inside [t0, t1] in which some op ran."""
    return merge(clip([(a, b) for _, a, b in ops], t0, t1))


def idle_gaps(busy, t0: float, t1: float):
    gaps, cur = [], t0
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        gaps.append((cur, t1))
    return gaps


def matched(ops, patterns, t0: float, t1: float) -> tuple[float, int]:
    """Summed duration and count of the ops inside [t0, t1] whose name
    matches any of the regular expressions."""
    regs = [re.compile(p) for p in patterns]
    secs, count = 0.0, 0
    for name, a, b in ops:
        if a >= t0 and b <= t1 and any(r.search(name) for r in regs):
            secs += b - a
            count += 1
    return secs, count


def self_times(ops):
    """[name, self seconds, start, end] per op: an op's time less that of the
    ops nested inside it (a loop's event spans its body's on the same line)."""
    out, stack = [], []
    for name, a, b in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            stack.pop()
        if stack:
            stack[-1][3] -= min(b, stack[-1][2]) - a
        rec = [name, a, b, b - a]
        out.append(rec)
        stack.append(rec)
    return [[n, s, a, b] for n, a, b, s in out]


_HLO = re.compile(r"^(%[^ ]+) = (.*?)\s([a-z][a-z\-]*)\(")


def short_name(name: str) -> str:
    """"%fusion.12 = bf16[8,1024]{...} fusion(..." -> "%fusion fusion bf16[8,1024]":
    the instruction's name with its number folded, its opcode, its first shape."""
    m = _HLO.match(name)
    if not m:
        return re.sub(r"\.\d+$", "", name.split(" = ")[0])[:80]
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", m.group(2))
    return " ".join(filter(None, [re.sub(r"\.\d+$", "", m.group(1)), m.group(3),
                                  shape.group(1) if shape else ""]))


def top_ops(ops, t0: float, t1: float, k: int = 10):
    """The k ops (numbered HLO names folded) with most self time."""
    acc: dict[str, float] = {}
    for name, s, a, b in self_times(ops):
        if a >= t0 and b <= t1:
            key = short_name(name)
            acc[key] = acc.get(key, 0.0) + s
    return [[n, s] for n, s in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def busy_in_runs(ops, runs) -> list[float]:
    """`total(busy_union(ops, a, b))` for each (a, b) of `runs`, reading only
    the slice of the ops (in start order) that can reach the run: from the
    first op by which some op has ended after `a`, to the first that starts
    at or after `b`. Every op outside that slice fails `clip`'s test, so each
    run's merged intervals, and its seconds, are the same to the last bit."""
    ops = sorted(ops, key=lambda e: e[1])
    starts = [a for _, a, _ in ops]
    reach, hi = [], float("-inf")  # running maximum of the ends, in start order
    for _, _, b in ops:
        hi = max(hi, b)
        reach.append(hi)
    return [total(busy_union(ops[bisect.bisect_right(reach, a):bisect.bisect_left(starts, b)],
                             a, b))
            for a, b in runs]


def attribute_gaps(gaps, host_spans, k: int = 10):
    """Each idle gap goes to the shortest host span that covers at least half
    of it (so a child wins over its parent), else to the span that overlaps
    it most, else to "no_host_span". Returns [[name, seconds], ...].

    `gaps` come in time order and do not overlap, as `idle_gaps` gives them:
    a sweep then keeps the spans open at the gap (begun before its end, not
    ended by its start) and visits only those, in their start order, so the
    cost is linear in gaps and spans times the spans open at once."""
    acc: dict[str, float] = {}
    spans = sorted(host_spans, key=lambda s: s[1])
    nxt, open_ = 0, []
    for a, b in gaps:
        while nxt < len(spans) and spans[nxt][1] < b:
            open_.append(spans[nxt])
            nxt += 1
        open_ = [s for s in open_ if s[2] > a]
        covering, most = None, None
        for name, sa, sb in open_:
            ov = min(b, sb) - max(a, sa)
            if ov <= 0:
                continue
            if 2 * ov >= b - a and (covering is None or sb - sa < covering[1]):
                covering = (name, sb - sa)
            if most is None or ov > most[1]:
                most = (name, ov)
        best = (covering or most or ("no_host_span", 0.0))[0]
        acc[best] = acc.get(best, 0.0) + (b - a)
    return [[n, s] for n, s in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def reduce(trace: dict, t0: float | None = None, t1: float | None = None,
           extra_spans=()) -> dict:
    """Busy and idle over [t0, t1] (default: first op's start to last op's
    end over all devices), averaged over the devices, with the breakdown."""
    devs = trace["devices"]
    if not devs or not any(d["ops"] for d in devs.values()):
        raise RuntimeError("the trace holds no device operation")
    if t0 is None:
        t0 = min(d["ops"][0][1] for d in devs.values() if d["ops"])
    if t1 is None:
        t1 = max(max(e[2] for e in d["ops"]) for d in devs.values() if d["ops"])
    busy_s, first = [], None
    for idx in sorted(devs):
        busy = busy_union(devs[idx]["ops"], t0, t1)
        busy_s.append(total(busy))
        if first is None:
            first = (devs[idx]["ops"], busy)
    ops, busy = first
    spans = [s for s in list(trace["host"]) + list(extra_spans)
             if s[2] > t0 and s[1] < t1]
    return {
        "t0": t0, "t1": t1,
        "window_s": t1 - t0,
        "busy_s": sum(busy_s) / len(busy_s),
        "ops": ops,
        "device_ops": top_ops(ops, t0, t1),
        "idle_gaps": attribute_gaps(idle_gaps(busy, t0, t1), spans),
    }


def describe(path: str, per_line: int = 12) -> None:
    """Print a trace's planes, lines and the first events of each with their
    stats: what to look at by hand before writing a pattern."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events), "events")
            seen = set()
            for ev in events:
                key = re.sub(r"\d+", "#", ev.name)
                if key in seen or len(seen) >= per_line:
                    continue
                seen.add(key)
                stats = {k: str(v)[:80] for k, v in ev.stats}
                print("    ", ev.name[:100], ev.start_ns, ev.duration_ns, stats)


if __name__ == "__main__":
    import sys

    describe(find_xplane(sys.argv[1]) if os.path.isdir(sys.argv[1]) else sys.argv[1])
