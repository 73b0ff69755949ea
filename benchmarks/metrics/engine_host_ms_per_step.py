"""Host time a decoding step adds to what the device does, from the engine's
own `step` spans (see the metric's file)."""

from lib import trace as tracelib
from lib.common import log


def decoding_steps(spans):
    """The `step` spans that hold a `decode`, each with the intervals of the
    named spans that lie inside it: [(step, {name: [(a, b), ...]}), ...]."""
    held = {s.get("parent") for s in spans if s["name"] == "decode"}
    steps = sorted((s for s in spans if s["name"] == "step" and s["span"] in held),
                   key=lambda s: s["t0_s"])
    others = sorted((s for s in spans if s["name"] != "step" and s["dur_s"] > 0.0),
                    key=lambda s: s["t0_s"])
    out, i = [], 0
    for step in steps:
        a, b = step["t0_s"], step["t0_s"] + step["dur_s"]
        while i < len(others) and others[i]["t0_s"] < a:
            i += 1
        inside: dict[str, list] = {}
        j = i
        while j < len(others) and others[j]["t0_s"] < b:
            s = others[j]
            if s["t0_s"] + s["dur_s"] <= b + 1e-9:
                inside.setdefault(s["name"], []).append((s["t0_s"], s["t0_s"] + s["dur_s"]))
            j += 1
        out.append((step, inside))
    return out


def read(ctx, spec):
    steps = decoding_steps(ctx.get("spans", ()))
    if not steps:
        return None
    host, quiet = [], []
    for step, inside in steps:
        waits = [iv for name in spec["device_waits"] for iv in inside.get(name, ())]
        host.append(step["dur_s"] - tracelib.total(tracelib.merge(waits)))
        if not inside.get("prefill"):
            quiet.append(step["dur_s"])
    if quiet:
        log(f"{spec['name']}: {len(steps)} decoding steps, mean step span "
            f"{1e3 * sum(s['dur_s'] for s, _ in steps) / len(steps):.3f} ms; the {len(quiet)} that "
            f"admitted nothing {1e3 * sum(quiet) / len(quiet):.3f} ms")
    return 1e3 * sum(host) / len(host)
