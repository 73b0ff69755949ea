"""What one admission costs the step it lands in, from the engine's `admit`
spans (see the metric's file)."""


def read(ctx, spec):
    admits = [s for s in ctx.get("spans", ()) if s["name"] == "admit" and s.get("admitted", 0) >= 1]
    if not admits:
        return None
    return 1e3 * sum(s["dur_s"] for s in admits) / sum(s["admitted"] for s in admits)
