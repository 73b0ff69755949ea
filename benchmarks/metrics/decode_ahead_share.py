"""How often the engine's one step of lookahead engages, from the engine's own
`ahead` on each `decode` span (see the metric's file)."""


def read(ctx, spec):
    ahead = [s["ahead"] for s in ctx.get("spans", ())
             if s["name"] == "decode" and "ahead" in s]
    return 100.0 * sum(ahead) / len(ahead) if ahead else None
