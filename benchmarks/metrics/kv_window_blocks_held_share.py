"""How full the sliding layers' pool is, from the engine's own count on each
`decode` span (see the metric's file)."""


def read(ctx, spec):
    shares = [s["window_blocks_in_use"] / s["window_pool_blocks"]
              for s in ctx.get("spans", ())
              if s["name"] == "decode" and s.get("window_pool_blocks")]
    return 100.0 * sum(shares) / len(shares) if shares else None
