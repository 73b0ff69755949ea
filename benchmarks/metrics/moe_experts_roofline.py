"""Share of its roofline that the grouped expert kernel reaches in the decode
steps of the traced part (see the metric's file)."""

from lib import decode_steps, roofline


def routed_experts_work(touched: float, pairs: float, hidden: int, width: int,
                        itemsize: int = 2) -> tuple[float, float]:
    """Operations and bytes that the routed experts' gated feed-forwards need,
    whatever computes them: an expert that got a token is read once, its three
    [hidden, width] matrices (bytes; the tokens' own rows are left out, which
    only lowers the share); a token-expert pair is three such products, two
    operations a multiply-add (FLOPs)."""
    nbytes = touched * 3 * hidden * width * itemsize
    flops = pairs * 3 * 2 * hidden * width
    return flops, nbytes


def read(ctx, spec):
    got = decode_steps.traced(ctx, spec)
    peaks, moe = ctx.get("peaks"), ctx["model"].get("moe") or {}
    if not got or not peaks or "experts_touched" not in got["steps"][0]:
        return None
    secs, count = decode_steps.matched_seconds(got["ops"], spec["patterns"])
    if not count:
        return None
    flops, nbytes = routed_experts_work(
        sum(s["experts_touched"] for s in got["steps"]),
        sum(s["expert_pairs"] for s in got["steps"]),
        ctx["model"]["hidden_dim"], moe["expert_dim"])
    least, _ = roofline.least_seconds(flops, nbytes, peaks)
    return 100.0 * least / secs
