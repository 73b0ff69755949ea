"""Share of its roofline that the grouped, windowed paged decode kernel reaches
in the decode steps of the traced part (see the metric's file)."""

from lib import decode_steps, roofline


def mixed_decode_attention_work(kv_full: float, kv_window: float, model: dict,
                                itemsize: int = 2) -> tuple[float, float]:
    """Operations and bytes of single-token decode attention over two kinds of
    layer. `kv_full` / `kv_window`: positions ONE full / ONE sliding layer
    attends over, summed over the live rows of the steps counted. K and V of
    every such position are read once a layer (bytes: `num_kv_heads` heads of
    `head_dim`); q.K and p.V for each of the kind's query heads (FLOPs)."""
    kinds = model["layer_types"]
    n_full = sum(k == "full_attention" for k in kinds)
    n_sliding = len(kinds) - n_full
    hd, hkv = model["head_dim"], model["num_kv_heads"]
    positions = kv_full * n_full + kv_window * n_sliding
    nbytes = positions * 2 * hkv * hd * itemsize
    flops = 2 * 2 * hd * (kv_full * n_full * model["num_heads"]
                          + kv_window * n_sliding * model["num_heads_sliding"])
    return flops, nbytes


def read(ctx, spec):
    got = decode_steps.traced(ctx, spec)
    peaks = ctx.get("peaks")
    if not got or not peaks or "kv_tokens_full" not in got["steps"][0]:
        return None
    secs, count = decode_steps.matched_seconds(got["ops"], spec["patterns"])
    if not count:
        return None
    flops, nbytes = mixed_decode_attention_work(
        sum(s["kv_tokens_full"] for s in got["steps"]),
        sum(s.get("kv_tokens_window", 0) for s in got["steps"]), ctx["model"])
    least, _ = roofline.least_seconds(flops, nbytes, peaks)
    return 100.0 * least / secs
