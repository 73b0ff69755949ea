"""Traffic from a mix's parameters and the seed: the one general generator.

Serving: NOT a random draw. Every seed gets the same multiset of prompt
lengths, output lengths and inter-arrival gaps (the (i + 1/2)/n quantiles of
the mix's distributions: a stratified sample), in another order, so that the
seed changes which request comes when and never how much work the window
holds. Runs therefore spread less than under independent Poisson arrivals and
drawn lengths; the mix files name this `sampling: permuted_quantiles`.
Training: the token rule of the program's synthetic LM
corpus, copied, so that the reference reads its rows from here.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """n whole numbers at the (i + 1/2)/n quantiles of a log-normal with the
    spec's `mean` and `sigma` (of the logarithm), clipped to [low, high]."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    sigma = float(spec["sigma"])
    mu = math.log(float(spec["mean"])) - 0.5 * sigma * sigma
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(mu + sigma * z)
    return np.clip(np.rint(x), int(spec["low"]), int(spec["high"])).astype(np.int64)


def _part(mix: dict, n: int, rate: float, rng) -> tuple:
    """n requests: the quantile multisets of gaps and lengths, permuted."""
    if mix["arrivals"] != "exponential_gaps" or mix["sampling"] != "permuted_quantiles":
        raise ValueError(f"unknown arrivals {mix['arrivals']!r} / sampling {mix['sampling']!r}")
    prompts = rng.permutation(_quantiles(mix["prompt_tokens"], n))
    outputs = rng.permutation(_quantiles(mix["output_tokens"], n))
    cap = int(mix.get("max_total_tokens", 0))
    if cap:
        outputs = np.minimum(outputs, np.maximum(cap - prompts, 1))
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u) / rate)
    return gaps, prompts, outputs


def serve_schedule(mix: dict, seconds: float, seed: int, vocab: int,
                   rate: float | None = None) -> list[dict]:
    """Open-loop requests: due time (s, 0 = the window's opening), prompt
    token ids, output length; sorted by due time. The ramp-in (due before 0)
    and the window are drawn apart, so that the requests DUE IN THE WINDOW are
    the same multiset of gaps and lengths for every seed, in another order.
    A mix that gives `order_seed` draws that order from it, the same in every
    run, and the run's seed draws the token ids alone."""
    rate = float(mix["rate_per_s"] if rate is None else rate)
    ramp = float(mix.get("ramp_s", 0.0))
    rng = np.random.default_rng([int(seed), 0x5EED])
    order = (np.random.default_rng([int(mix["order_seed"]), 0x0DE5]) if "order_seed" in mix
             else rng)
    parts = []
    n_ramp = int(round(rate * ramp))
    if n_ramp:
        gaps, prompts, outputs = _part(mix, n_ramp, rate, order)
        due = -np.cumsum(gaps[::-1])[::-1]  # the ramp's last arrival is one gap before 0
        parts.append((due, prompts, outputs))
    gaps, prompts, outputs = _part(mix, max(1, int(round(rate * seconds))), rate, order)
    parts.append((np.cumsum(gaps) - gaps[0], prompts, outputs))
    out = []
    for due, prompts, outputs in parts:
        for d, p, o in zip(due, prompts, outputs):
            out.append({
                "index": len(out),
                "due_s": float(d),
                "prompt": rng.integers(0, vocab, size=int(p), dtype=np.int64).astype(np.int32),
                "max_new": int(o),
            })
    return out


def warmup_prompt_lengths(mix: dict, block: int, seq_len: int) -> list[int]:
    """One prompt length for every count of cache blocks the mix can draw
    (the program compiles one graft per count, one prefill per bucket)."""
    lo, hi = int(mix["prompt_tokens"]["low"]), int(mix["prompt_tokens"]["high"])
    counts = range(-(-lo // block), -(-hi // block) + 1)
    return [min(max(c * block, lo), hi, seq_len - 2) for c in counts]


def synthetic_lm_batch(seed: int, step: int, batch: int, seq_len: int,
                       vocab: int, host_offset: int = 0) -> np.ndarray:
    """Tokens [batch, seq_len + 1] of the program's `lm_synthetic` corpus
    (data/synthetic.py SyntheticLM, train split): a noisy affine next-token
    rule, a pure function of (seed, step). Copied so that the reference's rows
    come from the benchmark; run.py checks them against what the pipeline fed."""
    a, b, noise_p = 31, 17, 0.05
    rng = np.random.default_rng((seed, step, host_offset))
    toks = np.empty((batch, seq_len + 1), dtype=np.int64)
    toks[:, 0] = rng.integers(0, vocab, size=batch)
    for t in range(seq_len):
        nxt = (a * toks[:, t] + b) % vocab
        flip = rng.random(batch) < noise_p
        nxt = np.where(flip, rng.integers(0, vocab, batch), nxt)
        toks[:, t + 1] = nxt
    return toks.astype(np.int32)
