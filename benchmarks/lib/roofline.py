"""Operations and bytes that the mathematics of a kernel call needs, from the
shapes of the call and never from what an implementation happens to do, and
the least time the chip could take for them."""

from __future__ import annotations


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    by_flops = flops / peaks["flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes else (by_bytes, "memory")


def causal_attention_train(batch: int, heads: int, seq: int, head_dim: int,
                           layers: int, itemsize: int = 2) -> tuple[float, float]:
    """Forward and backward of causal self-attention over all layers of one
    step. Forward: QK^T and PV, 2*T*T*hd multiply-adds each, of which the mask
    leaves half. Backward: dV, dP, dQ, dK, four products of the same size (the
    recomputation of the scores is the implementation's and is not counted).
    Bytes: forward reads Q, K, V and writes O; backward reads Q, K, V, O, dO
    and writes dQ, dK, dV: twelve [B,T,H,hd] tensors, once each."""
    one = 2.0 * seq * seq * head_dim * 0.5  # one product, causal half, FLOPs
    flops = batch * heads * layers * 6 * one
    nbytes = batch * heads * layers * 12 * seq * head_dim * itemsize
    return flops, nbytes


def paged_decode_attention(context_tokens: float, heads: int, head_dim: int,
                           layers: int, itemsize: int = 2) -> tuple[float, float]:
    """Single-token decode attention over `context_tokens` cached positions
    summed over the live rows of the steps counted: K and V of every live
    position read once per layer (bytes), q.K and p.V (FLOPs)."""
    nbytes = context_tokens * layers * 2 * heads * head_dim * itemsize
    flops = context_tokens * layers * heads * 2 * 2 * head_dim
    return flops, nbytes
