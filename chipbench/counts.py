"""The work an algorithm needs, counted from its shapes alone, whatever
program implements it: the numerators of the roofline metrics."""
from __future__ import annotations


def causal_attention(bh: int, bh_kv: int, seq: int, d: int,
                     itemsize: int) -> tuple:
    """``(flops, bytes)`` of one causal attention call with grouped KV
    heads: q k^T and p v are 2 seq^2 d each per query head, of which the
    causal mask needs half; q, k and v are read once and o written once."""
    flops = 4.0 * bh * seq * seq * d / 2
    hbm = float(itemsize * seq * d * (2 * bh + 2 * bh_kv))
    return flops, hbm


def least_time(flops: float, hbm: float, peaks: dict) -> tuple:
    """``(seconds, bound)``: the larger of operations over the peak rate
    and bytes over the memory bandwidth, and which of the two it is."""
    compute = flops / peaks["bf16_flops_per_s"]
    memory = hbm / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
