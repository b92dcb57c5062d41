"""The work of the grouped Mamba-2 SSD scan, counted from its shapes
alone, whatever program implements it: the numerators of
``ssd_roofline``."""
from __future__ import annotations


def grouped_scan(bh: int, bh_g: int, seq: int, p: int, n: int,
                 itemsize: int, chunks) -> tuple:
    """``(flops, bytes)`` of one scan of ``bh`` heads over ``bh_g`` groups
    of B and C. Bytes: x read once and y written once (bh x seq x p each),
    B and C once per group (bh_g x seq x n each), dt (bh x seq) and A (bh)
    once. Flops: the chunked algorithm at the chunk length of ``chunks``
    that needs fewest, with C·Bᵀ (2 Q² N a chunk) counted once per group;
    per chunk and head W·X (2 Q² P), C·h and Bᵀ·X (2 Q N P each)."""
    hbm = float(itemsize * (seq * (2 * bh * p + 2 * bh_g * n + bh) + bh))

    def flops(q: int) -> int:
        per_chunk = bh * (2 * q * q * p + 4 * q * n * p) + bh_g * 2 * q * q * n
        return seq // q * per_chunk

    return float(min(flops(q) for q in chunks if seq % q == 0)), hbm
