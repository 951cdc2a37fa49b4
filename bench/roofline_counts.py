"""Bytes and operations per kernel call, from its shapes, and the least
time the chip could take for them.

``bitmap_query`` (``kernels/bitmap_query``, packed batched form): a
(K, W) uint32 attribute plane and a (Q, K) select block in, (Q, W) uint32
word masks out, W = ⌈n/32⌉.  The plane is read once for all Q rows; per
word and attribute row a query does one AND and one OR.
"""
from __future__ import annotations


def words(n: int) -> int:
    return -(-n // 32)


def bitmap_query_bytes(q: int, k: int, w: int) -> int:
    return 4 * k * w + 4 * q * k + 4 * q * w


def bitmap_query_ops(q: int, k: int, w: int) -> int:
    return 2 * q * k * w


def least_seconds(nbytes: float, ops: float, peak: dict) -> tuple:
    """``(seconds, bound)``: the larger of bytes over HBM bandwidth and
    operations over the bf16 peak, and which of the two it is."""
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    t_ops = ops / peak["bf16_flops"]
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "compute")
