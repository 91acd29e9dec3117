"""The power-of-two shape ladder shared by the serving bucketer and the
decode engine's prefill buckets.

A static-shape compiler wants a small closed set of programs: the
serving engine coalesces requests into power-of-two batch buckets
(serving/batching.py) and the paged decoder pads a prompt to a
power-of-two length bucket (decode/model.py).  Both round through THIS
module so their ladders can never drift apart.

Pure python, no jax/numpy imports — serving imports this at module
load.
"""

from __future__ import annotations

from typing import Tuple


def bucket_dim(n: int) -> int:
    """Smallest power-of-two >= n (n <= 1 maps to 1).

    This is the serving engine's ``next_bucket`` ladder: 1, 2, 4, 8...
    """
    if n <= 1:
        return 1
    return 1 << (n - 1).bit_length()


def bucket_ladder(max_value: int) -> Tuple[int, ...]:
    """All buckets up to (and including) the one covering max_value:
    1, 2, 4, ..., bucket_dim(max_value)."""
    out = []
    b = 1
    while b < max_value:
        out.append(b)
        b <<= 1
    out.append(bucket_dim(max_value))
    return tuple(out)
