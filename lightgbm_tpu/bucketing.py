"""Power-of-two shape bucketing shared by serving and training.

XLA compiles one executable per input shape, so any dimension that varies
at runtime must be snapped to a small ladder of compile-time sizes or the
process retraces forever. Serving learned this first (serving/predictor.py
pads request rows to a pow-2 bucket); frontier growth
(core/grow_frontier.py) has the same problem in the NODE dimension — wave
``w`` has at most ``min(2^w, leaf budget)`` live splits, but a fixed-width
wave pays ``num_leaves - 1`` slot-sweeps regardless. Both now share this
module: the ladder is the warmup schedule, the bucket function is the
dispatch key, and ``log2(cap) + 1`` specializations bound the compile
count.
"""
from __future__ import annotations

from typing import List, Optional


def pow2_bucket(n: int, min_bucket: int = 1,
                cap: Optional[int] = None) -> int:
    """Smallest power-of-two multiple of ``min_bucket`` that covers ``n``
    (doubling from ``min_bucket``), clamped to ``cap`` when given. The
    serving row-pad and the frontier wave width both key on this."""
    b = max(int(min_bucket), 1)
    n = int(n)
    while b < n:
        b <<= 1
    return b if cap is None else min(b, int(cap))


def pow2_ladder(min_bucket: int, cap: int) -> List[int]:
    """Every bucket ``pow2_bucket`` can return for sizes in [1, cap] — the
    warmup schedule. Doubles from ``min_bucket`` and always ends exactly at
    ``cap`` (which need not be a power of two)."""
    out: List[int] = []
    b = max(int(min_bucket), 1)
    cap = int(cap)
    while b < cap:
        out.append(b)
        b <<= 1
    out.append(cap)
    return out


def frontier_max_width(num_leaves: int, max_depth: int = -1) -> int:
    """Largest possible frontier wave: ``num_leaves - 1`` (every remaining
    split may land in one wave), clamped by ``max_depth`` — a depth-``d``
    tree's frontier never exceeds ``2^(d-1)`` leaves, because wave ``w``
    splits only depth-``w`` leaves and depth-capped children are never
    granted positive gain (grow_batched.apply_split_wave)."""
    kb = max(int(num_leaves) - 1, 1)
    if max_depth is not None and int(max_depth) > 0:
        kb = min(kb, 1 << (int(max_depth) - 1))
    return kb


def wave_width_ladder(num_leaves: int, max_depth: int = -1) -> List[int]:
    """The frontier grower's bucket ladder: pow-2 widths up to the clamped
    maximum wave width. One wave-step specialization exists per entry."""
    return pow2_ladder(1, frontier_max_width(num_leaves, max_depth))


def wave_width_bucket(live: int, num_leaves: int,
                      max_depth: int = -1) -> int:
    """Bucketed width a wave with ``live`` positive-gain leaves runs at —
    the host-side mirror of the grower's ``lax.switch`` branch selection,
    used by profiling occupancy accounting."""
    return pow2_bucket(max(int(live), 1), 1,
                       frontier_max_width(num_leaves, max_depth))
