"""Bytes and operations the pricing DP needs, from its shapes alone.

One dispatch solves ``B`` knapsacks over ``S`` grid states in ``T`` 0/1
steps, each weight a ``D``-vector.  Every step must read each knapsack's
value table, write it back and write one take bit per state (the table
the host backtracks the pattern from).  That is the least traffic the
algorithm needs, with the state in 8-byte integers and a take flag in one
byte; the grid coordinates (``S × D`` int64) and the step inputs are read
once.  No matrix unit serves 64-bit integer compares and adds, so the
bound is the memory's: least time = bytes / HBM bandwidth.
"""
from __future__ import annotations

STATE_BYTES = 8
TAKE_BYTES = 1


def pricing_bytes(b: int, t: int, s: int, d: int) -> int:
    state = t * b * s * (2 * STATE_BYTES + TAKE_BYTES)
    grid = s * d * STATE_BYTES
    steps = t * b * (STATE_BYTES * (2 + d))  # value, shift, weight vector
    return state + grid + steps


def pricing_ops(b: int, t: int, s: int, d: int) -> int:
    """Integer operations: per state and step, ``D`` fit compares, their
    ``D - 1`` ands, one add, one compare, one and and one select."""
    return t * b * s * (2 * d + 3)


def least_seconds(shapes, hbm_bytes_per_s: float) -> float:
    """Least device time for dispatches of the given ``(B, T, S, D)``."""
    return sum(pricing_bytes(*shape) for shape in shapes) / hbm_bytes_per_s
