"""Plain references the benchmark judges the program against.

Nothing here imports the program.  The pricing reference solves the same
bounded multi-dimensional integer knapsacks a certification dispatch
solves, by the textbook dynamic programme: every copy bound is split into
0/1 items of 1, 2, 4, ... copies, and each item relaxes the table of
"best value with total weight at most this grid point" once.  It runs on
the dispatch's own inputs: dual values, integer weights, copy bounds and
capacities on the shared grid.

The certificate references work on the fleet's real requirements:
``pattern_optimum`` is the best capacity-feasible packing of one bin kind
under a set of class prices (an integer programme, solved by HiGHS), and
``covering_lp`` the covering LP over a set of packings (HiGHS's simplex).
"""
from __future__ import annotations

import numpy as np
from scipy import optimize

#: Capacity slack of a feasible packing, per dimension: a packing that
#: fills a bin to rounding (nine copies of 0.1 in 0.9) still fits.
SLACK = 1e-9


def knapsack_optimum(values, weights, bounds, cap_levels, dtype=np.float64):
    """Optimal value of each knapsack ``b``: max ``Σ_e n_e · values[b, e]``
    subject to ``Σ_e n_e · weights[b, e] <= cap_levels[b]`` (every
    dimension) and ``0 <= n_e <= bounds[b, e]``, computed in ``dtype``.

    ``values (B, E)``, ``weights (B, E, D)`` and ``bounds (B, E)`` are
    whole numbers except ``values``; ``cap_levels (B, D)``.
    """
    values = np.asarray(values, dtype=np.float64)
    weights = np.asarray(weights, dtype=np.int64)
    bounds = np.asarray(bounds, dtype=np.int64).copy()
    cap = np.asarray(cap_levels, dtype=np.int64)
    b_n, e_n = values.shape
    if b_n == 0 or e_n == 0:
        return np.zeros(b_n, dtype)
    levels = cap.max(axis=0) + 1
    strides = np.ones_like(levels)
    for d in range(levels.size - 2, -1, -1):
        strides[d] = strides[d + 1] * levels[d + 1]
    s_n = int(levels.prod())
    idx = np.arange(s_n, dtype=np.int64)
    coord = (idx[:, None] // strides[None, :]) % levels[None, :]  # (S, D)
    rows = np.arange(b_n)
    vals = values.astype(dtype)
    val = np.zeros((b_n, s_n), dtype)
    for e in range(e_n):
        rem = bounds[:, e]
        rem[(weights[:, e] > cap).any(axis=1)] = 0  # not even one copy fits
        k = 1
        while (rem > 0).any():
            m = np.minimum(k, rem)  # copies in this 0/1 item, per knapsack
            w = weights[:, e] * m[:, None]  # (B, D)
            v = vals[:, e] * m.astype(dtype)
            fits = np.ones((b_n, s_n), dtype=bool)
            for d in range(w.shape[1]):
                fits &= coord[None, :, d] >= w[:, d, None]
            fits &= (m > 0)[:, None]
            pred = np.maximum(idx[None, :] - (w @ strides)[:, None], 0)
            cand = np.take_along_axis(val, pred, axis=1) + v[:, None]
            val = np.where(fits & (cand > val), cand, val)
            rem = rem - m
            k *= 2
    return val[rows, cap @ strides]


def pattern_value(counts, values, weights, bounds, cap_levels):
    """Value of each returned pattern, or NaN where it breaks a bound or
    a capacity (an infeasible column is never a right answer)."""
    counts = np.asarray(counts, dtype=np.int64)
    used = np.einsum("be,bed->bd", counts, np.asarray(weights, dtype=np.int64))
    ok = (
        (counts >= 0).all(axis=1)
        & (counts <= np.asarray(bounds, dtype=np.int64)).all(axis=1)
        & (used <= np.asarray(cap_levels, dtype=np.int64)).all(axis=1)
    )
    value = (counts * np.asarray(values, dtype=np.float64)).sum(axis=1)
    return np.where(ok, value, np.nan)


def pricing_gap(best, counts, values, weights, bounds, cap_levels, ref):
    """Largest relative gap between a dispatch's answers and the optimum.

    Per knapsack, the larger of ``|best - ref|`` and ``|value(counts) -
    ref|`` over ``max(ref, 1e-12)``; an infeasible pattern reads 1.
    """
    value = pattern_value(counts, values, weights, bounds, cap_levels)
    scale = np.maximum(np.abs(ref), 1e-12)
    gap = np.maximum(np.abs(np.asarray(best, np.float64) - ref), np.abs(value - ref))
    gap = np.where(np.isnan(value), 1.0, gap / scale)
    return float(gap.max()) if gap.size else 0.0


def fits(counts, reqs, cap) -> bool:
    """Whether ``Σ_e counts[e] · reqs[e] <= cap`` in every dimension."""
    used = np.asarray(counts, np.float64) @ np.asarray(reqs, np.float64)
    return bool((used <= np.asarray(cap, np.float64) + SLACK).all())


def pattern_optimum(values, reqs, cap) -> float:
    """Best ``Σ_e n_e · values[e]`` over whole ``n_e >= 0`` with
    ``Σ_e n_e · reqs[e] <= cap``: one bin kind's pricing problem.

    ``values (E,)``, ``reqs (E, D)``, ``cap (D,)``.  An entry worth more
    than 0 that needs nothing is unbounded (inf).  The value returned is
    that of a packing checked against ``cap`` here, so it never exceeds
    the optimum; HiGHS stops within 1e-6 of it.
    """
    values = np.asarray(values, np.float64)
    reqs = np.asarray(reqs, np.float64)
    cap = np.asarray(cap, np.float64)
    keep = (values > 0) & (reqs <= cap + SLACK).all(axis=1)
    if not keep.any():
        return 0.0
    v, r = values[keep], reqs[keep]
    pos = r > 0
    if not pos.any(axis=1).all():
        return np.inf
    with np.errstate(divide="ignore"):
        ub = np.floor(np.where(pos, (cap + SLACK) / np.where(pos, r, 1), np.inf).min(axis=1))
    room = cap + SLACK
    for _ in range(8):
        res = optimize.milp(
            -v, integrality=np.ones_like(v),
            bounds=optimize.Bounds(0, ub),
            constraints=optimize.LinearConstraint(r.T, -np.inf, room),
            options={"mip_rel_gap": 0.0},
        )
        if res.x is None:
            raise RuntimeError(f"pattern_optimum: HiGHS gave no packing ({res.message})")
        n = np.rint(res.x)
        over = n @ r - (cap + SLACK)
        if (over <= 0).all():
            return float(n @ v)
        room = room - np.maximum(over, 0) * 2  # HiGHS took its tolerance
    raise RuntimeError("pattern_optimum: no packing within the capacity")


def covering_lp(columns, costs, demand) -> float:
    """min ``costs · x`` over ``x >= 0`` with ``columns.T @ x >= demand``.

    ``columns (P, C)`` copies of each class a packing holds, ``costs
    (P,)``, ``demand (C,)``; inf where the packings cannot cover it.
    """
    columns = np.asarray(columns, np.float64)
    res = optimize.linprog(
        np.asarray(costs, np.float64), A_ub=-columns.T,
        b_ub=-np.asarray(demand, np.float64), bounds=(0, None), method="highs",
    )
    if res.status == 2:
        return np.inf
    if res.status != 0:
        raise RuntimeError(f"covering_lp: {res.message}")
    return float(res.fun)
