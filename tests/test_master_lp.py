"""The restricted-master LP: one covering LP, and many right-hand sides
over one pattern matrix from shared optimal bases.

* `_covering_lp` returns, bit for bit, what its loop did before the
  simplex was split out (a copy of that loop is the reference here), and
  what `_covering_simplex` and `_feasible_duals` give it.
* `_covering_lp_rows` gives every row the LP value of a cold solve of
  that row (and of scipy's HiGHS) to 1e-9 relative, with duals that are
  >= 0 and dual feasible: on degenerate rows, rows no shared basis fits
  and zero rows.
* `batched_dual_prices` counts the cold solves and basis hits on its
  ``colgen.master_lp`` span, and certifies every cell as it would with a
  cold solve per distinct row.
"""
import time

import numpy as np
import pytest
from scipy.optimize import linprog

from repro import telemetry
from repro.core.binpack import BinType, arcflow, colgen
from repro.core.manager import ResourceManager
from repro.core.profiler import paper_profile_table
from repro.core.shard import hash_cells
from repro.core.strategies import ST3
from repro.core.streams import AnalysisProgram, StreamSpec


def _reference_covering_lp(pat_mat, pat_cost, demand):
    """`_covering_lp` as one loop, before its simplex was split out."""
    n_pat, k = pat_mat.shape
    if k == 0:
        return np.zeros(0), np.zeros(n_pat)
    big_m = (float(demand.sum()) + 1.0) * (float(pat_cost.max()) + 1.0)
    cols = np.concatenate([pat_mat.T, -np.eye(k), np.eye(k)], axis=1)
    costs = np.concatenate([pat_cost, np.zeros(k), np.full(k, big_m)])
    basis = list(range(n_pat + k, n_pat + 2 * k))
    x_b = demand.astype(np.float64).copy()
    y = np.zeros(k)
    for _ in range(2000):
        b_mat = cols[:, basis]
        try:
            y = np.linalg.solve(b_mat.T, costs[basis])
        except np.linalg.LinAlgError:
            break
        reduced = costs - y @ cols
        entering_candidates = np.where(reduced < -1e-9)[0]
        if entering_candidates.size == 0:
            break
        j = int(entering_candidates[0])
        try:
            u = np.linalg.solve(b_mat, cols[:, j])
        except np.linalg.LinAlgError:
            break
        pos = np.where(u > 1e-10)[0]
        if pos.size == 0:
            break
        ratios = x_b[pos] / u[pos]
        r_min = ratios.min()
        leave_pos = min(
            (int(basis[int(i)]), int(i)) for i in pos[ratios <= r_min + 1e-12]
        )[1]
        step = x_b[leave_pos] / u[leave_pos]
        x_b = x_b - step * u
        x_b[leave_pos] = step
        basis[leave_pos] = j
    y = np.maximum(y, 0.0)
    used = y @ pat_mat.T
    with np.errstate(divide="ignore", invalid="ignore"):
        scale_all = np.where(
            used > 1e-12, np.maximum(pat_cost, 0.0) / used, np.inf
        )
    scale = float(min(1.0, scale_all.min())) if scale_all.size else 1.0
    if not np.isfinite(scale) or scale < 0:
        scale = 0.0
    x_primal = np.zeros(n_pat)
    for b_i, x_v in zip(basis, x_b):
        if b_i < n_pat and x_v > 1e-12:
            x_primal[b_i] = x_v
    return y * scale, x_primal


def _instance(seed, n_pat=40, k=5, max_count=4):
    """Integer packing patterns over k classes, every class covered by a
    singleton, costs roughly by size."""
    rng = np.random.default_rng(seed)
    pats = rng.integers(0, max_count + 1, size=(n_pat, k)).astype(np.float64)
    pats = np.concatenate([np.eye(k), pats[pats.sum(axis=1) > 0]])
    cost = np.round(0.4 + 0.1 * pats @ rng.uniform(0.5, 1.5, k), 3)
    return pats, cost


def _lp_value(pats, cost, d):
    """The covering LP's optimum by scipy's HiGHS (its dual)."""
    res = linprog(cost, A_ub=-pats.T, b_ub=-d, bounds=(0, None), method="highs")
    assert res.status == 0
    return res.fun


@pytest.mark.parametrize("seed", range(6))
def test_covering_lp_is_bit_identical_to_its_loop(seed):
    pats, cost = _instance(seed, n_pat=30 + 10 * seed, k=2 + seed % 5)
    rng = np.random.default_rng(100 + seed)
    for d in [rng.integers(0, 60, pats.shape[1]).astype(np.float64),
              np.zeros(pats.shape[1]), np.full(pats.shape[1], 7.0)]:
        y, x = arcflow._covering_lp(pats, cost, d)
        y_ref, x_ref = _reference_covering_lp(pats, cost, d)
        assert y.tobytes() == y_ref.tobytes() and x.tobytes() == x_ref.tobytes()
        lp = arcflow._covering_simplex(pats, cost, d)
        assert lp.optimal and lp.pivots > 0
        assert arcflow._feasible_duals(lp.y, pats, cost).tobytes() == y.tobytes()
        x_split = np.zeros(len(pats))
        for b, v in zip(lp.basis, lp.x_b):
            if b < len(pats) and v > 1e-12:
                x_split[b] = v
        assert x_split.tobytes() == x.tobytes()
    # No classes: nothing to solve.
    y, x = arcflow._covering_lp(np.zeros((3, 0)), np.ones(3), np.zeros(0))
    assert y.shape == (0,) and x.tobytes() == np.zeros(3).tobytes()


def _rows_agree_with_cold_solves(pats, cost, demands):
    Y, info = arcflow._covering_lp_rows(pats, cost, demands)
    assert info["cold_solves"] + info["basis_hits"] == len(demands)
    assert info["pivots"] > 0
    for d, y in zip(demands, Y):
        cold, _x = arcflow._covering_lp(pats, cost, d)
        assert d @ y == pytest.approx(d @ cold, rel=1e-9, abs=1e-12)
        assert d @ y == pytest.approx(_lp_value(pats, cost, d), rel=1e-9, abs=1e-9)
        assert (y >= 0).all()
        assert (pats @ y <= cost + 1e-9).all()
    # Rows on one basis carry one y.
    assert len({y.tobytes() for y in Y}) <= info["cold_solves"]
    return Y, info


@pytest.mark.parametrize("seed", range(4))
def test_rows_match_cold_solves(seed):
    pats, cost = _instance(seed)
    rng = np.random.default_rng(seed)
    base = rng.integers(20, 200, size=5).astype(np.float64)
    # Cells of one fleet: demands near one mix, so most share a corner.
    demands = np.maximum(base + rng.integers(-10, 11, size=(60, 5)), 0.0)
    _Y, info = _rows_agree_with_cold_solves(pats, cost, demands)
    assert info["basis_hits"] > info["cold_solves"]


def test_degenerate_and_zero_rows():
    pats, cost = _instance(7)
    # Demands that an optimal basis meets with some basic level at 0:
    # multiples of one pattern, a single class, and rows with classes at 0.
    demands = np.asarray(
        [pats[12] * 3, pats[12] * 7, [0, 0, 9, 0, 0], [0, 0, 2, 0, 0],
         [5, 0, 5, 0, 0], [10, 0, 10, 0, 0], [0] * 5, [0] * 5, [4, 4, 4, 4, 4]],
        dtype=np.float64,
    )
    Y, info = _rows_agree_with_cold_solves(pats, cost, demands)
    # The zero rows take the first basis found: nothing of theirs is cold.
    assert info["cold_solves"] < len(demands)
    assert Y[6].tobytes() == Y[7].tobytes()
    # A zero row first: its basis is optimal for it, and a valid corner.
    _rows_agree_with_cold_solves(pats, cost, demands[::-1].copy())


def test_rows_no_shared_basis_fits_get_a_cold_solve():
    # Two classes, two pure patterns and a cheaper mixed one: a demand
    # skewed to the first class is met by {4-of-first, mixed}, one skewed
    # to the second by {mixed, 4-of-second}, and neither basis fits the
    # other side.  The balanced row is degenerate and takes the second.
    pats = np.asarray([[1, 0], [0, 1], [4, 0], [0, 4], [2, 2]], np.float64)
    cost = np.asarray([1.0, 1.0, 2.0, 2.0, 1.5])
    demands = np.asarray([[40, 40], [41, 39], [100, 1], [1, 100], [99, 2]], np.float64)
    Y, info = _rows_agree_with_cold_solves(pats, cost, demands)
    assert info == {"cold_solves": 2, "basis_hits": 3, "pivots": 10}
    assert {Y[1].tobytes(), Y[4].tobytes()} == {Y[2].tobytes()}
    assert Y[0].tobytes() == Y[3].tobytes() != Y[1].tobytes()


def test_no_classes_and_no_rows():
    Y, info = arcflow._covering_lp_rows(np.zeros((3, 0)), np.ones(3), np.zeros((4, 0)))
    assert Y.shape == (4, 0) and info["cold_solves"] == 0
    pats, cost = _instance(0)
    Y, info = arcflow._covering_lp_rows(pats, cost, np.zeros((0, 5)))
    assert Y.shape == (0, 5) and info == {"cold_solves": 0, "basis_hits": 0, "pivots": 0}


VGG = AnalysisProgram("VGG-16", "vgg16")
ZF = AnalysisProgram("ZF", "zf")
CATALOG = (
    BinType("c4.2xlarge", (8, 15, 0, 0), 0.419),
    BinType("c4.8xlarge", (36, 60, 0, 0), 1.675),
    BinType("g2.2xlarge", (8, 15, 1536, 4), 0.650),
)
KINDS = [(VGG, 0.25), (VGG, 0.2), (ZF, 0.5), (ZF, 2.0), (ZF, 5.0)]


@pytest.fixture(scope="module")
def fleet_problems():
    mgr = ResourceManager(CATALOG, paper_profile_table(), max_nodes=20_000)
    ctrl = mgr.sharded_controller(ST3, cell_key=hash_cells(8))
    ctrl.reset(
        [StreamSpec(f"s{i}", *KINDS[i % len(KINDS)]) for i in range(400)], at=0.0
    )
    return [c._problem for c in ctrl._cell_list if c._problem is not None]


def test_batched_certification_counts_cold_solves_and_basis_hits(
    fleet_problems, monkeypatch
):
    t0 = time.perf_counter()
    quotes = colgen.batched_dual_prices(fleet_problems, colgen.ColumnPool())
    spans = [r for r in telemetry.records(t0, time.perf_counter())
             if r.name == "colgen.master_lp"]
    assert spans
    for s in spans:
        a = s.attrs
        assert a["cold_solves"] + a["basis_hits"] == a["solves"]
        assert a["solves"] + a["cache_hits"] == a["active"]
        assert a["pivots"] > 0
    first = spans[0].attrs
    assert first["solves"] > 1 and first["cold_solves"] < first["solves"]

    # A cold solve for every distinct row, as before bases were shared:
    # every cell's certified bound is the same to 1e-9.
    def cold_rows(pats, cost, demands):
        Y = np.asarray([arcflow._covering_lp(pats, cost, d)[0] for d in demands])
        return Y.reshape(len(demands), pats.shape[1]), {}

    monkeypatch.setattr(colgen, "_covering_lp_rows", cold_rows)
    cold = colgen.batched_dual_prices(fleet_problems, colgen.ColumnPool())
    for (_p, lp), (_cp, cold_lp) in zip(quotes, cold):
        assert lp == pytest.approx(cold_lp, rel=1e-9, abs=1e-9)
