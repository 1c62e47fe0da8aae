"""Branch-and-price solver + pricing-kernel tests (PR 8).

Three contracts pinned here:

* **LP equivalence** — on instances small enough for full pattern
  enumeration, colgen's Farley-certified bound must equal arc-flow's
  covering-LP bound (column generation converged to the same LP without
  ever materializing the pattern set), and its integer cost must match
  the exact solvers on the golden seed scenarios.
* **Dual admissibility** — `colgen.dual_prices` yields class prices
  with ``sum demand_c * y_c <= OPT`` for the priced fleet AND for other
  fleets over the same catalog (the churn-reuse contract the controller
  leans on), warm pool included.
* **Kernel bit-equivalence** — the jax pricing DP returns
  bit-identical ``(best, counts)`` to the numpy reference across
  dtypes and shapes (hypothesis-driven when available, seeded sweep
  otherwise), and the same take table as the numpy DP on the shift
  network's edge cases, from a step program with no gather.
"""
import re
import time

import jax
import numpy as np
import pytest

from repro import telemetry
from repro.core.binpack import (
    BinType,
    Choice,
    ColumnPool,
    Item,
    Problem,
    dual_prices as arcflow_dual_prices,
    solve,
    solve_arcflow,
    solve_colgen,
)
from repro.core.binpack import colgen
from repro.kernels import knapsack

FULL = (
    BinType("c4.2xlarge", (8, 15, 0, 0), 0.419),
    BinType("c4.8xlarge", (36, 60, 0, 0), 1.675),
    BinType("g2.2xlarge", (8, 15, 1536, 4), 0.650),
)


def _fleet(n, seed, n_kinds, catalog=FULL):
    """Matches tests/test_binpack_golden.py's generator (same seeds)."""
    rng = np.random.RandomState(seed)
    kinds = []
    for _ in range(n_kinds):
        cpu = rng.uniform(1.0, 5.0)
        kinds.append(
            (
                (cpu, rng.uniform(0.2, 1.0), 0.0, 0.0),
                (
                    cpu * 0.13,
                    rng.uniform(0.2, 1.0),
                    rng.uniform(30, 300),
                    rng.uniform(0.1, 0.6),
                ),
            )
        )
    items = []
    for i in range(n):
        c, g = kinds[i % n_kinds]
        items.append(Item(f"s{i}", (Choice("cpu", c), Choice("accel", g))))
    return Problem(bin_types=catalog, items=tuple(items))


# Reuse the golden suite's seeds: (n, seed, n_kinds) per scenario.
GOLDEN_FLEETS = {
    "hetero3": (10, 42, 3),
    "hetero5": (12, 7, 5),
    "small2": (6, 1, 2),
    "small3": (8, 2, 3),
    "small4": (16, 5, 4),
}


# ---------------------------------------------------------------- LP parity


@pytest.mark.parametrize("name", sorted(GOLDEN_FLEETS))
def test_colgen_lp_equals_enumeration_lp(name):
    n, seed, kinds = GOLDEN_FLEETS[name]
    p = _fleet(n, seed, kinds)
    _af, af_stats = solve_arcflow(p)
    cg, cg_stats = solve_colgen(p)
    cg.validate()
    # The certified colgen bound never exceeds the true LP, and on small
    # converged instances matches full enumeration's covering-LP value.
    assert cg_stats.lp_bound <= af_stats.lp_bound + 1e-6
    assert cg_stats.lp_bound == pytest.approx(af_stats.lp_bound, abs=1e-6)


@pytest.mark.parametrize("name", sorted(GOLDEN_FLEETS))
def test_colgen_cost_matches_exact_solvers(name):
    n, seed, kinds = GOLDEN_FLEETS[name]
    p = _fleet(n, seed, kinds)
    exact, _stats = solve(p)
    cg, cg_stats = solve_colgen(p)
    cg.validate()
    assert cg.cost == pytest.approx(exact.cost, abs=1e-6)
    # The certified bound is a true lower bound on the integer optimum.
    assert cg_stats.lp_bound <= exact.cost + 1e-9


def test_colgen_stats_counters_move():
    p = _fleet(12, 7, 5)
    _sol, stats = solve_colgen(p)
    assert stats.pricing_rounds > 0
    assert stats.columns_generated > 0
    assert stats.n_patterns > 0


def test_colgen_pool_warm_start_consistent():
    pool = ColumnPool()
    p = _fleet(10, 42, 3)
    cold, _ = solve_colgen(p, pool=pool)
    n_cols = len(pool)
    warm, _warm_stats = solve_colgen(p, pool=pool)
    warm.validate()
    assert n_cols > 0
    assert warm.cost == pytest.approx(cold.cost, abs=1e-9)
    # A pure price change keeps the pool (columns reprice lazily) …
    repriced = tuple(
        BinType(bt.name, bt.capacity, bt.cost * 2.0) for bt in FULL
    )
    solve_colgen(_fleet(6, 1, 2, catalog=repriced), pool=pool)
    assert len(pool) >= n_cols
    # … but a capacity change invalidates it: columns packed against
    # other capacities must never leak in.
    resized = tuple(
        BinType(bt.name, tuple(c * 2 for c in bt.capacity), bt.cost)
        for bt in FULL
    )
    sized, _ = solve_colgen(_fleet(6, 1, 2, catalog=resized), pool=pool)
    sized.validate()
    assert pool._sig == ColumnPool._catalog_sig(
        Problem(bin_types=resized, items=_fleet(6, 1, 2).items)
    )


# ---------------------------------------------------------- dual admissibility


@pytest.mark.parametrize("name", sorted(GOLDEN_FLEETS))
def test_colgen_duals_admissible_on_priced_fleet(name):
    n, seed, kinds = GOLDEN_FLEETS[name]
    p = _fleet(n, seed, kinds)
    exact, _ = solve(p)
    prices, lb = colgen.dual_prices(p)
    assert lb <= exact.cost + 1e-6
    assert all(y >= -1e-12 for y in prices.values())


def test_colgen_duals_admissible_across_churn():
    """Prices computed on one fleet lower-bound OTHER fleets over the
    same catalog — the churn-reuse contract (`arcflow.dual_prices`'s
    docstring), preserved by the colgen pricer."""
    from repro.core.binpack.arcflow import group_items, class_key

    pool = ColumnPool()
    base = _fleet(12, 7, 5)
    prices, _lb = colgen.dual_prices(base, pool)
    for seed, n, kinds in ((3, 6, 2), (9, 9, 3), (13, 15, 4)):
        other = _fleet(n, seed, kinds)
        exact, _ = solve(other)
        class_reqs, demands, _members = group_items(other)
        bound = sum(
            d * prices.get(class_key(r), 0.0)
            for r, d in zip(class_reqs, demands)
        )
        assert bound <= exact.cost + 1e-6


def test_colgen_duals_never_above_arcflow_lp():
    for name in sorted(GOLDEN_FLEETS):
        n, seed, kinds = GOLDEN_FLEETS[name]
        p = _fleet(n, seed, kinds)
        _prices, lb = colgen.dual_prices(p)
        _ap, alb = arcflow_dual_prices(p)
        # Both are admissible; colgen's budgeted certificate may be
        # looser but must never beat the exact capacity-maximal LP.
        assert lb <= alb + 1e-6


# ------------------------------------------------------- kernel equivalence


def _random_pricing(rng, b_n, e_n, dim, dtype):
    values = rng.uniform(0.0, 1.0, size=(b_n, e_n)).astype(dtype)
    weights = rng.randint(0, 4, size=(b_n, e_n, dim)).astype(np.int64)
    # Ensure no zero-weight positive-value entry loops forever: the DP
    # takes each pseudo-step at most once, so zero weights are legal,
    # but keep at least one loaded dimension per entry for realism.
    weights[..., 0] = np.maximum(weights[..., 0], 1)
    bounds = rng.randint(0, 5, size=(b_n, e_n)).astype(np.int64)
    cap_levels = rng.randint(1, 7, size=(b_n, dim)).astype(np.int64)
    return values, weights, bounds, cap_levels


def _assert_impls_match(values, weights, bounds, cap_levels, impls):
    ref = knapsack.price_knapsacks(values, weights, bounds, cap_levels,
                                   impl="numpy")
    for impl in impls:
        got = knapsack.price_knapsacks(values, weights, bounds, cap_levels,
                                       impl=impl)
        np.testing.assert_array_equal(
            np.asarray(got.best), ref.best,
            err_msg=f"best mismatch vs numpy ({impl})",
        )
        np.testing.assert_array_equal(
            np.asarray(got.counts), ref.counts,
            err_msg=f"counts mismatch vs numpy ({impl})",
        )
        # The argmax pattern must actually achieve the reported value
        # and respect capacity in every implementation.
        recon = (got.counts * values).sum(axis=1)
        np.testing.assert_allclose(recon, ref.best, rtol=0, atol=1e-6)
        used = np.einsum("be,bed->bd", got.counts, weights)
        assert (used <= cap_levels).all()
        assert (got.counts <= np.where(
            (weights <= cap_levels[:, None, :]).all(-1), bounds, 0
        )).all()


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
@pytest.mark.parametrize("seed", range(6))
def test_kernel_bit_equivalent_seeded(seed, dtype):
    rng = np.random.RandomState(seed)
    b_n = int(rng.randint(1, 5))
    e_n = int(rng.randint(1, 6))
    dim = int(rng.randint(1, 4))
    args = _random_pricing(rng, b_n, e_n, dim, dtype)
    _assert_impls_match(*args, impls=["jax"])


def test_kernel_degenerate_shapes():
    # Empty batch / empty entries short-circuit identically.
    for b_n, e_n in ((0, 3), (2, 0)):
        r = knapsack.price_knapsacks(
            np.zeros((b_n, e_n)), np.zeros((b_n, e_n, 2), dtype=np.int64),
            np.zeros((b_n, e_n), dtype=np.int64),
            np.ones((b_n, 2), dtype=np.int64), impl="jax",
        )
        assert r.best.shape == (b_n,) and r.counts.shape == (b_n, e_n)
    # All-zero bounds: nothing packs anywhere.
    r = knapsack.price_knapsacks(
        np.ones((2, 3)), np.ones((2, 3, 2), dtype=np.int64),
        np.zeros((2, 3), dtype=np.int64),
        np.full((2, 2), 5, dtype=np.int64), impl="jax",
    )
    assert (np.asarray(r.best) == 0).all() and (r.counts == 0).all()


def test_kernel_rejects_unknown_impl():
    with pytest.raises(ValueError):
        knapsack.price_knapsacks(
            np.ones((1, 1)), np.ones((1, 1, 1), dtype=np.int64),
            np.ones((1, 1), dtype=np.int64),
            np.ones((1, 1), dtype=np.int64), impl="cuda",
        )


def _dp_case(case):
    """Step inputs ``(step_values, step_weights, shifts, coord)`` for one
    shift-network edge case, on the grid of ``levels`` (state counts)."""
    rng = np.random.RandomState(sum(map(ord, case)))
    levels, b_n, t_n, dtype = {
        "S_pow2": ((4, 8), 5, 7, np.int64),
        "S_not_pow2": ((3, 5, 7), 5, 7, np.int64),
        "S_1": ((1,), 3, 4, np.int64),
        "padding_steps": ((6, 5), 4, 8, np.int64),
        "shift_S_minus_1": ((6, 7), 4, 5, np.int64),
        "row_shifts_distinct": ((23,), 23, 6, np.int64),
        "float32": ((5, 6), 5, 7, np.float32),
        "B_1": ((9, 4), 1, 9, np.int64),
    }[case]
    levels = np.array(levels)
    _levels, strides, coord = knapsack._grid(levels[None, :] - 1)
    s_n = coord.shape[0]
    if dtype == np.int64:
        values = rng.randint(0, 2**40, size=(b_n, t_n)).astype(np.int64)
    else:
        values = rng.uniform(0.0, 1.0, size=(b_n, t_n)).astype(dtype)
    weights = rng.randint(0, 3, size=(b_n, t_n, levels.size)).astype(np.int64)
    if case == "padding_steps":  # as build_pricing_steps pads: -1, 0
        values[:, -3:] = -1
        weights[:, -3:] = 0
    elif case == "shift_S_minus_1":  # the whole capacity in one step
        weights[:, 1] = levels - 1
    elif case == "row_shifts_distinct":  # shift (b + t) mod S in row b
        flat = (np.arange(b_n)[:, None] + np.arange(t_n)[None, :]) % s_n
        weights = flat[:, :, None]
    shifts = weights @ strides
    return values, weights, shifts, coord


@pytest.mark.parametrize(
    "case",
    ["S_pow2", "S_not_pow2", "S_1", "padding_steps", "shift_S_minus_1",
     "row_shifts_distinct", "float32", "B_1"],
)
def test_shift_network_take_table_matches_numpy(case):
    """`_jax_kernel`'s shift network reads ``val[s - sh]`` where the
    numpy reference gathers it: the take tables agree bit for bit."""
    step_values, step_weights, shifts, coord = _dp_case(case)
    s_n = coord.shape[0]
    if case == "shift_S_minus_1":
        assert shifts.max() == s_n - 1
    if case == "row_shifts_distinct":
        assert all(len(set(col)) == len(col) for col in shifts.T)
    ref = knapsack._dp_numpy(step_values, step_weights, shifts, coord)
    with jax.enable_x64(True):
        got = np.asarray(knapsack._jax_kernel()(
            step_values, step_weights, shifts, coord
        ))
    assert got.dtype == bool and got.shape == ref.shape
    assert ref.any()  # something is taken, so the comparison bites
    np.testing.assert_array_equal(got, ref)


def test_pricing_kernel_is_gather_free_and_keeps_its_name():
    """The DP step lowers with no gather, in the XLA module ``jit_run``
    that the benchmark's ``pricing_roofline`` reads, and the
    ``knapsack.run`` span records the stages a step ran."""
    step_values, step_weights, shifts, coord = _dp_case("S_not_pow2")
    s_n = coord.shape[0]
    with jax.enable_x64(True):
        lowered = knapsack._jax_kernel().lower(
            step_values, step_weights, shifts, coord
        )
        hlo = lowered.compile().as_text()
    assert "gather" not in lowered.as_text()
    assert "gather" not in hlo
    assert re.search(r"HloModule (\S+)", hlo).group(1).startswith("jit_run")

    since = time.perf_counter()
    knapsack._dp_jax(step_values, step_weights, shifts, coord)
    runs = [r for r in telemetry.records(since) if r.name == "knapsack.run"]
    assert runs[-1].attrs["shift_stages"] == (s_n - 1).bit_length() == 7


@pytest.mark.parametrize("seed", range(6))
def test_fixed_point_matches_float64_dp(seed):
    # Fixed point may pick another pattern only where float64 sees a tie.
    rng = np.random.RandomState(seed)
    args = _random_pricing(rng, 4, 5, 2, np.float64)
    got = knapsack.price_knapsacks(*args, impl="jax")
    ref = knapsack._price_float64(*args)
    np.testing.assert_allclose(got.best, ref.best, rtol=1e-12, atol=0)


@pytest.mark.parametrize("exponent", [-60, 0, 60])
def test_fixed_point_is_scale_free(exponent):
    # The fixed-point unit follows the batch's values, so prices in any
    # unit (here up to 2^60 at 4096 copies) neither overflow nor change
    # the chosen patterns.
    rng = np.random.RandomState(5)
    values, weights, _bounds, cap_levels = _random_pricing(
        rng, 3, 4, 2, np.float64
    )
    bounds = np.full_like(_bounds, 4096)
    ref = knapsack.price_knapsacks(values, weights, bounds, cap_levels)
    got = knapsack.price_knapsacks(
        np.ldexp(values, exponent), weights, bounds, cap_levels
    )
    np.testing.assert_array_equal(got.counts, ref.counts)
    np.testing.assert_array_equal(got.best, np.ldexp(ref.best, exponent))


# Hypothesis-driven sweep on top of the seeded one, when available.
try:  # pragma: no cover - optional dependency
    from hypothesis import given, settings, strategies as st

    HAS_HYPOTHESIS = True
except Exception:  # pragma: no cover
    HAS_HYPOTHESIS = False

if HAS_HYPOTHESIS:

    @settings(max_examples=30, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        b_n=st.integers(1, 4),
        e_n=st.integers(1, 5),
        dim=st.integers(1, 3),
        dtype=st.sampled_from([np.float64, np.float32]),
    )
    def test_kernel_bit_equivalent_hypothesis(seed, b_n, e_n, dim, dtype):
        rng = np.random.RandomState(seed)
        args = _random_pricing(rng, b_n, e_n, dim, dtype)
        _assert_impls_match(*args, impls=["jax"])

    @settings(max_examples=15, deadline=None)
    @given(
        n=st.integers(2, 8),
        seed=st.integers(0, 10_000),
        kinds=st.integers(1, 3),
    )
    def test_colgen_lp_parity_hypothesis(n, seed, kinds):
        p = _fleet(n, seed, kinds)
        _af, af_stats = solve_arcflow(p)
        _cg, cg_stats = solve_colgen(p)
        assert cg_stats.lp_bound == pytest.approx(
            af_stats.lp_bound, abs=1e-6
        )
