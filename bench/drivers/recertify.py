"""Closed loop of re-certification cycles.

A cycle folds the next block of trace events (the traffic's mix) through
``ShardedController.apply_events`` and then re-certifies the whole fleet
with ``refresh_prices()``: one stacked column-generation run whose
pricing rounds are batched knapsack dispatches on the chip.  The window
runs cycles back to back; ``recertify_s`` is the time to the end of the
last cycle over the cycles completed.

Set-up builds the fleet (cold batched pack), certifies it cold, compiles
the pricing kernel for every batch size a round can shrink to where the
fleet has at most ``WARM_ALL_CELLS`` cells (a round prices every cell
still moving, times the bin kinds).  Folding events runs nothing on the
device, so nothing else compiles.

``correct`` holds what the window's certifications produced to the plain
references of ``bench.reference``:

- ``pricing_gap``: each pricing dispatch's answers against the knapsack
  optimum, on a seeded sample of dispatches and knapsacks;
- ``price_excess``: each cell's certified class prices, on a seeded
  sample of cells in every cycle, must leave every packing of every bin
  kind worth at most the kind's cost (what makes them a lower bound);
- ``bound_gap``: each cell's certified bound after the last cycle
  against the covering LP over the column pool the fleet then holds;
- ``lower_bound_over_cost`` and ``cycles_unpriced``: the fleet's bound
  stays under the plan's cost, and every cycle prices.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from bench import fleet, reference

#: Fleets with at most this many cells get every batch size compiled in
#: set-up; larger ones only those their cold certification met.
WARM_ALL_CELLS = 64


@dataclasses.dataclass
class Dispatch:
    cycle: int
    values: np.ndarray
    weights: np.ndarray
    bounds: np.ndarray
    cap_levels: np.ndarray
    best: np.ndarray
    counts: np.ndarray
    shape: tuple[int, int, int, int]  # (B, T, S, D) as the kernel ran it


@dataclasses.dataclass
class Certification:
    """One ``batched_dual_prices`` call: the cells' problems as priced and
    the ``(prices, bound)`` it returned for each."""

    cycle: int
    problems: list
    quotes: list


class Recorder:
    """Records every pricing dispatch and certification while ``active``."""

    def __init__(self) -> None:
        self.active = False
        self.cycle = -1
        self.dispatches: list[Dispatch] = []
        self.certifications: list[Certification] = []

    def clear(self) -> None:
        self.dispatches, self.certifications = [], []

    @contextlib.contextmanager
    def installed(self):
        from repro.core.binpack import colgen
        from repro.kernels import knapsack

        price, certify = knapsack.price_knapsacks, colgen.batched_dual_prices

        def price_spy(values, weights, bounds, cap_levels, *args, **kwargs):
            res = price(values, weights, bounds, cap_levels, *args, **kwargs)
            if self.active:
                self.dispatches.append(Dispatch(
                    self.cycle, np.asarray(values), np.asarray(weights),
                    np.asarray(bounds), np.asarray(cap_levels),
                    np.asarray(res.best), np.asarray(res.counts),
                    (len(res.best), res.steps, res.states,
                     int(np.asarray(weights).shape[-1])),
                ))
            return res

        def certify_spy(problems, *args, **kwargs):
            problems = list(problems)
            quotes = certify(problems, *args, **kwargs)
            if self.active:
                self.certifications.append(Certification(self.cycle, problems, quotes))
            return quotes

        knapsack.price_knapsacks, colgen.batched_dual_prices = price_spy, certify_spy
        try:
            yield self
        finally:
            knapsack.price_knapsacks, colgen.batched_dual_prices = price, certify


@dataclasses.dataclass
class State:
    cfg: dict
    traffic: dict
    seed: int
    sc: object
    trace: list
    recorder: Recorder
    exit_stack: contextlib.ExitStack
    next_event: int = 0
    setup_info: dict = dataclasses.field(default_factory=dict)


def _warm_batches(sc, cold: list[Dispatch]) -> list[int]:
    """Compile the pricing kernel for every batch a round can price."""
    from repro.kernels import knapsack

    full = max(cold, key=lambda d: d.shape[0])
    n_kinds = len(sc.manager.catalog)
    seen = {d.shape[0] for d in cold}
    if sc.n_cells > WARM_ALL_CELLS:
        return sorted(seen)
    for b in range(n_kinds, full.shape[0] + 1, n_kinds):
        if b not in seen:
            knapsack.price_knapsacks(
                full.values[:b], full.weights[:b], full.bounds[:b],
                full.cap_levels[:b],
            )
            seen.add(b)
    return sorted(seen)


def events_per_cycle(traffic: dict) -> int:
    return sum(traffic["mix"].values())


def setup(cfg: dict, traffic: dict, seed: int, spans) -> State:
    stack = contextlib.ExitStack()
    rec = stack.enter_context(Recorder().installed())
    with spans("bench.setup.build"):
        sc = fleet.build_controller(cfg)
    trace = fleet.closed_loop_trace(cfg, traffic, sc.fleet, seed, traffic["max_cycles"])
    state = State(cfg, traffic, seed, sc, trace, rec, stack)
    rec.active = True
    with spans("bench.setup.certify_cold"):
        state.setup_info["cold_lower_bound"] = sc.refresh_prices()
    cold = rec.dispatches
    rec.clear()
    rec.active = False
    state.setup_info["cold_dispatches"] = len(cold)
    with spans("bench.setup.compile_batches"):
        state.setup_info["batches_compiled"] = _warm_batches(sc, cold)
    return state


def _cycle(state: State, spans) -> tuple[float, float]:
    per = events_per_cycle(state.traffic)
    j = state.next_event
    if j + per > len(state.trace):
        raise RuntimeError("trace exhausted: raise max_cycles in the traffic file")
    with spans("bench.apply_events"):
        state.sc.apply_events(state.trace[j:j + per])
    state.next_event = j + per
    with spans("bench.refresh_prices"):
        lb = state.sc.refresh_prices()
    return lb, state.sc.total_cost()


def pool_columns(state: State) -> list[tuple[str, tuple]]:
    """The fleet's column pool as ``(bin kind, ((class key, choice,
    copies), ...))`` packings."""
    return [(c.bt_name, c.entries) for c in state.sc._colgen_pool._cols]


def window(state: State, seconds: float, spans) -> dict:
    rec = state.recorder
    stats0 = state.sc.stats()
    cycles = []
    rec.active = True
    t0 = time.perf_counter()
    while not cycles or time.perf_counter() - t0 < seconds:
        rec.cycle = len(cycles)
        lb, cost = _cycle(state, spans)
        cycles.append({"end_s": time.perf_counter() - t0, "lower_bound": lb,
                       "cost": cost})
    rec.active = False
    stats1 = state.sc.stats()
    pool = pool_columns(state)
    return {
        "seconds": seconds,
        "cycles": cycles,
        "pool": pool,
        "events": len(cycles) * events_per_cycle(state.traffic),
        "stats": {k: stats1[k] - stats0[k] for k in (
            "pricing_dispatches", "pricing_rounds", "events_routed",
            "seg_cache_hits", "seg_cache_misses",
        )},
        "shapes": [d.shape for d in rec.dispatches],
        "log": {
            "cycle_end_s": [c["end_s"] for c in cycles],
            "lower_bound": [c["lower_bound"] for c in cycles],
            "dispatches": len(rec.dispatches),
            "pool_columns": len(pool),
            "setup": state.setup_info,
        },
    }


def end_to_end(record: dict) -> dict:
    cycles = record["cycles"]
    return {"recertify_s": cycles[-1]["end_s"] / len(cycles)}


def attempted_failed(record: dict) -> tuple[int, int]:
    return len(record["cycles"]), 0


def release(state: State) -> None:
    """Free the controller and the kernel's compiled state before the check."""
    state.exit_stack.close()
    state.sc = None


def pricing_gaps(dispatches, check: dict, seed: int) -> list[float]:
    """Per-dispatch gap of the answers to the reference, on a seeded sample."""
    gen = fleet.rng(seed, 1)
    picked = list(dispatches)
    if len(picked) > check["max_dispatches"]:
        keep = np.sort(gen.choice(len(picked), check["max_dispatches"], replace=False))
        picked = [picked[i] for i in keep]
    gaps = []
    for d in picked:
        rows = np.arange(len(d.best))
        if len(rows) > check["rows_per_dispatch"]:
            rows = np.sort(gen.choice(len(rows), check["rows_per_dispatch"], replace=False))
        args = (d.values[rows], d.weights[rows], d.bounds[rows], d.cap_levels[rows])
        ref = reference.knapsack_optimum(*args)
        gaps.append(reference.pricing_gap(d.best[rows], d.counts[rows], *args, ref))
    return gaps


def _classes(problem) -> dict[bytes, list]:
    """The cell's stream classes: ``key -> [choice matrix, streams]``,
    keyed as the certifier keys its prices (requirements to 9 places)."""
    out: dict[bytes, list] = {}
    for item in problem.items:
        mat = np.asarray([c.requirement for c in item.choices], np.float64).round(9)
        key = np.ascontiguousarray(mat).tobytes()
        if key in out:
            out[key][1] += 1
        else:
            out[key] = [mat, 1]
    return out


def _bins(problem) -> list[tuple[str, float, np.ndarray]]:
    return [
        (bt.name, float(bt.cost),
         np.asarray(bt.capacity, np.float64) * problem.utilization_cap)
        for bt in problem.bin_types
    ]


def price_excess(problem, prices: dict, memo: dict) -> float:
    """How far the best packing of any bin kind is worth more than the
    kind's cost under the cell's prices, over that cost (0 if none is)."""
    classes = _classes(problem)
    vals, reqs = [], []
    for key, (mat, _n) in classes.items():
        for row in mat:
            vals.append(prices.get(key, 0.0))
            reqs.append(row)
    sig = (tuple(vals), np.asarray(reqs).tobytes())
    if sig not in memo:
        worst = 0.0
        for _name, cost, cap in _bins(problem):
            z = reference.pattern_optimum(vals, reqs, cap)
            worst = max(worst, (z - cost) / cost)
        memo[sig] = worst
    return memo[sig]


def bound_gaps(problems, quotes, pool) -> list[float]:
    """Per cell, ``|LP - bound| / LP``: the covering LP over the pool's
    packings that fit their bin kind, against the cell's certified bound."""
    if not problems:
        return []
    bins = {name: (cost, cap) for name, cost, cap in _bins(problems[0])}
    dim = len(next(iter(bins.values()))[1])
    columns, costs = [], []
    for bt_name, entries in pool:
        if bt_name not in bins:
            continue
        counts = [n for _k, _j, n in entries]
        reqs = [np.frombuffer(k, np.float64).reshape(-1, dim)[j] for k, j, _n in entries]
        cost, cap = bins[bt_name]
        if reference.fits(counts, reqs, cap):
            col: dict[bytes, int] = {}
            for key, _j, n in entries:
                col[key] = col.get(key, 0) + n
            columns.append(col)
            costs.append(cost)
    gaps = []
    for problem, (_prices, bound) in zip(problems, quotes):
        classes = _classes(problem)
        if not classes:
            continue
        keys = list(classes)
        mat = np.asarray([[col.get(k, 0) for k in keys] for col in columns], np.float64)
        demand = [classes[k][1] for k in keys]
        lp = reference.covering_lp(mat.reshape(len(columns), len(keys)), costs, demand)
        gaps.append(abs(lp - bound) / lp)
    return gaps


def checks(recorder: Recorder, record: dict, traffic: dict, seed: int):
    """Every number ``correct`` compares, as ``(name, value, limit)``."""
    limits, check = traffic["limits"], traffic["check"]
    gaps = pricing_gaps(recorder.dispatches, check, seed)
    gen = fleet.rng(seed, 2)
    memo: dict = {}
    excess = 0.0
    for cert in recorder.certifications:
        n = len(cert.problems)
        cells = np.sort(gen.choice(n, min(n, check["price_cells"]), replace=False))
        for i in cells:
            excess = max(excess, price_excess(cert.problems[i], cert.quotes[i][0], memo))
    last = [c for c in recorder.certifications if c.cycle == len(record["cycles"]) - 1]
    bounds = bound_gaps(last[-1].problems, last[-1].quotes, record["pool"]) if last else []
    priced = {d.cycle for d in recorder.dispatches}
    over = max(
        (c["lower_bound"] - c["cost"]) / c["cost"] for c in record["cycles"]
    )
    return [
        ("pricing_gap", max(gaps) if gaps else 1.0, limits["pricing_gap"]),
        ("price_excess", excess, limits["price_excess"]),
        ("bound_gap", max(bounds) if bounds else 1.0, limits["bound_gap"]),
        ("lower_bound_over_cost", max(over, 0.0), limits["lower_bound_over_cost"]),
        ("cycles_unpriced",
         float(sum(1 for i in range(len(record["cycles"])) if i not in priced)),
         limits["cycles_unpriced"]),
    ]


def check(state: State, record: dict) -> list[tuple[str, float, float]]:
    return checks(state.recorder, record, state.traffic, state.seed)
