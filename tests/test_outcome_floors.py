"""Outcome floors: what a plan is worth on seeded whole-fleet scenarios.

Each case is one floor on one outcome of one scenario: a certified gap,
a warm/cold or policy-vs-policy cost ratio, a bill, a count of blacked-out
stream-seconds or of SLA violations.  A scenario is a fleet, a seeded
event trace and the controllers it is replayed through; it runs once per
process and serves all of its floors.  The scenarios and floors:

* ``replan``: 500 streams on calibrated EC2 vectors, 200 churn events
  (seed 1802); every warm plan stays within its certified gap.
* ``policy``: 500 streams, a removal-heavy 200-event trace (seed 1802),
  pinning vs consolidation (k = 3 migrations an event): consolidation
  ends >= 5% cheaper and never moves more than k streams on an event.
* ``lifecycle``: the same trace timed through the billing engine with
  zero boot and per-second billing reproduces pinning's cost timeline bit
  for bit; on a bursty growth trace (seed 2618, 2-minute boots) hourly
  billing only rounds up; under hourly billing, billing-aware
  consolidation never bills more than blind.
* ``spot``: a two-tier spot market and a preemption-heavy trace
  (seed 7113): risk-aware allocation bills >= 10% less than on-demand
  and loses no more stream-seconds to preemptions than naive all-spot,
  which loses >= 600.
* ``storm``: 120 tiered streams through a seeded failure storm
  (seed 8231): the tiered posture ends with no GOLD violation, cuts
  blackout >= 60% vs notice-blind pinning at <= 10% more bill and a
  lower utility penalty; notice draining converts >= 80% of noticed
  victims tail-free.
* ``solver``: branch-and-price certifies <= 1% on the calibrated
  n=500 / 10-kind fleet.
* ``calibration``: a calibrated 50-stream TPU-cloud mix lands streams on
  both device classes, and a 2x faster accelerator profile cuts the
  certified fleet cost >= 2%.
"""
from __future__ import annotations

import functools

import numpy as np
import pytest

from repro.core import calibration as cal
from repro.core.binpack import Problem, solve_colgen
from repro.core.catalog import paper_ec2_catalog, tpu_cloud_catalog, with_spot_variants
from repro.core.lifecycle import BillingModel
from repro.core.manager import ResourceManager
from repro.core.policy import (
    CompositePolicy,
    ConsolidationPolicy,
    DualPriceAgingPolicy,
    GracefulDegradationPolicy,
    PinningPolicy,
    risk_adjusted_catalog,
)
from repro.core.profiler import paper_profile_table
from repro.core.simulator import simulate_churn
from repro.core.streams import (
    BRONZE,
    GOLD,
    SILVER,
    AnalysisProgram,
    PriceChanged,
    StormPhase,
    StreamAdded,
    StreamRateChanged,
    StreamRemoved,
    StreamSpec,
    TimedTrace,
    apply_events,
    storm_trace,
    synthetic_timed_trace,
)

VGG = AnalysisProgram("VGG-16", "vgg16")
ZF = AnalysisProgram("ZF", "zf")
#: The paper's two programs at the rates a camera renegotiates between.
KINDS = [(VGG, 0.25), (VGG, 0.2), (ZF, 0.5), (ZF, 2.0), (ZF, 5.0)]
N_STREAMS = 500
N_EVENTS = 200
MAX_NODES = 20_000
#: Wide: the compared replays stay on the warm path.
GAP_THRESHOLD = 0.3
BOOT_H = 2.0 / 60.0
HOURLY = BillingModel(boot_hours=BOOT_H, quantum_hours=1.0)
PER_SECOND = BillingModel(boot_hours=BOOT_H, quantum_hours=0.0)
SNAPSHOT = BillingModel(boot_hours=0.0, quantum_hours=0.0)


def _fleet():
    return [StreamSpec(f"s{i}", *KINDS[i % len(KINDS)]) for i in range(N_STREAMS)]


def _rates(s):
    return [fps for prog, fps in KINDS if prog.program_id == s.program.program_id]


def _paper_manager(catalog=None):
    return ResourceManager(
        catalog if catalog is not None else paper_ec2_catalog(),
        paper_profile_table(), max_nodes=MAX_NODES,
    )


# ---------------------------------------------------------------- replan


def _churn_event(ctrl, rng, at):
    """One random churn event against the controller's live fleet."""
    roll = rng.rand()
    if roll < 0.30:
        return StreamAdded(
            StreamSpec(f"j{rng.randint(10**9)}", *KINDS[rng.randint(len(KINDS))]),
            at=at,
        )
    if roll < 0.55:
        live = ctrl.fleet
        return StreamRemoved(live[rng.randint(len(live))].name, at=at)
    if roll < 0.95:
        live = ctrl.fleet
        s = live[rng.randint(len(live))]
        rates = _rates(s)
        return StreamRateChanged(s.name, rates[rng.randint(len(rates))], at=at)
    bt = ("c4.2xlarge", "c4.8xlarge", "g2.2xlarge")[rng.randint(3)]
    base = {"c4.2xlarge": 0.419, "c4.8xlarge": 1.675, "g2.2xlarge": 0.650}[bt]
    return PriceChanged(bt, round(base * (1.0 + 0.05 * rng.randn()), 4), at=at)


@functools.cache
def replan() -> dict:
    rng = np.random.RandomState(1802)
    mgr = ResourceManager(
        paper_ec2_catalog(), calibration=cal.load_or_calibrate("ec2"),
        max_nodes=MAX_NODES,
    )
    mgr.allocate(_fleet())
    ctrl = mgr.controller()
    gaps = [
        ctrl.apply(_churn_event(ctrl, rng, at=(i + 1) * 0.02)).gap
        for i in range(N_EVENTS)
    ]
    return {"max_certified_gap": max(gaps)}


# ---------------------------------------------------------------- policy


@functools.cache
def removal_trace() -> TimedTrace:
    """Removal-heavy churn (0.18 join / 0.52 leave / 0.30 re-rate), floored
    at half the initial fleet, 72 s apart; pre-generated so every compared
    policy replays the identical events."""
    rng = np.random.RandomState(1802)
    fleet, events = _fleet(), []
    for i in range(N_EVENTS):
        at = (i + 1) * 0.02
        roll = rng.rand()
        if roll < 0.18 or len(fleet) < N_STREAMS // 2:
            ev = StreamAdded(
                StreamSpec(f"j{i}", *KINDS[rng.randint(len(KINDS))]), at=at
            )
        elif roll < 0.70:
            ev = StreamRemoved(fleet[rng.randint(len(fleet))].name, at=at)
        else:
            s = fleet[rng.randint(len(fleet))]
            rates = _rates(s)
            ev = StreamRateChanged(s.name, rates[rng.randint(len(rates))], at=at)
        events.append(ev)
        fleet = list(apply_events(fleet, [ev]))
    return TimedTrace(events, horizon=(N_EVENTS + 1) * 0.02)


def _policy_replay(pol):
    """Per-event hourly costs, and migrations of the warm/noop re-plans."""
    mgr = _paper_manager()
    mgr.allocate(_fleet())
    ctrl = mgr.controller(policy=pol, gap_threshold=GAP_THRESHOLD)
    costs, migrations = [], []
    for ev in removal_trace():
        r = ctrl.apply(ev)
        costs.append(r.plan.hourly_cost)
        if r.mode in ("warm", "noop"):
            migrations.append(len(r.migrated))
    return costs, migrations


@functools.cache
def policy() -> dict:
    pin, _ = _policy_replay(PinningPolicy())
    cons, migrations = _policy_replay(CompositePolicy(
        ConsolidationPolicy(max_migrations=3), DualPriceAgingPolicy(patience=3),
    ))
    return {
        "pinning_costs": pin,
        "consolidation_saving": (pin[-1] - cons[-1]) / pin[-1],
        "max_migrations_per_event": max(migrations, default=0),
    }


# ---------------------------------------------------------------- lifecycle


def _billed_replay(trace, pol, billing, catalog=None, *, initial=None,
                   gap_threshold=GAP_THRESHOLD, **kw):
    mgr = _paper_manager(catalog)
    mgr.controller(gap_threshold=gap_threshold)
    return simulate_churn(
        mgr, initial or _fleet(), trace, paper_profile_table(),
        policy=pol, billing=billing, **kw,
    )


@functools.cache
def lifecycle() -> dict:
    removal = removal_trace()
    snap = _billed_replay(removal, PinningPolicy(), SNAPSHOT)
    timed = [t["cost"] for t in snap["timeline"][1:]]
    untimed = policy()["pinning_costs"]
    bitident = (max(abs(a - b) for a, b in zip(timed, untimed))
                if len(timed) == len(untimed) else float("inf"))

    growth = synthetic_timed_trace(
        _fleet(), np.random.RandomState(2618), n_events=90,
        mean_gap_hours=0.03, p_join=0.6, p_leave=0.15,
        make_join=lambda i: StreamSpec(f"g{i}", *KINDS[i % len(KINDS)]),
        rerate_fps=_rates, burst=3,
    )
    hourly = _billed_replay(growth, PinningPolicy(), HOURLY)
    per_second = _billed_replay(growth, PinningPolicy(), PER_SECOND)
    blind = _billed_replay(removal, ConsolidationPolicy(max_migrations=3), HOURLY)
    aware = _billed_replay(
        removal, ConsolidationPolicy(max_migrations=3, billing_horizon=1.0), HOURLY,
    )
    return {
        "pinning_bitident_delta": bitident,
        "hourly_premium": hourly["billed_cost"] / per_second["billed_cost"] - 1.0,
        "billing_aware_excess": aware["billed_cost"] / blind["billed_cost"] - 1.0,
    }


# ---------------------------------------------------------------- spot

FLAKY_HAZARD = 0.9
DEGRADED_PENALTY = 25.0  # $ per stream-hour of post-preemption downtime


def _market():
    """(on-demand catalog, two-tier spot catalog, per-type billing: spot
    pools bill per second, on-demand hourly)."""
    base = paper_ec2_catalog()
    cat = with_spot_variants(base, price_ratio=0.30, hazard=FLAKY_HAZARD,
                             suffix="-spot")
    cat = with_spot_variants(cat, price_ratio=0.45, hazard=0.08,
                             suffix="-spot-stable")
    return base, cat, {bt.name: PER_SECOND for bt in cat if bt.is_spot}


def _risk_catalog(spot_cat, by_type):
    return risk_adjusted_catalog(spot_cat, HOURLY, billing_by_type=by_type,
                                 degraded_penalty=DEGRADED_PENALTY)


@functools.cache
def spot() -> dict:
    base, spot_cat, by_type = _market()
    trace = synthetic_timed_trace(
        _fleet(), np.random.RandomState(7113), n_events=80,
        mean_gap_hours=0.02, p_join=0.45, p_leave=0.2,
        make_join=lambda i: StreamSpec(f"g{i}", *KINDS[i % len(KINDS)]),
        rerate_fps=_rates, burst=2, tail_hours=0.3,
        preemption_hazard=FLAKY_HAZARD, hazard_pool=192,
    )
    od, naive, risk = (
        _billed_replay(trace, PinningPolicy(), HOURLY, catalog,
                       billing_by_type=by_type)
        for catalog in (base, spot_cat, _risk_catalog(spot_cat, by_type))
    )
    lost = "preemption_degraded_stream_seconds"
    return {
        "risk_aware_billed_saving": 1.0 - risk["billed_cost"] / od["billed_cost"],
        "risk_vs_naive_degraded_excess": risk[lost] - naive[lost],
        "preempt_degraded_seconds_naive_spot": naive[lost],
    }


# ---------------------------------------------------------------- storm

#: 20% GOLD / 30% SILVER / 50% BRONZE by stream index.
TIER_WHEEL = (GOLD, GOLD, SILVER, SILVER, SILVER) + (BRONZE,) * 5


def _storm_trace():
    spike, notice_h = "c4.2xlarge-spot-stable", 2.5 / 60.0
    phases = [
        StormPhase("notice", at=0.5, count=10, notice_hours=notice_h),
        StormPhase("flash_crowd", at=0.9, count=12),
        StormPhase("price", at=1.2, instance_type=spike, cost=0.60),
        StormPhase("price", at=1.5, instance_type=spike, cost=0.189),
        StormPhase("reclaim", at=1.6, count=8),
        StormPhase("false_alarm", at=2.0, count=3),
    ]

    def tier(i):
        return TIER_WHEEL[i % len(TIER_WHEEL)]

    initial = [
        StreamSpec(f"s{i}", *KINDS[i % len(KINDS)], tier=tier(i))
        for i in range(120)
    ]
    trace = storm_trace(
        initial, np.random.RandomState(8231), phases=phases, n_background=30,
        mean_gap_hours=0.02, p_join=0.35, p_leave=0.25,
        make_join=lambda i: StreamSpec(f"g{i}", *KINDS[i % len(KINDS)],
                                       tier=tier(i)),
        rerate_fps=_rates, hazard_pool=48,
    )
    return initial, trace


def _storm_replay(catalog, by_type, pol, drain):
    initial, trace = _storm_trace()
    # Warm repair only: re-certification is a calm-time activity.
    return _billed_replay(trace, pol, HOURLY, catalog, initial=initial,
                          gap_threshold=10.0, billing_by_type=by_type,
                          drain_on_notice=drain)


@functools.cache
def storm() -> dict:
    _, spot_cat, by_type = _market()
    risk_cat = _risk_catalog(spot_cat, by_type)
    base = _storm_replay(risk_cat, by_type, PinningPolicy(), False)
    drain = _storm_replay(risk_cat, by_type, PinningPolicy(), True)
    # With headroom to boot replacements, degrade-and-rehome beats parking.
    tiered = _storm_replay(
        risk_cat, by_type, GracefulDegradationPolicy(park_stranded=False), True,
    )
    steps = [
        t for t in drain["timeline"]
        if t["event"] == "InstancePreemptionNotice" and t["notice_victims"]
    ]
    clean = sum(t["notice_tail_stream_hours"] <= 1e-9 for t in steps)
    blackout = "blackout_stream_seconds"
    return {
        "gold_violations_tiered": tiered["sla"].get("GOLD", {}).get("violations", 0),
        "blackout_drop_vs_baseline": 1.0 - tiered[blackout] / max(base[blackout], 1e-12),
        "tiered_billed_overhead": tiered["billed_cost"] / base["billed_cost"] - 1.0,
        "notice_conversion": clean / len(steps) if steps else 1.0,
        "notice_victim_steps": len(steps),
        "utility_penalty_ratio": tiered["utility_penalty"]
        / max(base["utility_penalty"], 1e-12),
    }


# ---------------------------------------------------------------- solver


@functools.cache
def solver() -> dict:
    """Colgen's certified gap on the calibrated n=500 / 10-kind fleet: the
    paper's programs at fractions of their calibrated max rates."""
    art = cal.load_or_calibrate("ec2")
    kinds = cal.stream_kinds(art, 10, fps_fractions=(0.03, 0.06, 0.1, 0.13, 0.16))
    streams = cal.stream_mix(art, 500, kinds=kinds)
    p = Problem(bin_types=tuple(paper_ec2_catalog()),
                items=cal.requirements_from_calibration(art, streams))
    sol, stats = solve_colgen(p)
    sol.validate()
    return {"colgen_gap_calibrated_n500k10":
            (sol.cost - stats.lp_bound) / stats.lp_bound}


# ---------------------------------------------------------------- calibration

#: A fixed TPU-cloud mix, (program, fps, count): vision nets and LLM frame
#: analyzers at rates that put some kinds on CPU hosts, some on
#: accelerators.
TPU_MIX = (
    ("vgg16", 0.2, 12),
    ("zf", 5.0, 8),
    ("internlm2-1.8b", 0.05, 10),
    ("gemma2-2b", 4.0, 8),
    ("llava-next-mistral-7b", 1.5, 6),
    ("mamba2-1.3b", 0.4, 6),
)


@functools.cache
def calibration() -> dict:
    art = cal.load_or_calibrate("tpu")
    streams = []
    for pid, fps, n in TPU_MIX:
        for i in range(n):
            s = StreamSpec(f"{pid[:5]}{i}", AnalysisProgram(pid, pid), fps)
            art.check_stream(s)
            streams.append(s)
    mgr = ResourceManager(tpu_cloud_catalog(), calibration=art, solver="colgen")
    plan = mgr.allocate(streams)
    devices = [p.device for p in plan.placements]
    fast = mgr.controller().recalibrate(art.with_accelerator_speedup(2.0))
    return {
        "calibrated_cpu_streams": devices.count("cpu"),
        "calibrated_accel_streams": devices.count("accel"),
        "accel2x_cost_saving": 1.0 - fast.plan.hourly_cost / plan.hourly_cost,
    }


SCENARIOS = {f.__name__: f for f in (
    replan, policy, lifecycle, spot, storm, solver, calibration,
)}

#: (scenario, outcome, comparison, floor).
FLOORS = [
    ("replan", "max_certified_gap", "<=", 0.15),
    ("policy", "consolidation_saving", ">=", 0.05),
    ("policy", "max_migrations_per_event", "<=", 3),
    ("lifecycle", "pinning_bitident_delta", "<=", 0.0),
    ("lifecycle", "hourly_premium", ">=", 0.0),
    ("lifecycle", "billing_aware_excess", "<=", 1e-9),
    ("spot", "risk_aware_billed_saving", ">=", 0.10),
    ("spot", "risk_vs_naive_degraded_excess", "<=", 1e-9),
    ("spot", "preempt_degraded_seconds_naive_spot", ">=", 600.0),
    ("storm", "gold_violations_tiered", "<=", 0),
    ("storm", "blackout_drop_vs_baseline", ">=", 0.60),
    ("storm", "tiered_billed_overhead", "<=", 0.10),
    ("storm", "notice_conversion", ">=", 0.80),
    ("storm", "notice_victim_steps", ">=", 1),
    ("storm", "utility_penalty_ratio", "<=", 1.0),
    ("solver", "colgen_gap_calibrated_n500k10", "<=", 0.01),
    ("calibration", "calibrated_cpu_streams", ">=", 1),
    ("calibration", "calibrated_accel_streams", ">=", 1),
    ("calibration", "accel2x_cost_saving", ">=", 0.02),
]


@pytest.mark.parametrize(
    "scenario,outcome,op,floor", FLOORS, ids=[f"{s}-{o}" for s, o, *_ in FLOORS],
)
def test_outcome_floor(scenario, outcome, op, floor):
    value = SCENARIOS[scenario]()[outcome]
    assert (value >= floor if op == ">=" else value <= floor), (
        f"{scenario}: {outcome} = {value!r}, needs {op} {floor}"
    )
