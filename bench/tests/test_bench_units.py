"""The benchmark's yardstick on the CPU: loader, traffic, roofline, reference."""
from __future__ import annotations

import itertools
import json
import re

import numpy as np
import pytest

from bench import fleet, harness, reference, roofline

BENCHMARK = harness.load_json(harness.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _config(name):
    return harness.load_json(harness.BENCH / "configs" / f"{name}.json")


# -------------------------------------------------------------------- loader


@pytest.mark.parametrize("workload", [w["name"] for w in BENCHMARK["workloads"]])
def test_loader_finds_every_part_by_name(workload):
    work = harness.load_workload(workload)
    assert work.config["name"] == work.spec["config"]
    for fn in ("setup", "window", "end_to_end", "attempted_failed", "release", "check"):
        assert callable(getattr(work.driver, fn))
    names = {m["name"] for m in work.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert work.per_layer
    for m in work.per_layer:
        assert callable(harness.metric_reader(m["name"]))
        assert m["moves"] in names


def test_unknown_device_kind_is_an_error():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("cpu")


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        harness.load_workload("no-such-cell")


def test_benchmark_file_keeps_its_shape():
    assert set(BENCHMARK) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    named = BENCHMARK["configs"] + BENCHMARK["workloads"] + BENCHMARK["end_to_end"]
    named += BENCHMARK["per_layer"]
    for entry in named:
        assert NAME.match(entry["name"]), entry["name"]
    metrics = [m["name"] for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    for m in BENCHMARK["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    for m in BENCHMARK["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    for c in BENCHMARK["configs"]:
        assert c["file"].startswith(BENCHMARK["paths"][0] + "/")
        assert _config(c["name"])["reduced"] == c["reduced"]


# ------------------------------------------------------------------- traffic


TRAFFIC = {"mix": {"join": 10, "leave": 8, "rerate": 14}, "trace_seed": 99}
SMALL = {**_config("ec2-paper-100k"), "streams": 300, "cells": 3}


def _key(ev):
    return (type(ev).__name__, getattr(ev, "name", None),
            getattr(getattr(ev, "stream", None), "name", None),
            getattr(ev, "desired_fps", None))


@pytest.mark.parametrize("seed", [0, 2**31 + 7, 3 * 2**40, -5])
def test_closed_loop_trace_orders_its_blocks_by_the_seed(seed):
    base = fleet.build_fleet(SMALL)
    a = fleet.closed_loop_trace(SMALL, TRAFFIC, base, seed, 10)
    b = fleet.closed_loop_trace(SMALL, TRAFFIC, base, seed, 10)
    c = fleet.closed_loop_trace(SMALL, TRAFFIC, base, seed + 1, 10)
    assert a == b and a != c and len(a) == 320
    assert [ev.at for ev in a] == sorted(ev.at for ev in a)
    for block in range(10):
        # The same events in every seed's block, in the seed's order.
        mine, other = a[32 * block: 32 * block + 32], c[32 * block: 32 * block + 32]
        assert sorted(map(_key, mine), key=str) == sorted(map(_key, other), key=str)
        kinds = [type(ev).__name__ for ev in mine]
        assert kinds.count("StreamAdded") == 10
        assert kinds.count("StreamRemoved") == 8
        assert kinds.count("StreamRateChanged") == 14


def test_stream_names_stay_unique_and_every_event_is_valid():
    base = fleet.build_fleet(SMALL)
    trace = fleet.closed_loop_trace(SMALL, TRAFFIC, base, 11, 120)
    live = {s.name: s.program.program_id for s in base}
    joined = [ev.stream.name for ev in trace if type(ev).__name__ == "StreamAdded"]
    assert len(set(joined)) == len(joined) and not set(joined) & set(live)
    for ev in trace:
        kind = type(ev).__name__
        if kind == "StreamAdded":
            live[ev.stream.name] = ev.stream.program.program_id
        elif kind == "StreamRemoved":
            del live[ev.name]
        else:
            assert ev.desired_fps in SMALL["rates"][live[ev.name]]


def test_fleet_tables_are_the_sources():
    cfg = _config("ec2-paper-100k")
    cat = {b.name: b for b in fleet.catalog(cfg)}
    assert cat["g2.2xlarge"].capacity == (8, 15, 1536, 4) and cat["c4.2xlarge"].cost == 0.419
    vgg_cpu = fleet.profiles(cfg).get("vgg16", "640x480", "cpu")
    assert vgg_cpu.requirement == (0.394 * 8, 0.9, 0.0, 0.0) and vgg_cpu.max_fps == 0.28
    zf_gpu = fleet.profiles(cfg).get("zf", "640x480", "accel")
    assert zf_gpu.requirement == (0.022 * 8, 0.55, 0.012 * 1536, 0.22)


# ------------------------------------------------------------------ roofline


def test_pricing_bytes_at_the_100k_certification_shape():
    b, t, s, d = 1536, 18, 6825, 4
    got = roofline.pricing_bytes(b, t, s, d)
    assert got == t * b * s * 17 + s * d * 8 + t * b * 8 * (2 + d)
    assert 3.2e9 < got < 3.3e9  # 188.7M state-steps at 17 bytes each
    assert roofline.pricing_ops(b, t, s, d) == t * b * s * 11
    least = roofline.least_seconds([(b, t, s, d)] * 2, 819e9)
    assert least == pytest.approx(2 * got / 819e9)


# ----------------------------------------------------------------- reference


def _brute_force(values, weights, bounds, cap):
    best = 0.0
    for n in itertools.product(*(range(int(b) + 1) for b in bounds)):
        n = np.asarray(n)
        if (n @ weights <= cap).all():
            best = max(best, float(n @ values))
    return best


def test_knapsack_reference_matches_brute_force():
    gen = np.random.default_rng(7)
    b_n, e_n, d = 12, 3, 2
    values = gen.uniform(0.0, 2.0, (b_n, e_n))
    weights = gen.integers(0, 5, (b_n, e_n, d))
    bounds = gen.integers(0, 6, (b_n, e_n))
    cap = gen.integers(0, 12, (b_n, d))
    got = reference.knapsack_optimum(values, weights, bounds, cap)
    want = [_brute_force(values[i], weights[i], bounds[i], cap[i]) for i in range(b_n)]
    np.testing.assert_allclose(got, want, rtol=1e-12)


def test_pricing_gap_reads_wrong_and_infeasible_answers():
    values = np.array([[1.0, 0.7]])
    weights = np.array([[[2], [1]]])
    bounds = np.array([[3, 3]])
    cap = np.array([[4]])
    ref = reference.knapsack_optimum(values, weights, bounds, cap)
    assert ref[0] == pytest.approx(2.4)  # one of the first and two of the second
    gap = reference.pricing_gap
    assert gap([2.4], [[1, 2]], values, weights, bounds, cap, ref) < 1e-15
    assert gap([2.1], [[0, 3]], values, weights, bounds, cap, ref) == pytest.approx(0.125)
    assert gap([2.5], [[1, 2]], values, weights, bounds, cap, ref) > 0.04
    assert gap([2.4], [[2, 3]], values, weights, bounds, cap, ref) == 1.0  # too big


def _brute_patterns(values, reqs, cap):
    best = 0.0
    ub = [int(min(c / r for c, r in zip(cap, row) if r > 0)) for row in reqs]
    for n in itertools.product(*(range(u + 1) for u in ub)):
        if reference.fits(n, reqs, cap):
            best = max(best, float(np.asarray(n) @ values))
    return best


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_pattern_optimum_matches_brute_force(seed):
    gen = np.random.default_rng(seed)
    values = gen.uniform(0.0, 1.0, 4)
    reqs = gen.uniform(0.1, 2.0, (4, 2)).round(3)
    cap = np.array([4.0, 5.0])
    got = reference.pattern_optimum(values, reqs, cap)
    assert got == pytest.approx(_brute_patterns(values, reqs, cap), abs=1e-6)


def test_pattern_optimum_counts_a_bin_filled_to_rounding():
    # Nine copies of 0.1 sum past 0.9 in floating point, and still fit.
    assert reference.pattern_optimum([1.0], [[0.1]], [0.9]) == 9.0
    assert reference.pattern_optimum([1.0, 0.0], [[0.0], [1.0]], [1.0]) == np.inf


def test_covering_lp_is_the_cheapest_fractional_cover():
    columns = [[2, 0], [0, 3], [1, 1]]
    # Four of the mixed packing, then two thirds of the second.
    assert reference.covering_lp(columns, [1.0, 1.0, 0.8], [4, 6]) == pytest.approx(
        4 * 0.8 + 2 / 3)
    assert reference.covering_lp([[1, 0]], [1.0], [1, 1]) == np.inf


def test_config_files_parse():
    for c in BENCHMARK["configs"]:
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert cfg["source"] and cfg["guarantees"] and "assumed" in cfg
