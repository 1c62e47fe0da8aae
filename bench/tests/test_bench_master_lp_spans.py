"""The cold-solve count of the master LPs, read from the program's own
``colgen.master_lp`` spans on the tiny traced run of
``test_bench_recertify`` (300 streams / 3 cells, the CPU).

It comes out, in its declared unit, with at least one cold solve a cycle,
and the run stays correct.  Where the program records no spans, or its
master-LP spans carry no ``cold_solves`` (as before the master LPs of a
round shared their optimal bases), the reader returns nothing and does
not raise.
"""
from __future__ import annotations

import sys

from test_bench_recertify import _run, tiny  # noqa: F401  (fixture)

from bench import harness

METRIC = "master_lp_cold_solves_per_cycle"


def test_cold_solves_on_a_traced_run(tiny):  # noqa: F811
    out = _run(trace=1)
    assert out["correct"], out["checks"]
    metric = out["metrics"][METRIC]
    # At least one cold master-LP solve a round, one round at least a cycle.
    assert metric["value"] >= 1
    units = {m["name"]: m["unit"] for m in harness.load_json(
        harness.ROOT / "BENCHMARK.json")["per_layer"]}
    assert metric["unit"] == units[METRIC]


def test_cold_solve_reader_returns_nothing_without_program_spans(tiny, monkeypatch):  # noqa: F811
    import repro

    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    monkeypatch.delattr(repro, "telemetry")
    out = _run(trace=1)
    assert out["correct"]
    assert METRIC not in out["metrics"]


def test_cold_solve_reader_returns_nothing_without_the_attribute(tiny, monkeypatch):  # noqa: F811
    # A program whose master-LP spans count no cold solves.
    from repro.core.binpack import colgen

    rows = colgen._covering_lp_rows
    monkeypatch.setattr(
        colgen, "_covering_lp_rows", lambda *a: (rows(*a)[0], {}))
    out = _run(trace=1)
    assert out["correct"]
    assert METRIC not in out["metrics"]
    assert out["metrics"]["master_lp_s_per_cycle"]["value"] > 0
