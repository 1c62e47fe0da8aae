"""The per-layer metrics read from the program's own spans, on the tiny
traced run of ``test_bench_recertify`` (300 streams / 3 cells, the CPU).

All seven come out and are positive, the take-table bytes match the
shapes of the pricing dispatches ``bench.drivers.recertify`` recorded in
the window exactly, and the run stays correct.  On a checkout whose
program records no spans, the readers return nothing and do not raise.
"""
from __future__ import annotations

import sys

import pytest
from test_bench_recertify import _run, tiny  # noqa: F401  (fixture)

from bench import harness
from bench.drivers import recertify

SPAN_METRICS = (
    "route_ms_per_event", "fold_ms_per_event", "master_lp_s_per_cycle",
    "exact_dfs_s_per_cycle", "pricing_host_s_per_cycle",
    "take_fetch_s_per_cycle", "take_bytes_per_cycle",
)


@pytest.fixture
def window_record(monkeypatch):
    """The record ``recertify.window`` returned for the run, kept for
    the test."""
    kept = {}
    real = recertify.window

    def window(state, seconds, spans):
        kept["record"] = real(state, seconds, spans)
        return kept["record"]

    monkeypatch.setattr(recertify, "window", window)
    return kept


def test_span_metrics_on_a_traced_run(tiny, window_record):  # noqa: F811
    out = _run(trace=1)
    assert out["correct"], out["checks"]
    metrics = out["metrics"]
    for name in SPAN_METRICS:
        assert metrics[name]["value"] > 0, name
    units = {m["name"]: m["unit"] for m in harness.load_json(
        harness.ROOT / "BENCHMARK.json")["per_layer"]}
    assert all(metrics[n]["unit"] == units[n] for n in SPAN_METRICS)

    record = window_record["record"]
    cycles = len(record["cycles"])
    fetched = sum(b * t * s for b, t, s, _d in record["shapes"])
    assert fetched > 0
    assert round(metrics["take_bytes_per_cycle"]["value"] * cycles * 1e6) == fetched
    # The four metrics read from outside the program still come out.
    assert metrics["pricing_rounds_per_cycle"]["value"] >= 1
    assert metrics["window_compiles.recertify"]["value"] == 0


def test_readers_return_nothing_without_program_spans(tiny, monkeypatch):  # noqa: F811
    # A checkout whose program has no ``repro.telemetry``, as before the
    # spans existed: the import fails and every reader returns None.
    import repro

    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    monkeypatch.delattr(repro, "telemetry")
    out = _run(trace=1)
    assert out["correct"]
    assert not set(SPAN_METRICS) & set(out["metrics"])
    assert "pricing_rounds_per_cycle" in out["metrics"]
