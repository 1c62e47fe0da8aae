"""The trace reduction, on a small trace recorded on one TPU v5 lite.

``data/pricing_dispatch.xplane.pb`` holds a ``bench.window`` span with a
50 ms ``bench.apply_events`` span (host only) and a ``bench.refresh_prices``
span around one 12-knapsack pricing dispatch and a 20 ms sleep.
"""
import pathlib

import pytest

from bench import xplane

TRACE = pathlib.Path(__file__).parent / "data" / "pricing_dispatch.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return xplane.reduce(TRACE)


def test_window_and_busy_time(red):
    assert red.devices == 1
    assert 0.07 < red.window_s < 0.1
    assert 0.0 < red.busy_s < 0.01
    assert red.busy_s == pytest.approx(red.module_s["jit_run"], rel=1e-4)


def test_idle_gaps_are_named_by_the_host_span_around_them(red):
    longest, second = red.gaps[0], red.gaps[1]
    assert longest[0] == "bench.apply_events" and 0.05 <= longest[1] < 0.06
    assert second[0] == "bench.refresh_prices" and 0.02 <= second[1] < 0.03
    assert sum(s for _n, s in red.gaps) == pytest.approx(red.window_s - red.busy_s)
    assert red.span_busy_s["bench.apply_events"] == 0.0
    assert red.span_busy_s["bench.refresh_prices"] == pytest.approx(red.busy_s)


def test_ops_are_named_by_their_module(red):
    top = max(red.op_s, key=red.op_s.get)
    assert top.startswith("jit_run/%while")
    assert all(" = " not in name for name in red.op_s)
