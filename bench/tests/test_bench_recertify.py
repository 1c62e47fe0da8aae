"""The recertify driver at a tiny fleet on the CPU: sound runs are correct,
the float32 control and each fault the cell can have are not.

Each run goes through ``bench.run.run`` with the look for a chip skipped
and the peaks stubbed, on the cell's traffic file and a copy of its
configuration cut to a few hundred streams.  The control and the faults
are planted under the timed path (``bench.faults``).
"""
from __future__ import annotations

import argparse
import json

import jax
import pytest

from bench import faults, harness, run

CELL = "ec2-paper-100k.recertify"
SIZE = {"streams": 300, "cells": 3}


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    """Point the harness at a tiny copy of the cell's configuration."""
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    for c in bench["configs"]:
        cfg = {**harness.load_json(harness.ROOT / c["file"]), **SIZE}
        path = tmp_path / f"{c['name']}.json"
        path.write_text(json.dumps(cfg))
        c["file"] = str(path)
    real = harness.load_workload
    monkeypatch.setattr(harness, "load_workload", lambda name: real(name, bench))
    monkeypatch.setattr(run, "require_devices", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "peaks", lambda kind: {"hbm_bytes_per_s": 819e9})


def _run(trace=0, seed=5):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.0, trace=trace)
    return run.run(args)


@pytest.mark.parametrize("seed", [5, 2**31 + 11])
def test_sound_run_is_correct(tiny, seed):
    out = _run(seed=seed)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["metrics"]["recertify_s"]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0
    checks = {k: v["value"] for k, v in out["checks"].items()}
    assert checks["pricing_gap"] < 1e-14 and checks["price_excess"] == 0.0
    assert checks["bound_gap"] < 1e-8


def test_traced_run_reports_per_layer_metrics(tiny):
    out = _run(trace=1)
    assert out["correct"]
    assert out["metrics"]["pricing_rounds_per_cycle"]["value"] >= 1
    assert out["metrics"]["window_compiles.recertify"]["value"] == 0
    assert "busy_s" in out["device"] and "breakdown" in out


@pytest.mark.parametrize("name", [faults.CONTROL, *faults.FAULTS])
def test_control_and_faults_make_the_run_incorrect(tiny, name):
    with faults.plant(name, real_refreshes=1):
        out = _run()
    assert not out["correct"], out["checks"]
