"""Calibration determinism, persistence, and staleness contracts.

The calibrated-requirements path (`core.calibration`) only earns its
place in the tests' pinned scenarios if it is *deterministic*: the same
catalog + workloads must produce bit-identical requirement vectors
across repeated runs and across the numpy / jax implementations, the
JSON artifact must round-trip unchanged, and a stale artifact (taken
against a different catalog shape) must be rejected loudly everywhere
it can be consumed.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from repro.core import calibration as cal
from repro.core.binpack.problem import BinType
from repro.core.catalog import paper_ec2_catalog
from repro.core.manager import ResourceManager
from repro.core.profiler import DIM_ACC, DIM_ACC_MEM, DIM_CPU, DIM_MEM
from repro.core.streams import (
    AnalysisProgram,
    StreamSpec,
    synthetic_timed_trace,
)


def _ec2_kwargs() -> dict:
    preset = cal.PRESETS["ec2"]
    return dict(
        cpu=preset.cpu,
        roofline=preset.roofline,
        host_cores_fraction=preset.host_cores_fraction,
    )


def _ec2_calibrate(**overrides) -> cal.CalibrationArtifact:
    kwargs = {**_ec2_kwargs(), **overrides}
    return cal.calibrate(paper_ec2_catalog(), cal.preset_workloads("ec2"), **kwargs)


# ---------------------------------------------------------------------------
# Determinism
# ---------------------------------------------------------------------------

def test_repeated_calibration_is_bit_identical():
    a = _ec2_calibrate()
    b = _ec2_calibrate()
    assert a == b  # whole artifact, provenance included


def test_numpy_and_jax_paths_agree_bit_for_bit():
    pytest.importorskip("jax")
    np_art = _ec2_calibrate(impl="numpy")
    jx_art = _ec2_calibrate(impl="jax")
    # Provenance records the impl, so compare the payload: entries carry
    # every requirement vector and max rate.
    assert np_art.entries == jx_art.entries
    assert np_art.catalog_signature == jx_art.catalog_signature


def test_numpy_and_jax_agree_on_the_tpu_preset():
    pytest.importorskip("jax")
    preset = cal.PRESETS["tpu"]
    kwargs = dict(
        cpu=preset.cpu,
        roofline=preset.roofline,
        host_cores_fraction=preset.host_cores_fraction,
    )
    catalog = preset.catalog_fn()
    workloads = preset.workloads_fn()
    np_art = cal.calibrate(catalog, workloads, impl="numpy", **kwargs)
    jx_art = cal.calibrate(catalog, workloads, impl="jax", **kwargs)
    assert np_art.entries == jx_art.entries


def test_committed_artifacts_are_fresh():
    """CALIBRATION_*.json must equal an in-process recalibration
    (the contract `scripts/recalibrate.py --check` enforces at the CLI)."""
    for name, preset in sorted(cal.PRESETS.items()):
        on_disk = cal.CalibrationArtifact.load(cal.default_artifact_path(name))
        fresh = cal.calibrate(
            preset.catalog_fn(),
            preset.workloads_fn(),
            cpu=preset.cpu,
            roofline=preset.roofline,
            host_cores_fraction=preset.host_cores_fraction,
        )
        assert on_disk == fresh, f"CALIBRATION_{name}.json is stale"


# ---------------------------------------------------------------------------
# Persistence
# ---------------------------------------------------------------------------

def test_json_round_trip_is_unchanged(tmp_path):
    art = _ec2_calibrate()
    p = tmp_path / "cal.json"
    art.save(p)
    assert cal.CalibrationArtifact.load(p) == art
    # And a second save of the loaded artifact is byte-identical.
    p2 = tmp_path / "cal2.json"
    cal.CalibrationArtifact.load(p).save(p2)
    assert p.read_text() == p2.read_text()


def test_from_dict_rejects_unknown_version():
    d = _ec2_calibrate().to_dict()
    d["version"] = 999
    with pytest.raises(ValueError, match="version"):
        cal.CalibrationArtifact.from_dict(d)


# ---------------------------------------------------------------------------
# Staleness
# ---------------------------------------------------------------------------

def _grown_catalog() -> tuple[BinType, ...]:
    catalog = paper_ec2_catalog()
    first = catalog[0]
    caps = tuple(c * 2 for c in first.capacity)
    return (dataclasses.replace(first, capacity=caps),) + tuple(catalog[1:])


def test_stale_catalog_signature_is_rejected():
    art = _ec2_calibrate()
    art.verify(paper_ec2_catalog())  # fresh: no raise
    with pytest.raises(cal.StaleCalibrationError, match="recalibrate"):
        art.verify(_grown_catalog())


def test_manager_refuses_a_stale_artifact():
    art = _ec2_calibrate()
    with pytest.raises(cal.StaleCalibrationError):
        ResourceManager(_grown_catalog(), calibration=art)
    with pytest.raises(cal.StaleCalibrationError):
        cal.requirements_from_calibration(
            art,
            cal.stream_mix(art, 2, n_kinds=2),
            catalog=_grown_catalog(),
        )


def test_price_drift_does_not_stale_the_artifact():
    """The signature covers (name, capacity): repricing an instance type —
    the churn trace's PriceChanged events — must not invalidate it."""
    art = _ec2_calibrate()
    catalog = paper_ec2_catalog()
    repriced = (dataclasses.replace(catalog[0], cost=99.0),) + tuple(
        catalog[1:]
    )
    art.verify(repriced)  # no raise


# ---------------------------------------------------------------------------
# Consumption: calibrated items and trace validation
# ---------------------------------------------------------------------------

def test_calibrated_items_scale_linearly_with_fps():
    art = _ec2_calibrate()
    table = art.profile_table()
    lo = table.choices_for(StreamSpec("a", AnalysisProgram("zf", "zf"), 0.5))
    hi = table.choices_for(StreamSpec("b", AnalysisProgram("zf", "zf"), 1.0))
    for c_lo, c_hi in zip(lo.choices, hi.choices):
        assert c_lo.label == c_hi.label
        # CPU and accel-compute scale with the rate; memory floors do not.
        for dim in (DIM_CPU, DIM_ACC):
            assert c_hi.requirement[dim] == pytest.approx(
                2.0 * c_lo.requirement[dim]
            )
        for dim in (DIM_MEM, DIM_ACC_MEM):
            assert c_hi.requirement[dim] == c_lo.requirement[dim]


def test_stream_mix_rejects_uncalibrated_rates():
    art = _ec2_calibrate()
    zf_max = art.max_feasible_fps("zf", "640x480")
    assert zf_max > 0.0
    with pytest.raises(ValueError, match="exceeds the calibrated max"):
        art.check_stream(
            StreamSpec("hot", AnalysisProgram("zf", "zf"), zf_max * 2.0)
        )
    with pytest.raises(ValueError, match="no calibration entry"):
        art.check_stream(
            StreamSpec("who", AnalysisProgram("nope", "nope"), 0.1)
        )


def test_timed_trace_validates_streams_against_calibration():
    art = _ec2_calibrate()
    rng = np.random.RandomState(0)
    ok = cal.stream_mix(art, 6, n_kinds=3)
    trace = synthetic_timed_trace(
        list(ok), rng, n_events=20, calibration=art
    )
    assert len(trace) == 20
    bad = [StreamSpec("b0", AnalysisProgram("zf", "zf"), 10_000.0)]
    with pytest.raises(ValueError, match="exceeds the calibrated max"):
        synthetic_timed_trace(
            bad, np.random.RandomState(0), n_events=5, calibration=art
        )


def test_accelerator_speedup_halves_compute_not_memory():
    art = cal.load_or_calibrate("tpu")
    fast = art.with_accelerator_speedup(2.0)
    by_key = {(e.program_id, e.device): e for e in art.entries}
    sped = {(e.program_id, e.device): e for e in fast.entries}
    assert set(by_key) == set(sped)
    compute_bound_seen = 0
    for key, e in by_key.items():
        f = sped[key]
        if e.device == "cpu":
            assert f == e  # CPU entries untouched
            continue
        # Memory floors and the host-core draw never move.
        assert f.requirement[DIM_MEM] == e.requirement[DIM_MEM]
        assert f.requirement[DIM_ACC_MEM] == e.requirement[DIM_ACC_MEM]
        assert f.requirement[DIM_CPU] == e.requirement[DIM_CPU]
        # Accel compute shrinks by 2x up to the artifact's significant-
        # digit quantization (entries re-quantize after the transform).
        assert f.requirement[DIM_ACC] == pytest.approx(
            e.requirement[DIM_ACC] / 2.0, rel=1e-5
        )
        if f.max_fps > e.max_fps:
            compute_bound_seen += 1
    assert compute_bound_seen > 0  # the kernel→dollars lever exists
    assert fast.provenance["accelerator_speedup"] == 2.0
    assert fast.with_accelerator_speedup(2.0).provenance[
        "accelerator_speedup"
    ] == 4.0


# ---------------------------------------------------------------------------
# Measured mode (real wall-clock test runs — heavy, tier-2)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_measured_cpu_mode_runs_the_real_programs():
    art = _ec2_calibrate(cpu_mode="measured")
    cpu_sources = {
        e.program_id: e.source for e in art.entries if e.device == "cpu"
    }
    # Both paper vision nets have runnable implementations, so the
    # measured path must actually engage (no silent analytic fallback).
    assert cpu_sources == {"vgg16": "measured", "zf": "measured"}
    for e in art.entries:
        if e.device == "cpu":
            assert e.requirement[DIM_CPU] > 0.0
            assert e.max_fps > 0.0
