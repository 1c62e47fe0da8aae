"""Regenerate the committed calibration artifacts (CALIBRATION_*.json).

Usage:
  PYTHONPATH=src python scripts/recalibrate.py            # all presets
  PYTHONPATH=src python scripts/recalibrate.py tpu        # one preset
  PYTHONPATH=src python scripts/recalibrate.py --impl jax # vectorized path
  PYTHONPATH=src python scripts/recalibrate.py --measured # wall-clock CPU runs
  PYTHONPATH=src python scripts/recalibrate.py --check    # freshness gate

This is the regeneration entry point of the calibrated-requirements loop:
when kernels (or hardware constants, or the workload set) change, rerun it
and every calibrated scenario re-derives its requirement vectors from the
new artifact.  The default analytic mode is deterministic — rerunning
without a source change rewrites byte-identical files, which is what
``--check`` verifies (exit 1 when a committed artifact is stale or
missing).  ``--measured`` swaps in real `measure_cpu_profile` wall-clock
test runs for the runnable vision programs (the paper's actual procedure;
nondeterministic, recorded in provenance).
"""
from __future__ import annotations

import argparse
import sys

from repro.core import calibration as cal
from repro.launch.compile_cache import use_compile_cache


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("presets", nargs="*", default=None,
                    help=f"presets to regenerate (default: all of {sorted(cal.PRESETS)})")
    ap.add_argument("--impl", choices=("numpy", "jax"), default="numpy")
    ap.add_argument("--measured", action="store_true",
                    help="wall-clock CPU test runs instead of analytic")
    ap.add_argument("--check", action="store_true",
                    help="verify committed artifacts match a fresh analytic "
                         "calibration; write nothing")
    args = ap.parse_args()
    use_compile_cache()
    names = args.presets or sorted(cal.PRESETS)
    stale = []
    for name in names:
        preset = cal.PRESETS[name]
        artifact = cal.calibrate(
            preset.catalog_fn(),
            preset.workloads_fn(),
            cpu=preset.cpu,
            roofline=preset.roofline,
            impl=args.impl,
            cpu_mode="measured" if args.measured else "analytic",
            host_cores_fraction=preset.host_cores_fraction,
        )
        path = cal.default_artifact_path(name)
        if args.check:
            try:
                on_disk = cal.CalibrationArtifact.load(path)
            except (OSError, ValueError, KeyError):
                on_disk = None
            fresh = on_disk == artifact
            print(f"{path.name}: {'fresh' if fresh else 'STALE'} "
                  f"({len(artifact.entries)} entries, sig {artifact.catalog_signature})")
            if not fresh:
                stale.append(name)
            continue
        artifact.save(path)
        print(f"wrote {path.name}: {len(artifact.entries)} profiles over "
              f"{len(artifact.programs())} programs, catalog sig "
              f"{artifact.catalog_signature}, mode "
              f"{artifact.provenance['cpu_mode']}/{artifact.provenance['impl']}")
        for e in artifact.entries:
            req = ", ".join(f"{x:.4g}" for x in e.requirement)
            print(f"  {e.program_id:24s} {e.device:5s} [{req}] "
                  f"max {e.max_fps:.4g} fps ({e.source})")
    if stale:
        print(f"stale artifacts: {stale} — rerun scripts/recalibrate.py",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
