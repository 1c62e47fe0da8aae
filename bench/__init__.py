"""The on-chip benchmark: one cell per run, found by name in BENCHMARK.json.

The system under test lives in the checkout's ``src/``; every entry point
of this package imports it from there.
"""
import pathlib
import sys

_SRC = str(pathlib.Path(__file__).resolve().parent.parent / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
