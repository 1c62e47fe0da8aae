"""What every cell shares: loading by name, the clock, spans, compiles.

``BENCHMARK.json`` names a workload; the workload names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``), whose ``kind`` names the driver module
``bench/drivers/<kind>.py``.  Each per-layer metric is read by
``bench/metrics/<metric>.py``.  Peaks come from ``bench/peaks.json`` by the
device's ``device_kind``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import pathlib
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


@dataclasses.dataclass
class Workload:
    spec: dict  # the workload's entry in BENCHMARK.json
    config: dict
    traffic: dict
    driver: object  # the driver module
    end_to_end: list[dict]  # the metrics this cell reports with --trace 0
    per_layer: list[dict]  # ... and with --trace 1


def _reports(metric: dict, name: str) -> bool:
    return name in metric.get("workloads", [name])


def load_workload(name: str, benchmark: dict | None = None) -> Workload:
    if benchmark is None:
        benchmark = load_json(ROOT / "BENCHMARK.json")
    specs = {w["name"]: w for w in benchmark["workloads"]}
    if name not in specs:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    spec = specs[name]
    configs = {c["name"]: c for c in benchmark["configs"]}
    config = load_json(ROOT / configs[spec["config"]]["file"])
    traffic = load_json(BENCH / "traffic" / f"{spec['traffic']}.json")
    driver = importlib.import_module(f"bench.drivers.{traffic['kind']}")
    e2e = [m for m in benchmark["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [
        m for m in benchmark["per_layer"]
        if name in m.get("workloads", [name] if m["moves"] in moved else [])
    ]
    return Workload(spec, config, traffic, driver, e2e, per_layer)


def metric_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read(ctx)``; dots become ``_``."""
    module = importlib.import_module(f"bench.metrics.{name.replace('.', '_')}")
    return module.read


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]


class Spans:
    """Host spans of the benchmark's own calls, kept in memory.

    Each span also enters the profiler's trace as a ``TraceAnnotation`` of
    the same name, so a traced run can say what the host was doing while
    the device sat idle.
    """

    def __init__(self) -> None:
        self.records: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))


class Compiles:
    """Counts XLA backend compiles; a load from the persistent cache is none."""

    def __init__(self) -> None:
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


@dataclasses.dataclass
class Context:
    """What the per-layer metric readers read."""

    workload: Workload
    peaks: dict
    spans: Spans
    window: dict  # the driver's window record
    compiles_in_window: int
    trace: object | None  # xplane.Reduction, with --trace 1
