"""Run one benchmark cell once on the chip and print its result line.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  Set-up (fleet, cold certification, every
compile the window needs) is timed as ``setup_s``; then the window runs
for ``--seconds``.  With ``--trace 1`` the window runs under the JAX
profiler and the line carries the cell's per-layer metrics, device busy
time and a breakdown; with ``--trace 0``, its end-to-end metrics.  Once
the window has closed and device memory has been read, the program's
state is freed and what the window produced is checked against the plain
references: each number beside its limit, as the last lines on standard
error and under ``checks``, the last key of the line.  The last line of
standard output is the result, a JSON object.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))  # run as a script: bench/run.py

#: The persistent compilation cache: a fixed directory in the checkout,
#: so the second run of a cell finds every program the first compiled.
CACHE_DIR = ROOT / ".jax_cache" / "bench"


def require_devices(chips: int):
    """The devices a cell runs on; exits 2 without enough TPU chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX sees {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        sys.exit(2)
    return devices[:chips]


def _configure_jax() -> None:
    import jax

    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # No eviction: it keeps an access-time file per entry, and once one
    # went missing every later write failed, so runs recompiled.
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _breakdown(red) -> dict:
    ops = sorted(red.op_s.items(), key=lambda kv: -kv[1])[:10]
    return {
        "device_ops": [[name, s] for name, s in ops],
        "idle_gaps": [[name, s] for name, s in red.gaps[:10]],
    }


def run(args) -> dict:
    import jax

    from bench import harness, xplane

    work = harness.load_workload(args.workload)
    devices = require_devices(work.spec["chips"])
    _configure_jax()
    peaks = harness.peaks(devices[0].device_kind)
    compiles = harness.Compiles()
    spans = harness.Spans()
    driver = work.driver

    state = driver.setup(work.config, work.traffic, args.seed, spans)
    gc.collect()
    setup_s = time.perf_counter() - _T0

    red = None
    c0 = compiles.count
    if args.trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with spans("bench.window"):
            record = driver.window(state, args.seconds, spans)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    window_compiles = compiles.count - c0
    if args.trace:
        try:
            red = xplane.reduce(xplane.find_xplane(log_dir))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
    memory_peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
    )

    driver.release(state)
    gc.collect()
    jax.clear_caches()
    checks = driver.check(state, record)
    correct = all(value <= limit for _name, value, limit in checks)

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(memory_peak),
    }
    attempted, failed = driver.attempted_failed(record)
    print(json.dumps({"window": record["log"], "setup_s": setup_s}), file=sys.stderr)
    if args.trace:
        ctx = harness.Context(work, peaks, spans, record, window_compiles, red)
        metrics = {}
        for m in work.per_layer:
            value = harness.metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
    else:
        values = {**driver.end_to_end(record), "setup_s": setup_s}
        metrics = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in work.end_to_end
        }
    result = {
        "correct": correct,
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if red is not None:
        result["breakdown"] = _breakdown(red)
    result["checks"] = {
        name: {"value": value, "limit": limit} for name, value, limit in checks
    }
    for name, value, limit in checks:
        print(f"check {name} = {value!r} (limit {limit!r})", file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run(args)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
