"""Readings that set a recertify cell's limits: the program, the control
and the planted faults, each through the timed path and the harness's own
check.

    python3 -m bench.control --workload <name> --seeds 1,2,3 \
        --control-seeds 4,5,6 --fault-seeds 7,8,9 --cycles 2

On the chip, at the cell's own size: one set-up, then for each seed a
fresh trace of that seed's events and ``--cycles`` re-certification
cycles, checked by ``recertify.checks`` as a run checks its window.  The
program reads the ``--seeds``; the float32 control (``bench.faults``)
the ``--control-seeds``; ``bound_weakened`` and ``prices_inflated`` the
``--fault-seeds``.  One JSON line per seed and mode, with every number
the check compares.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

from bench import faults, fleet, harness, run
from bench.drivers import recertify


def replant(state, mode: str) -> None:
    """Swap what runs under the timed path, and record it afresh."""
    state.exit_stack.close()
    stack = contextlib.ExitStack()
    if mode != "program":
        stack.enter_context(faults.plant(mode))
    state.recorder = stack.enter_context(recertify.Recorder().installed())
    state.exit_stack = stack


def readings(state, seed: int, cycles: int, tag: int, spans) -> dict:
    """Run ``cycles`` cycles of ``seed``'s trace and check them."""
    start_h = state.trace[state.next_event - 1].at if state.next_event else 0.0
    state.trace = fleet.closed_loop_trace(
        state.cfg, state.traffic, state.sc.fleet, seed, cycles,
        start_h=start_h, prefix=f"c{tag}_",
    )
    state.next_event = 0
    rec = state.recorder
    rec.clear()
    rec.active = True
    out = []
    t0 = time.perf_counter()
    for c in range(cycles):
        rec.cycle = c
        lb, cost = recertify._cycle(state, spans)
        out.append({"end_s": time.perf_counter() - t0, "lower_bound": lb, "cost": cost})
    rec.active = False
    record = {"cycles": out, "pool": recertify.pool_columns(state)}
    t1 = time.perf_counter()
    checks = recertify.checks(rec, record, state.traffic, seed)
    return {
        "seed": seed,
        "cycles_s": t1 - t0,
        "check_s": time.perf_counter() - t1,
        "dispatches": len(rec.dispatches),
        "lower_bound": [c["lower_bound"] for c in out],
        **{name: value for name, value, _limit in checks},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--cycles", type=int, default=2)
    args = ap.parse_args(argv)

    def seeds(s):
        return [int(x) for x in s.split(",") if x]

    plan = [("program", s) for s in seeds(args.seeds)]
    plan += [(faults.CONTROL, s) for s in seeds(args.control_seeds)]
    plan += [(f, s) for f in faults.SCALES for s in seeds(args.fault_seeds)]
    work = harness.load_workload(args.workload)
    if work.traffic["kind"] != "recertify":
        raise SystemExit("bench.control reads recertify cells only")
    devices = run.require_devices(work.spec["chips"])
    run._configure_jax()
    spans = harness.Spans()
    state = recertify.setup(work.config, work.traffic, plan[0][1], spans)
    mode = "program"
    for tag, (want, seed) in enumerate(plan):
        if want != mode:
            replant(state, want)
            mode = want
        line = {"mode": mode, **readings(state, seed, args.cycles, tag, spans)}
        line["device_kind"] = devices[0].device_kind
        print(json.dumps(line), flush=True)
    state.exit_stack.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
