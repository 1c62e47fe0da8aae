"""Deployments and fleet-event traces, built from the data files by name.

The yardstick's own copy of what the legacy ``benchmarks/`` suite used to
build its 100k-stream fleet (``benchmarks/shard.py``: ``_fleet``,
``_events``, ``_manager``; ``benchmarks/consolidation.py``: ``KINDS``),
and of the catalog and profiles its manager read.  The constants live in
``bench/configs/<config>.json``, so a later change to that suite or to
the program's tables cannot move the benchmark.

Every trace is drawn from the traffic file and ``--seed``: the file fixes
which events a block of the trace holds, the seed their order.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.binpack.problem import BinType
from repro.core.manager import ResourceManager
from repro.core.profiler import ProfileTable, ResourceProfile
from repro.core.shard import ShardedController, hash_cells
from repro.core.strategies import ST3
from repro.core.streams import (
    AnalysisProgram,
    StreamAdded,
    StreamRateChanged,
    StreamRemoved,
    StreamSpec,
)

#: Seconds of trace time between two events of a closed-loop trace, in
#: the hours ``FleetEvent.at`` counts (``benchmarks/shard.py``'s spacing).
EVENT_GAP_H = 0.01


def rng(seed: int, stream: int = 0) -> np.random.Generator:
    """The generator for ``--seed``: any whole number, negative or past 2**32."""
    return np.random.default_rng([seed % 2**64, stream])


def kinds(cfg: dict) -> list[tuple[AnalysisProgram, float]]:
    """The fleet's stream kinds in build order: ``(program, fps)`` each.

    ``cfg["kinds"]`` lists ``[name, program_id, fps, count]``; stream ``i``
    of the fleet is kind ``i % len(kinds)`` of the expanded list.
    """
    out = []
    for name, pid, fps, count in cfg["kinds"]:
        out.extend([(AnalysisProgram(name, pid), float(fps))] * int(count))
    return out


def build_fleet(cfg: dict) -> list[StreamSpec]:
    ks = kinds(cfg)
    return [StreamSpec(f"s{i}", *ks[i % len(ks)]) for i in range(cfg["streams"])]


def catalog(cfg: dict) -> tuple[BinType, ...]:
    return tuple(
        BinType(b["name"], capacity=tuple(b["capacity"]), cost=b["cost"])
        for b in cfg["catalog"]
    )


def profiles(cfg: dict) -> ProfileTable:
    """The programs' requirement profiles, as the source's tables give them."""
    p = cfg["profiles"]
    table = ProfileTable()
    for row in p["rows"]:
        prog, c_cpu, g_cpu, g_gpu, mem, gmem, max_cpu, max_gpu = row
        for device, req, max_fps in (
            ("cpu", (c_cpu * p["cores"], mem, 0.0, 0.0), max_cpu),
            ("accel", (g_cpu * p["cores"], mem, g_gpu * p["gpu_cores"], gmem), max_gpu),
        ):
            table.add(ResourceProfile(
                program_id=prog, frame_size=p["frame_size"], device=device,
                reference_fps=p["reference_fps"], requirement=req, max_fps=max_fps,
            ))
    return table


def build_manager(cfg: dict) -> ResourceManager:
    return ResourceManager(catalog(cfg), profiles(cfg), max_nodes=cfg["max_nodes"])


def build_controller(cfg: dict) -> ShardedController:
    """The deployment's controller, cold-started on its fleet (batched pack)."""
    if cfg["strategy"] != "ST3":
        raise ValueError(f"unknown strategy {cfg['strategy']!r}")
    sc = ShardedController(
        build_manager(cfg),
        ST3,
        cell_key=hash_cells(cfg["cells"]),
        sub_max_nodes=cfg["sub_max_nodes"],
        gap_threshold=cfg["gap_threshold"],
    )
    sc.reset(build_fleet(cfg), at=0.0, pack="batched")
    return sc


def _block(counts: dict) -> list[str]:
    block = [k for k in ("join", "leave", "rerate") for _ in range(counts.get(k, 0))]
    if not block:
        raise ValueError("traffic mix names no events")
    return block


def closed_loop_trace(
    cfg: dict, traffic: dict, fleet, seed: int, blocks: int, *,
    start_h: float = 0.0, prefix: str = "e",
) -> list:
    """``blocks`` blocks of the traffic's mix, ``EVENT_GAP_H`` hours apart.

    A block holds exactly ``traffic["mix"][k]`` events of each kind ``k``.
    What they are is drawn from ``traffic["trace_seed"]``: joins take the
    fleet's kinds round robin and are named ``<prefix><j>``, unique over
    the trace; leaves and re-rates each pick a different stream that is
    live when the block starts, and a re-rate moves it to another rate
    the configuration lists for its program.  ``seed`` draws the order of
    the events inside each block.  So every seed asks for the same work,
    in its own order, and any order inside a block is a valid trace.
    """
    block = _block(traffic["mix"])
    what = rng(traffic["trace_seed"])
    order = rng(seed)
    ks = kinds(cfg)
    rates = {pid: [float(r) for r in rs] for pid, rs in cfg["rates"].items()}
    live = {s.name: (s.program.program_id, s.desired_fps) for s in fleet}
    nxt, j, out = int(what.integers(len(ks))), 0, []
    for _ in range(blocks):
        names = list(live)
        picked = iter(what.choice(len(names), len(block), replace=False))
        evs = []
        for kind in block:
            if kind == "join":
                program, rate = ks[nxt % len(ks)]
                nxt += 1
                name = f"{prefix}{j}"
                j += 1
                evs.append(StreamAdded(StreamSpec(name, program, rate)))
                live[name] = (program.program_id, rate)
                continue
            name = names[next(picked)]
            pid, fps = live[name]
            if kind == "leave":
                evs.append(StreamRemoved(name))
                del live[name]
            else:
                other = [r for r in rates[pid] if r != fps] or [fps]
                rate = other[int(what.integers(len(other)))]
                evs.append(StreamRateChanged(name, rate))
                live[name] = (pid, rate)
        for i in order.permutation(len(evs)):
            out.append(dataclasses.replace(
                evs[i], at=start_h + EVENT_GAP_H * (len(out) + 1)
            ))
    return out
