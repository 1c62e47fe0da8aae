"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

A device is busy while any of its operations runs: the union of the
events on the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane (the
``XLA Modules`` line where a plane has no op line).  The window is the
benchmark's own host span ``bench.window``; what the host was doing in a
gap is the innermost other ``bench.*`` span around the gap's middle.
"""
from __future__ import annotations

import dataclasses
import pathlib

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU:"


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float  # averaged over the devices that ran anything
    devices: int
    module_s: dict[str, float]  # device seconds per XLA module, in window
    op_s: dict[str, float]  # device seconds per "<module>/<op>", in window
    gaps: list[tuple[str, float]]  # (host span, seconds), longest first
    span_busy_s: dict[str, float]  # device busy seconds inside each span kind


def find_xplane(log_dir: str | pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _union(intervals):
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _overlap(intervals, lo, hi) -> int:
    return sum(e - s for s, e in _clip(intervals, lo, hi))


def _events(line):
    for ev in line.events:
        start = int(ev.start_ns)
        yield ev, start, start + int(ev.duration_ns)


def reduce(path: str | pathlib.Path) -> Reduction:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    spans: list[tuple[str, int, int]] = []
    device_lines = []
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith(DEVICE_PREFIX):
            ops = lines.get("XLA Ops") or lines.get("XLA Modules")
            if ops is not None:
                device_lines.append((ops, lines.get("XLA Modules")))
            continue
        for line in plane.lines:
            for ev, s, e in _events(line):
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, s, e))
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(windows)}")
    lo, hi = windows[0]
    inner = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN]

    busy_each, module_s, op_s = [], {}, {}
    merged: list[tuple[int, int]] = []
    for ops, modules in device_lines:
        mods = sorted(
            (s, e, ev.name.split("(")[0]) for ev, s, e in _events(modules)
        ) if modules is not None else []
        for s, e, name in mods:
            clipped = min(e, hi) - max(s, lo)
            if clipped > 0:
                module_s[name] = module_s.get(name, 0.0) + clipped / 1e9
        intervals, m = [], 0
        for ev, s, e in sorted(_events(ops), key=lambda x: x[1]):
            clipped = min(e, hi) - max(s, lo)
            if clipped <= 0:
                continue
            intervals.append((s, e))
            while m < len(mods) and mods[m][1] < s:
                m += 1
            owner = mods[m][2] if m < len(mods) and mods[m][0] <= s else "?"
            name = f"{owner}/{ev.name.split(' = ')[0]}"
            op_s[name] = op_s.get(name, 0.0) + clipped / 1e9
        busy = _clip(_union(intervals), lo, hi)
        if busy:
            busy_each.append(sum(e - s for s, e in busy))
            merged.extend(busy)
    union = _union(merged)

    gaps, cursor = [], lo
    for s, e in union + [(hi, hi)]:
        if s > cursor:
            mid = (cursor + s) // 2
            around = [(e2 - s2, n) for n, s2, e2 in inner if s2 <= mid <= e2]
            label = min(around)[1] if around else "bench.idle"
            gaps.append((label, (s - cursor) / 1e9))
        cursor = max(cursor, e)
    gaps.sort(key=lambda g: -g[1])

    span_busy: dict[str, float] = {}
    for name, s, e in inner:
        span_busy[name] = span_busy.get(name, 0.0) + _overlap(union, s, e) / 1e9
    return Reduction(
        window_s=(hi - lo) / 1e9,
        busy_s=(sum(busy_each) / len(busy_each) / 1e9) if busy_each else 0.0,
        devices=len(busy_each),
        module_s=module_s,
        op_s=op_s,
        gaps=gaps,
        span_busy_s=span_busy,
    )
