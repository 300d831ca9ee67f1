"""From a profiler trace (`.xplane.pb`) to device busy time, the
operations that took most of it, and the idle gaps by what the host
was doing. Run after the server has exited: it imports JAX (for
`jax.profiler.ProfileData`), held to the CPU.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]          # (start, end), nanoseconds

#: the line of a device plane that holds one event per executed HLO op
OPS_LINE = "XLA Ops"
#: host events shorter than this (ns) explain no idle gap worth listing
#: and are most of a Python trace; they are dropped when loading
MIN_HOST_NS = 50_000.0
TOP = 10


def short_name(name: str) -> str:
    """The trace names a device operation by its whole HLO text, e.g.
    `%gptq_matmul_a8.66 = bf16[16,28672]{...} custom-call(...)`. Keep
    the name without its instance number, the result's shape and, for
    a custom call, its target: the 32 layers' calls of one kernel at
    one shape then count as one operation."""
    lhs, _, rhs = name.partition(" = ")
    if not rhs:
        return name[:160]
    parts = [re.sub(r"\.\d+$", "", lhs.lstrip("%"))]
    shape = re.search(r"\w+\[[\d,]*\]", rhs)
    if shape:
        parts.append(shape.group(0))
    target = re.search(r'custom_call_target="([^"]*)"', rhs)
    if target:
        parts.append(target.group(1))
    return " ".join(parts)[:160]


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path: str, cpu_as_device: bool = False) -> dict:
    """The planes of the trace as plain lists:
    `{"devices": {plane: [(name, start, end)]}, "host": [(name, start,
    end)]}`, times in nanoseconds on the trace's one clock. With
    `cpu_as_device` (the CPU rehearsal, which has no device plane) the
    host events that carry an `hlo_op` stand in for device operations."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        is_device = plane.name.startswith("/device:") and \
            "CPU" not in plane.name
        for line in plane.lines:
            if is_device and line.name != OPS_LINE:
                continue
            if not is_device and not plane.name.startswith("/host:"):
                continue
            floor = 0.0 if is_device or cpu_as_device else MIN_HOST_NS
            kept = [e for e in line.events if e.duration_ns > floor]
            events = [(e.name, float(e.start_ns),
                       float(e.start_ns + e.duration_ns)) for e in kept]
            if is_device:
                devices.setdefault(plane.name, []).extend(events)
            elif cpu_as_device and line.name.startswith("tf_XLA"):
                devices.setdefault("cpu (rehearsal)", []).extend(
                    ev for ev, e in zip(events, kept)
                    if "hlo_op" in dict(e.stats))
            else:
                host.extend(events)
    return dict(devices=devices, host=host)


def union(intervals: List[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    merged: List[Interval] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def _attribute(gap: Interval, host: List[Tuple[str, float, float]]) -> str:
    """The shortest (innermost) host event that covers at least half
    of the gap, else `unattributed`."""
    half = (gap[1] - gap[0]) / 2.0
    covering = [(e - s, name) for name, s, e in host
                if min(e, gap[1]) - max(s, gap[0]) >= half]
    return min(covering)[1] if covering else "unattributed"


def reduce(planes: dict) -> dict:
    """`busy_s` (union of device-operation intervals, averaged over
    the chips), `window_s`, `device_ops` and `idle_gaps` (each at most
    ten `[name, seconds]`, largest first), and `ops`: every device
    operation, whole, as `{short name: [seconds, calls]}`, both a chip
    (an event cut by the window's edge counts as a call). The window
    is the time in which both tracers were at work: from the later of the
    first device operation and the first host event to the earlier of
    the last of each. The host's tracer starts before the device's and
    stops after it, and the time between them is not idle time. Raises
    if no operation ran on a device."""
    devices = {plane: evs for plane, evs in planes["devices"].items()
               if evs}
    if not devices:
        raise ValueError("no operation ran on a device in the trace")
    on_device = [ev for evs in devices.values() for ev in evs]
    t_first = min(s for _, s, _ in on_device)
    t_last = max(e for _, _, e in on_device)
    if planes["host"]:
        t_first = max(t_first, min(s for _, s, _ in planes["host"]))
        t_last = min(t_last, max(e for _, _, e in planes["host"]))
    busy, by_op, calls, gaps = [], {}, {}, {}
    for events in devices.values():
        events = [(name, max(s, t_first), min(e, t_last))
                  for name, s, e in events if e > t_first and s < t_last]
        merged = union([(s, e) for _, s, e in events])
        busy.append(sum(e - s for s, e in merged))
        for name, s, e in events:
            name = short_name(name)
            by_op[name] = by_op.get(name, 0.0) + (e - s)
            calls[name] = calls.get(name, 0) + 1
        edges = [(t_first, t_first)] + merged + [(t_last, t_last)]
        idle = sorted(((b[0] - a[1], (a[1], b[0]))
                       for a, b in zip(edges, edges[1:])
                       if b[0] > a[1]), reverse=True)
        for length, gap in idle[:TOP * 5]:
            name = _attribute(gap, planes["host"])
            gaps[name] = gaps.get(name, 0.0) + length
    chips = len(devices)

    def top(table: Dict[str, float]) -> list:
        ranked = sorted(table.items(), key=lambda kv: -kv[1])[:TOP]
        return [[name, seconds / chips / 1e9] for name, seconds in ranked]

    return dict(busy_s=sum(busy) / chips / 1e9,
                window_s=(t_last - t_first) / 1e9,
                device_ops=top(by_op), idle_gaps=top(gaps),
                ops={name: [seconds / chips / 1e9, calls[name] / chips]
                     for name, seconds in by_op.items()})


def cut(planes: dict, span_ns: float = 150e6, name_chars: int = 400,
        most: int = 4000) -> dict:
    """A slice of a loaded trace, small enough to keep as a fixture:
    the device events that start in `span_ns` from a quarter into the
    trace (at most `most` a plane), and the host events that overlap
    them."""
    starts = [s for evs in planes["devices"].values() for _, s, _ in evs]
    t0 = min(starts) + (max(starts) - min(starts)) / 4.0
    t1 = t0 + span_ns
    return dict(
        devices={plane: [(n[:name_chars], s, e) for n, s, e in evs
                         if t0 <= s < t1][:most]
                 for plane, evs in planes["devices"].items()},
        host=[(n[:name_chars], s, e) for n, s, e in planes["host"]
              if s < t1 and e > t0][:most])
