"""Reduce a profiler trace to device busy time, op times and idle gaps.

The traced window is the host span named `WINDOW`, which the runners open
around the traced calls.  A device's busy time is the union of the
intervals in which an op of its "XLA Ops" line runs, clipped to the window;
the idle share is one minus busy over the window.  That line nests ops:
a ``while`` or ``conditional`` spans the ops of its body.  So each op's
time is its self time (its duration less that of the ops nested in it),
summed per HLO instruction name (the text before `` = ``) and averaged
over the devices used.  Each idle gap of the first device is attributed to
the host event that overlaps it most (the window span itself excluded).
"""
from __future__ import annotations

import collections
import glob
import os
from typing import Dict, List, Tuple

WINDOW = "chipbench_window"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"


def load(trace_dir: str):
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(files[0])


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name.split(" = ", 1)[0], e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def self_times(ops, lo, hi) -> Dict[str, float]:
    """Per-name self time (ns) of nested op intervals, clipped to [lo, hi]."""
    out: Dict[str, float] = collections.Counter()
    stack: List[list] = []  # [name, start, end, child time]

    def close(frame):
        name, s, e, child = frame
        out[name] += max(0.0, min(e, hi) - max(s, lo) - child)
        if stack:
            stack[-1][3] += max(0.0, min(e, hi) - max(s, lo))

    for name, s, e in sorted(ops, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return out


def device_ops(pd) -> Dict[str, List[Tuple[str, float, float]]]:
    """Op events of each device plane, by plane name."""
    out = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out[plane.name] = _events(line)
    return out


def host_events(pd) -> List[Tuple[str, float, float]]:
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend(_events(line))
    return out


def window(host) -> Tuple[float, float]:
    spans = [(s, e) for name, s, e in host if name == WINDOW]
    if not spans:
        raise ValueError(f"no host span named {WINDOW!r} in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


def merge(intervals, lo, hi) -> List[Tuple[float, float]]:
    """Union of intervals clipped to [lo, hi], sorted."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce_profile(pd, num_devices: int) -> Dict:
    host = host_events(pd)
    lo, hi = window(host)
    devices = device_ops(pd)
    names = sorted(devices)[:num_devices]
    if not names:
        raise ValueError("the trace holds no device op line")
    busy, op_ns = [], collections.Counter()
    for name in names:
        ops = devices[name]
        busy.append(sum(e - s for s, e in merge([(s, e) for _, s, e in ops], lo, hi)))
        op_ns.update(self_times(ops, lo, hi))
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy) / len(busy) * 1e-9
    op_s = {k: v / len(names) * 1e-9 for k, v in op_ns.items()}

    intervals = merge([(s, e) for _, s, e in devices[names[0]]], lo, hi)
    edges = [lo] + [x for iv in intervals for x in iv] + [hi]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]), reverse=True)
    idle = []
    for length, s, e in gaps[:10]:
        best, best_overlap = "none", 0.0
        for name, hs, he in host:
            overlap = min(he, e) - max(hs, s)
            if name != WINDOW and overlap > best_overlap:
                best, best_overlap = name, overlap
        idle.append([best, length * 1e-9])
    top = sorted(op_s.items(), key=lambda kv: kv[1], reverse=True)[:10]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "op_seconds": op_s,
        "breakdown": {"device_ops": [[k, v] for k, v in top], "idle_gaps": idle},
    }


def reduce(trace_dir: str, num_devices: int) -> Dict:
    """`reduce_profile` of the trace written under ``trace_dir``."""
    return reduce_profile(load(trace_dir), num_devices)
