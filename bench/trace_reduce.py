"""Reduction of a JAX profiler trace of the measured window.

From the ``.xplane.pb`` that ``jax.profiler.trace`` writes: the device's busy
time (the union of the intervals in which an operation ran on it), the
traced window (the harness's ``bench/window`` annotation), the device
operations that took the most time, and the longest idle gaps, each named by
the harness's host annotation that was open at the gap's middle.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import NamedTuple

HOST_PREFIX = "bench/"
WINDOW = "bench/window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
TOP = 10
_SHAPE = re.compile(r"[a-z0-9]+\[[0-9,]*\]")


class Span(NamedTuple):
    start: float   # seconds on the trace's clock
    end: float
    name: str


class Trace(NamedTuple):
    devices: list   # per device: list[Span] of its operations
    host: list      # list[Span] of the harness's annotations


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {paths}")
    return paths[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE) and plane.name[len(DEVICE_PLANE):].isdigit():
            ops = [Span(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
                   for line in plane.lines if line.name == OPS_LINE for e in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            host.extend(Span(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
                        for line in plane.lines for e in line.events
                        if e.name.startswith(HOST_PREFIX))
    return Trace(devices, host)


def union(spans: list, lo: float, hi: float) -> list:
    """Merged [start, end) intervals of ``spans`` clipped to [lo, hi)."""
    merged: list = []
    for s in sorted(spans):
        a, b = max(s.start, lo), min(s.end, hi)
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def op_name(full: str) -> str:
    """``fusion.412 f32[2,512,151936]`` from an op's HLO text."""
    head, eq, rest = full.partition(" = ")
    if not eq:
        return full[:120]
    m = _SHAPE.search(rest)
    return head.lstrip("%") + (" " + m.group(0) if m else "")


def self_times(ops: list, lo: float, hi: float) -> dict:
    """Seconds per op name inside [lo, hi), each op less the ops nested in
    it (a while loop's events hold its body's)."""
    clipped = sorted(((max(s.start, lo), min(s.end, hi), s.name) for s in ops
                      if min(s.end, hi) > max(s.start, lo)), key=lambda e: (e[0], -e[1]))
    out: dict = defaultdict(float)
    stack: list = []
    for a, b, name in clipped:
        while stack and stack[-1][1] <= a:
            stack.pop()
        out[name] += b - a
        if stack:
            out[stack[-1][2]] -= min(b, stack[-1][1]) - a
        stack.append((a, b, name))
    return out


def host_name_at(host: list, t: float) -> str:
    """The innermost harness annotation (other than the window) open at t."""
    best = None
    for s in host:
        if s.name != WINDOW and s.start <= t < s.end:
            if best is None or s.end - s.start < best.end - best.start:
                best = s
    return best.name[len(HOST_PREFIX):] if best is not None else "untracked"


def reduce(trace: Trace) -> dict:
    """``busy_s`` (mean over devices), ``window_s``, and the breakdown."""
    if not trace.devices or not any(trace.devices):
        raise ValueError("the trace holds no device operations")
    windows = [s for s in trace.host if s.name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} annotation, found {len(windows)}")
    lo, hi = windows[0].start, windows[0].end
    busy = []
    per_op: dict = defaultdict(float)
    for ops in trace.devices:
        busy.append(sum(b - a for a, b in union(ops, lo, hi)))
        for name, t in self_times(ops, lo, hi).items():
            per_op[op_name(name)] += t
    first = union(trace.devices[0], lo, hi)
    edges = [lo] + [x for ab in first for x in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": hi - lo,
        "device_ops": sorted(([k, v] for k, v in per_op.items()),
                             key=lambda kv: kv[1], reverse=True)[:TOP],
        "idle_gaps": [[host_name_at(trace.host, (a + b) / 2), b - a] for a, b in gaps[:TOP]],
    }
