"""Reduction of a ``jax.profiler`` trace (``*.xplane.pb``) to the device's
busy time, its idle gaps, the operations that took the most time, and what
the host was doing in each gap.

Busy time is the union of the intervals in which an operation ran on a
device's op line; idle is the rest of the traced window. A gap is named by
the host event that covers most of it, preferring the innermost one (the
benchmark's own ``bench.*`` annotations, the runtime's dispatch events).
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

import numpy as np

#: Line of a device plane that holds one event per executed operation.
OP_LINES = ("XLA Ops",)
#: Gaps shorter than this are not worth naming (launch jitter).
MIN_GAP_S = 1e-5


def find_xplane(log_dir):
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _event(e):
    return (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)


def read_xplane(path, platform="TPU"):
    """Device op events per device and host events, all as
    ``(name, start_s, end_s)`` on the trace's common clock. On the CPU,
    whose operations run on host threads, the device is the set of host
    events that carry an ``hlo_op`` (tests only)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        name = plane.name
        if name.startswith(f"/device:{platform}:"):
            ops = [_event(e) for line in plane.lines if line.name in OP_LINES
                   for e in line.events]
            if ops:
                devices[name] = ops
        elif name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    if platform == "CPU" and any(k == "hlo_op" for k, _ in e.stats):
                        devices.setdefault("/host:CPU ops", []).append(_event(e))
                    else:
                        host.append(_event(e))
    return devices, host


def union(intervals):
    """Merge ``(start, end)`` pairs; returns sorted disjoint pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(intervals):
    """Length of the union of intervals."""
    return sum(e - s for s, e in union(intervals))


def idle_gaps(intervals, lo, hi):
    """Gaps of [lo, hi] in which no interval runs, as (start, end)."""
    gaps, t = [], lo
    for s, e in union(intervals):
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


class HostEvents:
    """Host events as arrays, for naming many gaps."""

    def __init__(self, events):
        self.names = [n for n, _, _ in events]
        self.start = np.array([s for _, s, _ in events], np.float64)
        self.end = np.array([e for _, _, e in events], np.float64)

    def name_gap(self, gap):
        """The host event that overlaps the gap most; among overlaps equal
        to the nanosecond the shortest (innermost). ``"no host event"``
        when none overlaps."""
        if not self.names:
            return "no host event"
        s, e = gap
        ov = np.minimum(e, self.end) - np.maximum(s, self.start)
        best = ov.max()
        if best <= 0:
            return "no host event"
        tied = np.flatnonzero(ov >= best - 1e-9)
        i = tied[np.argmin(self.end[tied] - self.start[tied])]
        return self.names[int(i)]


def short_name(name):
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``%fusion.12``."""
    return name.split(" = ", 1)[0]


def self_times(ops):
    """Each op's duration less the ops nested inside it (a ``while`` and
    its body both appear on the op line), as (name, seconds)."""
    out, stack = [], []                 # stack: [end, index]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack and e <= stack[-1][0]:
            out[stack[-1][1]][1] -= e - s
        out.append([name, e - s])
        stack.append((e, len(out) - 1))
    return out


def top_ops(ops, n=10):
    """Operations that took the most device self time, by short name."""
    by_name = defaultdict(float)
    for name, t in self_times(ops):
        by_name[short_name(name)] += t
    return sorted(by_name.items(), key=lambda kv: -kv[1])[:n]


def gaps_by_host(gaps, host_events, n=10):
    """Idle time summed by the host activity that covered it."""
    host = HostEvents(host_events)
    by_name = defaultdict(float)
    for g in gaps:
        if g[1] - g[0] >= MIN_GAP_S:
            by_name[host.name_gap(g)] += g[1] - g[0]
    return sorted(by_name.items(), key=lambda kv: -kv[1])[:n]


def reduce(log_dir, platform="TPU"):
    """Busy seconds averaged over the traced devices, the span from each
    device's first to its last op, and the breakdown. The whole trace is
    the traced window: the profiler runs only while the harness traces."""
    devices, host = read_xplane(find_xplane(log_dir), platform)
    if not devices:
        raise ValueError(f"trace under {log_dir} has no {platform} op events")
    busy, windows, all_ops, all_gaps = [], [], [], []
    for ops in devices.values():
        iv = [(s, e) for _, s, e in ops]
        lo, hi = min(s for s, _ in iv), max(e for _, e in iv)
        busy.append(busy_seconds(iv))
        windows.append(hi - lo)
        all_ops.extend(ops)
        all_gaps.extend(idle_gaps(iv, lo, hi))
    k = len(devices)
    return {
        "devices": sorted(devices),
        "busy_s": sum(busy) / k,
        "window_s": sum(windows) / k,
        "n_ops": len(all_ops),
        "device_ops": [[n, s / k] for n, s in top_ops(all_ops)],
        "idle_gaps": [[n, s / k] for n, s in gaps_by_host(all_gaps, host)],
    }
