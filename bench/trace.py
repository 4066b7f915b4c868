"""Reduce a profiler trace to what the per-layer metrics read.

A trace, as `load_dir` returns it, is plain data:

    {"devices": {"/device:TPU:0": [[name, start_ns, dur_ns], ...], ...},
     "host": [[name, start_ns, dur_ns, line], ...]}

with the device events of each chip's "XLA Ops" line and every host event.
Device and host times share one clock (the profiler's). The device line
nests events: a `while` op spans the ops of its body, so busy time is the
union of intervals and an op's own time leaves out the ops inside it.

`reduce` gives, over the window that the harness's `bench.unit` spans
cover:

- busy_s, window_s: device busy seconds (averaged over the chips) and the
  window's length;
- device_ops: [[op, seconds], ...], the ops of device 0 by their own time;
- idle_gaps: [[label, seconds], ...], device 0's idle time by what the host
  was doing: the innermost `bench.*` span around the gap's middle, then the
  innermost other host event there;
- events: device 0's events in the window, for kernel metrics.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Any, Dict, List

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."


def load_dir(path: str) -> Dict[str, Any]:
    """The trace the profiler wrote under `path` (one .xplane.pb)."""
    import jax
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {path}, "
                           f"found {len(files)}")
    pd = jax.profiler.ProfileData.from_file(files[0])
    devices: Dict[str, List[list]] = {}
    host: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [e.name, e.start_ns, e.duration_ns]
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns, line.name]
                            for e in line.events)
    return {"devices": devices, "host": host}


def op_name(hlo: str) -> str:
    """'%fusion.12 = f32[...] fusion(...)' -> 'fusion.12'."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def merge(intervals: List[tuple]) -> List[tuple]:
    """Union of [start, end) intervals, sorted."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(events: List[list], t0: float, t1: float) -> List[list]:
    """Events cut to [t0, t1); those wholly outside are dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append([name, a, b - a])
    return out


def self_times(events: List[list]) -> Dict[str, float]:
    """ns of each op name, less the ops nested inside it."""
    own: Dict[str, float] = defaultdict(float)
    stack: List[tuple] = []          # (end, name)
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        key = op_name(name)
        own[key] += d
        if stack:
            own[stack[-1][1]] -= d
        stack.append((s + d, key))
    return dict(own)


def window_of(host: List[list]) -> tuple:
    units = [(s, s + d) for name, s, d, _ in host
             if name == SPAN_PREFIX + "unit"]
    if not units:
        raise RuntimeError("the trace holds no bench.unit span")
    return min(u[0] for u in units), max(u[1] for u in units)


def _innermost(host: List[list], t: float, want_span: bool):
    best = None
    for name, s, d, _ in host:
        if s <= t < s + d and name.startswith(SPAN_PREFIX) == want_span:
            if best is None or d < best[1]:
                best = (name, d)
    return best[0] if best else None


def gap_label(host: List[list], t: float) -> str:
    span = _innermost(host, t, True) or "outside bench spans"
    other = _innermost(host, t, False)
    return f"{span} / {other}" if other else span


def idle_gaps(busy: List[tuple], t0: float, t1: float) -> List[tuple]:
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        gaps.append((cur, t1))
    return gaps


def reduce(tr: Dict[str, Any]) -> Dict[str, Any]:
    t0, t1 = window_of(tr["host"])
    names = sorted(tr["devices"], key=lambda n: int(n[len(DEVICE_PREFIX):]))
    if not names:
        raise RuntimeError("the trace holds no TPU device plane")
    busy_ns = []
    for n in names:
        ev = clip(tr["devices"][n], t0, t1)
        busy_ns.append(sum(e - s for s, e in merge(
            [(s, s + d) for _, s, d in ev])))
    dev0 = clip(tr["devices"][names[0]], t0, t1)
    busy0 = merge([(s, s + d) for _, s, d in dev0])
    by_label: Dict[str, float] = defaultdict(float)
    host = [h for h in tr["host"] if h[1] < t1 and h[1] + h[2] > t0]
    for s, e in idle_gaps(busy0, t0, t1):
        by_label[gap_label(host, (s + e) / 2)] += e - s
    ops = sorted(self_times(dev0).items(), key=lambda kv: -kv[1])
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": (t1 - t0) / 1e9,
        "n_devices": len(names),
        "device_ops": [[k, v / 1e9] for k, v in ops],
        "idle_gaps": [[k, v / 1e9] for k, v in
                      sorted(by_label.items(), key=lambda kv: -kv[1])],
        "events": dev0,
    }
