"""Reduce the profiler's trace of the measured window to device numbers.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into plain
events: per device plane the ``XLA Ops`` line, and the host thread that ran
the harness (the line that holds its ``bench.window`` annotation; every
host line where none does).  ``summarize`` then works on those events alone, so
the tests can run it on a small recorded trace:

* the window is the host annotation ``bench.window`` that the harness opens
  around its loop;
* device busy time is the union of the intervals in which an op ran on the
  device, clipped to the window, averaged over the devices;
* an op's self time is its duration less that of the ops nested in it (a
  ``while`` holds its body's ops), grouped by the op's name without its
  numeric suffix: ``%bucket_scan_topk_pallas.6 = ...`` is
  ``bucket_scan_topk_pallas``, the jitted wrapper of the Pallas kernel;
* each idle gap of the device is put down to the innermost host event that
  covers its midpoint, or to ``host idle`` where none does.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
DEVICE_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
TOP = 10  # entries in each breakdown list

Event = tuple[str, int, int]  # (name, start ns, duration ns)


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def load(path: str) -> dict:
    """{"devices": {plane: [Event]}, "host": [Event]} from an xplane file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    devices[plane.name] = [
                        (e.name.split(" = ", 1)[0], int(e.start_ns), int(e.duration_ns))
                        for e in line.events
                    ]
        elif plane.name == HOST_PLANE:
            lines = [[(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events]
                     for line in plane.lines]
            mine = [ev for ev in lines if any(n == WINDOW_SPAN for n, _, _ in ev)]
            host = mine[0] if mine else [e for ev in lines for e in ev]
    return {"devices": devices, "host": host}


def op_name(name: str) -> str:
    """``%bucket_scan_topk_pallas.6`` -> ``bucket_scan_topk_pallas``."""
    return re.sub(r"\.\d+$", "", name.lstrip("%"))


def _clip(events: list[Event], lo: int, hi: int) -> list[tuple[str, int, int]]:
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b))
    return out


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def self_times(ops: list[tuple[str, int, int]]) -> dict[str, int]:
    """Self time (ns) per op name: duration less the ops nested inside."""
    order = sorted(ops, key=lambda o: (o[1], -o[2]))
    child = [0] * len(order)
    stack: list[int] = []
    for i, (_, a, b) in enumerate(order):
        while stack and order[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            child[stack[-1]] += b - a
        stack.append(i)
    out: dict[str, int] = {}
    for (name, a, b), c in zip(order, child):
        key = op_name(name)
        out[key] = out.get(key, 0) + max(b - a - c, 0)
    return out


@dataclass
class Summary:
    window_s: float
    busy_s: float  # mean over devices
    op_s: dict[str, float] = field(default_factory=dict)  # self seconds per op, mean over devices
    gaps_s: dict[str, float] = field(default_factory=dict)  # idle seconds by host activity

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, name: str) -> float | None:
        return self.op_s.get(name)

    def breakdown(self) -> dict:
        def top(d):
            return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

        return {"device_ops": top(self.op_s), "idle_gaps": top(self.gaps_s)}


def attribute_gaps(gaps: list[tuple[int, int]], host: list[tuple[str, int, int]]) -> dict[str, int]:
    """Idle ns per host activity: each gap goes to the shortest host event
    that covers its midpoint (one sweep over both, sorted)."""
    hs = sorted(host, key=lambda h: h[1])
    out: dict[str, int] = {}
    active: list[tuple[str, int, int]] = []
    j = 0
    for a, b in sorted(gaps):
        t = (a + b) // 2
        while j < len(hs) and hs[j][1] <= t:
            active.append(hs[j])
            j += 1
        active = [h for h in active if h[2] > t]
        who = min(active, key=lambda h: h[2] - h[1])[0] if active else "host idle"
        out[who] = out.get(who, 0) + (b - a)
    return out


def summarize(events: dict) -> Summary | None:
    """The window's device numbers; None when no device op ran in it."""
    host = events["host"]
    spans = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
    devs = events["devices"]
    if spans:
        lo, hi = spans[0]
    else:
        every = [(s, s + d) for ev in devs.values() for _, s, d in ev]
        if not every:
            return None
        lo, hi = min(a for a, _ in every), max(b for _, b in every)
    if not devs or hi <= lo:
        return None
    host_in = [h for h in _clip(host, lo, hi) if h[0] != WINDOW_SPAN]
    busy_ns = 0
    ops: dict[str, float] = {}
    gaps: dict[str, float] = {}
    n = len(devs)
    for ev in devs.values():
        clipped = _clip(ev, lo, hi)
        busy = union([(a, b) for _, a, b in clipped])
        busy_ns += sum(b - a for a, b in busy)
        for k, v in self_times(clipped).items():
            ops[k] = ops.get(k, 0.0) + v / 1e9 / n
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for who, ns in attribute_gaps(idle, host_in).items():
            gaps[who] = gaps.get(who, 0.0) + ns / 1e9 / n
    if busy_ns == 0:
        return None
    return Summary((hi - lo) / 1e9, busy_ns / 1e9 / n, ops, gaps)
