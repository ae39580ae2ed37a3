"""Reduce a profiler trace (``.xplane.pb``) to the device's busy and idle
time, the time per device operation, and the idle gaps by what the host was
doing.

* The window is the host span ``bench.window`` that ``run.py`` opens around
  the measured requests; everything is clipped to it.
* A device is a plane named ``/device:TPU:<i>``; its operations are the
  events of its ``XLA Ops`` line, named ``<program>:<op>`` from the
  enclosing event of its ``XLA Modules`` line and the HLO name before
  `` = ``; the whole HLO text of each (its operand and result shapes) is
  kept under ``op_hlo``.  Busy time is the union of their intervals (nested or
  overlapping operations count once); idle time is the rest.  An
  operation's time is its self time: a ``while`` loop's body ops are
  charged to themselves, not to the loop.
* An idle gap is named after the innermost ``bench.*`` host span covering its
  midpoint (the benchmark's own calls into the client and its waits), or
  ``outside bench spans``.

Run as a script to print the reduction of one trace file as JSON.
"""

from __future__ import annotations

import bisect
import json
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
WINDOW = "bench.window"

Interval = Tuple[int, int]


def _union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: int, e: int, w: Interval) -> Interval:
    return max(s, w[0]), min(e, w[1])


def _events(line) -> List[Tuple[str, int, int]]:
    return [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
            for ev in line.events]


def _named(ops, modules, hlo: Dict[str, str]) -> List[Tuple[str, int, int]]:
    """``<program>:<op>`` names: the module event enclosing each op.  The
    op's whole HLO text goes into ``hlo`` under that name."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out = []
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        mod = modules[i][0].split("(")[0] if i >= 0 and \
            modules[i][2] >= e else "?"
        short = f"{mod}:{name.split(' = ')[0]}"
        hlo.setdefault(short, name)
        out.append((short, s, e))
    return out


def load(path: str):
    """(device planes' ops, host bench spans, HLO text by op name)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, int, int]]] = {}
    spans: List[Tuple[str, int, int]] = []
    hlo: Dict[str, str] = {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: _events(line) for line in plane.lines}
            devices[plane.name] = _named(lines.get(OPS_LINE, []),
                                         lines.get(MODULES_LINE, []), hlo)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.name, int(ev.start_ns),
                              int(ev.start_ns + ev.duration_ns))
                             for ev in line.events
                             if ev.name.startswith(HOST_PREFIX))
    return devices, spans, hlo


def _self_times(ops: List[Tuple[str, int, int]]):
    """(name, self seconds) of each op: its interval less the intervals of
    the ops nested inside it."""
    out = []
    stack: List[list] = []          # [name, end, start, self ns]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            n, _, _, own = stack.pop()
            out.append((n, own))
        if stack:
            stack[-1][3] -= min(e, stack[-1][1]) - s
        stack.append([name, e, s, e - s])
    out.extend((n, own) for n, _, _, own in stack)
    return out


def reduce_events(devices: Dict[str, List[Tuple[str, int, int]]],
                  spans: List[Tuple[str, int, int]],
                  hlo: Optional[Dict[str, str]] = None,
                  top: int = 10) -> dict:
    """Busy/idle seconds, per-op seconds and named gaps within the window,
    and the HLO text of each op that ran in it."""
    windows = [(s, e) for name, s, e in spans if name == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    if not devices:
        raise ValueError("no /device:TPU:<i> plane in the trace")
    win = windows[0]
    window_s = (win[1] - win[0]) / 1e9
    inner = [(n, s, e) for n, s, e in spans if n != WINDOW]
    busy: List[float] = []
    op_s: Dict[str, float] = defaultdict(float)
    op_n: Dict[str, int] = defaultdict(int)
    gaps_by: Dict[str, float] = defaultdict(float)
    for k, plane in enumerate(sorted(devices)):
        clipped = [(name,) + _clip(s, e, win) for name, s, e in devices[plane]]
        clipped = [c for c in clipped if c[2] > c[1]]
        iv = [(s, e) for _, s, e in clipped]
        for name, own in _self_times(clipped):
            op_s[name] += own / 1e9
            op_n[name] += 1
        merged = _union(iv)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        if k:
            continue                   # gaps are named on the first device
        edges = [win[0]] + [x for se in merged for x in se] + [win[1]]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                mid = (gs + ge) // 2
                cover = [(s, n) for n, s, e in inner if s <= mid < e]
                name = max(cover)[1] if cover else "outside bench spans"
                gaps_by[name] += (ge - gs) / 1e9
    ops = sorted(op_s.items(), key=lambda kv: -kv[1])
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy),
        "devices": len(busy),
        "op_seconds": dict(ops),
        "op_counts": dict(op_n),
        "op_hlo": {n: (hlo or {}).get(n, n) for n in op_s},
        "device_ops": [[n, s] for n, s in ops[:top]],
        "idle_gaps": [[n, s] for n, s in sorted(
            gaps_by.items(), key=lambda kv: -kv[1])[:top]],
    }


def reduce(path: str, top: int = 10) -> dict:
    return reduce_events(*load(path), top=top)


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1]), indent=1))
