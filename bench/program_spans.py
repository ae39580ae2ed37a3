"""The program's own spans in a profiler trace (``.xplane.pb``).

The program mirrors each live ``obs`` span onto the profiler's clock as a
TraceMe of the same name (``repro/obs/trace.py``), so a trace taken with
``bench/run.py --trace 1 --trace-dir <dir>`` holds the program's spans
beside the device ops they enclose.  This reads them, within the window
``bench.window``, as :mod:`trace_reduce` reads the device:

* ``span_s``: for each span name, the seconds of the union of its
  intervals;
* ``span_busy_s``: for each span name, the device busy seconds inside that
  union (the first device, as ``trace_reduce`` names gaps);
* ``program_gaps``: the window's device idle gaps, each named by the
  innermost program span covering its midpoint, else
  ``outside program spans``.

A span is a host event whose name starts with one of the program's span
families (``PREFIXES``).  Run as a script to print one trace's reduction as
JSON.
"""

from __future__ import annotations

import json
import sys
from collections import defaultdict
from typing import Dict, List, Tuple

import trace_reduce as tr

PREFIXES = ("rpc.", "service.", "sched.", "engine.", "serve.", "plan.")
OUTSIDE = "outside program spans"

Event = Tuple[str, int, int]


def load_program_spans(path: str) -> List[Event]:
    """Host events of the program's span families."""
    from jax.profiler import ProfileData
    out: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((ev.name, int(ev.start_ns),
                            int(ev.start_ns + ev.duration_ns))
                           for ev in line.events
                           if ev.name.startswith(PREFIXES))
    return out


def _overlap(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce_program_spans(devices: Dict[str, List[Event]],
                         bench_spans: List[Event],
                         program: List[Event], top: int = 10) -> dict:
    """``span_s``, ``span_busy_s`` and ``program_gaps`` in the window."""
    windows = [(s, e) for n, s, e in bench_spans if n == tr.WINDOW]
    if not windows:
        raise ValueError(f"no {tr.WINDOW!r} span in the trace")
    if not devices:
        raise ValueError("no /device:TPU:<i> plane in the trace")
    win = windows[0]
    first = devices[sorted(devices)[0]]
    busy = tr._union([c for c in (tr._clip(s, e, win) for _, s, e in first)
                      if c[1] > c[0]])
    spans = [(n,) + tr._clip(s, e, win) for n, s, e in program]
    spans = [sp for sp in spans if sp[2] > sp[1]]
    by_name: Dict[str, List[Tuple[int, int]]] = defaultdict(list)
    for n, s, e in spans:
        by_name[n].append((s, e))
    span_s, span_busy_s = {}, {}
    for n, iv in sorted(by_name.items()):
        merged = tr._union(iv)
        span_s[n] = sum(e - s for s, e in merged) / 1e9
        span_busy_s[n] = _overlap(merged, busy) / 1e9
    gaps: Dict[str, float] = defaultdict(float)
    edges = [win[0]] + [x for se in busy for x in se] + [win[1]]
    for gs, ge in zip(edges[::2], edges[1::2]):
        if ge > gs:
            mid = (gs + ge) // 2
            # innermost: the latest-starting span that covers the midpoint
            cover = [(s, -e, n) for n, s, e in spans if s <= mid < e]
            gaps[max(cover)[2] if cover else OUTSIDE] += (ge - gs) / 1e9
    return {"span_s": span_s, "span_busy_s": span_busy_s,
            "program_gaps": [[n, s] for n, s in sorted(
                gaps.items(), key=lambda kv: -kv[1])[:top]]}


def reduce(path: str, top: int = 10) -> dict:
    devices, bench_spans, _ = tr.load(path)
    return reduce_program_spans(devices, bench_spans,
                                load_program_spans(path), top=top)


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1]), indent=1))
