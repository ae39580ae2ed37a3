"""What the per-layer readers take from the program's own instruments: its
histograms in the metrics snapshots ``run.py`` takes before and after the
window, and the device time of the frontier engine's round programs in the
trace.

The frontier engine's rounds run as three jitted programs, named in a
profiler trace by their functions in ``core/engine.py``: a sparse push
round is a compaction (``jit__compact``) and a push step
(``jit__frontier_push_step``), a dense pull round one pull step
(``jit__frontier_dense_step``).  A program that is missing from the trace,
or an instrument missing from the snapshots, reads as nothing.
"""

from __future__ import annotations

from typing import Iterable, Optional

PUSH_PROGRAMS = ("jit__compact", "jit__frontier_push_step")
PULL_PROGRAMS = ("jit__frontier_dense_step",)


def hist_mean_in_window(ctx, name: str) -> Optional[float]:
    """Mean of the observations a histogram took inside the window:
    delta of its sum over delta of its count."""
    a, b = ctx.after.get(name), ctx.before.get(name)
    if a is None:
        return None
    n = a["count"] - (b["count"] if b else 0)
    if n <= 0:
        return None
    return (a["sum"] - (b["sum"] if b else 0.0)) / n


def hist_sum_before_window(ctx, name: str) -> Optional[float]:
    """Sum of a histogram's observations before the window (set-up)."""
    b = ctx.before.get(name)
    return float(b["sum"]) if b and b["count"] else None


def program_seconds(trace: dict, programs: Iterable[str]) -> float:
    """Device self seconds, in the window, of the ops of these programs."""
    want = set(programs)
    return sum(s for op, s in trace["op_seconds"].items()
               if op.split(":", 1)[0] in want)


def ns_per_edge(ctx, programs: Iterable[str], counter: str
                ) -> Optional[float]:
    """Device nanoseconds of the programs per edge the counter counted in
    the window."""
    if not ctx.trace:
        return None
    edges = ctx.counter_delta(counter)
    secs = program_seconds(ctx.trace, programs)
    if not edges or secs <= 0:
        return None
    return 1e9 * secs / edges
