"""Engine profiling: where fixpoint time actually goes.

PR 7's spans say *that* an engine call took 80 ms; this layer says *why*:

* **compiles** — ``engine.compiles`` (a counter) and ``engine.compile_ms``
  (a histogram) count and time every backend compile, read from JAX's own
  ``/jax/core/compile/backend_compile_duration`` event by one listener per
  process (:func:`listen_compiles`), whatever code asked for the compile.
* **per-round frontier phase timing** — each frontier round's span
  (``engine.frontier.push`` / ``engine.frontier.pull``, from the round's
  first dispatch to the fetch of its stats, so it covers the round's device
  work) lands its duration in ``engine.profile.frontier.{sparse,dense}_ms``.
* **sharded halo traffic** — the sharded backend runs its whole fixpoint
  inside one ``shard_map`` region, so per-round halo *time* is not
  attributable from the host; what is exact is the per-round halo *bytes*
  (``d * halo_width * itemsize``, the same figure as
  ``ShardPlan.halo_bytes_per_round``), and the total exchanged bytes when
  the round count is known (tol/n_iter modes).

Everything lands in ordinary registry instruments — snapshot/Prometheus/
wire exposition come for free — and :func:`profile_report` renders any
snapshot (live, remote, or from a saved debug bundle) as a text table.

The module is bound to a registry by ``obs/__init__`` (:func:`bind`); all
record calls are no-ops until then and the engine additionally guards them
with ``obs.REGISTRY.enabled``, preserving the zero-cost disabled path.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional

from .metrics import (BYTE_BUCKETS, DEFAULT_BUCKETS_MS, LONG_BUCKETS_MS,
                      Registry, quantile_from_snapshot)

__all__ = ["bind", "listen_compiles", "record_frontier_round",
           "record_sharded", "profile_report"]

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_REG: Optional[Registry] = None
_lock = threading.Lock()
_cache: Dict[str, Any] = {}
_listening = False


def bind(registry: Registry) -> None:
    """Attach the profiling instruments to a registry (done once by the
    ``obs`` package for the process-global one)."""
    global _REG
    with _lock:
        _REG = registry
        _cache.clear()


def _hist(name: str, buckets=DEFAULT_BUCKETS_MS):
    h = _cache.get(name)
    if h is None:
        if _REG is None:
            return None
        with _lock:
            h = _cache.get(name)
            if h is None and _REG is not None:
                h = _cache[name] = _REG.histogram(name, buckets)
    return h


def _counter(name: str):
    c = _cache.get(name)
    if c is None:
        if _REG is None:
            return None
        with _lock:
            c = _cache.get(name)
            if c is None and _REG is not None:
                c = _cache[name] = _REG.counter(name)
    return c


def _on_duration(event: str, duration_secs: float, **kwargs: Any) -> None:
    if event != BACKEND_COMPILE_EVENT:
        return
    c = _counter("engine.compiles")
    h = _hist("engine.compile_ms", LONG_BUCKETS_MS)
    if c is not None and h is not None:
        c.inc()
        h.observe(duration_secs * 1e3)


def listen_compiles() -> None:
    """Count JAX's backend compiles into ``engine.compiles`` and
    ``engine.compile_ms``; registers its listener once per process."""
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    import jax
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def record_frontier_round(mode: str, dt_ms: float) -> None:
    """One frontier round's step duration; ``mode`` is ``dense`` or
    ``sparse``."""
    h = _hist(f"engine.profile.frontier.{mode}_ms")
    if h is not None:
        h.observe(dt_ms)


def record_sharded(halo_bytes_per_round: int,
                   rounds: Optional[int] = None) -> None:
    """One sharded fixpoint loop: per-round halo bytes, and the total when
    the round count is static."""
    hb = _hist("engine.profile.sharded.halo_bytes_per_round", BYTE_BUCKETS)
    if hb is not None:
        hb.observe(float(halo_bytes_per_round))
    if rounds is not None:
        c = _counter("engine.profile.sharded.halo_bytes_total")
        if c is not None:
            c.inc(int(rounds) * int(halo_bytes_per_round))
        cr = _counter("engine.profile.sharded.rounds")
        if cr is not None:
            cr.inc(int(rounds))


# -- reporting --------------------------------------------------------------

def _fmt(v: Optional[float]) -> str:
    if v is None:
        return "-"
    if v >= 100:
        return f"{v:,.0f}"
    return f"{v:.2f}"


def _hist_row(name: str, snap: Dict[str, Any]) -> tuple:
    n = int(snap.get("count", 0))
    total = float(snap.get("sum", 0.0))
    p50 = quantile_from_snapshot(snap, 0.5) if n else None
    p99 = quantile_from_snapshot(snap, 0.99) if n else None
    mean = (total / n) if n else None
    return (name, n, mean, p50, p99, total)


def profile_report(snapshot: Optional[Dict[str, Any]] = None) -> str:
    """Text table of ``engine.compile_ms`` and every ``engine.profile.*``
    instrument in a registry snapshot (defaults to the bound registry's
    live snapshot).

    Works identically against a remote server's shipped snapshot or the
    ``metrics`` block of a saved debug bundle — the renderer only needs
    the plain snapshot dict.
    """
    if snapshot is None:
        if _REG is None:
            return "engine profile: no registry bound\n"
        snapshot = _REG.snapshot()
    rows = []
    counters = []
    for name in sorted(snapshot):
        if name == "engine.compile_ms":
            short = "compile_ms"
        elif name.startswith("engine.profile."):
            short = name[len("engine.profile."):]
        else:
            continue
        snap = snapshot[name]
        if snap.get("type") == "histogram":
            rows.append(_hist_row(short, snap))
        else:
            counters.append((short, snap.get("value", 0)))
    lines = ["engine profile"]
    if not rows and not counters:
        lines.append("  (no compile or engine.profile.* samples recorded)")
        return "\n".join(lines) + "\n"
    if rows:
        w = max(len(r[0]) for r in rows)
        lines.append(f"  {'phase':<{w}}  {'count':>7}  {'mean':>10}  "
                     f"{'p50':>10}  {'p99':>10}  {'total':>12}")
        for name, n, mean, p50, p99, total in rows:
            lines.append(f"  {name:<{w}}  {n:>7}  {_fmt(mean):>10}  "
                         f"{_fmt(p50):>10}  {_fmt(p99):>10}  "
                         f"{_fmt(total):>12}")
    for name, v in counters:
        lines.append(f"  {name} = {v:g}")
    # companion engine counters that contextualize the phases
    extras = [n for n in ("engine.compiles",
                          "engine.frontier.rounds",
                          "engine.frontier.dense_rounds",
                          "engine.frontier.direction_switches",
                          "engine.frontier.push_edges",
                          "engine.frontier.pull_edges",
                          "engine.frontier.retraces",
                          "engine.exec_cache.hits",
                          "engine.exec_cache.misses")
              if n in snapshot]
    if extras:
        lines.append("  --")
        for n in extras:
            lines.append(f"  {n} = {snapshot[n].get('value', 0):g}")
    return "\n".join(lines) + "\n"
