"""Memory-budget benchmark: bounded vs unbounded serving -> BENCH_memory.json.

Runs the fused+cached interactive workload twice, each leg in its **own
subprocess** so peak RSS (``VmHWM`` from ``/proc/self/status``) is a clean
per-leg number:

    unbounded   MemoryPolicy(budget_bytes=None) — accounting on, eviction
                off; records the tracked-bytes peak the workload reaches
    budgeted    budget = 25% of the unbounded leg's tracked peak; the
                byte-accounted LRU must evict continuously to stay inside

The workload mirrors ``bench_service.py``'s interactive profile: several
sessions, each round issuing single-source traversals from a small **hot**
source pool (repeat queries — should stay cache-resident under the budget)
plus one per-round **cold** source (queried once, never again — the LRU's
natural victims), with periodic PageRank re-runs and one pass of the
plan-family-heavy ops (connected components, triangles) so evictable plan
members carry real weight.

Per leg it records every post-query ``tracked_bytes`` sample, a sha256
digest chained over every result in submission order, wall time over the
query loop (after a warmup pass that absorbs JIT compilation in both legs
identically), and peak RSS.  The gates — enforced by ``ci_check.sh`` —
hold the PR 8 acceptance contract:

* ``within_budget``  — every budgeted-leg sample <= budget;
* ``bit_identical``  — the budgeted digest equals the unbounded digest
  (evicted cache entries re-execute, evicted plan members re-derive,
  nothing changes a single bit);
* ``slowdown``       — budgeted wall time <= 1.5x unbounded (same-run,
  same-machine ratio, hardware-independent);
* ``rss_ratio``      — budgeted peak RSS must not exceed unbounded's
  (with slack for allocator noise): bounding tracked bytes must not
  *grow* the actual process footprint.
"""

import argparse
import hashlib
import json
import os
import subprocess
import sys
import time

import numpy as np

#: budgeted leg's budget as a fraction of the unbounded tracked peak
BUDGET_FRACTION = 0.25


def peak_rss_bytes() -> int:
    """Peak resident set (VmHWM) of this process, from /proc."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return 0


def _digest_update(h, result) -> None:
    arr = np.asarray(result)
    h.update(arr.tobytes())


def run_leg(scale: int, edge_factor: int, sessions: int, rounds: int,
            hot_pool: int, budget: int) -> dict:
    from repro.core.graph import Graph
    from repro.data.rmat import rmat_edges
    from repro.serve.graph_service import GraphService
    from repro.serve.policy import MemoryPolicy

    s, d = rmat_edges(scale, edge_factor=edge_factor, seed=7)
    g = Graph.from_edges(s, d)
    n = g.n_nodes
    svc = GraphService(memory=MemoryPolicy(
        budget_bytes=budget if budget > 0 else None))
    svc.workspace.put("g", g)
    sess = [svc.session(f"s{i}") for i in range(sessions)]

    def q(i, req):
        return svc.execute(sess[i], req)

    # warmup: compile every op shape once so wall time measures serving, not
    # JIT (identical in both legs; results discarded from the digest)
    q(0, {"op": "bfs", "graph": "g", "params": {"source": 0}})
    q(0, {"op": "sssp", "graph": "g", "params": {"source": 0}})
    q(0, {"op": "pagerank", "graph": "g", "params": {"n_iter": 10}})

    h = hashlib.sha256()
    samples = []

    def sample():
        samples.append(int(svc.memory_stats()["tracked_bytes"]))

    t0 = time.perf_counter()
    # plan-family-heavy pass: materializes undirected/oriented members
    _digest_update(h, q(0, {"op": "connected_components", "graph": "g",
                            "params": {}}))
    sample()
    _digest_update(h, q(0, {"op": "triangle_count", "graph": "g",
                            "params": {}}))
    sample()
    n_queries = 2
    for r in range(rounds):
        for i in range(sessions):
            hot = (i + r) % hot_pool            # repeats across rounds
            cold = hot_pool + r * sessions + i  # unique: queried exactly once
            for src, op in ((hot, "sssp"), (cold % n, "bfs")):
                _digest_update(h, q(i, {"op": op, "graph": "g",
                                        "params": {"source": int(src)}}))
                sample()
                n_queries += 1
        if r % 3 == 2:
            _digest_update(h, q(0, {"op": "pagerank", "graph": "g",
                                    "params": {"n_iter": 10}}))
            sample()
            n_queries += 1
    wall_s = time.perf_counter() - t0

    st = dict(svc.stats)
    ms = svc.memory_stats()
    return {
        "budget_bytes": budget,
        "n_queries": n_queries,
        "wall_s": round(wall_s, 4),
        "qps": round(n_queries / wall_s, 1),
        "digest": h.hexdigest(),
        "tracked_peak": max(samples),
        "tracked_end": samples[-1],
        "n_samples": len(samples),
        "over_budget_samples": (sum(1 for b in samples if b > budget)
                                if budget > 0 else 0),
        "peak_rss_bytes": peak_rss_bytes(),
        "stats": {k: st[k] for k in
                  ("requests", "cache_hits", "engine_calls",
                   "evicted_results", "evicted_plan_families",
                   "evicted_bytes", "lineage_cuts")},
        "mem": ms,
    }


def _spawn_leg(args, budget: int) -> dict:
    out = f"{args.out}.leg{budget}.tmp"
    cmd = [sys.executable, os.path.abspath(__file__), "--_leg", out,
           "--budget", str(budget), "--scale", str(args.scale),
           "--edge-factor", str(args.edge_factor),
           "--sessions", str(args.sessions), "--rounds", str(args.rounds),
           "--hot-pool", str(args.hot_pool)]
    try:
        subprocess.run(cmd, check=True)
        with open(out) as f:
            return json.load(f)
    finally:
        if os.path.exists(out):
            os.remove(out)


def main() -> None:
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_memory.json")
    ap.add_argument("--scale", type=int, default=13)
    ap.add_argument("--edge-factor", type=int, default=8)
    ap.add_argument("--sessions", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--hot-pool", type=int, default=4)
    ap.add_argument("--budget", type=int, default=0,
                    help="(worker legs) budget in bytes; 0 = unbounded")
    ap.add_argument("--_leg", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args._leg:  # worker subprocess: one leg, json to the named file
        r = run_leg(args.scale, args.edge_factor, args.sessions, args.rounds,
                    args.hot_pool, args.budget)
        with open(args._leg, "w") as f:
            json.dump(r, f)
        return

    import jax
    print(f"memory bench: 2^{args.scale} RMAT x{args.edge_factor}, "
          f"{args.sessions} sessions x {args.rounds} rounds, "
          f"hot pool {args.hot_pool}")
    unb = _spawn_leg(args, 0)
    print(f"unbounded: {unb['n_queries']} queries {unb['qps']} qps, tracked "
          f"peak {unb['tracked_peak']/1e6:.2f}MB, "
          f"rss peak {unb['peak_rss_bytes']/1e6:.1f}MB")

    budget = max(int(unb["tracked_peak"] * BUDGET_FRACTION), 64 * 1024)
    bud = _spawn_leg(args, budget)
    print(f"budgeted({budget/1e6:.2f}MB): {bud['n_queries']} queries "
          f"{bud['qps']} qps, tracked peak {bud['tracked_peak']/1e6:.2f}MB, "
          f"rss peak {bud['peak_rss_bytes']/1e6:.1f}MB, evicted "
          f"{bud['stats']['evicted_results']} results / "
          f"{bud['stats']['evicted_plan_families']} plan families "
          f"({bud['stats']['evicted_bytes']/1e6:.2f}MB)")

    results = {
        "device": jax.default_backend(),
        "scale": args.scale, "edge_factor": args.edge_factor,
        "sessions": args.sessions, "rounds": args.rounds,
        "hot_pool": args.hot_pool,
        "budget_fraction": BUDGET_FRACTION,
        "budget_bytes": budget,
        "unbounded": unb,
        "budgeted": bud,
        "within_budget": bud["over_budget_samples"] == 0,
        "bit_identical": bud["digest"] == unb["digest"],
        "slowdown": round(bud["wall_s"] / unb["wall_s"], 3),
        "rss_ratio": round(bud["peak_rss_bytes"]
                           / max(unb["peak_rss_bytes"], 1), 3),
    }
    print(f"within_budget={results['within_budget']} "
          f"bit_identical={results['bit_identical']} "
          f"slowdown={results['slowdown']}x rss_ratio={results['rss_ratio']}")
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
