"""Engine smoke benchmark — per-backend PageRank latency → BENCH_engine.json.

Runs PageRank through the unified traversal engine on an RMAT graph (default
2^16 nodes, the paper-table scale knob) once per backend and records wall
time plus the one-off plan build cost, so the perf trajectory of the
plan/engine substrate is tracked across PRs.

Also records dense-vs-frontier BFS latency on a 2^15-node RMAT graph (from
the max-out-degree source, so the traversal actually covers the giant
component): the "bfs" block carries ``dense_ms`` / ``frontier_ms`` /
``speedup`` and ``ci_check.sh`` gates frontier >= 1.5x dense.

The "delta" block measures incremental maintenance on a 0.1% edge delta at
the same scale: plan patching vs full re-derivation, warm-started
tol-stopped pagerank vs cold, and frontier re-seeded BFS vs cold.
``ci_check.sh`` gates ``plan_patch_speedup`` >= 5x and
``warm_pagerank_speedup`` >= 2x — both ratios of same-host wall times, so
the gates are hardware-independent.

The Pallas/BSR backends execute in interpret mode off-TPU, which is a
correctness emulation, not a speed path — on non-TPU hosts they are measured
at a reduced scale (recorded in the JSON) to keep the smoke run fast.
"""

import argparse
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np

from repro import compile_cache
from repro.core import algorithms as A
from repro.core.graph import EdgeDelta, Graph
from repro.data.rmat import rmat_edges


def _sync_plan(plan):
    jax.block_until_ready((plan.in_src, plan.in_dst, plan.out_src,
                           plan.out_dst, plan.inv_out_deg))


def bench_backend(backend: str, scale: int, edge_factor: int, n_iter: int,
                  repeats: int) -> dict:
    src, dst = rmat_edges(scale, edge_factor=edge_factor, seed=0)
    # shape warm-up: an identically-shaped throwaway graph pays the
    # per-shape op-compile cost, so plan_build_ms measures per-graph work
    _sync_plan(Graph.from_edges(src, dst).plan())
    g = Graph.from_edges(src, dst)
    t0 = time.perf_counter()
    plan = g.plan()
    _sync_plan(plan)
    plan_ms = (time.perf_counter() - t0) * 1e3
    # warmup: jit compile + lazy plan structures (BSR tiles / chunk layouts)
    A.pagerank(g, n_iter=n_iter, backend=backend).block_until_ready()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        A.pagerank(g, n_iter=n_iter, backend=backend).block_until_ready()
        best = min(best, (time.perf_counter() - t0) * 1e3)
    return {"scale": scale, "n_nodes": g.n_nodes, "n_edges": g.n_edges,
            "n_iter": n_iter, "plan_build_ms": round(plan_ms, 3),
            "pagerank_ms": round(best, 3)}


def bench_bfs(scale: int, edge_factor: int, repeats: int) -> dict:
    """Dense Bellman-Ford vs frontier-sparse BFS on one RMAT graph."""
    src, dst = rmat_edges(scale, edge_factor=edge_factor, seed=0)
    g = Graph.from_edges(src, dst)
    source = int(np.argmax(np.asarray(g.plan().out_deg)))

    def best(backend):
        A.bfs(g, source, backend=backend).block_until_ready()   # warm/trace
        b = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            A.bfs(g, source, backend=backend).block_until_ready()
            b = min(b, (time.perf_counter() - t0) * 1e3)
        return b

    dense_ms = best("xla")
    frontier_ms = best("frontier")
    levels = np.asarray(A.bfs(g, source, backend="frontier"))
    return {"scale": scale, "n_nodes": g.n_nodes, "n_edges": g.n_edges,
            "source": source, "reached": int((levels >= 0).sum()),
            "dense_ms": round(dense_ms, 3),
            "frontier_ms": round(frontier_ms, 3),
            "speedup": round(dense_ms / frontier_ms, 3)}


def bench_delta(scale: int, edge_factor: int, repeats: int,
                frac: float = 0.001, tol: float = 1e-6) -> dict:
    """Incremental maintenance vs from-scratch on a small (``frac``) delta.

    Three hardware-independent ratios on one RMAT graph:

    * ``plan_patch_speedup`` — ``apply_delta`` + patched plan build vs
      ``add_edges`` + full plan re-derivation (same resulting CSR);
    * ``warm_pagerank_speedup`` — end-to-end refreshed pagerank after the
      delta: incremental (``apply_delta`` + patched plan + tol-stopped
      solve warm-started from the parent vector) vs from-scratch
      (``add_edges`` + re-derived plan + cold solve), both converging to
      the same tolerance.  Solver-only times are recorded alongside as
      ``cold_solve_ms`` / ``warm_solve_ms`` — on fast-mixing RMAT graphs
      the solver alone converges in a handful of iterations either way, so
      the interactive win lives in maintenance + solve, which is what an
      analyst waiting on a refreshed ranking actually pays;
    * ``bfs_reseed_speedup`` — frontier re-seeded BFS from the parent levels
      vs a cold traversal (bit-identical results, asserted).
    """
    src, dst = rmat_edges(scale, edge_factor=edge_factor, seed=0)
    g = Graph.from_edges(src, dst)
    _sync_plan(g.plan())
    ids = np.asarray(g.node_ids)[:g.n_nodes]
    rng = np.random.default_rng(7)
    n_delta = max(1, int(g.n_edges * frac))
    add_s = ids[rng.integers(0, g.n_nodes, n_delta)].astype(np.int32)
    add_d = ids[rng.integers(0, g.n_nodes, n_delta)].astype(np.int32)
    delta = EdgeDelta.inserts(add_s, add_d)

    def best(fn):
        fn()                                     # shape/trace warm-up
        b = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            fn()
            b = min(b, (time.perf_counter() - t0) * 1e3)
        return b

    # plan maintenance: patch (delta merge into the parent's sorted arrays)
    # vs re-derive (full device sort of the grown edge list).  A fresh child
    # every run — the plan is identity-memoized per graph.
    patch_ms = best(lambda: _sync_plan(g.apply_delta(delta).plan()))
    rederive_ms = best(lambda: _sync_plan(g.add_edges(add_s, add_d).plan()))

    child = g.apply_delta(delta)
    assert child._delta is not None, "delta fast path did not engage"
    _sync_plan(child.plan())

    parent_pr = A.pagerank(g, tol=tol).block_until_ready()
    cold_solve_ms = best(
        lambda: A.pagerank(child, tol=tol).block_until_ready())
    warm_solve_ms = best(
        lambda: A.pagerank(child, tol=tol,
                           init=parent_pr).block_until_ready())
    # end-to-end refresh: what a session waits for after publishing the
    # delta — graph + plan maintenance and the solve, on a fresh child
    # every run (plan and graph caches are identity-memoized)
    cold_refresh_ms = best(lambda: A.pagerank(
        g.add_edges(add_s, add_d), tol=tol).block_until_ready())
    warm_refresh_ms = best(lambda: A.pagerank(
        g.apply_delta(delta), tol=tol, init=parent_pr).block_until_ready())

    source = int(np.argmax(np.asarray(g.plan().out_deg)))
    parent_bfs = A.bfs(g, source).block_until_ready()
    cold_bfs_ms = best(lambda: A.bfs(child, source).block_until_ready())
    warm_bfs = A.incremental_bfs(child, source, parent_bfs)
    assert warm_bfs is not None, "incremental bfs fell back"
    if not np.array_equal(np.asarray(warm_bfs),
                          np.asarray(A.bfs(child, source))):
        raise AssertionError("incremental bfs diverged from cold run")
    warm_bfs_ms = best(lambda: jax.block_until_ready(
        A.incremental_bfs(child, source, parent_bfs)))

    return {"scale": scale, "n_nodes": g.n_nodes, "n_edges": g.n_edges,
            "n_delta_edges": int(n_delta), "tol": tol,
            "plan_patch_ms": round(patch_ms, 3),
            "plan_rederive_ms": round(rederive_ms, 3),
            "plan_patch_speedup": round(rederive_ms / patch_ms, 3),
            "cold_solve_ms": round(cold_solve_ms, 3),
            "warm_solve_ms": round(warm_solve_ms, 3),
            "warm_solve_speedup": round(cold_solve_ms / warm_solve_ms, 3),
            "cold_pagerank_ms": round(cold_refresh_ms, 3),
            "warm_pagerank_ms": round(warm_refresh_ms, 3),
            "warm_pagerank_speedup":
                round(cold_refresh_ms / warm_refresh_ms, 3),
            "cold_bfs_ms": round(cold_bfs_ms, 3),
            "warm_bfs_ms": round(warm_bfs_ms, 3),
            "bfs_reseed_speedup": round(cold_bfs_ms / warm_bfs_ms, 3)}


def bench_sharded(scale: int, edge_factor: int, n_iter: int, repeats: int,
                  n_shards: int) -> dict:
    """PageRank + BFS through the ``"sharded"`` backend at one shard count.

    Needs ``len(jax.devices()) >= n_shards`` — the device count is fixed at
    the first jax import, so the multi-device leg is spawned as a subprocess
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` when the
    ambient session is smaller (see ``_sharded_leg``).  Also records the
    halo-exchange volume per round, the hardware-independent number that
    tells you what a real multi-host mesh would put on the wire.
    """
    os.environ["REPRO_SHARD_COUNT"] = str(n_shards)
    try:
        src, dst = rmat_edges(scale, edge_factor=edge_factor, seed=0)
        g = Graph.from_edges(src, dst)
        plan = g.plan()
        _sync_plan(plan)
        t0 = time.perf_counter()
        sp = plan.sharded(n_shards)
        jax.block_until_ready((sp.pull.gather_idx, sp.push.gather_idx))
        shard_plan_ms = (time.perf_counter() - t0) * 1e3

        def best(fn):
            fn()                                 # trace/compile warm-up
            b = float("inf")
            for _ in range(repeats):
                t1 = time.perf_counter()
                fn()
                b = min(b, (time.perf_counter() - t1) * 1e3)
            return b

        pr_ms = best(lambda: A.pagerank(g, n_iter=n_iter,
                                        backend="sharded").block_until_ready())
        source = int(np.argmax(np.asarray(plan.out_deg)))
        bfs_ms = best(lambda: A.bfs(g, source,
                                    backend="sharded").block_until_ready())
        # the leg is only worth timing if it honours the bitwise contract
        np.testing.assert_array_equal(
            np.asarray(A.pagerank(g, n_iter=n_iter, backend="sharded")),
            np.asarray(A.pagerank(g, n_iter=n_iter, backend="xla")))
        return {"devices": n_shards, "scale": scale, "n_nodes": g.n_nodes,
                "n_edges": g.n_edges, "n_iter": n_iter,
                "shard_plan_build_ms": round(shard_plan_ms, 3),
                "pagerank_ms": round(pr_ms, 3), "bfs_ms": round(bfs_ms, 3),
                "halo_bytes_per_round": int(sp.halo_bytes_per_round())}
    finally:
        os.environ.pop("REPRO_SHARD_COUNT", None)


def _sharded_leg(n_shards: int, args) -> dict:
    """Run one sharded leg, in-process when the devices exist, else (on a
    CPU host only) in a subprocess that raises the simulated host device
    count first."""
    if len(jax.devices()) >= n_shards:
        return bench_sharded(args.bfs_scale, args.edge_factor, args.n_iter,
                             args.repeats, n_shards)
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"sharded leg d={n_shards} needs {n_shards} devices but only "
            f"{len(jax.devices())} {jax.default_backend()} device(s) are "
            f"visible; a simulated CPU mesh would report CPU timings under "
            f"this {jax.default_backend()} run")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_shards}")
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--sharded-leg", str(n_shards), "--scale", str(args.scale),
         "--bfs-scale", str(args.bfs_scale),
         "--edge-factor", str(args.edge_factor),
         "--n-iter", str(args.n_iter), "--repeats", str(args.repeats)],
        capture_output=True, text=True, timeout=1800, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if proc.returncode != 0:
        raise RuntimeError(f"sharded leg d={n_shards} failed:\n"
                           f"{proc.stdout}\n{proc.stderr[-3000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main():
    compile_cache.enable()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--scale", type=int, default=16,
                   help="log2 nodes for the native backend run")
    p.add_argument("--interp-scale", type=int, default=9,
                   help="log2 nodes for interpret-mode backends off-TPU")
    p.add_argument("--bfs-scale", type=int, default=15,
                   help="log2 nodes for the dense-vs-frontier BFS gate")
    p.add_argument("--edge-factor", type=int, default=8)
    p.add_argument("--n-iter", type=int, default=10)
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--out", default="BENCH_engine.json")
    p.add_argument("--sharded-leg", type=int, default=0,
                   help="internal: run ONE sharded leg at this shard count "
                        "and print its JSON block (used by the subprocess "
                        "re-entry that raises the simulated device count)")
    args = p.parse_args()

    if args.sharded_leg:
        print(json.dumps(bench_sharded(args.bfs_scale, args.edge_factor,
                                       args.n_iter, args.repeats,
                                       args.sharded_leg)))
        return

    on_tpu = jax.default_backend() == "tpu"
    scales = {"xla": args.scale,
              "pallas": args.scale if on_tpu else args.interp_scale,
              "bsr": args.scale if on_tpu else args.interp_scale}
    results = {"device": jax.default_backend(), "backends": {}}
    for backend, scale in scales.items():
        r = bench_backend(backend, scale, args.edge_factor, args.n_iter,
                          args.repeats)
        r["interpret_mode"] = not on_tpu and backend != "xla"
        results["backends"][backend] = r
        print(f"{backend:7s} scale={scale:2d} plan={r['plan_build_ms']:9.2f}ms"
              f" pagerank={r['pagerank_ms']:9.2f}ms"
              f"{'  (interpret)' if r['interpret_mode'] else ''}")

    results["bfs"] = bench_bfs(args.bfs_scale, args.edge_factor, args.repeats)
    b = results["bfs"]
    print(f"bfs     scale={b['scale']:2d} dense={b['dense_ms']:9.2f}ms"
          f" frontier={b['frontier_ms']:9.2f}ms speedup={b['speedup']:.2f}x")

    results["delta"] = bench_delta(args.bfs_scale, args.edge_factor,
                                   args.repeats)
    d = results["delta"]
    print(f"delta   scale={d['scale']:2d} ({d['n_delta_edges']} edges)"
          f" plan patch={d['plan_patch_ms']:.2f}ms vs"
          f" rederive={d['plan_rederive_ms']:.2f}ms"
          f" ({d['plan_patch_speedup']:.1f}x);"
          f" pagerank warm={d['warm_pagerank_ms']:.2f}ms vs"
          f" cold={d['cold_pagerank_ms']:.2f}ms"
          f" ({d['warm_pagerank_speedup']:.1f}x);"
          f" bfs reseed {d['bfs_reseed_speedup']:.1f}x")

    # sharded backend: 1 vs 8 simulated devices.  Absolute times are
    # info-only — the 8 "devices" share one CPU, so the dense (replicated)
    # portion of every round runs 8x over; the portable numbers here are
    # halo_bytes_per_round and the bitwise-identity assert inside each leg.
    leg1 = bench_sharded(args.bfs_scale, args.edge_factor, args.n_iter,
                         args.repeats, 1)
    leg8 = _sharded_leg(8, args)
    results["sharded"] = {
        "scale": args.bfs_scale, "legs": {"1": leg1, "8": leg8},
        "pagerank_ratio_8v1":
            round(leg1["pagerank_ms"] / leg8["pagerank_ms"], 3),
        "bfs_ratio_8v1": round(leg1["bfs_ms"] / leg8["bfs_ms"], 3)}
    for leg in (leg1, leg8):
        print(f"sharded d={leg['devices']} scale={leg['scale']:2d}"
              f" pagerank={leg['pagerank_ms']:9.2f}ms"
              f" bfs={leg['bfs_ms']:9.2f}ms"
              f" halo={leg['halo_bytes_per_round']}B/round")

    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
