"""Interactive service benchmark — concurrent-session query throughput and
latency -> BENCH_service.json.

Simulates the paper's multi-analyst trial-and-error loop against one shared
RMAT graph (default 2^15 nodes): every round, each session issues one
single-source traversal (sssp or bfs) from a small rotating source pool plus
periodic PageRank re-runs, exactly the redundancy profile of interactive
exploration.  The workload runs three ways:

    sequential    fusion off, cache off — every query is its own engine call
    fused         the scheduler coalesces each round's single-source queries
                  into one vmapped multi-source fixpoint
    fused_cached  fusion + the versioned result cache (repeat queries free)

and records throughput (qps) and per-query p50/p99 latency for each.  The
accept gate for the service subsystem is fused_cached >= 2x sequential
throughput on the same workload.

The **overload** block measures the scheduler's admission-control/fair-share
contract (ISSUE 4): one hostile session floods the service with expensive
non-fusable queries (held to its in-flight quota by admission control, its
spillover absorbed as RejectedError+retry-after backoff) while N interactive
sessions run a closed query loop.  The same workload runs under ``"fifo"``
(global arrival order — what a naive queue gives you) and ``"fair"``
(deficit-round-robin charged in engine-ms); the gate asserts interactive p99
under fair share is >= 3x better than FIFO.

The **remote** block (ISSUE 5) is the honest serving benchmark: a real
server subprocess (``python -m repro.serve.server``) and genuinely
independent client OS processes speaking the wire protocol.  It records
(a) the cached-query overhead of the wire — a single remote client vs the
same closed loop through an in-process worker-dispatched service, gated at
<= 3x p50 with a 1 ms floor on the baseline (see
``REMOTE_OVERHEAD_FLOOR_MS``: a dict-lookup baseline would make any socket
fail a pure ratio) — and (b) an aggregate multi-process block: N client
processes hammering one shared engine.
"""

import argparse
import json
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np

from repro import compile_cache, obs
from repro.core.graph import Graph
from repro.data.rmat import rmat_edges
from repro.serve.graph_service import (GraphService, RejectedError, Workspace)
from repro.serve.policy import (AdmissionPolicy, BatchPolicy, FairSharePolicy,
                                SchedulerPolicy)


def pctl(samples, q: float) -> float:
    """Interpolated percentile (numpy's default linear method), NaN-safe on
    empty input — at small n the interpolation estimates the tail instead
    of handing back the single worst outlier as p99."""
    xs = np.asarray(list(samples), dtype=np.float64)
    if xs.size == 0:
        return float("nan")
    return float(np.percentile(xs, q))


def latency_pctls(hist, samples):
    """(p50, p99) served from an obs histogram when it recorded the samples
    — the metrics registry is the latency source of truth now — with the
    hand-rolled interpolated :func:`pctl` kept as the fallback for runs
    where observability is disabled (the overhead measurement's off leg)
    and for degenerate histograms (quantile() returns None when all mass
    sits in the first or overflow bucket — e.g. an all-cache-hit workload
    whose sub-0.05ms latencies land entirely in the first bucket)."""
    if hist is not None and hist.count > 0:
        p50, p99 = hist.quantile(0.5), hist.quantile(0.99)
        if p50 is not None and p99 is not None:
            return p50, p99
    return pctl(samples, 50), pctl(samples, 99)


def jain_index(xs) -> float:
    """Jain's fairness index over per-session shares: 1.0 = perfectly even,
    1/n = one session took everything."""
    xs = np.asarray(list(xs), dtype=np.float64)
    if xs.size == 0 or float((xs ** 2).sum()) == 0.0:
        return 1.0
    return float(xs.sum() ** 2 / (xs.size * (xs ** 2).sum()))


def build_workload(n_sessions: int, n_rounds: int, source_pool: int):
    """Per-round request lists: deterministic mix with source reuse.

    Sessions 0..2/3 issue sssp, the rest bfs — the per-op group size stays
    constant across rounds so the vmapped fixpoint compiles once.  Sources
    rotate through a small pool (interactive users revisit the same seeds),
    and every 3rd round each session re-asks for the shared PageRank.
    """
    n_sssp = max((n_sessions * 2) // 3, 1)
    rounds = []
    for r in range(n_rounds):
        reqs = []
        for i in range(n_sessions):
            op = "sssp" if i < n_sssp else "bfs"
            src = (r * 7 + i * 3) % source_pool
            reqs.append((i, {"op": op, "graph": "g",
                             "params": {"source": int(src)}}))
            if r % 3 == 2:
                reqs.append((i, {"op": "pagerank", "graph": "g",
                                 "params": {"n_iter": 10}}))
        rounds.append(reqs)
    return rounds


def run_mode(graph, rounds, n_sessions, *, fuse: bool, cache: bool) -> dict:
    ws = Workspace()
    ws.put("g", graph)
    svc = GraphService(ws, fuse=fuse, cache=cache)
    sessions = [svc.session(f"u{i}") for i in range(n_sessions)]

    # warmup: pay jit compiles (single-source + the fused batch widths)
    for sid, req in rounds[0]:
        sessions[sid].submit(dict(req))
    svc.flush()
    for sid, req in rounds[0]:
        sessions[sid].execute(dict(req))
    warm_stats = dict(svc.stats)

    # scope the obs registry to the timed loop: end-to-end latencies land in
    # a histogram (the percentiles below read from it), and the scheduler's
    # own queued/engine histograms are reported from the same snapshot
    obs.reset()
    lat_hist = obs.histogram("bench.latency_ms")
    latencies = []
    t0 = time.perf_counter()
    n_queries = 0
    for reqs in rounds:
        pending = [sessions[sid].submit(dict(req)) for sid, req in reqs]
        svc.flush()
        for p in pending:
            p.result()
            latencies.append(p.latency_ms)
            lat_hist.observe(p.latency_ms)
        n_queries += len(pending)
    wall_s = time.perf_counter() - t0

    p50, p99 = latency_pctls(lat_hist, latencies)
    sched = {}
    snap = obs.dump_metrics()
    for key, label in (("sched.queued_ms", "queued"),
                       ("sched.engine_ms", "engine")):
        h = snap.get(key)
        if h and h.get("count"):
            for q, lab in ((0.5, "p50"), (0.99, "p99")):
                v = obs.quantile_from_snapshot(h, q)
                # None = degenerate histogram (all mass below the first
                # edge, e.g. an all-cached queue): skip rather than invent
                if v is not None:
                    sched[f"{label}_{lab}_ms"] = round(v, 3)
    for k in svc.stats:
        svc.stats[k] -= warm_stats[k]
    return {"n_queries": n_queries,
            "wall_s": round(wall_s, 4),
            "qps": round(n_queries / wall_s, 2),
            "p50_ms": round(p50, 3),
            "p99_ms": round(p99, 3),
            "sched": sched,
            "stats": dict(svc.stats)}


# ---------------------------------------------------------------------------
# observability overhead: the instrumentation must stay under 5%
# ---------------------------------------------------------------------------


def run_obs_overhead(graph, rounds, n_sessions, reps: int = 9) -> dict:
    """Fused-service workload with observability on vs off, interleaved.

    Each rep runs the fused+cached mode twice — once with the metrics
    registry + tracer + SLO/flight/profiler judgment layer enabled (the
    shipping default) and once fully disabled — alternating which leg goes
    first so thermal/JIT drift cannot systematically favor one side.  The
    gated ratio is **min over reps** of each leg: wall-clock noise on a
    shared machine is strictly additive, so the per-leg minimum is the
    best estimate of true cost (the ``timeit`` argument) — medians of
    ~1.5 s reps swing ±10% run-to-run, which a 1.05x gate cannot survive.
    Medians ride along for reference; ``ci_check.sh`` and
    ``bench_delta.py`` gate ``ratio`` at <= 1.05x.
    """
    walls = {"on": [], "off": []}
    try:
        for r in range(reps):
            order = ("on", "off") if r % 2 == 0 else ("off", "on")
            for which in order:
                (obs.enable if which == "on" else obs.disable)()
                res = run_mode(graph, rounds, n_sessions,
                               fuse=True, cache=True)
                walls[which].append(res["wall_s"])
    finally:
        obs.enable()
    on = float(min(walls["on"]))
    off = float(min(walls["off"]))
    out = {"reps": reps,
           "enabled_wall_s": walls["on"],
           "disabled_wall_s": walls["off"],
           "enabled_min_s": round(on, 4),
           "disabled_min_s": round(off, 4),
           "enabled_median_s": round(float(np.median(walls["on"])), 4),
           "disabled_median_s": round(float(np.median(walls["off"])), 4),
           "ratio": round(on / off, 4) if off > 0 else 1.0}
    print(f"obs overhead: enabled {on:.3f}s vs disabled {off:.3f}s "
          f"-> {out['ratio']}x (gate <= 1.05x)")
    return out


# ---------------------------------------------------------------------------
# overload: 1 flooding session vs N interactive, fifo vs fair share
# ---------------------------------------------------------------------------


def run_overload_mode(graph, *, mode: str, n_interactive: int,
                      queries_per_session: int, flood_quota: int,
                      source_pool: int = 64) -> dict:
    """One hostile flooding session vs N closed-loop interactive sessions.

    Fusion and caching are OFF: the comparison isolates *scheduling order*
    (every query is a real engine call in both modes).  The flood keeps its
    admission quota saturated with expensive PageRanks; each interactive
    session serially issues single-source BFS queries and waits.  Reported
    latencies are interactive submit->resolve times.
    """
    ws = Workspace()
    ws.put("g", graph)
    policy = SchedulerPolicy(
        mode=mode,
        admission=AdmissionPolicy(max_inflight=8,
                                  inflight_overrides={"flood": flood_quota}),
        fair=FairSharePolicy(quantum_ms=5.0),
        batch=BatchPolicy(window_ms=0.0))
    svc = GraphService(ws, fuse=False, cache=False, policy=policy, workers=1)

    # warmup: compile the two op shapes before any timing (several bfs
    # sources so the frontier path's size buckets are warm too)
    warm = svc.session("warm")
    warm.execute({"op": "pagerank", "graph": "g", "params": {"n_iter": 10}})
    for s in (0, 7, 19):
        warm.execute({"op": "bfs", "graph": "g", "params": {"source": s}})

    stop = threading.Event()
    flood = svc.session("flood")
    flood_submitted = [0]

    def flood_loop():
        while not stop.is_set():
            try:
                flood.submit({"op": "pagerank", "graph": "g",
                              "params": {"n_iter": 10}})
                flood_submitted[0] += 1
            except RejectedError as e:
                time.sleep(min(e.retry_after, 0.05))

    lat_by_session = {i: [] for i in range(n_interactive)}

    def interactive_loop(i):
        sess = svc.session(f"i{i}")
        for q in range(queries_per_session):
            src = (q * 13 + i * 5) % source_pool
            p = sess.submit({"op": "bfs", "graph": "g",
                             "params": {"source": int(src)}})
            p.result(timeout=600)
            lat_by_session[i].append(p.latency_ms)

    flooder = threading.Thread(target=flood_loop, daemon=True)
    flooder.start()
    time.sleep(0.4)              # let the flood build its quota-deep backlog
    t0 = time.perf_counter()
    threads = [threading.Thread(target=interactive_loop, args=(i,),
                                daemon=True) for i in range(n_interactive)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall_s = time.perf_counter() - t0
    stop.set()
    flooder.join(timeout=5)
    svc.flush()                  # drain the flood's leftover backlog
    flood_stats = svc.session_stats("flood")
    svc.close()

    all_lat = [x for lats in lat_by_session.values() for x in lats]
    per_qps = [len(lats) / wall_s for lats in lat_by_session.values()]
    return {"wall_s": round(wall_s, 4),
            "interactive_p50_ms": round(pctl(all_lat, 50), 3),
            "interactive_p99_ms": round(pctl(all_lat, 99), 3),
            "per_session_p99_ms": {f"i{i}": round(pctl(lats, 99), 3)
                                   for i, lats in lat_by_session.items()},
            "fairness_index": round(jain_index(per_qps), 4),
            "flood_submitted": flood_submitted[0],
            "flood_completed": flood_stats["completed"],
            "flood_rejected": flood_stats["rejected"],
            "flood_engine_ms": flood_stats["engine_ms"]}


def run_overload(scale: int, edge_factor: int, n_interactive: int,
                 queries_per_session: int, flood_quota: int) -> dict:
    src, dst = rmat_edges(scale, edge_factor=edge_factor, seed=1)
    g = Graph.from_edges(src, dst)
    g.plan()
    out = {"scale": scale, "n_nodes": g.n_nodes, "n_edges": g.n_edges,
           "interactive_sessions": n_interactive,
           "queries_per_session": queries_per_session,
           "flood_quota": flood_quota, "modes": {}}
    for mode in ("fifo", "fair"):
        r = run_overload_mode(g, mode=mode, n_interactive=n_interactive,
                              queries_per_session=queries_per_session,
                              flood_quota=flood_quota)
        out["modes"][mode] = r
        print(f"overload/{mode:4s}  interactive p50={r['interactive_p50_ms']:8.1f}ms"
              f"  p99={r['interactive_p99_ms']:8.1f}ms"
              f"  fairness={r['fairness_index']:.3f}"
              f"  flood done/rejected={r['flood_completed']}/{r['flood_rejected']}")
    fifo99 = out["modes"]["fifo"]["interactive_p99_ms"]
    fair99 = out["modes"]["fair"]["interactive_p99_ms"]
    out["p99_improvement"] = round(fifo99 / fair99, 2) if fair99 > 0 else 0.0
    print(f"overload: fair-share interactive p99 {out['p99_improvement']}x "
          f"better than FIFO")
    return out


# ---------------------------------------------------------------------------
# remote: real server subprocess + independent client processes (ISSUE 5)
# ---------------------------------------------------------------------------


def _remote_client_loop(port: int, worker_id: int, queries: int,
                        source_pool: int) -> dict:
    """Closed-loop cached-query workload over the wire (one connection)."""
    from repro.serve.client import RemoteService

    cli = RemoteService(port=port, timeout=600.0)
    sess = cli.session("w")
    # warm: touch every source once (first toucher pays the engine call,
    # everyone else hits the shared result cache)
    for s in range(source_pool):
        sess.execute({"op": "bfs", "graph": "g", "params": {"source": s}})
    lat = []
    t0 = time.perf_counter()
    for q in range(queries):
        src = (q * 13 + worker_id * 5) % source_pool
        p = sess.submit({"op": "bfs", "graph": "g",
                         "params": {"source": int(src)}})
        p.result(timeout=600)
        lat.append(p.latency_ms)
    wall_s = time.perf_counter() - t0
    cli.close()
    return {"worker": worker_id, "wall_s": round(wall_s, 4),
            "queries": queries, "latencies_ms": lat}


def _worker_main(args) -> int:
    """Hidden subcommand: one client process of the remote phases."""
    from repro.serve.client import pin_host_only
    pin_host_only()          # load only: the server process owns the device
    out = _remote_client_loop(args.port, args.id, args.queries,
                              args.source_pool)
    with open(args.out, "w") as f:
        json.dump(out, f)
    return 0


def _run_clients(port: int, ids, queries: int, source_pool: int) -> list:
    """Run one client process per id against ``port``; their result dicts.

    Clients are separate processes pinned to the host CPU, so the process
    running this benchmark never decodes an array while the server holds
    the device (a chip belongs to one process)."""
    bench_path = os.path.abspath(__file__)
    outs, procs = [], []
    try:
        for i in ids:
            out_path = f"/tmp/bench_remote_worker_{os.getpid()}_{i}.json"
            outs.append(out_path)
            procs.append(subprocess.Popen(
                [sys.executable, bench_path, "--_worker",
                 "--port", str(port), "--id", str(i),
                 "--queries", str(queries),
                 "--source-pool", str(source_pool), "--out", out_path]))
        for cp in procs:
            rc = cp.wait(timeout=900)
            assert rc == 0, f"remote client worker failed rc={rc}"
        workers = []
        for out_path in outs:
            with open(out_path) as f:
                workers.append(json.load(f))
        return workers
    finally:
        for cp in procs:               # don't leave clients spinning
            if cp.poll() is None:
                cp.kill()
        for out_path in outs:
            if os.path.exists(out_path):
                os.unlink(out_path)


#: a wire hop costs a fixed few hundred microseconds of framing + syscalls;
#: against an in-process cached hit (a dict lookup, ~0.05 ms) *any* socket
#: fails a pure latency ratio.  The overhead ratio therefore compares
#: against max(in-process p50, this floor): the serving contract is "the
#: wire adds at most ~a millisecond-scale constant", which the 3x gate then
#: bounds at ~3 ms absolute for sub-millisecond in-process baselines.
REMOTE_OVERHEAD_FLOOR_MS = 1.0


def run_remote(scale: int, edge_factor: int, clients: int,
               queries: int, source_pool: int) -> dict:
    """Remote serving vs in-process: cached-query overhead + multi-process
    aggregate throughput against one spawned server.

    The spawned server is the only process that touches the device while
    it runs: every client is its own CPU-pinned process, and the in-process
    baseline runs after the server has exited.  Call this before the
    calling process has touched JAX on an accelerator.
    """
    from repro.serve.client import RemoteService
    from repro.serve.server import spawn_server

    # -- spawn the server (same RMAT seed -> same graph) -------------------
    # generous startup deadline: on a contended single-core box the child's
    # import + graph build can be starved for minutes without being wedged
    proc, port = spawn_server(("--rmat-scale", str(scale),
                               "--edge-factor", str(edge_factor),
                               "--workers", "2"), timeout=300.0)
    try:
        # phase a: one remote client, solo -> clean wire-overhead number
        remote_lat = _run_clients(port, [0], queries,
                                  source_pool)[0]["latencies_ms"]

        # phase b: N genuinely independent client processes
        t0 = time.perf_counter()
        workers = _run_clients(port, range(clients), queries, source_pool)
        multi_wall = time.perf_counter() - t0
        multi_lat = [x for w in workers for x in w["latencies_ms"]]

        # ask the server to drain and exit; a clean rc is part of the bench
        # (a bare socket client: this process stays off JAX meanwhile)
        cli = RemoteService(port=port)
        cli.shutdown_server()
        cli.close()
        server_rc = proc.wait(timeout=120)
    except BaseException:
        proc.kill()
        raise

    # -- in-process baseline: same closed cached loop through the same
    # serving configuration (worker-dispatched service, submit -> result) --
    src, dst = rmat_edges(scale, edge_factor=edge_factor, seed=0)
    g = Graph.from_edges(src, dst)
    g.plan()
    svc = GraphService(workers=1)
    svc.workspace.put("g", g)
    base = svc.session("base")
    for s in range(source_pool):
        base.execute({"op": "bfs", "graph": "g", "params": {"source": s}})
    inproc_lat = []
    for q in range(queries):
        p = base.submit({"op": "bfs", "graph": "g",
                         "params": {"source": (q * 13) % source_pool}})
        p.result(timeout=600)
        inproc_lat.append(p.latency_ms)
    svc.close()

    overhead = pctl(remote_lat, 50) / max(pctl(inproc_lat, 50),
                                          REMOTE_OVERHEAD_FLOOR_MS)
    out = {"scale": scale, "n_nodes": g.n_nodes, "n_edges": g.n_edges,
           "queries": queries, "source_pool": source_pool,
           "overhead_floor_ms": REMOTE_OVERHEAD_FLOOR_MS,
           "inproc_cached_p50_ms": round(pctl(inproc_lat, 50), 3),
           "inproc_cached_p99_ms": round(pctl(inproc_lat, 99), 3),
           "remote_cached_p50_ms": round(pctl(remote_lat, 50), 3),
           "remote_cached_p99_ms": round(pctl(remote_lat, 99), 3),
           "overhead_cached_p50": round(overhead, 2),
           "server_exit_code": server_rc,
           "multiproc": {
               "clients": clients,
               "queries_per_client": queries,
               "total_queries": sum(w["queries"] for w in workers),
               "wall_s": round(multi_wall, 4),
               "agg_qps": round(sum(w["queries"] for w in workers)
                                / multi_wall, 2),
               "p50_ms": round(pctl(multi_lat, 50), 3),
               "p99_ms": round(pctl(multi_lat, 99), 3),
               "per_client_qps": [round(w["queries"] / w["wall_s"], 2)
                                  for w in workers]}}
    print(f"remote: cached p50 in-process {out['inproc_cached_p50_ms']}ms "
          f"vs wire {out['remote_cached_p50_ms']}ms "
          f"-> overhead {out['overhead_cached_p50']}x "
          f"(baseline floored at {REMOTE_OVERHEAD_FLOOR_MS}ms); "
          f"{clients} client processes {out['multiproc']['agg_qps']} qps "
          f"aggregate (server rc={server_rc})")
    return out


def main():
    if "--_worker" in sys.argv:
        wp = argparse.ArgumentParser()
        wp.add_argument("--_worker", action="store_true")
        wp.add_argument("--port", type=int, required=True)
        wp.add_argument("--id", type=int, required=True)
        wp.add_argument("--queries", type=int, required=True)
        wp.add_argument("--source-pool", type=int, required=True)
        wp.add_argument("--out", required=True)
        sys.exit(_worker_main(wp.parse_args()))

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--scale", type=int, default=15,
                   help="log2 nodes of the shared RMAT graph")
    p.add_argument("--edge-factor", type=int, default=8)
    p.add_argument("--sessions", type=int, default=12)
    p.add_argument("--rounds", type=int, default=6)
    p.add_argument("--source-pool", type=int, default=16)
    p.add_argument("--obs-reps", type=int, default=9,
                   help="on/off repetitions of the obs-overhead measurement")
    p.add_argument("--overload-scale", type=int, default=13,
                   help="log2 nodes of the overload-mode RMAT graph")
    p.add_argument("--overload-sessions", type=int, default=8)
    p.add_argument("--overload-queries", type=int, default=4)
    p.add_argument("--flood-quota", type=int, default=16,
                   help="flooding session's in-flight admission quota")
    p.add_argument("--skip-overload", action="store_true")
    p.add_argument("--remote-scale", type=int, default=12,
                   help="log2 nodes of the remote-serving RMAT graph")
    p.add_argument("--remote-clients", type=int, default=3,
                   help="independent client OS processes in the remote "
                        "multi-process phase")
    p.add_argument("--remote-queries", type=int, default=60,
                   help="cached queries per client in the remote phases")
    p.add_argument("--remote-source-pool", type=int, default=8)
    p.add_argument("--skip-remote", action="store_true")
    p.add_argument("--out", default="BENCH_service.json")
    args = p.parse_args()
    compile_cache.enable()

    # the remote block runs first: its server child needs the device, so
    # this process must not have touched JAX on it yet
    remote = None if args.skip_remote else run_remote(
        args.remote_scale, args.edge_factor, args.remote_clients,
        args.remote_queries, args.remote_source_pool)

    src, dst = rmat_edges(args.scale, edge_factor=args.edge_factor, seed=0)
    g = Graph.from_edges(src, dst)
    g.plan()   # shared plan build paid once, like a workspace-resident graph
    rounds = build_workload(args.sessions, args.rounds, args.source_pool)

    modes = {
        "sequential": dict(fuse=False, cache=False),
        "fused": dict(fuse=True, cache=False),
        "fused_cached": dict(fuse=True, cache=True),
    }
    results = {"device": jax.default_backend(), "scale": args.scale,
               "n_nodes": g.n_nodes, "n_edges": g.n_edges,
               "sessions": args.sessions, "rounds": args.rounds,
               "source_pool": args.source_pool, "modes": {}}
    for name, kw in modes.items():
        r = run_mode(g, rounds, args.sessions, **kw)
        results["modes"][name] = r
        print(f"{name:13s} {r['n_queries']:4d} queries  {r['qps']:8.1f} qps"
              f"  p50={r['p50_ms']:8.2f}ms  p99={r['p99_ms']:8.2f}ms"
              f"  (hits={r['stats']['cache_hits']}, "
              f"fused={r['stats']['fused_requests']})")

    results["obs_overhead"] = run_obs_overhead(g, rounds, args.sessions,
                                               reps=args.obs_reps)

    base = results["modes"]["sequential"]["qps"]
    results["speedup_fused"] = round(results["modes"]["fused"]["qps"] / base, 2)
    results["speedup_fused_cached"] = round(
        results["modes"]["fused_cached"]["qps"] / base, 2)
    print(f"speedup: fused {results['speedup_fused']}x, "
          f"fused+cached {results['speedup_fused_cached']}x vs sequential")

    if not args.skip_overload:
        results["overload"] = run_overload(
            args.overload_scale, args.edge_factor, args.overload_sessions,
            args.overload_queries, args.flood_quota)

    if remote is not None:
        results["remote"] = remote

    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
