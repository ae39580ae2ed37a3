#!/usr/bin/env python3
"""Bring-up smoke of the served graph path on a TPU.

One process runs a ``GraphServer`` on a loopback port in front of a
``GraphService`` (the set-up ``python -m repro.serve.server`` builds), publishes
RMAT graphs generated from ``--seed``, and drives them through a
``RemoteService`` client over the socket:

* scale 22, edge factor 16 (4.19 M vertices, 67.1 M generated edges: the
  size of LiveJournal in Table 2 of the Ringo paper) — PageRank, one
  single-source BFS, a burst of BFS and personalized PageRank from several
  sources, connected components;
* scale 14, edge factor 16, symmetrized — PageRank and
  ``triangle_count(backend="bsr")``.

Every answer must be a result, not an error frame.  Each is checked against
the ``"xla"`` backend on the same chip: exactly for BFS, connected components
and triangles, and within ``PR_RTOL`` / ``PR_ATOL_FRAC`` for PageRank and
PPR.  BFS is also checked against a NumPy BFS over the host CSR.  The
service's result cache is off so that repeated requests reach the engine.

``--chips 4`` instead serves the scale-22 graph with
``GraphService(engine_backend="sharded")`` over all four chips: sharded
PageRank, BFS and connected components against single-device ``"xla"``, and
a check that the per-shard buffers are placed on every device.

Earlier lines report, per phase, the backend the engine chose (from the
``engine.backend.*`` counters), the graph size, cold and warm wall time and
the largest difference from the reference; they are information only.  The
last line is ``{"ok": true, "device": {...}}``.  Without a TPU the script
exits non-zero before doing any work.

Run from the root of a checkout:  python chip_smoke.py [--chips 4]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

BIG_SCALE, SMALL_SCALE, EDGE_FACTOR = 22, 14, 16
BURST_SOURCES = 4
# PageRank / PPR: |got - want| <= PR_RTOL * |want| + PR_ATOL_FRAC * max|want|.
# Both sides sum positive f32 terms in different orders; relative error of
# such sums stays far below 1e-4 at these in-degrees.
PR_RTOL = 1e-4
PR_ATOL_FRAC = 1e-7
SRC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")


_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


def pr_close(got, want) -> float:
    """Largest scaled PageRank error; raises when out of tolerance."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        raise AssertionError(f"bad PageRank result: shape {got.shape} vs "
                             f"{want.shape}, finite={np.isfinite(got).all()}")
    err = np.abs(got - want)
    bound = PR_RTOL * np.abs(want) + PR_ATOL_FRAC * np.abs(want).max()
    if np.any(err > bound):
        i = int(np.argmax(err - bound))
        raise AssertionError(f"PageRank off at vertex {i}: {got[i]!r} vs "
                             f"{want[i]!r} (bound {bound[i]!r})")
    return float(err.max())


def exact(got, want) -> float:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = int(np.sum(got != want)) if got.shape == want.shape else -1
        raise AssertionError(f"exact result mismatch ({bad} entries differ, "
                             f"shapes {got.shape} vs {want.shape})")
    return 0.0


def bfs_reference(ptr: np.ndarray, idx: np.ndarray, n: int,
                  source: int) -> np.ndarray:
    """Level-synchronous BFS over a host CSR; -1 for unreachable."""
    level = np.full(n, -1, np.int32)
    level[source] = 0
    frontier = np.array([source], np.int64)
    depth = 0
    while frontier.size:
        starts, ends = ptr[frontier], ptr[frontier + 1]
        lens = ends - starts
        pos = np.repeat(starts - np.cumsum(lens) + lens, lens) \
            + np.arange(int(lens.sum()))
        seen = np.zeros(n, bool)
        seen[idx[pos]] = True
        nbrs = np.flatnonzero(seen & (level < 0))
        depth += 1
        level[nbrs] = depth
        frontier = nbrs
    return level


class Requester:
    """A client session plus the counters the phase lines report."""

    def __init__(self, client):
        self.client = client
        self.sess = client.session("smoke")

    def backends(self) -> dict:
        m = self.client.metrics()
        return {k.split(".", 2)[2]: v["value"] for k, v in m.items()
                if k.startswith("engine.backend.")}

    def ask(self, op: str, graph: str, **params):
        """One request over the wire; an error frame raises here."""
        return self.sess.execute({"op": op, "graph": graph, "params": params})

    def timed(self, op: str, graph: str, **params):
        """(result, backends chosen, cold s, warm s) of two identical calls."""
        before = self.backends()
        t0 = time.perf_counter()
        out = self.ask(op, graph, **params)
        cold = time.perf_counter() - t0
        after = self.backends()
        chosen = {b: after[b] - before.get(b, 0) for b in after
                  if after[b] != before.get(b, 0)}
        t0 = time.perf_counter()
        again = self.ask(op, graph, **params)
        warm = time.perf_counter() - t0
        exact(np.asarray(again), np.asarray(out))      # deterministic repeat
        return out, chosen, cold, warm


def report(phase: str, g, chosen, cold, warm, diff, **extra) -> None:
    mem = _device_memory()
    fields = dict(phase=phase, nodes=g.n_nodes, edges=g.n_edges,
                  backend=chosen, cold_s=round(cold, 3),
                  warm_s=None if warm is None else round(warm, 3),
                  max_diff=diff, **extra, **mem)
    log("phase " + json.dumps(fields))


def _device_memory() -> dict:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use")
            if k in stats}


def single_chip_phases(drv: Requester, service, seed: int, big_scale: int,
                       small_scale: int, edge_factor: int) -> None:
    from repro.core.graph import Graph
    from repro.data.rmat import rmat_edges
    from repro.serve.server import publish_rmat

    # triangles are defined on the undirected simple graph
    small = Graph.from_edges(*rmat_edges(small_scale, edge_factor=edge_factor,
                                         seed=seed + 1)).to_undirected()
    small.plan()
    service.workspace.put("small", small)
    log(f"setup: published small ({small.n_nodes} nodes, {small.n_edges} "
        f"edges)")

    # -- small graph: BSR PageRank and BSR triangles -------------------------
    pr, chosen, cold, warm = drv.timed("pagerank", "small", n_iter=10)
    ref = drv.ask("pagerank", "small", n_iter=10, backend="xla")
    report("pagerank", small, chosen, cold, warm, pr_close(pr, ref),
           sum=float(np.sum(np.asarray(pr, np.float64))))
    tri, chosen, cold, warm = drv.timed("triangle_count", "small",
                                        backend="bsr")
    ref = drv.ask("triangle_count", "small")
    report("triangle_count", small, chosen, cold, warm, exact(tri, ref),
           triangles=int(tri))
    # its dense tiles are ~2 GB of device memory the big graph needs more
    small.plan().evict_all()

    # -- big graph ----------------------------------------------------------
    big = publish_rmat(service, "big", big_scale, edge_factor, seed)
    log(f"setup: published big ({big.n_nodes} nodes, {big.n_edges} edges)")
    pr, chosen, cold, warm = drv.timed("pagerank", "big", n_iter=10)
    ref = drv.ask("pagerank", "big", n_iter=10, backend="xla")
    report("pagerank", big, chosen, cold, warm, pr_close(pr, ref),
           sum=float(np.sum(np.asarray(pr, np.float64))))

    n = big.n_nodes
    ptr = np.asarray(big.out_ptr)[: n + 1].astype(np.int64)
    idx = np.asarray(big.out_idx)
    deg = np.diff(ptr)
    source = int(np.argmax(deg))
    lev, chosen, cold, warm = drv.timed("bfs", "big", source=source)
    ref = drv.ask("bfs", "big", source=source, backend="xla")
    exact(lev, bfs_reference(ptr, idx, n, source))
    report("bfs", big, chosen, cold, warm, exact(lev, ref), source=source,
           reached=int(np.sum(np.asarray(lev) >= 0)),
           depth=int(np.max(np.asarray(lev))))

    rng = np.random.default_rng(seed)
    sources = [int(s) for s in rng.choice(np.flatnonzero(deg), BURST_SOURCES,
                                          replace=False)]
    got, chosen, cold, fused = burst(drv, sources)
    want, _, _, _ = burst(drv, sources, backend="xla")
    diff = 0.0
    for s, gb, wb in zip(sources, got["bfs"], want["bfs"]):
        exact(gb, wb)
        exact(gb, bfs_reference(ptr, idx, n, s))
    for gp, wp in zip(got["personalized_pagerank"],
                      want["personalized_pagerank"]):
        diff = max(diff, pr_close(gp, wp))
    report("burst_bfs_ppr", big, chosen, cold, None, diff,
           sources=sources, fused=fused)

    cc, chosen, cold, warm = drv.timed("connected_components", "big")
    ref = drv.ask("connected_components", "big", backend="xla")
    report("connected_components", big, chosen, cold, warm, exact(cc, ref),
           components=int(np.unique(np.asarray(cc)).size))


def burst(drv: Requester, sources, **params):
    """BFS + PPR from every source, all submitted before any is awaited."""
    before = drv.backends()
    t0 = time.perf_counter()
    pend = {op: [drv.sess.submit({"op": op, "graph": "big",
                                  "params": dict(params, source=s)})
                 for s in sources]
            for op in ("bfs", "personalized_pagerank")}
    out = {op: [np.asarray(p.result(timeout=drv.client.rpc_timeout))
                for p in ps] for op, ps in pend.items()}
    wall = time.perf_counter() - t0
    after = drv.backends()
    chosen = {b: after[b] - before.get(b, 0) for b in after
              if after[b] != before.get(b, 0)}
    fused = sum(p.fused for ps in pend.values() for p in ps)
    return out, chosen, wall, fused


def four_chip_phases(drv: Requester, service, seed: int, big_scale: int,
                     edge_factor: int) -> None:
    import jax
    from repro.core import engine
    from repro.serve.server import publish_rmat

    big = publish_rmat(service, "big", big_scale, edge_factor, seed)
    log(f"setup: published big ({big.n_nodes} nodes, {big.n_edges} edges)")
    d = engine.shard_count()

    pr, chosen, cold, warm = drv.timed("pagerank", "big", n_iter=10)
    ref = drv.ask("pagerank", "big", n_iter=10, backend="xla")
    report("sharded_pagerank", big, chosen, cold, warm, pr_close(pr, ref),
           shards=d)

    # the per-shard buffers must span every device, not pile up on one
    sp = big.plan().sharded(d)
    want = set(jax.devices())
    for name, arr in (("pull.gather_idx", sp.pull.gather_idx),
                      ("push.gather_idx", sp.push.gather_idx),
                      ("out_deg", sp.out_deg)):
        placed = {s.device for s in arr.addressable_shards}
        if placed != want:
            raise AssertionError(f"sharded {name} lives on {placed}, "
                                 f"want all of {want}")
    log(f"placement: sharded plan buffers on {len(want)} devices "
        f"({sp.pull.gather_idx.sharding})")

    n = big.n_nodes
    deg = np.diff(np.asarray(big.out_ptr)[: n + 1])
    source = int(np.argmax(deg))
    lev, chosen, cold, warm = drv.timed("bfs", "big", source=source)
    ref = drv.ask("bfs", "big", source=source, backend="xla")
    report("sharded_bfs", big, chosen, cold, warm, exact(lev, ref),
           source=source, shards=d)

    cc, chosen, cold, warm = drv.timed("connected_components", "big")
    ref = drv.ask("connected_components", "big", backend="xla")
    report("sharded_connected_components", big, chosen, cold, warm,
           exact(cc, ref), shards=d)


def run(chips: int, seed: int, big_scale: int = BIG_SCALE,
        small_scale: int = SMALL_SCALE,
        edge_factor: int = EDGE_FACTOR) -> None:
    """Serve, drive and check every phase; raises on any failure."""
    from repro.serve.client import RemoteService
    from repro.serve.graph_service import GraphService
    from repro.serve.policy import SchedulerPolicy
    from repro.serve.server import GraphServer

    service = GraphService(policy=SchedulerPolicy(mode="fair"), workers=2,
                           cache=False,
                           engine_backend="sharded" if chips > 1 else None)
    server = GraphServer(service).start()
    client = RemoteService(port=server.port, timeout=1800.0)
    try:
        drv = Requester(client)
        if chips > 1:
            four_chip_phases(drv, service, seed, big_scale, edge_factor)
        else:
            single_chip_phases(drv, service, seed, big_scale, small_scale,
                               edge_factor)
    finally:
        client.close()
        server.shutdown()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = sharded phases over four chips only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {len(devices)} {platform} "
              f"device(s)); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} TPU "
              f"device(s) are visible", file=sys.stderr)
        return 1
    if not os.path.isdir(os.path.join(SRC_DIR, "repro")):
        print(f"chip_smoke: {SRC_DIR}/repro not found; run from a checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, SRC_DIR)
    from repro import compile_cache
    cache_dir = compile_cache.enable()
    log(f"device: {devices[0].device_kind} x{len(devices)}; "
        f"compile cache {cache_dir}")

    run(args.chips, args.seed)
    log("all phases passed")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
