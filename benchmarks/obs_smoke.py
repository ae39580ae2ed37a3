"""CI smoke for the observability subsystem: run a traced in-process
workload through the full service stack, then validate every export
surface — the Chrome trace-event JSON schema, the metrics snapshot, and
(PR 10) the judgment layer: SLO health/report schemas, the engine profile
report, and the flight-recorder debug bundle, both in-process and over a
real socket.

This is the fast-tier guard for ``repro.obs``: if an instrumentation hook
regresses (spans stop nesting, the exporter emits malformed events, a
counter family disappears, a bundle stops JSON-round-tripping), this fails
in seconds on a tiny graph long before the overhead bench or a human
looking at chrome://tracing would.

Run:  PYTHONPATH=src python benchmarks/obs_smoke.py
"""

import json
import sys
import tempfile
import time


def main() -> int:
    t_start = time.perf_counter()
    import numpy as np

    from repro import compile_cache
    compile_cache.enable()

    from repro import obs
    from repro.core import algorithms as A
    from repro.core.graph import Graph
    from repro.serve.graph_service import GraphService, Workspace

    obs.reset()
    # a deliberately-unmeetable objective on bfs: every bfs completion is
    # "slow", so the flight recorder is guaranteed to capture exemplars
    obs.SLO.set_objective("bfs", latency_ms=0.0)

    rng = np.random.default_rng(7)
    n, m = 512, 2048
    g = Graph.from_edges(rng.integers(0, n, m).astype(np.int32),
                         rng.integers(0, n, m).astype(np.int32))

    # traced service workload: traversal burst + cached repeat + pagerank
    ws = Workspace()
    ws.put("g", g)
    svc = GraphService(ws, workers=2)
    try:
        sess = svc.session("obs-smoke")
        trace = obs.new_trace_id()
        pend = [svc.submit(sess, {"op": "bfs", "graph": "g",
                                  "params": {"source": s}}, trace=trace)
                for s in range(4)]
        svc.flush()
        for p in pend:
            p.result(timeout=120)
        repeat = svc.submit(sess, {"op": "bfs", "graph": "g",
                                   "params": {"source": 0}}, trace=trace)
        repeat.result(timeout=120)
        assert repeat.cached, "repeat query missed the result cache"
        svc.execute(sess, {"op": "pagerank", "graph": "g",
                           "params": {"n_iter": 5}})
    finally:
        svc.close()

    # the frontier engine emits per-round spans with frontier sizes
    with obs.span("smoke.frontier", trace=trace):
        A.bfs(g, 0, backend="frontier")

    # --- Chrome trace export: validate the trace-event schema -------------
    with tempfile.NamedTemporaryFile("r", suffix=".json") as f:
        doc = obs.export_chrome_trace(f.name, trace=trace)
        assert json.load(open(f.name)) == doc, "on-disk trace != export"
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and evs, "empty trace"
    for e in evs:
        assert e["ph"] in ("X", "i", "M"), e
        assert isinstance(e["name"], str) and isinstance(e["pid"], int)
        if e["ph"] in ("X", "i"):
            assert isinstance(e["ts"], float) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert e["dur"] >= 0
    names = {e["name"] for e in evs}
    for want in ("service.submit", "sched.queued", "sched.execute",
                 "engine.bfs", "service.cache_hit_submit",
                 "engine.frontier_fixpoint", "engine.frontier.sync"):
        assert want in names, f"span {want!r} missing from trace: {names}"
    rounds = [e for e in evs if e["name"] in ("engine.frontier.push",
                                              "engine.frontier.pull")]
    assert rounds, f"no frontier round spans in trace: {names}"
    assert all("frontier" in e["args"] for e in rounds)

    # --- metrics snapshot: non-empty, and the core families are present ---
    snap = obs.dump_metrics()
    assert snap, "metrics snapshot is empty"
    assert snap["service.requests"]["value"] >= 5
    assert snap["service.cache_hits"]["value"] >= 1
    assert snap["sched.engine_ms"]["count"] >= 1
    assert snap["engine.frontier.rounds"]["value"] >= 1
    assert "# TYPE repro_service_requests counter" in obs.dump_metrics("prom")

    # --- judgment layer: SLO health / report schemas ----------------------
    health = obs.health()
    assert health["status"] in ("ok", "degraded", "breaching"), health
    assert health["ops"]["bfs"]["slow"] >= 1, health["ops"]
    assert health["ops"]["bfs"]["status"] == "breaching"
    assert isinstance(health["reasons"], list) and health["reasons"]
    assert health["combined"]["status"] in ("ok", "degraded", "breaching")
    report = obs.slo_report()
    for key in ("ops", "objectives", "default_objective", "thresholds",
                "service", "window_s"):
        assert key in report, f"slo_report missing {key!r}"
    assert report["ops"]["bfs"]["n"] >= 5
    assert report["ops"]["bfs"]["burn_rate"] > 0

    # --- engine profiler: compiles, frontier phases + report --------------
    prof_series = [k for k in snap if k.startswith("engine.profile.")]
    assert prof_series, "engine profiler recorded nothing"
    assert snap["engine.compiles"]["value"] >= 1, "no compile counted"
    prep = obs.profile_report()
    assert prep.startswith("engine profile"), prep
    assert "frontier" in prep

    # --- flight recorder: exemplars + bundle round trip -------------------
    exs = obs.FLIGHT.exemplars("bfs")
    assert exs and exs[-1]["slow"] and exs[-1]["spans"], \
        "forced-slow bfs must leave an exemplar with span evidence"
    with tempfile.NamedTemporaryFile("r", suffix=".json") as f:
        bundle = obs.debug_bundle(f.name)
        assert json.load(open(f.name)) == bundle, "bundle != on-disk JSON"
    assert bundle["kind"] == "repro-debug-bundle" and bundle["version"] == 1
    for key in ("health", "slo", "metrics", "profile", "trace", "tracer",
                "flight", "exemplars", "log_tail", "config", "versions"):
        assert key in bundle, f"bundle missing {key!r}"
    assert bundle["exemplars"]["bfs"]
    from repro.obs.report import render_bundle
    assert "flight recorder" in render_bundle(bundle)

    # --- the same three surfaces over a real socket -----------------------
    from repro.serve.client import RemoteService
    from repro.serve.server import GraphServer
    ws2 = Workspace()
    ws2.put("g", g)
    server = GraphServer(GraphService(ws2, workers=0)).start()
    client = RemoteService(port=server.port, timeout=120.0)
    try:
        rs = client.session("obs-smoke-wire")
        rp = rs.submit({"op": "bfs", "graph": "g", "params": {"source": 1}})
        client.flush()
        rp.result(120)
        wh = client.health()
        assert wh["status"] in ("ok", "degraded", "breaching")
        assert client.slo_report()["ops"]["bfs"]["n"] >= 1
        wb = client.debug_bundle(trace=rp.trace)
        assert wb["kind"] == "repro-debug-bundle"
        assert wb["exemplars"]["bfs"][-1]["spans"], \
            "wire bundle lost exemplar span evidence"
        assert client.profile_report().startswith("engine profile")
    finally:
        client.close()
        server.shutdown()
    obs.reset()

    print(f"obs smoke OK ({time.perf_counter() - t_start:.1f}s: "
          f"{len(evs)} trace events, {len(snap)} metric series, "
          f"{len(bundle['exemplars'])} exemplar op(s))")
    return 0


if __name__ == "__main__":
    sys.exit(main())
