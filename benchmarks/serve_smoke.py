"""CI smoke for cross-process serving: spawn a real server subprocess on an
ephemeral port, run a scripted client workload over the wire, assert a clean
drain-and-exit.

This is the fast-tier guard for the serving stack: it proves the subprocess
entry point (``python -m repro.serve.server``), the binary protocol, typed
admission errors, provenance adoption and graceful shutdown all work across
a genuine process boundary — in seconds, on a tiny graph.

Run:  PYTHONPATH=src python benchmarks/serve_smoke.py
"""

import sys
import time

import numpy as np


def main() -> int:
    t_start = time.perf_counter()
    from repro import compile_cache
    from repro.serve.client import pin_host_only
    compile_cache.enable()
    # this process only generates load: the spawned server owns the device
    pin_host_only()
    from repro.core import provenance as prov
    from repro.core.table import INT, Table
    from repro.serve.client import RemoteService
    from repro.serve.policy import ServiceError
    from repro.serve.server import spawn_server

    proc, port = spawn_server(
        ("--workers", "2", "--rmat-scale", "8", "--edge-factor", "4"))
    print(f"smoke: server pid={proc.pid} port={port}")
    try:
        client = RemoteService(port=port, timeout=300.0)
        assert client.server_pid == proc.pid, "handshake pid mismatch"
        sess = client.session("smoke")

        # workspace round trip
        t = Table.from_columns({"x": INT}, {"x": [5, 1, 3]})
        client.workspace.put("t", t)
        assert client.workspace.get("t").to_pydict() == t.to_pydict()

        # a burst of traversals: fusion + out-of-order streaming exercised
        pendings = [sess.submit({"op": "bfs", "graph": "g",
                                 "params": {"source": s}})
                    for s in range(6)]
        dists = [np.asarray(p.result(timeout=300)) for p in pendings]
        assert all(d.shape == dists[0].shape for d in dists)

        # result cache: the repeat is served without a new engine call
        again = sess.submit({"op": "bfs", "graph": "g",
                             "params": {"source": 0}})
        np.testing.assert_array_equal(np.asarray(again.result(300)),
                                      dists[0])
        assert again.cached, "repeat query missed the result cache"

        # provenance crossed the wire: the remote result exports locally
        pr = sess.execute({"op": "pagerank", "graph": "g",
                           "params": {"n_iter": 5}, "as": "pr"})
        assert [r.op for r in prov.records_of(pr)] == ["algorithms.pagerank"]

        # typed errors: an unknown op is a ServiceError at the call site
        try:
            sess.submit({"op": "frobnicate", "graph": "g"})
        except ServiceError:
            pass
        else:
            raise AssertionError("unknown op did not raise ServiceError")

        stats = client.stats
        assert stats["requests"] >= 8
        print(f"smoke: {stats['requests']} requests, "
              f"{stats['cache_hits']} cache hits, "
              f"{stats['fused_requests']} fused")

        # observability over the wire: the server's metrics snapshot agrees
        # with the legacy stats counters, and the client can pull a Chrome
        # trace filtered to its own requests
        metrics = client.metrics()
        assert metrics["service.requests"]["value"] == stats["requests"]
        assert metrics["sched.engine_ms"]["count"] >= 1
        assert "# TYPE repro_service_requests counter" in client.metrics_text()
        doc = client.chrome_trace(trace=again.trace)
        names = {e["name"] for e in doc["traceEvents"]}
        assert "rpc.submit" in names and "service.submit" in names, names
        print(f"smoke: obs snapshot {len(metrics)} series, "
              f"{len(doc['traceEvents'])} trace events for cached repeat")

        client.shutdown_server()
        client.close()
    except BaseException:
        proc.kill()
        raise
    rc = proc.wait(timeout=120)
    assert rc == 0, f"server exited rc={rc} (expected clean drain)"
    print(f"serve smoke OK ({time.perf_counter() - t_start:.1f}s: "
          f"subprocess server, wire workload, clean shutdown)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
