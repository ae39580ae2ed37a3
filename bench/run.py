#!/usr/bin/env python3
"""Benchmark of the served graph path on the chip: one cell per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: its entry in ``BENCHMARK.json``,
its configuration ``bench/configs/<config>.json`` and the configuration's
generator ``bench/generators/<generator>.py``, its traffic mix
``bench/traffic/<traffic>.json`` (read by ``loadgen.py``) and the mix's
loop ``bench/loops/<loop>.py``, one reader per metric
``bench/metrics/<metric>.py``, and the reference of its op
``bench/reference/<op>.py``.

One process holds the chip.  It makes the graph from ``--seed``, serves it
with a ``GraphServer`` on a loopback port in front of a ``GraphService``,
publishes it, warms up every request shape the window will send (set-up
ends there), and then drives the mix's sessions through a ``RemoteService``
client for ``--seconds``.  Every
request sent inside the window is completed and counted, and the window ends
at the last completion.  With ``--trace 1`` the profiler records the window
and the per-layer metrics are printed; with ``--trace 0`` the end-to-end
ones.  Once the window has closed and the server is shut down, every answer
the client received is compared with the NumPy reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared with its limit.
Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits non-zero.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import defaultdict  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

# generous per-request limit: a reply that comes late is late, not wrong
REQUEST_TIMEOUT_S = 600.0
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - _T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def load_json(*parts: str):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _by_name(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell_metrics(spec: dict, cell: str, trace: bool) -> List[dict]:
    """The metric entries a run of ``cell`` reports: end-to-end without the
    trace, per-layer with it."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if ("workloads" in m and cell in m["workloads"])
            or ("workloads" not in m and m["moves"] in moved)]


def load_reader(name: str):
    import byname
    return byname.module("metrics", name)


class CompileCounter:
    """Counts JAX's backend compile events while ``active`` (a compile that
    misses the in-memory cache, whether XLA compiles or the persistent cache
    supplies the executable), and sums the seconds of every JAX duration
    event (tracing, lowering, compiling) by name, for the set-up log.  One
    listener per process."""

    _instance: Optional["CompileCounter"] = None

    def __init__(self):
        self.active = False
        self.count = 0
        self.seconds: Dict[str, float] = defaultdict(float)

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            import jax
            cls._instance = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._instance._on_event)
        return cls._instance

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        self.seconds[event] += duration
        if self.active and event == BACKEND_COMPILE_EVENT:
            self.count += 1

    def take_seconds(self) -> Dict[str, float]:
        """The summed durations since the last call, and a fresh start."""
        out = {k.rsplit("/", 1)[-1]: round(v, 3)
               for k, v in sorted(self.seconds.items())}
        self.seconds.clear()
        return out


class Context:
    """What the metric readers read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def counter_delta(self, name: str) -> Optional[float]:
        a, b = self.after.get(name), self.before.get(name, {"value": 0})
        if a is None:
            return None
        return float(a["value"]) - float(b["value"])


def _device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    peak = None
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peak = max(peak or 0, int(stats["peak_bytes_in_use"]))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run_cell(spec: dict, workload: str, seed: int, seconds: float,
             trace: bool, config: Optional[dict] = None,
             trace_dir: Optional[str] = None) -> dict:
    """Set up, measure and check one run of a cell; returns the result.

    ``config`` replaces the configuration file (tests pass a small graph).
    """
    import jax
    from repro.core.graph import Graph
    from repro.serve.client import RemoteService
    from repro.serve.graph_service import GraphService
    from repro.serve.policy import SchedulerPolicy
    from repro.serve.server import GraphServer

    import byname
    import loadgen
    import peaks

    cell = _by_name(spec["workloads"], workload, "workload")
    cfg_entry = _by_name(spec["configs"], cell["config"], "config")
    cfg = config or load_json(ROOT, cfg_entry["file"])
    mix = load_json(BENCH, "traffic", f"{cell['traffic']}.json")
    ref = importlib.import_module(f"reference.{mix['op']}")
    wanted = cell_metrics(spec, workload, trace)
    phases: Dict[str, float] = {"start": time.perf_counter() - _T0}
    counter = CompileCounter.get()

    t = time.perf_counter()
    graph = byname.module("generators", cfg["generator"]).generate(cfg, seed)
    n = graph.n
    phases["generate"] = time.perf_counter() - t
    log(f"generated {cfg['name']}: {n} vertices, {graph.src.size} edges "
        f"({phases['generate']:.3f} s)")

    svc = mix["service"]
    service = GraphService(policy=SchedulerPolicy(mode=svc["mode"]),
                           workers=int(svc["workers"]),
                           cache=bool(svc["cache"]))
    server = GraphServer(service).start()
    client = RemoteService(port=server.port, timeout=REQUEST_TIMEOUT_S)
    tmp = None
    try:
        t = time.perf_counter()
        g = Graph.from_dense_edges(graph.src.copy(), graph.dst.copy(), n)
        g.plan()
        service.workspace.put(cfg["name"], g)
        del g
        phases["publish"] = time.perf_counter() - t
        log("jax time until published (s): "
            + json.dumps(counter.take_seconds()))

        t = time.perf_counter()
        lists = loadgen.session_lists(mix, graph)
        loop = loadgen.loop(client, mix, cfg["name"], lists)
        warm = loop.warm_up()
        phases["warmup"] = time.perf_counter() - t
        bad = [r for r in warm if not r.ok]
        if bad:
            raise RuntimeError(f"warm-up request failed: {bad[0].params}")
        setup_s = time.perf_counter() - _T0
        log("warm-up requests (s): " + json.dumps(
            [round(r.end - r.start, 3) for r in warm]))
        log("jax time in warm-up (s): " + json.dumps(counter.take_seconds()))
        log("set-up phases (s): " + json.dumps(phases))

        before = client.metrics()
        if trace:
            tmp = trace_dir or tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tmp, profiler_options=opts)
        counter.count, counter.active = 0, True
        with jax.profiler.TraceAnnotation("bench.window"):
            start, records = loop.window(seconds)
        counter.active = False
        if trace:
            jax.profiler.stop_trace()
        after = client.metrics()
        device = _device_info(int(cell["chips"]))
    finally:
        client.close()
        server.shutdown()
    del service, server, client, loop
    gc.collect()

    window_s = max((r.end for r in records), default=start) - start
    log(f"window: {len(records)} requests in {window_s:.3f} s, "
        f"{counter.count} compiles")
    reduced = None
    if trace:
        from trace_reduce import reduce
        files = [os.path.join(d, f) for d, _, fs in os.walk(tmp)
                 for f in fs if f.endswith(".xplane.pb")]
        reduced = reduce(files[0])
        if trace_dir is None:
            shutil.rmtree(tmp, ignore_errors=True)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]

    ctx = Context(records=records, window_s=window_s, setup_s=setup_s,
                  before=before, after=after,
                  compiles_in_window=counter.count, trace=reduced,
                  graph=graph, peaks=peaks.lookup(device["kind"])
                  if device["platform"] == "tpu" else None)
    metrics = {}
    for m in wanted:
        v = load_reader(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    t = time.perf_counter()
    answers = [(r.params, r.answer) for r in records if r.ok]
    numbers = ref.check(graph, answers)
    log(f"reference check: {time.perf_counter() - t:.3f} s")
    failed = sum(not r.ok for r in records)
    checks = {k: {"value": float(v), "limit": float(ref.LIMITS[k])}
              for k, v in numbers.items()}
    correct = bool(answers) and failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())

    result = {"correct": correct, "attempted": len(records),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory, removed after reading)")
    args = ap.parse_args(argv)

    spec = load_json(ROOT, "BENCHMARK.json")
    cell = _by_name(spec["workloads"], args.workload, "workload")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < int(cell["chips"]):
        print(f"bench: the cell needs {cell['chips']} TPU chip(s); JAX sees "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"bench: no program under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro import compile_cache
    log(f"device {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{compile_cache.enable()}")
    result = run_cell(spec, args.workload, args.seed, args.seconds,
                      bool(args.trace), trace_dir=args.trace_dir)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
