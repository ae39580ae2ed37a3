#!/usr/bin/env python3
"""Readings of a cell's control: the reference put in the program's place in
a lower precision, or with one guarantee broken (``reference/<op>.py``,
``control``), on the cell's own graph and requests for each seed.

    python3 bench/control.py --workload <cell> --seeds 11 12 13

Prints one JSON line per seed with each number compared, beside its limit.
The benchmark's own runs never run this; it sets the upper reading from
which each limit in ``reference/`` was chosen.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import byname  # noqa: E402
import loadgen  # noqa: E402
import run  # noqa: E402


def readings(workload: str, seed: int, config=None) -> dict:
    spec = run.load_json(run.ROOT, "BENCHMARK.json")
    cell = run._by_name(spec["workloads"], workload, "workload")
    cfg = config or run.load_json(run.ROOT, run._by_name(
        spec["configs"], cell["config"], "config")["file"])
    mix = run.load_json(BENCH, "traffic", f"{cell['traffic']}.json")
    ref = importlib.import_module(f"reference.{mix['op']}")
    graph = byname.module("generators", cfg["generator"]).generate(cfg, seed)
    params = [p for lst in loadgen.session_lists(mix, graph) for p in lst]
    got = ref.control(graph, params)
    return {k: {"value": float(v), "limit": float(ref.LIMITS[k])}
            for k, v in got.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": readings(args.workload, seed)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
