"""Benchmark driver: one function per paper table + kernel validation +
roofline summary.  Prints ``name,us_per_call,derived`` CSV.

Usage: PYTHONPATH=src python -m benchmarks.run [--skip-roofline]
"""

from __future__ import annotations

import argparse


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-roofline", action="store_true")
    args = ap.parse_args(argv)

    from repro import compile_cache
    compile_cache.enable()
    from . import paper_tables
    rows = paper_tables.run_all()
    print("name,us_per_call,derived")
    for name, us, extra in rows:
        print(f"{name},{us:.1f},{extra}")

    # kernel sanity at benchmark scale (interpret mode on CPU)
    import numpy as np
    from repro.core.graph import Graph
    from repro.core import algorithms as A
    from repro.kernels import ops
    from repro.data.rmat import rmat_edges
    s, d = rmat_edges(scale=9, edge_factor=8, seed=3)
    keep = s != d
    g = Graph.from_edges(s[keep], d[keep], dedupe=True)
    pr_k = np.asarray(ops.pagerank_bsr(g, n_iter=3))
    pr_r = np.asarray(A.pagerank(g, n_iter=3))
    print(f"kernel.bsr_spmv_allclose,0,max_err={np.abs(pr_k-pr_r).max():.2e}")
    u = g.to_undirected()
    print(f"kernel.bsr_tricount_match,0,"
          f"{ops.triangle_count_bsr(u)}=={A.triangle_count(u)}")

    if not args.skip_roofline:
        # roofline summary from the dry-run cells (if present)
        try:
            from .roofline import load
            rl = load("baseline", "single")
            for r in rl:
                print(f"roofline.{r['arch']}.{r['shape']},0,"
                      f"dominant={r['dominant']} "
                      f"compute_ms={r['compute_s']*1e3:.1f} "
                      f"memory_ms={r['memory_s']*1e3:.1f} "
                      f"collective_ms={r['collective_s']*1e3:.1f}")
        except Exception as e:  # dry-run results absent: not an error here
            print(f"roofline.unavailable,0,{e!r}")


if __name__ == "__main__":
    main()
