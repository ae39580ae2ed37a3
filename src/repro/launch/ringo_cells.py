"""Dry-run cells for the paper's own workload: distributed PageRank at
Twitter2010/LiveJournal scale on the production mesh.

The graph engine treats the pod as one big-memory machine: edges live with
their destination owner across all 256 (or 512) chips — the mesh axes are
flattened into one logical "graph" axis via a (pod·data·model)-wide
PartitionSpec, matching `core/distributed.py` semantics.
"""

from __future__ import annotations

import functools
import time
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import make_production_mesh

GRAPHS = {
    # paper Table 2
    "pagerank_twitter": dict(n_nodes=41_700_000, n_edges=1_470_000_000),
    "pagerank_livejournal": dict(n_nodes=4_850_000, n_edges=69_000_000),
    # §Perf variants: 2D SUMMA partition (Θ(N/d) collectives) ± bf16 wire
    "pagerank_twitter_2d": dict(n_nodes=41_700_000, n_edges=1_470_000_000,
                                partition="2d"),
    "pagerank_twitter_2d_bf16": dict(n_nodes=41_700_000,
                                     n_edges=1_470_000_000,
                                     partition="2d", compress=True),
    "pagerank_twitter_bf16": dict(n_nodes=41_700_000, n_edges=1_470_000_000,
                                  compress=True),
}


def pagerank_step_fn(mesh, axes, n_nodes: int, ns: int, es: int,
                     damping: float = 0.85, compress_bf16: bool = False):
    """One distributed PageRank iteration over dst-partitioned edge shards."""

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axes), P(axes), P(axes), P(axes), P(axes)),
        out_specs=P(axes))
    def step(src, dst_local, evalid, inv_deg_shard, pr_shard):
        inv_full = jax.lax.all_gather(inv_deg_shard, axes, tiled=True)
        if compress_bf16:
            msg = jax.lax.optimization_barrier(pr_shard.astype(jnp.bfloat16))
        else:
            msg = pr_shard
        pr_full = jax.lax.all_gather(msg, axes, tiled=True
                                     ).astype(jnp.float32)
        contrib = jnp.where(evalid, pr_full[src] * inv_full[src], 0.0)
        local = jax.ops.segment_sum(contrib, dst_local, num_segments=ns,
                                    indices_are_sorted=True)
        dang = jax.lax.psum(
            jnp.sum(jnp.where(inv_deg_shard == 0.0, pr_shard, 0.0)), axes)
        return (1.0 - damping) / n_nodes + damping * (local + dang / n_nodes)

    return step


def run_ringo_cell(shape_name: str, multi_pod: bool) -> Dict:
    if shape_name not in GRAPHS:
        return {"arch": "ringo-graph", "shape": shape_name,
                "multi_pod": multi_pod, "status": "skipped",
                "reason": f"graph cells are {sorted(GRAPHS)}"}
    g = GRAPHS[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    d = mesh.devices.size

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(spec)))

    t0 = time.time()
    if g.get("partition") == "2d":
        if multi_pod:
            return {"arch": "ringo-graph", "shape": shape_name,
                    "multi_pod": multi_pod, "status": "skipped",
                    "reason": "2D partition defined on the square "
                              "single-pod grid; pods run independent rows"}
        from ..core.distributed import DistGraph2D, pagerank_distributed_2d
        side = mesh.shape["data"]
        nb = -(-g["n_nodes"] // side)
        es = -(-g["n_edges"] // d)
        grid = ("data", "model")
        dg = DistGraph2D(
            n_nodes=g["n_nodes"], n_edges=g["n_edges"], nb=nb, es=es,
            d=side,
            src_local=sds((d * es,), jnp.int32, grid),
            dst_local=sds((d * es,), jnp.int32, grid),
            evalid=sds((d * es,), jnp.bool_, grid),
            inv_deg_col=sds((side * nb,), jnp.float32, "model"),
        )
        fn = lambda dgx: pagerank_distributed_2d(
            dgx, mesh, n_iter=1, compress_bf16=bool(g.get("compress")),
            unshuffle=False)
        with mesh:
            lowered = jax.jit(fn).lower(dg)
            compiled = lowered.compile()
    else:
        axes = tuple(mesh.axis_names)
        ns = -(-g["n_nodes"] // d)
        es = -(-g["n_edges"] // d)
        args = (
            sds((d * es,), jnp.int32, axes),    # src (global ids)
            sds((d * es,), jnp.int32, axes),    # dst_local
            sds((d * es,), jnp.bool_, axes),    # edge valid
            sds((d * ns,), jnp.float32, axes),  # 1/out_degree
            sds((d * ns,), jnp.float32, axes),  # pagerank shard
        )
        fn = pagerank_step_fn(mesh, axes, g["n_nodes"], ns, es,
                              compress_bf16=bool(g.get("compress")))
        with mesh:
            lowered = jax.jit(fn).lower(*args)
            compiled = lowered.compile()
    t1 = time.time()
    from .hlo_cost import analyze_hlo
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    corrected = analyze_hlo(compiled.as_text())
    return {
        "arch": "ringo-graph", "shape": shape_name, "kind": "graph",
        "multi_pod": multi_pod, "status": "ok",
        "n_chips": int(d), "compile_s": round(t1 - t0, 1),
        "xla_flops_per_device": float(cost.get("flops", 0.0)),
        "flops_per_device": corrected.flops or float(cost.get("flops", 0.0)),
        "bytes_per_device": corrected.bytes,
        "collective_bytes_per_device": corrected.collective_bytes,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0) or
            (getattr(mem, "argument_size_in_bytes", 0)
             + getattr(mem, "temp_size_in_bytes", 0)),
        },
        "graph": g,
    }
