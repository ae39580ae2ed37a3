"""Layer 2 of the unified traversal engine: backend-dispatched push/pull.

Architecture map (Ringo §2.2: one shared in-memory representation serving a
whole algorithm library):

    core/graph.py       Graph         static-shape dual-CSR storage
        |  .plan()  (identity-memoized; functional updates -> fresh Graph)
        v
    core/plan.py        GraphPlan     cached derived arrays: dst-/src-sorted
        |                             edges, degrees, oriented adjacency,
        |                             BSR tiles, Pallas chunk layouts
        v
    core/engine.py      Exec          gather + segment-reduce primitives
        |   push / pull / fixpoint    with *backend dispatch*:
        |   frontier_fixpoint           "xla"    jax.ops.segment_{sum,min,max}
        |                               "pallas" kernels/segment_sum one-hot
        |                                        matmul (sum reductions)
        |                               "bsr"    kernels/bsr_spmv MXU SpMV
        |                                        (fused gather+sum pulls and
        |                                        pushes via transpose tiles)
        |                               "frontier" sparse compacted-frontier
        |                                        relaxation (monotone min)
        v                               "sharded" shard_map over a 1-D device
                                                 mesh: vertex-range partition
                                                 + halo boundary exchange
    core/algorithms.py  pagerank, hits, eigenvector_centrality, CC, SCC,
                        sssp/bfs (batched multi-source), k-core, label
                        propagation, triangles — thin compositions over the
                        engine, so a backend speedup applies to all of them.

Primitives (all methods of an ``Exec`` pytree, usable inside jit):

    pull(x, combine)        per-node reduce over in-edges of x[src]
    push(x, combine)        per-node reduce over out-edges of x[dst]
    in_src_vals / in_dst_vals / out_src_vals / out_dst_vals
                            edge-order gathers (pull order / push order)
    reduce_in / reduce_out  the bare segmented reductions

``fixpoint`` drives iteration: a fixed number of rounds (``n_iter``) or
until the state stops changing.  Bodies must be module-level functions
(the jitted runner is cached per body); per-call parameters go through
``args`` so they are traced, not baked into the compile cache.

``frontier_fixpoint`` is the sparse dual of ``fixpoint`` for **monotone
min-relaxations** (BFS / SSSP / min-label propagation): instead of relaxing
every edge each round, it keeps a compacted index array of the vertices
whose value changed last round (padded to a bucketed power of two so jit
re-traces are bounded by log2 n), gathers only their adjacency slices from
the plan's CSR offsets, and scatter-mins candidates into the state.  When
the frontier's out-edge count grows past a fraction of |E| it
direction-optimizes into a dense pull over all in-edges (Beamer-style
push/pull switch), which is round-for-round identical to the sparse push
for monotone relaxations — so backend choice never changes results.

Backend/primitive support matrix (unsupported cells transparently fall back
to the XLA primitives, so backend choice never changes semantics — only
speed):

    backend    pull/push sum      min/max     weighted    batched   frontier
    "xla"      segment reduce     yes         yes         yes       —
    "pallas"   one-hot matmul     fallback    yes (f32)   fallback  —
    "bsr"      MXU SpMV           fallback    fallback    fallback  —
    "frontier" fallback (xla)     fallback    —           —         sparse
    "sharded"  shard_map reduce   yes         yes         fallback  —

The "sharded" backend partitions both CSR orders by contiguous vertex
ranges over a 1-D device mesh (``plan.sharded(d)``): each device owns
``ceil(n/d)`` vertices, the whole in-segment of every owned destination
(pull) and out-segment of every owned source (push), plus halo index sets
for the cut edges.  Each round is one ``shard_map``: gather each shard's
exported boundary values, ``all_gather`` them into a halo, reduce locally.
Because a vertex's entire edge segment stays on its owner in global order,
the shard-local segment reduction is **bit-identical** to the global one —
backend neutrality holds exactly, not just approximately.

``select_backend(plan, backend, op=...)`` resolves op/backend combinations:
ops outside a backend's support set (``_FRONTIER_OPS`` for "frontier")
resolve to "xla" instead of failing.
"""

from __future__ import annotations

import functools
import os
import threading
from dataclasses import dataclass, field
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from .. import obs
from ..kernels.bsr_spmv import bsr_spmv
from ..kernels.ops import auto_interpret
from ..kernels.segment_sum import (DEFAULT_BLOCK, DEFAULT_CHUNK,
                                   chunk_values, segment_sum_chunked)
from .graph import building
from .table import next_capacity

__all__ = ["BACKENDS", "select_backend", "get_exec", "push", "pull",
           "fixpoint", "frontier_fixpoint", "XlaExec", "PallasExec",
           "BsrExec", "FrontierExec", "ShardedExec"]

BACKENDS = ("xla", "pallas", "bsr", "frontier", "sharded")

# -- observability instruments (module-cached: no registry lookup on the hot
# path; all of them no-op on one attribute check when obs is disabled) -------
_C_BACKEND = {b: obs.counter(f"engine.backend.{b}") for b in BACKENDS}
_C_EXEC_HIT = obs.counter("engine.exec_cache.hits")
_C_EXEC_MISS = obs.counter("engine.exec_cache.misses")
_H_TOL_ITERS = obs.histogram("engine.fixpoint.tol_iters",
                             buckets=obs.COUNT_BUCKETS)
_H_FRONTIER = obs.histogram("engine.frontier.frontier_size",
                            buckets=obs.COUNT_BUCKETS)
_C_ROUNDS = obs.counter("engine.frontier.rounds")
_C_DENSE = obs.counter("engine.frontier.dense_rounds")
_C_SWITCH = obs.counter("engine.frontier.direction_switches")
_C_RELAX = obs.counter("engine.frontier.relaxed_edges")
# edges each direction reads: rows x frontier out-edges in a push round,
# rows x |E| in a dense pull round
_C_PUSH_EDGES = obs.counter("engine.frontier.push_edges")
_C_PULL_EDGES = obs.counter("engine.frontier.pull_edges")
_C_RETRACE = obs.counter("engine.frontier.retraces")
# (rows, node bucket, edge budget, weighted, dtype) signatures already traced
# by the bucketed-pow2 frontier steps: a new signature = one jit retrace
_TRACED_SHAPES: set = set()

# trace-time flag: True while tracing inside a ShardedExec shard_map manual
# region (``run_loop``), so nested primitive calls emit collectives directly
# instead of opening another (illegal) nested shard_map
_MANUAL_REGION = threading.local()

# Auto-selection thresholds: below them the re-blocked kernels cannot beat
# plain segment reductions (tile/chunk padding dominates).
_PALLAS_MIN_EDGES = 1 << 16
_BSR_MAX_NODES = 1 << 14  # tiles are dense 128x128: only small/dense graphs
# below this the frontier path's per-round host sync outweighs the saved
# edge relaxations (measured ~1.9x dense at 2^15 nodes / 2^18 edges on CPU)
_FRONTIER_MIN_EDGES = 1 << 15
# ops auto-routed to "frontier" on large graphs.  Deliberately narrower than
# _FRONTIER_OPS: batched multi-source runs (the fusion scheduler's case)
# union their frontiers and lose the sparsity win to the vmapped dense
# fixpoint, so algorithms only pass these op tags for single-source calls;
# CC's dense body pointer-jumps (O(log n) rounds vs frontier's O(diameter)),
# so it is frontier-only on request
_FRONTIER_AUTO_OPS = frozenset({"bfs", "sssp"})

# ops with a sparse monotone-relaxation formulation the frontier path serves;
# anything else on "frontier" resolves to "xla" (same results, dense speed)
_FRONTIER_OPS = frozenset({"bfs", "sssp", "connected_components",
                           "label_propagation"})

_REDUCERS = {
    "sum": jax.ops.segment_sum,
    "min": jax.ops.segment_min,
    "max": jax.ops.segment_max,
}


def backend_supports(backend: str, op: Optional[str]) -> bool:
    """Whether ``backend`` has a dedicated path for ``op`` (None = generic)."""
    if backend == "frontier" and op is not None:
        return op in _FRONTIER_OPS
    return True


def select_backend(plan, backend: Optional[str] = None,
                   op: Optional[str] = None) -> str:
    """Resolve the backend: per-call override > env var > device/size auto.

    ``op`` (an algorithm name) gates op-aware fallback: a resolved backend
    without a dedicated path for that op — e.g. ``"frontier"`` asked to run
    ``"pagerank"``, which has no sparse monotone formulation — resolves to
    ``"xla"`` so the call succeeds with identical results.
    """
    resolved = _select_backend(plan, backend, op)
    if obs.REGISTRY.enabled:
        _C_BACKEND[resolved].inc()
    return resolved


def _select_backend(plan, backend: Optional[str],
                    op: Optional[str]) -> str:
    if backend is not None:
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; want one of {BACKENDS}")
        return backend if backend_supports(backend, op) else "xla"
    env = os.environ.get("REPRO_ENGINE_BACKEND")
    if env:
        return _select_backend(plan, env, op)
    # sparse-traversal ops on large graphs: the frontier path wins on any
    # device (it relaxes only active edges instead of all of them)
    if op in _FRONTIER_AUTO_OPS and plan.n_edges >= _FRONTIER_MIN_EDGES:
        return "frontier"
    if jax.default_backend() == "tpu":
        if plan.n_nodes <= _BSR_MAX_NODES and plan.n_edges >= _PALLAS_MIN_EDGES:
            return "bsr"
        if plan.n_edges >= _PALLAS_MIN_EDGES:
            return "pallas"
    return "xla"


# ---------------------------------------------------------------------------
# Exec pytrees — one per backend
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclass
class XlaExec:
    """Traversal primitives over plan arrays; XLA segment reductions."""

    n_nodes: int
    n_edges: int
    in_src: jax.Array    # in-edge order = sorted by dst (pull order)
    in_dst: jax.Array
    out_src: jax.Array   # out-edge order = sorted by src (push order)
    out_dst: jax.Array

    def tree_flatten(self):
        return ((self.in_src, self.in_dst, self.out_src, self.out_dst),
                (self.n_nodes, self.n_edges))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*aux, *leaves)

    # -- edge-order gathers -----------------------------------------------------
    def in_src_vals(self, x: jax.Array) -> jax.Array:
        return x[self.in_src]

    def in_dst_vals(self, x: jax.Array) -> jax.Array:
        return x[self.in_dst]

    def out_src_vals(self, x: jax.Array) -> jax.Array:
        return x[self.out_src]

    def out_dst_vals(self, x: jax.Array) -> jax.Array:
        return x[self.out_dst]

    # -- segmented reductions ---------------------------------------------------
    def reduce_in(self, edge_vals: jax.Array, combine: str = "sum") -> jax.Array:
        """Per-destination reduction of in-edge-order values (sorted ids)."""
        return _REDUCERS[combine](edge_vals, self.in_dst,
                                  num_segments=self.n_nodes,
                                  indices_are_sorted=True)

    def reduce_out(self, edge_vals: jax.Array, combine: str = "sum") -> jax.Array:
        """Per-source reduction of out-edge-order values (sorted ids)."""
        return _REDUCERS[combine](edge_vals, self.out_src,
                                  num_segments=self.n_nodes,
                                  indices_are_sorted=True)

    # -- fixpoint hooks -----------------------------------------------------------
    def run_loop(self, loop, *args):
        """Run a fixpoint loop (identity wrapper for local backends).

        :class:`ShardedExec` overrides this to run the whole loop inside a
        shard_map manual region so the partitioner cannot turn the body's
        dense reductions into per-shard partials (see there).
        """
        return loop(self, *args)

    # -- fused traversal primitives ---------------------------------------------
    def pull(self, x: jax.Array, combine: str = "sum",
             edge_values: Optional[jax.Array] = None,
             edge_op: str = "mul") -> jax.Array:
        """out[v] = combine over in-edges (u -> v) of x[u] (o edge_values)."""
        ev = self.in_src_vals(x)
        if edge_values is not None:
            ev = ev * edge_values if edge_op == "mul" else ev + edge_values
        return self.reduce_in(ev, combine)

    def push(self, x: jax.Array, combine: str = "sum",
             edge_values: Optional[jax.Array] = None,
             edge_op: str = "mul") -> jax.Array:
        """out[u] = combine over out-edges (u -> v) of x[v] (o edge_values)."""
        ev = self.out_dst_vals(x)
        if edge_values is not None:
            ev = ev * edge_values if edge_op == "mul" else ev + edge_values
        return self.reduce_out(ev, combine)


@jax.tree_util.register_pytree_node_class
@dataclass
class PallasExec(XlaExec):
    """Sum reductions via the one-hot-matmul Pallas kernel.

    The chunk *structure* (which edge lands in which chunk/slot) is static
    per graph and comes precomputed from the plan; each reduction only
    scatters fresh values into the (C, L) chunk buffer on device.  min/max
    and batched reductions fall back to the XLA primitives.
    """

    p_src: jax.Array = None     # pull layout: (C, L) edge in each slot, pad E
    p_lids: jax.Array = None    # (C, L) local ids, pad = 128
    p_blk: jax.Array = None     # (C,) owning output block
    q_src: jax.Array = None     # push layout (over out_src)
    q_lids: jax.Array = None
    q_blk: jax.Array = None
    nb_in: int = 0
    nb_out: int = 0
    interpret: bool = field(kw_only=True)   # stated by every construction

    def tree_flatten(self):
        return ((self.in_src, self.in_dst, self.out_src, self.out_dst,
                 self.p_src, self.p_lids, self.p_blk,
                 self.q_src, self.q_lids, self.q_blk),
                (self.n_nodes, self.n_edges, self.nb_in, self.nb_out,
                 self.interpret))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        n_nodes, n_edges, nb_in, nb_out, interpret = aux
        return cls(n_nodes, n_edges, *leaves, nb_in=nb_in, nb_out=nb_out,
                   interpret=interpret)

    def _chunked_sum(self, edge_vals, src, lids, blk, nb):
        out = segment_sum_chunked(chunk_values(edge_vals, src), lids, blk, nb,
                                  interpret=self.interpret)
        return out.reshape(-1)[: self.n_nodes]

    def reduce_in(self, edge_vals, combine="sum"):
        # non-sum, batched, and integer reductions fall back: the f32 matmul
        # path would change exactness/dtype, violating backend neutrality
        if (combine != "sum" or edge_vals.ndim != 1
                or not jnp.issubdtype(edge_vals.dtype, jnp.floating)):
            return super().reduce_in(edge_vals, combine)
        return self._chunked_sum(edge_vals, self.p_src, self.p_lids,
                                 self.p_blk, self.nb_in)

    def reduce_out(self, edge_vals, combine="sum"):
        if (combine != "sum" or edge_vals.ndim != 1
                or not jnp.issubdtype(edge_vals.dtype, jnp.floating)):
            return super().reduce_out(edge_vals, combine)
        return self._chunked_sum(edge_vals, self.q_src, self.q_lids,
                                 self.q_blk, self.nb_out)


@jax.tree_util.register_pytree_node_class
@dataclass
class BsrExec(XlaExec):
    """Fused gather+sum pulls AND pushes as MXU SpMV over 128x128 BSR tiles.

    ``pull(x, "sum")`` is ``M @ x`` with M[dst, src] = 1; ``push(x, "sum")``
    is ``Mᵀ @ x`` over a separately-blocked transpose tile stream
    (``plan.bsr_t``), so the HITS hub step takes the same MXU path as the
    authority step.  Everything else — min/max, weighted or batched
    reductions — falls back to XLA.
    """

    tiles: jax.Array = None
    rows: jax.Array = None
    cols: jax.Array = None
    tiles_t: jax.Array = None   # transpose stream: M[src, dst] (push layout)
    rows_t: jax.Array = None
    cols_t: jax.Array = None
    nb: int = 0
    block: int = DEFAULT_BLOCK
    interpret: bool = field(kw_only=True)   # stated by every construction

    def tree_flatten(self):
        return ((self.in_src, self.in_dst, self.out_src, self.out_dst,
                 self.tiles, self.rows, self.cols,
                 self.tiles_t, self.rows_t, self.cols_t),
                (self.n_nodes, self.n_edges, self.nb, self.block,
                 self.interpret))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        n_nodes, n_edges, nb, block, interpret = aux
        return cls(n_nodes, n_edges, *leaves, nb=nb, block=block,
                   interpret=interpret)

    def _spmv(self, tiles, rows, cols, x):
        nb, b = self.nb, self.block
        xp = jnp.zeros((nb * b,), jnp.float32)
        xp = xp.at[: self.n_nodes].set(x.astype(jnp.float32))
        y = bsr_spmv(tiles, rows, cols, xp.reshape(nb, b), nb,
                     interpret=self.interpret)
        return y.reshape(-1)[: self.n_nodes]

    def pull(self, x, combine="sum", edge_values=None, edge_op="mul"):
        if (combine != "sum" or edge_values is not None or x.ndim != 1
                or not jnp.issubdtype(x.dtype, jnp.floating)):
            return super().pull(x, combine, edge_values, edge_op)
        return self._spmv(self.tiles, self.rows, self.cols, x)

    def push(self, x, combine="sum", edge_values=None, edge_op="mul"):
        if (combine != "sum" or edge_values is not None or x.ndim != 1
                or not jnp.issubdtype(x.dtype, jnp.floating)):
            return super().push(x, combine, edge_values, edge_op)
        return self._spmv(self.tiles_t, self.rows_t, self.cols_t, x)


@jax.tree_util.register_pytree_node_class
@dataclass
class FrontierExec(XlaExec):
    """CSR-slice gathers for the sparse frontier path.

    Generic ``pull``/``push`` inherit the XLA reductions (the automatic
    fallback for ops without a sparse formulation); the frontier-specific
    state lives in the trimmed CSR offset arrays consumed by
    :func:`frontier_fixpoint`'s push step and in ``w_perm``, the
    in-order→out-order weight permutation.
    """

    out_ptr: jax.Array = None    # (n+1,) trimmed row pointers
    adj: jax.Array = None        # capacity-padded out-neighbor array
    deg_pad: jax.Array = None    # (n+1,) out-degrees, sentinel row n = 0
    w_perm: jax.Array = None     # (E,) in-order position of each out-order edge

    def tree_flatten(self):
        return ((self.in_src, self.in_dst, self.out_src, self.out_dst,
                 self.out_ptr, self.adj, self.deg_pad, self.w_perm),
                (self.n_nodes, self.n_edges))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*aux, *leaves)


@jax.tree_util.register_pytree_node_class
@dataclass
class ShardedExec(XlaExec):
    """Multi-device primitives: shard_map over a 1-D vertex-range mesh.

    Every 1-D ``pull``/``push``/``reduce_in``/``reduce_out`` runs as one
    ``shard_map`` round: each device gathers its exported boundary values
    (``*_bnd``), an ``all_gather`` concatenates them into the halo, each
    local edge slot gathers from ``[local | halo]`` via ``*_gidx`` and
    reduces into its shard-local segment (``*_seg``).  Padding slots
    reduce into the overflow segment ``ns`` (sliced off), so they cannot
    perturb real vertices even by a signed zero, and because each vertex's
    whole edge segment stays on its owner in global order the result is
    bit-identical to ``XlaExec``.  Batched (2-D) inputs and per-edge-order
    gathers fall back to the inherited global primitives.

    The mesh is static aux data in the pytree (``Mesh`` is hashable), so
    jitted fixpoint runners cache per (device-count, shape) signature and
    the same body re-runs warm on the same mesh.
    """

    d: int = 1                      # shard / device count
    ns: int = 1                     # vertices per shard
    axis: str = "gp"                # mesh axis name
    mesh: object = None             # 1-D jax Mesh (static, hashable)
    p_es: int = 1                   # pull: padded edge slots per shard
    p_halo: int = 1                 # pull: boundary slots per shard
    q_es: int = 1                   # push duals
    q_halo: int = 1
    p_gidx: jax.Array = None        # (d*p_es,) into [local(ns) | halo]
    p_seg: jax.Array = None         # (d*p_es,) local segment, pad -> ns
    p_slot: jax.Array = None        # (E,) in-edge order -> flat pull slot
    p_bnd: jax.Array = None         # (d*p_halo,) exported local ids
    q_gidx: jax.Array = None
    q_seg: jax.Array = None
    q_slot: jax.Array = None
    q_bnd: jax.Array = None

    def tree_flatten(self):
        return ((self.in_src, self.in_dst, self.out_src, self.out_dst,
                 self.p_gidx, self.p_seg, self.p_slot, self.p_bnd,
                 self.q_gidx, self.q_seg, self.q_slot, self.q_bnd),
                (self.n_nodes, self.n_edges, self.d, self.ns, self.axis,
                 self.mesh, self.p_es, self.p_halo, self.q_es, self.q_halo))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        (n_nodes, n_edges, d, ns, axis, mesh,
         p_es, p_halo, q_es, q_halo) = aux
        return cls(n_nodes, n_edges, *leaves[:4], d=d, ns=ns, axis=axis,
                   mesh=mesh, p_es=p_es, p_halo=p_halo, q_es=q_es,
                   q_halo=q_halo, p_gidx=leaves[4], p_seg=leaves[5],
                   p_slot=leaves[6], p_bnd=leaves[7], q_gidx=leaves[8],
                   q_seg=leaves[9], q_slot=leaves[10], q_bnd=leaves[11])

    # -- shard_map building blocks ----------------------------------------------
    #
    # Bit-identity vs "xla" is non-negotiable here, and it constrains the
    # whole design: any value the GSPMD partitioner is free to shard gets
    # its dense reductions (PageRank's dangling mass, HITS' norms) split
    # into per-shard partials + all-reduce — numerically fine, bitwise
    # different.  Sharding *constraints* do not help: the partitioner may
    # re-shard the consumers of a pinned value (observed: it slices the
    # fixpoint carry to f32[ns] per device and partializes the sums even
    # through an optimization_barrier).  So nothing is left to GSPMD:
    # every sharded computation — including the whole fixpoint loop, see
    # ``run_loop`` — executes inside a shard_map *manual* region, where
    # dense ops run full-shape and replicated on every device in exactly
    # the single-device order, and only the explicitly written collectives
    # (the halo exchange and the result gather) move data.

    def _mapped(self, fn, *args):
        """Run ``fn`` in the manual region (entering one if needed).

        Inputs and outputs are replicated (``P()``); ``fn`` slices its own
        shard out of each flat ``(d * per_shard,)`` array via
        ``axis_index``.  ``check_vma=False`` because the final
        ``all_gather`` makes the output replicated by construction, which
        jax's varying-manual-axes checker cannot infer.
        """
        if getattr(_MANUAL_REGION, "active", False):
            return fn(*args)
        return jax.shard_map(fn, mesh=self.mesh,
                             in_specs=(PartitionSpec(),) * len(args),
                             out_specs=PartitionSpec(), check_vma=False)(*args)

    def run_loop(self, loop, *args):
        """Run a whole fixpoint loop as one shard_map manual region.

        The loop carry, the convergence tests, and every dense op in the
        body stay full-shape and replicated on each device; the pull/push
        primitives inside notice the active region (``_MANUAL_REGION``)
        and emit their collectives directly instead of nesting another
        shard_map.
        """

        def fn(ex, args_):
            _MANUAL_REGION.active = True
            try:
                return loop(ex, *args_)
            finally:
                _MANUAL_REGION.active = False

        return jax.shard_map(fn, mesh=self.mesh,
                             in_specs=(PartitionSpec(), PartitionSpec()),
                             out_specs=PartitionSpec(),
                             check_vma=False)(self, args)

    def _rekey(self, edge_vals: jax.Array, slot: jax.Array,
               es: int) -> jax.Array:
        """Scatter global-edge-order values into the flat padded layout."""
        # slots ascend with global edge order (shard-major, then in order)
        return jnp.zeros((self.d * es,), edge_vals.dtype).at[slot].set(
            edge_vals, indices_are_sorted=True, unique_indices=True)

    def _exchange_reduce(self, x, combine, gidx, seg, bnd, es, halo,
                         ev_sh, edge_op):
        """One boundary-exchange round: halo gather + local segment reduce."""
        reducer = _REDUCERS[combine]
        d, ns, ax = self.d, self.ns, self.axis

        def run(xp, gidx_f, seg_f, bnd_f, *ev_rest):
            i = jax.lax.axis_index(ax)
            x_loc = jax.lax.dynamic_slice(xp, (i * ns,), (ns,))
            bnd_loc = jax.lax.dynamic_slice(bnd_f, (i * halo,), (halo,))
            halo_vals = jax.lax.all_gather(x_loc[bnd_loc], ax, tiled=True)
            ev = jnp.concatenate([x_loc, halo_vals])[
                jax.lax.dynamic_slice(gidx_f, (i * es,), (es,))]
            if ev_rest:
                e = ev_rest[0]
                if e.ndim:
                    e = jax.lax.dynamic_slice(e, (i * es,), (es,))
                ev = ev * e if edge_op == "mul" else ev + e
            loc = reducer(ev, jax.lax.dynamic_slice(seg_f, (i * es,), (es,)),
                          num_segments=ns + 1, indices_are_sorted=True)[:ns]
            return jax.lax.all_gather(loc, ax, tiled=True)

        args = [jnp.pad(x, (0, d * ns - self.n_nodes)), gidx, seg, bnd]
        if ev_sh is not None:
            args.append(ev_sh)
        return self._mapped(run, *args)[: self.n_nodes]

    def _segment_reduce(self, ev_sh, seg, es, combine):
        """Halo-free shard-local segment reduction (values already placed)."""
        reducer = _REDUCERS[combine]
        ns, ax = self.ns, self.axis

        def run(ev_f, seg_f):
            i = jax.lax.axis_index(ax)
            loc = reducer(jax.lax.dynamic_slice(ev_f, (i * es,), (es,)),
                          jax.lax.dynamic_slice(seg_f, (i * es,), (es,)),
                          num_segments=ns + 1, indices_are_sorted=True)[:ns]
            return jax.lax.all_gather(loc, ax, tiled=True)

        return self._mapped(run, ev_sh, seg)[: self.n_nodes]

    # -- primitives --------------------------------------------------------------
    def reduce_in(self, edge_vals, combine="sum"):
        if edge_vals.ndim != 1:
            return super().reduce_in(edge_vals, combine)
        return self._segment_reduce(
            self._rekey(edge_vals, self.p_slot, self.p_es),
            self.p_seg, self.p_es, combine)

    def reduce_out(self, edge_vals, combine="sum"):
        if edge_vals.ndim != 1:
            return super().reduce_out(edge_vals, combine)
        return self._segment_reduce(
            self._rekey(edge_vals, self.q_slot, self.q_es),
            self.q_seg, self.q_es, combine)

    def pull(self, x, combine="sum", edge_values=None, edge_op="mul"):
        if x.ndim != 1:
            return super().pull(x, combine, edge_values, edge_op)
        ev_sh = None
        if edge_values is not None:
            ev = jnp.asarray(edge_values)
            if ev.ndim > 1:
                return super().pull(x, combine, edge_values, edge_op)
            ev_sh = ev if ev.ndim == 0 \
                else self._rekey(ev, self.p_slot, self.p_es)
        return self._exchange_reduce(x, combine, self.p_gidx, self.p_seg,
                                     self.p_bnd, self.p_es, self.p_halo,
                                     ev_sh, edge_op)

    def push(self, x, combine="sum", edge_values=None, edge_op="mul"):
        if x.ndim != 1:
            return super().push(x, combine, edge_values, edge_op)
        ev_sh = None
        if edge_values is not None:
            ev = jnp.asarray(edge_values)
            if ev.ndim > 1:
                return super().push(x, combine, edge_values, edge_op)
            ev_sh = ev if ev.ndim == 0 \
                else self._rekey(ev, self.q_slot, self.q_es)
        return self._exchange_reduce(x, combine, self.q_gidx, self.q_seg,
                                     self.q_bnd, self.q_es, self.q_halo,
                                     ev_sh, edge_op)


# ---------------------------------------------------------------------------
# exec construction (cached on the plan)
# ---------------------------------------------------------------------------


def shard_count(n_shards: Optional[int] = None) -> int:
    """Resolve the shard count: explicit > REPRO_SHARD_COUNT > all devices."""
    if n_shards is not None:
        return int(n_shards)
    env = os.environ.get("REPRO_SHARD_COUNT")
    if env:
        return int(env)
    return len(jax.devices())


def get_exec(plan, backend: Optional[str] = None, *,
             interpret: Optional[bool] = None,
             block: int = DEFAULT_BLOCK,
             chunk: int = DEFAULT_CHUNK,
             n_shards: Optional[int] = None) -> XlaExec:
    """Backend Exec for a :class:`GraphPlan`, memoized on the plan."""
    backend = select_backend(plan, backend)
    if plan.n_nodes == 0:
        backend = "xla"   # degenerate: the re-blocked kernels have no rows
    interp = auto_interpret(interpret)
    shards = shard_count(n_shards) if backend == "sharded" else 0
    key = (backend, interp, block, chunk, shards)
    ex = plan.execs.get(key)
    if ex is not None:
        _C_EXEC_HIT.inc()
        return ex
    _C_EXEC_MISS.inc()
    with building("exec." + backend):
        base = (plan.n_nodes, plan.n_edges, plan.in_src, plan.in_dst,
                plan.out_src, plan.out_dst)
        if backend == "xla":
            ex = XlaExec(*base)
        elif backend == "sharded":
            sp = plan.sharded(shards)
            ex = ShardedExec(
                *base, d=sp.d, ns=sp.ns, axis=sp.axis, mesh=sp.mesh,
                p_es=sp.pull.es, p_halo=sp.pull.halo,
                q_es=sp.push.es, q_halo=sp.push.halo,
                p_gidx=sp.pull.gather_idx, p_seg=sp.pull.seg_local,
                p_slot=sp.pull.edge_slot, p_bnd=sp.pull.boundary,
                q_gidx=sp.push.gather_idx, q_seg=sp.push.seg_local,
                q_slot=sp.push.edge_slot, q_bnd=sp.push.boundary)
        elif backend == "frontier":
            ptr, idx, deg_pad = plan.csr_out()
            ex = FrontierExec(*base, ptr, idx, deg_pad, plan.in_perm_out())
        elif backend == "pallas":
            p_src, p_lids, p_blk, nb_in, _ = plan.chunk_layout_in(chunk)
            q_src, q_lids, q_blk, nb_out, _ = plan.chunk_layout_out(chunk)
            ex = PallasExec(*base, p_src, p_lids, p_blk, q_src, q_lids, q_blk,
                            nb_in=nb_in, nb_out=nb_out, interpret=interp)
        else:
            tiles, rows, cols, nb = plan.bsr(block)
            tiles_t, rows_t, cols_t, _ = plan.bsr_t(block)
            ex = BsrExec(*base, tiles, rows, cols, tiles_t, rows_t, cols_t,
                         nb=nb, block=block, interpret=interp)
    plan.execs[key] = ex
    return ex


def pull(plan, values: jax.Array, combine: str = "sum", *,
         backend: Optional[str] = None,
         edge_values: Optional[jax.Array] = None, edge_op: str = "mul",
         **exec_kw) -> jax.Array:
    """Module-level convenience: ``get_exec(plan, backend).pull(...)``."""
    return get_exec(plan, backend, **exec_kw).pull(values, combine,
                                                   edge_values, edge_op)


def push(plan, values: jax.Array, combine: str = "sum", *,
         backend: Optional[str] = None,
         edge_values: Optional[jax.Array] = None, edge_op: str = "mul",
         **exec_kw) -> jax.Array:
    """Module-level convenience: ``get_exec(plan, backend).push(...)``."""
    return get_exec(plan, backend, **exec_kw).push(values, combine,
                                                   edge_values, edge_op)


# ---------------------------------------------------------------------------
# fixpoint driver
# ---------------------------------------------------------------------------

_RUNNERS = {}


def _record_halo(ex, rounds: Optional[int]) -> None:
    """The sharded backend's static halo traffic in ``engine.profile.*``
    (only called when obs is enabled and outside manual regions): bytes per
    round match ``ShardPlan.halo_bytes_per_round``, and the total follows
    when the round count is known.  Halo *time* is not attributable from
    the host: the whole loop runs inside one shard_map region."""
    if isinstance(ex, ShardedExec):
        obs.profile.record_sharded(ex.d * ex.p_halo * 4, rounds=rounds)


def _leaf_changed(o: jax.Array, n: jax.Array) -> jax.Array:
    neq = o != n
    if jnp.issubdtype(jnp.asarray(o).dtype, jnp.inexact):
        # NaN != NaN would spin the loop forever; a NaN that stays NaN is
        # converged (the deleted strict-decrease conditions terminated too)
        neq = neq & ~(jnp.isnan(o) & jnp.isnan(n))
    return jnp.any(neq)


def _changed(old, new) -> jax.Array:
    flags = [_leaf_changed(o, n) for o, n in
             zip(jax.tree_util.tree_leaves(old), jax.tree_util.tree_leaves(new))]
    return functools.reduce(jnp.logical_or, flags, jnp.bool_(False))


def _residual(old, new) -> jax.Array:
    """L1 residual between two state pytrees (f32 accumulation)."""
    tot = jnp.float32(0.0)
    for o, n in zip(jax.tree_util.tree_leaves(old),
                    jax.tree_util.tree_leaves(new)):
        tot = tot + jnp.sum(jnp.abs(n.astype(jnp.float32)
                                    - o.astype(jnp.float32)))
    return tot


def _runner(body: Callable, fixed, manual: bool = False):
    # ``manual`` = this fixpoint is being traced inside an enclosing
    # ShardedExec.run_loop region (nested fixpoints: SCC's color/reach
    # solves inside _scc_round).  Those must NOT wrap another shard_map —
    # manual regions cannot nest — so they run the bare loop; keying the
    # jit cache on the flag keeps the two tracings from sharing a jaxpr.
    key = (body, fixed, manual)
    run = _RUNNERS.get(key)
    if run is None:
        if fixed == "tol":
            def loop_py(ex, init, max_iter, tol, *args):
                def cond(carry):
                    _, i, res = carry
                    return (res > tol) & (i < max_iter)

                def step(carry):
                    s, i, _ = carry
                    ns = body(ex, s, *args)
                    return ns, i + 1, _residual(s, ns)

                final, iters, _ = jax.lax.while_loop(
                    cond, step, (init, jnp.int32(0), jnp.float32(jnp.inf)))
                # the iteration counter rides along so the caller can expose
                # warm-vs-cold convergence as a metric (one scalar, fetched
                # only when obs is enabled and the call is not being traced)
                return final, iters
        elif fixed:
            def loop_py(ex, init, n_iter, *args):
                return jax.lax.fori_loop(
                    0, n_iter, lambda _, s: body(ex, s, *args), init)
        else:
            def loop_py(ex, init, max_iter, *args):
                def cond(carry):
                    _, i, changed = carry
                    return changed & (i < max_iter)

                def step(carry):
                    s, i, _ = carry
                    ns = body(ex, s, *args)
                    return ns, i + 1, _changed(s, ns)

                final, _, _ = jax.lax.while_loop(
                    cond, step, (init, jnp.int32(0), jnp.bool_(True)))
                return final

        if manual:
            def run(ex, *a):
                return loop_py(ex, *a)
        else:
            def run(ex, *a):
                return ex.run_loop(loop_py, *a)

        # the jit's name is the program's name in profiler traces:
        # ``jit_fixpoint_pagerank_fori:fusion.7``, one per body and mode
        run.__name__ = run.__qualname__ = _runner_name(body, fixed, manual)
        run = _RUNNERS[key] = jax.jit(run)
    return run


def _runner_name(body: Callable, fixed, manual: bool) -> str:
    what = body.__name__.strip("_")
    if what.endswith("_body"):
        what = what[:-len("_body")]
    mode = "tol" if fixed == "tol" else "fori" if fixed else "while"
    return f"fixpoint_{what}_{mode}" + ("_manual" if manual else "")


def fixpoint(plan_or_exec, body: Callable, init, *,
             n_iter: Optional[int] = None, max_iter: Optional[int] = None,
             tol: Optional[float] = None,
             backend: Optional[str] = None, args: Tuple = (),
             obs_tag: Optional[str] = None):
    """Iterate ``body(exec, state, *args) -> state`` on the engine.

    With ``n_iter``: exactly that many rounds (fori_loop).  With ``tol``:
    until the L1 residual between consecutive states drops to ``tol``,
    capped at ``max_iter`` — the convergence stopping rule that makes
    warm-started contractions (PageRank from a parent vector after a small
    delta) finish in a handful of rounds.  Otherwise: until the state stops
    changing, capped at ``max_iter`` (while_loop).  ``body`` must be a
    module-level function — the jitted runner is cached per body identity;
    pass per-call parameters via ``args`` (traced).  ``obs_tag`` names the
    call in the tol-mode iteration-count metric
    (``engine.fixpoint.tol_iters[.<tag>]``) — how warm-started solves show
    their shortened convergence.
    """
    ex = (plan_or_exec if isinstance(plan_or_exec, XlaExec)
          else get_exec(plan_or_exec, backend))
    manual = getattr(_MANUAL_REGION, "active", False)
    # halo accounting counts real host-side calls only: inside a manual
    # region this function runs at trace time, once per tracing
    prof = obs.REGISTRY.enabled and not manual
    if tol is not None:
        cap = np.iinfo(np.int32).max if max_iter is None else int(max_iter)
        out, iters = _runner(body, "tol", manual)(ex, init, jnp.int32(cap),
                                                  jnp.float32(tol), *args)
        # skip the scalar fetch when disabled; under a jax trace (vmapped
        # tol solves) the counter is abstract and cannot be observed
        if obs.REGISTRY.enabled:
            try:
                n = int(iters)
            except Exception:        # tracer-stage call: no concrete count
                n = None
            if n is not None:
                _H_TOL_ITERS.observe(n)
                if obs_tag:
                    obs.histogram(f"engine.fixpoint.tol_iters.{obs_tag}",
                                  buckets=obs.COUNT_BUCKETS).observe(n)
            if prof:
                _record_halo(ex, n)
        return out
    if n_iter is not None:
        out = _runner(body, True, manual)(ex, init, jnp.int32(n_iter), *args)
        if prof:
            _record_halo(ex, int(n_iter))
        return out
    cap = np.iinfo(np.int32).max if max_iter is None else int(max_iter)
    out = _runner(body, False, manual)(ex, init, jnp.int32(cap), *args)
    if prof:
        _record_halo(ex, None)
    return out


# ---------------------------------------------------------------------------
# frontier fixpoint driver — sparse monotone min-relaxation
# ---------------------------------------------------------------------------

# direction-optimization switch: dense pull once the frontier's out-edges
# exceed |E| / _DENSE_EDGE_DIV (Beamer-style; the dense round costs ~|E|,
# the sparse round costs ~frontier edges plus compaction)
_DENSE_EDGE_DIV = 4
_MIN_BUCKET = 16


_SCAN_ROW = 1024


def _int_cumsum(x):
    """Inclusive prefix sum of a 1-D integer array, in rows of 1024.

    Equal to ``jnp.cumsum`` (integer addition is associative), but a TPU
    compiles one scan over millions of elements for about half a minute,
    and short row scans plus a scan of the row totals in about a second.
    """
    m = x.shape[0]
    if m <= _SCAN_ROW:
        return jnp.cumsum(x)
    r = -(-m // _SCAN_ROW)
    rows = jnp.cumsum(jnp.pad(x, (0, r * _SCAN_ROW - m)).reshape(r, _SCAN_ROW),
                      axis=1)
    last = rows[:, -1]
    return (rows + (_int_cumsum(last) - last)[:, None]).reshape(-1)[:m]


def _stats_of(mask, deg):
    """(frontier size, frontier out-edge count) — the host's planning pair."""
    return jnp.stack([jnp.sum(mask.astype(jnp.int32)),
                      jnp.sum(jnp.where(mask, deg, 0)).astype(jnp.int32)])


def _frontier_round_out(ex, state, new, caps, t):
    """Shared step epilogue: freeze capped rows, next mask + its stats.

    The (frontier size, frontier out-edge count) pair the host needs to
    plan the next round is computed inside the same jitted step, so each
    round costs one dispatch and one scalar fetch.
    """
    new = jnp.where((t < caps)[:, None], new, state)
    mask = jnp.any(new < state, axis=0)
    return new, mask, _stats_of(mask, ex.deg_pad[: ex.n_nodes])


# A profiler trace names the rounds' device programs after these jitted
# functions: jit__compact and jit__frontier_push_step for a push round,
# jit__frontier_dense_step for a pull round (bench/program_metrics.py).
@functools.partial(jax.jit, static_argnames=("e_budget",))
def _frontier_push_step(ex, state, f_idx, w_out, caps, t, *, e_budget):
    """One sparse push round over the compacted frontier.

    ``f_idx`` is the frontier padded with the sentinel vertex ``n`` (degree
    0 in ``deg_pad``, so pad slots own no edge lanes); ``e_budget`` is the
    static edge-lane count (bucketed power of two >= frontier out-edges).
    Each lane finds its owning frontier slot by prefix-sum search, gathers
    the neighbor from the plan CSR, and scatter-mins ``state[u] (+ w)``
    into the neighbor's column.  Rows with ``t >= caps`` are frozen (the
    per-request depth limits of fused service batches).
    """
    n = ex.n_nodes
    deg = ex.deg_pad[f_idx]
    off = ex.out_ptr[f_idx]
    cum = _int_cumsum(deg) - deg                          # exclusive prefix
    total = jnp.sum(deg)
    j = jnp.arange(e_budget, dtype=deg.dtype)
    owner = jnp.clip(jnp.searchsorted(cum, j, side="right") - 1,
                     0, f_idx.shape[0] - 1)
    valid = j < total
    pos = jnp.clip(off[owner] + (j - cum[owner]), 0, ex.adj.shape[0] - 1)
    v = jnp.where(valid, ex.adj[pos], n)                  # pad -> sentinel col
    u = jnp.minimum(f_idx[owner], n - 1)
    cand = state[:, u]
    if w_out is not None:
        # scalar = uniform edge weight (BFS hops); array = per-edge, already
        # re-keyed to out order
        cand = cand + (w_out if w_out.ndim == 0 else w_out[pos])
    new = jnp.pad(state, ((0, 0), (0, 1))).at[:, v].min(cand)[:, :n]
    return _frontier_round_out(ex, state, new, caps, t)


@jax.jit
def _frontier_dense_step(ex, state, w_in, caps, t):
    """One dense pull round (the direction-optimized big-frontier path).

    Round-for-round identical to the sparse push: for a monotone min
    relaxation, re-relaxing an edge whose source did not change last round
    is a no-op (its contribution is already in the state).
    """
    def one(s):
        ev = s[ex.in_src]
        if w_in is not None:
            ev = ev + w_in          # scalar hop or per-edge (in-order) array
        return jax.ops.segment_min(ev, ex.in_dst, num_segments=ex.n_nodes,
                                   indices_are_sorted=True)

    # single-row runs skip vmap batching overhead (the common service case)
    relaxed = one(state[0])[None] if state.shape[0] == 1 \
        else jax.vmap(one)(state)
    new = jnp.minimum(state, relaxed)
    return _frontier_round_out(ex, state, new, caps, t)


_frontier_stats = jax.jit(_stats_of)   # round-0 entry; later rounds get
                                       # stats fused into their step


@functools.partial(jax.jit, static_argnames=("b",))
def _compact(mask, *, b):
    """First ``b`` set positions of ``mask`` in order, padded with ``n``.

    ``nonzero(mask, size=b, fill_value=n)`` as a search of the running
    count: the k-th set position is the first index whose prefix count
    reaches k.  Same values, but it compiles in about a second at millions
    of vertices, where ``nonzero``'s scatter takes half a minute per bucket.
    """
    count = _int_cumsum(mask.astype(jnp.int32))
    return jnp.searchsorted(count, jnp.arange(1, b + 1, dtype=jnp.int32),
                            side="left").astype(jnp.int32)


def _fetch_stats(stats) -> Tuple[int, int]:
    """The host's one blocking fetch of a round's ``(cnt, fe)``.  A device
    gap inside its span is the round trip, not host work."""
    with obs.TRACER.span("engine.frontier.sync"):
        cnt, fe = (int(x) for x in np.asarray(stats))
    return cnt, fe


def frontier_fixpoint(plan_or_exec, init, frontier, *,
                      weights: Optional[jax.Array] = None,
                      caps=None, max_rounds: Optional[int] = None):
    """Sparse monotone min-relaxation to fixpoint (BFS/SSSP/min-label).

    Iterates ``state[v] <- min(state[v], min over frontier in-neighbors u of
    state[u] (+ w(u, v)))`` where the frontier is the set of vertices whose
    value changed last round, until the frontier empties (or a round bound).
    The frontier is kept *compacted* — an index array padded to a bucketed
    power of two, so jit re-traces are bounded by log2 n — and each round
    relaxes only the outgoing edges of frontier vertices, switching to a
    dense pull over all edges when the frontier exceeds ``|E| / 4``.

    ``init`` is ``(n,)`` or batched ``(k, n)``; ``frontier`` a ``(n,)`` bool
    mask seeding round 0 (for batched runs: the union over rows).
    ``weights`` is per-edge in in-edge order (the sssp convention) and is
    re-keyed to CSR push order via the plan's cached permutation.  ``caps``
    (scalar or ``(k,)``) freezes row ``i`` after ``caps[i]`` rounds — the
    exact equivalent of running that row alone for ``caps[i]`` iterations.

    The host drives the loop (frontier sizes are data-dependent); state and
    mask stay on device, with one scalar fetch per round.
    """
    ex = (plan_or_exec if isinstance(plan_or_exec, FrontierExec)
          else get_exec(plan_or_exec, "frontier"))
    state = jnp.asarray(init)
    batched = state.ndim == 2
    if not batched:
        state = state[None, :]
    k, n = state.shape
    if n == 0 or k == 0 or ex.n_edges == 0:
        return jnp.asarray(init)   # no edges: nothing can relax
    w_in = w_out = None
    if weights is not None:
        w_in = jnp.asarray(weights)
        # scalars broadcast (no per-edge gather); arrays re-key to out order
        w_out = w_in if w_in.ndim == 0 else w_in[ex.w_perm]
    big = np.iinfo(np.int32).max
    if caps is None:
        caps_np = np.full((k,), big, np.int64)
    else:
        caps_np = np.broadcast_to(
            np.atleast_1d(np.asarray(caps, dtype=np.int64)), (k,))
    caps_arr = jnp.asarray(np.minimum(caps_np, big).astype(np.int32))
    bound = int(min(caps_np.max(), big if max_rounds is None else max_rounds))

    mask = jnp.asarray(frontier, bool)
    stats = _frontier_stats(mask, ex.deg_pad[:-1])
    t = 0
    reg_on = obs.REGISTRY.enabled
    prev_dense: Optional[bool] = None
    with obs.TRACER.span("engine.frontier_fixpoint", rows=k, nodes=n,
                         edges=int(ex.n_edges),
                         weighted=weights is not None) as fspan:
        cnt, fe = _fetch_stats(stats)
        while t < bound and cnt:
            dense = fe * _DENSE_EDGE_DIV >= ex.n_edges
            if reg_on:
                _H_FRONTIER.observe(cnt)
                _C_ROUNDS.inc()
                _C_RELAX.inc(fe)
                if dense:
                    _C_DENSE.inc()
                if prev_dense is not None and dense != prev_dense:
                    _C_SWITCH.inc()
            # a round's span runs from its first dispatch to the fetch of
            # the stats its step computed: its device work lies inside it
            if dense:
                with obs.TRACER.span("engine.frontier.pull", round=t,
                                     frontier=cnt, edges=fe) as rspan:
                    state, mask, stats = _frontier_dense_step(
                        ex, state, w_in, caps_arr, jnp.int32(t))
                    next_cnt, next_fe = _fetch_stats(stats)
            else:
                b = min(next_capacity(cnt, minimum=_MIN_BUCKET),
                        next_capacity(max(n, 1)))
                eb = next_capacity(max(fe, 1), minimum=_MIN_BUCKET)
                shape_sig = (k, b, eb, w_out is None, str(state.dtype))
                if shape_sig not in _TRACED_SHAPES:
                    _TRACED_SHAPES.add(shape_sig)
                    if reg_on:
                        _C_RETRACE.inc()
                with obs.TRACER.span("engine.frontier.push", round=t,
                                     frontier=cnt, edges=fe, bucket=b,
                                     e_budget=eb) as rspan:
                    f_idx = _compact(mask, b=b)
                    state, mask, stats = _frontier_push_step(
                        ex, state, f_idx, w_out, caps_arr, jnp.int32(t),
                        e_budget=eb)
                    next_cnt, next_fe = _fetch_stats(stats)
            if reg_on:
                if dense:
                    _C_PULL_EDGES.inc(k * int(ex.n_edges))
                else:
                    _C_PUSH_EDGES.inc(k * fe)
                if rspan.duration_s is not None:
                    obs.profile.record_frontier_round(
                        "dense" if dense else "sparse",
                        rspan.duration_s * 1e3)
            cnt, fe = next_cnt, next_fe
            prev_dense = dense
            t += 1
        fspan.set(rounds=t)
    return state if batched else state[0]
