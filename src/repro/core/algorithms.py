"""Graph algorithms (Ringo §2.2/§3, paper Tables 3 & 6) on the shared engine.

The paper benchmarks PageRank and triangle counting (parallel, Table 3) and
3-core / SSSP / SCC (sequential, Table 6), drawn from SNAP's 200+ algorithm
library.  We implement the full set named in the paper plus the common
supporting measures, as **vectorized fixed-point iterations** — but every
one of them is now a thin composition over the two-layer execution
substrate:

    Graph.plan()      (core/plan.py)   cached derived arrays, paid once
    engine primitives (core/engine.py) pull/push/fixpoint with backend
                                       dispatch: "xla" | "pallas" | "bsr"

so repeated interactive calls on the same graph reuse the sorted edge
arrays, and a backend speedup applies to the whole library at once.  Every
algorithm accepts ``backend=`` (None = auto by device/size) and
``interpret=`` (Pallas interpret-mode override) kwargs.

Every algorithm works on dense node ids of a :class:`repro.core.graph.Graph`
and returns per-node arrays (convertible back to tables via
``convert.graph_to_node_table`` — the paper's results-to-tables loop).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import engine
from .. import obs
from .graph import Graph
from .provenance import track

__all__ = [
    "pagerank",
    "personalized_pagerank",
    "triangle_count",
    "per_node_triangles",
    "clustering_coefficient",
    "connected_components",
    "strongly_connected_components",
    "sssp",
    "bfs",
    "k_core",
    "core_numbers",
    "hits",
    "degree_histogram",
    "incremental_sssp",
    "incremental_bfs",
    "incremental_connected_components",
    "incremental_label_propagation",
]

_log = obs.get_logger(__name__)

_INF = jnp.float32(jnp.inf)


def _exec_for(g: Graph, backend: Optional[str], interpret: Optional[bool]):
    plan = g.plan()
    return plan, engine.get_exec(plan, backend, interpret=interpret)


def _undirected_presence(g: Graph, u: Graph):
    """(pos, present): where each g-node lands in the undirected view.

    ``to_undirected`` rebuilds the node set from edge endpoints, so vertices
    of ``g`` with no non-loop edges are absent from ``u`` — indexing ``u``
    results by ``u.dense_of`` alone would read a neighbor's slot for them.
    """
    orig = g.node_ids[: g.n_nodes]
    if u.n_nodes == 0:
        return (jnp.zeros((g.n_nodes,), jnp.int32),
                jnp.zeros((g.n_nodes,), bool))
    pos = jnp.clip(u.dense_of(orig), 0, u.n_nodes - 1)
    return pos, u.node_ids[pos] == orig


def _undirected_values_to_g(g: Graph, u: Graph, vals: jax.Array, missing
                            ) -> jax.Array:
    """Per-node values on the undirected view -> g's id space."""
    if g.n_nodes == 0:
        return vals[:0]
    pos, present = _undirected_presence(g, u)
    if u.n_nodes == 0:
        return jnp.broadcast_to(missing, (g.n_nodes,)).astype(vals.dtype)
    return jnp.where(present, vals[pos], missing)


def _undirected_ids_to_g(g: Graph, u: Graph, labels: jax.Array) -> jax.Array:
    """Id-valued results (CC/LP labels are u-dense ids) -> g-dense ids.

    Both dense numberings ascend with original id, so the translation is
    order-preserving and min-id semantics survive; absent vertices (no
    non-loop edges) label themselves.
    """
    own = jnp.arange(g.n_nodes, dtype=jnp.int32)
    if g.n_nodes == 0 or u.n_nodes == 0:
        return own
    pos, present = _undirected_presence(g, u)
    lab_g = g.dense_of(u.original_of(labels)).astype(jnp.int32)
    return jnp.where(present, lab_g[pos], own)


# ---------------------------------------------------------------------------
# PageRank (paper Table 3: 2.76 s LiveJournal / 60.5 s Twitter2010, 10 iters)
# ---------------------------------------------------------------------------


def _pagerank_body(ex, pr, damping, inv_deg, dangling):
    n = ex.n_nodes
    summed = ex.pull(pr * inv_deg, "sum")        # rank mass along in-edges
    dang = jnp.sum(jnp.where(dangling, pr, 0.0))
    # the uniform share is one scalar added after the scaled pull: written as
    # ``summed + dang / n``, XLA folds the broadcast into the segment sum's
    # initial value on the single-device path only, which changes the
    # addition order and breaks bit-identity with the "sharded" backend
    return damping * summed + ((1.0 - damping) + damping * dang) / n


@track("algorithms.pagerank", "A.pagerank")
def pagerank(g: Graph, n_iter: int = 10, damping: float = 0.85, *,
             tol: Optional[float] = None,
             init: Optional[jax.Array] = None,
             backend: Optional[str] = None,
             interpret: Optional[bool] = None) -> jax.Array:
    """Power-iteration PageRank with dangling-mass redistribution.

    The SpMV inner loop is ``engine.pull(pr * inv_deg, "sum")`` — on the
    "bsr" backend that is the MXU-tiled BSR SpMV, on "pallas" the one-hot
    matmul segment sum, on "xla" a sorted segmented reduction.

    With ``tol`` set, ``n_iter`` is ignored and the iteration runs until
    the L1 residual between rounds drops to ``tol``.  ``init`` seeds the
    iterate (default: uniform); PageRank is a contraction, so any seed
    converges to the same vector under the ``tol`` rule — passing a parent
    graph's vector after a small :class:`~repro.core.graph.EdgeDelta` is
    the warm-start path, converging in a handful of rounds.
    """
    if g.n_nodes == 0:
        return jnp.zeros((0,), jnp.float32)
    plan, ex = _exec_for(g, backend, interpret)
    pr0 = (jnp.asarray(init, jnp.float32) if init is not None
           else jnp.full((g.n_nodes,), 1.0 / g.n_nodes, dtype=jnp.float32))
    args = (jnp.float32(damping), plan.inv_out_deg, plan.dangling)
    if tol is not None:
        return engine.fixpoint(
            ex, _pagerank_body, pr0, tol=float(tol), max_iter=10_000,
            args=args,
            obs_tag="pagerank_warm" if init is not None else "pagerank")
    return engine.fixpoint(ex, _pagerank_body, pr0, n_iter=n_iter, args=args)


def _ppr_body(ex, pr, damping, inv_deg, dangling, restart):
    summed = ex.pull(pr * inv_deg, "sum")
    dang = jnp.sum(jnp.where(dangling, pr, 0.0))
    # restart terms added after the scaled pull (see _pagerank_body)
    return damping * summed + ((1.0 - damping) + damping * dang) * restart


def _ppr_capped_body(ex, st, damping, inv_deg, dangling, restart, cap):
    """PPR iterate frozen past a per-run round cap (cross-n_iter fusion)."""
    pr, t = st
    new = _ppr_body(ex, pr, damping, inv_deg, dangling, restart)
    return jnp.where(t < cap, new, pr), t + 1


@track("algorithms.personalized_pagerank", "A.personalized_pagerank")
def personalized_pagerank(g: Graph, source, n_iter=10,
                          damping: float = 0.85, *,
                          tol: Optional[float] = None,
                          init: Optional[jax.Array] = None,
                          backend: Optional[str] = None,
                          interpret: Optional[bool] = None) -> jax.Array:
    """Random-walk-with-restart PageRank personalized to ``source``.

    Teleport and dangling mass both return to the restart distribution
    (a one-hot at the source).  Like :func:`sssp`, ``source`` may be a
    scalar (returns ``(n,)``) or an array of k sources (returns ``(k, n)``,
    batched via ``vmap`` over the engine fixpoint) — the fusion target for
    the interactive service's scheduler.  ``n_iter`` may likewise be a
    ``(k,)`` array of per-source iteration counts: the batch runs to the
    max and every row freezes at its own count, exactly matching a
    standalone run.

    ``tol``/``init`` mirror :func:`pagerank`: run to L1-residual
    convergence from ``init`` (default: the restart distribution) instead
    of a fixed round count — the warm-start path after an edge delta.
    """
    if g.n_nodes == 0:
        return jnp.zeros((0,), jnp.float32)
    plan, ex = _exec_for(g, backend, interpret)
    scalar = np.ndim(source) == 0
    sources = jnp.atleast_1d(jnp.asarray(source, dtype=jnp.int32))
    args = (jnp.float32(damping), plan.inv_out_deg, plan.dangling)

    if tol is not None:
        init_rows = None if init is None else jnp.atleast_2d(
            jnp.asarray(init, jnp.float32))

        def one_tol(s, i):
            restart = jnp.zeros((g.n_nodes,), jnp.float32).at[s].set(1.0)
            pr0 = restart if init_rows is None else init_rows[i]
            return engine.fixpoint(ex, _ppr_body, pr0, tol=float(tol),
                                   max_iter=10_000, args=(*args, restart))

        prs = jax.vmap(one_tol)(sources, jnp.arange(sources.shape[0]))
        return prs[0] if scalar else prs

    if np.ndim(n_iter) == 0:
        def one(s):
            restart = jnp.zeros((g.n_nodes,), jnp.float32).at[s].set(1.0)
            return engine.fixpoint(ex, _ppr_body, restart, n_iter=int(n_iter),
                                   args=(*args, restart))

        prs = jax.vmap(one)(sources)
    else:
        caps = _source_caps(sources, n_iter)
        rounds = int(caps.max()) if caps.size else 0

        def one_capped(s, cap):
            restart = jnp.zeros((g.n_nodes,), jnp.float32).at[s].set(1.0)
            out, _ = engine.fixpoint(ex, _ppr_capped_body,
                                     (restart, jnp.int32(0)), n_iter=rounds,
                                     args=(*args, restart, cap))
            return out

        prs = jax.vmap(one_capped)(sources, jnp.asarray(caps))
    return prs[0] if scalar else prs


# ---------------------------------------------------------------------------
# Triangle counting (paper Table 3: 6.13 s / 263.6 s)
# ---------------------------------------------------------------------------


def _triangle_hits(plan, lo: int, hi: int):
    """Per-edge sorted-adjacency intersection over one oriented-edge chunk."""
    osrc, odst, nbr, _ = plan.oriented()
    pad_val = plan.n_nodes
    u, v = osrc[lo:hi], odst[lo:hi]
    cand = nbr[u]                                  # (c, w)
    rows = nbr[v]                                  # (c, w)
    pos = jnp.clip(jax.vmap(jnp.searchsorted)(rows, cand), 0, rows.shape[1] - 1)
    return u, v, cand, (jnp.take_along_axis(rows, pos, axis=1) == cand) \
        & (cand != pad_val)


def triangle_count(g: Graph, edge_chunk: int = 1 << 16, *,
                   backend: Optional[str] = None,
                   interpret: Optional[bool] = None) -> int:
    """Exact triangle count of the undirected simple graph ``g``.

    Default path: degeneracy orientation (cached in the plan) + per-edge
    sorted-adjacency intersection, chunked over edges to bound memory.
    ``backend="bsr"`` dispatches to the A∘(A·A) MXU kernel over the plan's
    cached 128×128 tiles and block triples (kernels/bsr_tricount.py);
    ``backend="sharded"`` partitions the oriented edges over the graph
    mesh (core/distributed.py) and ``psum``s the per-device counts.
    """
    if backend not in (None, "xla", "bsr", "sharded"):
        raise ValueError(f"triangle_count backends are None/'xla' (oriented "
                         f"intersection), 'bsr' (MXU kernel) or 'sharded' "
                         f"(mesh-partitioned); got {backend!r}")
    if g.n_edges == 0 or g.n_nodes == 0:
        return 0
    plan = g.plan()
    engine.select_backend(plan, backend or "xla")    # counts the choice
    if backend == "sharded":
        from ..launch.mesh import graph_mesh
        from .distributed import triangle_count_distributed
        return triangle_count_distributed(g, graph_mesh(engine.shard_count()))
    if backend == "bsr":
        from ..kernels.bsr_tricount import bsr_tricount
        from ..kernels.ops import auto_interpret
        tiles, _, _, _ = plan.bsr()
        t_ij, t_ik, t_kj = plan.tri_triples()
        six_t = bsr_tricount(jnp.minimum(tiles, 1.0), t_ij, t_ik, t_kj,
                             interpret=auto_interpret(interpret))
        return int(round(float(six_t) / 6.0))
    osrc, _, _, _ = plan.oriented()
    e = int(osrc.shape[0])
    total = 0
    for lo in range(0, e, edge_chunk):
        hi = min(lo + edge_chunk, e)
        _, _, _, hit = _triangle_hits(plan, lo, hi)
        total += int(jnp.sum(hit))
    return total


@track("algorithms.per_node_triangles", "A.per_node_triangles")
def per_node_triangles(g: Graph, edge_chunk: int = 1 << 16) -> jax.Array:
    """Triangles incident to each node (undirected simple graph)."""
    if g.n_edges == 0 or g.n_nodes == 0:
        return jnp.zeros((max(g.n_nodes, 1),), jnp.int32)[: g.n_nodes]
    plan = g.plan()
    osrc, _, _, _ = plan.oriented()
    e = int(osrc.shape[0])
    n = g.n_nodes
    counts = jnp.zeros((n,), jnp.int32)
    for lo in range(0, e, edge_chunk):
        hi = min(lo + edge_chunk, e)
        u, v, cand, hit = _triangle_hits(plan, lo, hi)
        per_edge = jnp.sum(hit, axis=1).astype(jnp.int32)        # apex count
        counts = counts.at[u].add(per_edge)
        counts = counts.at[v].add(per_edge)
        # the third vertex w of each triangle:
        w_hits = jnp.where(hit, cand, n)
        counts = counts + jnp.bincount(w_hits.reshape(-1),
                                       length=n + 1)[:n].astype(jnp.int32)
    return counts


@track("algorithms.clustering_coefficient", "A.clustering_coefficient")
def clustering_coefficient(g: Graph) -> jax.Array:
    """Local clustering coefficient per node (undirected simple graph)."""
    tri = per_node_triangles(g).astype(jnp.float32)
    deg = g.plan().out_deg.astype(jnp.float32)
    wedges = deg * (deg - 1.0) / 2.0
    return jnp.where(wedges > 0, tri / jnp.maximum(wedges, 1.0), 0.0)


# ---------------------------------------------------------------------------
# Connected components (WCC) — hash-min label propagation + pointer jumping
# ---------------------------------------------------------------------------


def _cc_body(ex, labels):
    # min label over in-neighbors (undirected view is symmetrized)
    m = ex.pull(labels, "min")
    new = jnp.minimum(labels, m)
    # pointer jumping: label <- label[label] until stable this round
    new = new[new]
    new = new[new]
    return new


@track("algorithms.connected_components", "A.connected_components")
def connected_components(g: Graph, *, backend: Optional[str] = None,
                         interpret: Optional[bool] = None) -> jax.Array:
    """Weakly-connected component labels (min node id in component).

    The ``"frontier"`` backend propagates min labels only from vertices
    whose label changed last round (no pointer jumping, more rounds, far
    less work per round on sparse graphs); both paths converge to the same
    unique fixpoint — min dense id per component.
    """
    u = g.plan().undirected()
    uplan = u.plan()
    be = engine.select_backend(uplan, backend, op="connected_components")
    labels0 = jnp.arange(u.n_nodes, dtype=jnp.int32)
    if be == "frontier" and u.n_nodes > 0:
        labels = engine.frontier_fixpoint(uplan, labels0,
                                          jnp.ones((u.n_nodes,), bool))
    else:
        ex = engine.get_exec(uplan, be, interpret=interpret)
        labels = engine.fixpoint(ex, _cc_body, labels0)
    # map back to g's dense id space; isolated vertices label themselves
    return _undirected_ids_to_g(g, u, labels)


# ---------------------------------------------------------------------------
# SSSP / BFS (paper Table 6: SSSP 7.4 s sequential on LiveJournal)
# ---------------------------------------------------------------------------


def _sssp_body(ex, dist, w):
    relaxed = ex.pull(dist, "min", edge_values=w, edge_op="add")
    return jnp.minimum(dist, relaxed)


def _sssp_capped_body(ex, st, w, cap):
    """Relaxation with a per-run round cap threaded through the state.

    Freezing at ``t >= cap`` makes a vmapped batch of runs with *different*
    caps exact: each row equals a standalone run of ``cap`` rounds — the
    mechanism behind the service's cross-``n_iter`` fusion.  The round
    counter itself freezes once the distances converge (a monotone
    relaxation that didn't change is at its fixpoint), so the
    until-unchanged driver exits early instead of grinding a
    convergence-bound cap (|V| for an uncapped fused request) to the end.
    """
    dist, t = st
    relaxed = ex.pull(dist, "min", edge_values=w, edge_op="add")
    new = jnp.where(t < cap, jnp.minimum(dist, relaxed), dist)
    return new, jnp.where(engine._changed(dist, new), t + 1, t)


def _source_caps(sources, n_iter):
    """Broadcast a scalar/array round limit to one cap per source."""
    if n_iter is None:
        return None
    return np.broadcast_to(np.atleast_1d(np.asarray(n_iter, np.int32)),
                           (int(sources.shape[0]),))


@track("algorithms.sssp", "A.sssp")
def sssp(g: Graph, source, weights: Optional[jax.Array] = None,
         n_iter=None, *, backend: Optional[str] = None,
         interpret: Optional[bool] = None) -> jax.Array:
    """Single- or multi-source shortest paths (relaxation to fixpoint).

    ``weights`` is per-edge in in-edge order (sorted by dst); defaults to 1.
    ``source`` may be a scalar (returns ``(n,)``) or an array of k sources
    (returns ``(k, n)`` — batched via ``vmap`` over the engine fixpoint, the
    data-parallel dual of SNAP's sequential Dijkstra from Table 6).
    ``n_iter`` caps relaxation rounds (None = run to convergence); it may be
    per-source — a ``(k,)`` array of caps — and each row then equals a
    standalone run with that cap (the service fuses mixed-depth requests
    this way).

    On the ``"frontier"`` backend the relaxation is frontier-sparse: only
    out-edges of vertices whose distance changed last round are relaxed,
    direction-optimizing to a dense pull when the frontier grows large.
    Results are identical to the dense backends round for round.
    """
    plan = g.plan()
    scalar = np.ndim(source) == 0
    sources = jnp.atleast_1d(jnp.asarray(source, dtype=jnp.int32))
    caps = _source_caps(sources, n_iter)
    # auto-selection routes only *single-source* runs to the frontier path:
    # a batch's union frontier densifies fast, and the vmapped dense
    # fixpoint wins there (explicit backend="frontier" batches still work)
    auto_op = "sssp" if int(sources.shape[0]) == 1 else None
    be = engine.select_backend(plan, backend,
                               op="sssp" if backend is not None else auto_op)
    w = jnp.ones((g.n_edges,), jnp.float32) if weights is None \
        else weights.astype(jnp.float32)

    if be == "frontier" and g.n_nodes > 0:
        k = int(sources.shape[0])
        dist0 = jnp.full((k, g.n_nodes), _INF) \
            .at[jnp.arange(k), sources].set(0.0)
        mask0 = jnp.zeros((g.n_nodes,), bool).at[sources].set(True)
        # unweighted runs relax with a broadcast scalar hop (no edge gather)
        fw = jnp.float32(1.0) if weights is None else w
        dists = engine.frontier_fixpoint(plan, dist0, mask0, weights=fw,
                                         caps=caps)
        return dists[0] if scalar else dists

    ex = engine.get_exec(plan, be, interpret=interpret)
    if caps is None:
        def one(s):
            dist0 = jnp.full((g.n_nodes,), _INF).at[s].set(0.0)
            return engine.fixpoint(ex, _sssp_body, dist0, args=(w,))

        dists = jax.vmap(one)(sources)
    else:
        rounds = int(caps.max()) if caps.size else 0

        def one_capped(s, cap):
            dist0 = jnp.full((g.n_nodes,), _INF).at[s].set(0.0)
            out, _ = engine.fixpoint(ex, _sssp_capped_body,
                                     (dist0, jnp.int32(0)), max_iter=rounds,
                                     args=(w, cap))
            return out

        dists = jax.vmap(one_capped)(sources, jnp.asarray(caps))
    return dists[0] if scalar else dists


@track("algorithms.bfs", "A.bfs")
def bfs(g: Graph, source, n_iter=None, *, backend: Optional[str] = None,
        interpret: Optional[bool] = None) -> jax.Array:
    """BFS levels (unweighted SSSP); -1 for unreachable.  Batched like sssp.

    ``n_iter`` is the depth limit: vertices deeper than ``n_iter`` hops
    report unreachable, exactly as if the traversal stopped there.
    """
    dist = sssp(g, source, n_iter=n_iter, backend=backend,
                interpret=interpret)
    return jnp.where(jnp.isinf(dist), -1, dist.astype(jnp.int32))


# ---------------------------------------------------------------------------
# k-core (paper Table 6: 3-core 31 s sequential)
# ---------------------------------------------------------------------------


def _k_core_body(ex, alive, k):
    # degree over alive neighbors; edges into dead nodes only affect rows
    # that the alive & ... mask kills anyway, so no dst-side mask is needed
    deg = ex.pull(alive.astype(jnp.float32), "sum")
    return alive & (deg >= k)


@track("algorithms.k_core", "A.k_core")
def k_core(g: Graph, k: int, *, backend: Optional[str] = None,
           interpret: Optional[bool] = None) -> jax.Array:
    """Boolean mask of nodes in the k-core (iterative parallel peeling)."""
    u = g.plan().undirected()
    _, ex = _exec_for(u, backend, interpret)
    alive = engine.fixpoint(ex, _k_core_body, jnp.ones((u.n_nodes,), bool),
                            args=(jnp.float32(k),))
    # vertices with no non-loop edges have undirected degree 0: in-core iff k<=0
    return _undirected_values_to_g(g, u, alive, jnp.bool_(k <= 0))


@track("algorithms.core_numbers", "A.core_numbers")
def core_numbers(g: Graph, k_max: Optional[int] = None, *,
                 backend: Optional[str] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Core number per node by sweeping k (exact; O(k_max) peels).

    All peels share one plan/exec — the sweep reuses the cached undirected
    view and sorted edge arrays across every k.
    """
    u = g.plan().undirected()
    _, ex = _exec_for(u, backend, interpret)
    if k_max is None:
        k_max = int(jnp.max(u.plan().out_deg)) if u.n_nodes else 0
    core = jnp.zeros((u.n_nodes,), jnp.int32)
    for k in range(1, k_max + 1):
        alive = engine.fixpoint(ex, _k_core_body,
                                jnp.ones((u.n_nodes,), bool),
                                args=(jnp.float32(k),))
        if not bool(jnp.any(alive)):
            break
        core = jnp.where(alive, k, core)
    return _undirected_values_to_g(g, u, core, jnp.int32(0))


# ---------------------------------------------------------------------------
# SCC (paper Table 6: 18 s sequential) — parallel coloring (Orzan) algorithm
# ---------------------------------------------------------------------------

_NOT_ASSIGNED = jnp.int32(-1)


def _scc_color_body(ex, color, un):
    # propagate color along forward edges: dst takes max(src color)
    m = ex.pull(jnp.where(un, color, _NOT_ASSIGNED), "max")
    return jnp.where(un, jnp.maximum(color, m), color)


def _scc_reach_body(ex, reach, un, color):
    # backward edge (u->v in G) propagates reach v->u, restricted to
    # unassigned endpoints of equal color: reduce out-edges to their source
    ok = (ex.out_src_vals(un) & ex.out_dst_vals(un)
          & (ex.out_src_vals(color) == ex.out_dst_vals(color)))
    ev = jnp.where(ok, ex.out_dst_vals(reach), False)
    m = ex.reduce_out(ev.astype(jnp.int32), "max")
    return reach | (m > 0)


def _scc_round(ex, scc):
    """Forward-max coloring + backward containment, one assignment round.

    1. color = max node id, propagated along *forward* edges among
       unassigned nodes, to fixpoint.
    2. nodes with color == own id are SCC roots.
    3. propagate "reached" backward from each root, restricted to nodes of
       the same color: those reached form the root's SCC.
    """
    n = ex.n_nodes
    un = scc == _NOT_ASSIGNED
    color0 = jnp.where(un, jnp.arange(n, dtype=jnp.int32), _NOT_ASSIGNED)
    color = engine.fixpoint(ex, _scc_color_body, color0, args=(un,))
    is_root = un & (color == jnp.arange(n, dtype=jnp.int32))
    reach = engine.fixpoint(ex, _scc_reach_body, is_root, args=(un, color))
    return jnp.where(un & reach, color, scc)


@track("algorithms.strongly_connected_components", "A.strongly_connected_components")
def strongly_connected_components(g: Graph, *,
                                  backend: Optional[str] = None,
                                  interpret: Optional[bool] = None
                                  ) -> jax.Array:
    """SCC id per node (id = max dense node id in the component)."""
    _, ex = _exec_for(g, backend, interpret)
    scc0 = jnp.full((g.n_nodes,), _NOT_ASSIGNED)
    # each round assigns at least the max unassigned id's component, so the
    # state strictly changes until everything is assigned — the generic
    # until-unchanged driver terminates one round after full assignment
    return engine.fixpoint(ex, _scc_round, scc0)


# ---------------------------------------------------------------------------
# HITS
# ---------------------------------------------------------------------------


def _hits_body(ex, ha):
    hub, auth = ha
    auth = ex.pull(hub, "sum")
    auth = auth / jnp.maximum(jnp.linalg.norm(auth), 1e-30)
    hub = ex.push(auth, "sum")
    hub = hub / jnp.maximum(jnp.linalg.norm(hub), 1e-30)
    return hub, auth


@track("algorithms.hits", "A.hits")
def hits(g: Graph, n_iter: int = 20, *, backend: Optional[str] = None,
         interpret: Optional[bool] = None) -> Tuple[jax.Array, jax.Array]:
    """HITS hub/authority scores (paper §4.1 mentions Hits for experts)."""
    _, ex = _exec_for(g, backend, interpret)
    ones = jnp.ones((g.n_nodes,), jnp.float32)
    return engine.fixpoint(ex, _hits_body, (ones, ones), n_iter=n_iter)


# ---------------------------------------------------------------------------
# misc measures
# ---------------------------------------------------------------------------


def degree_histogram(g: Graph, direction: str = "out") -> jax.Array:
    plan = g.plan()
    deg = plan.out_deg if direction == "out" else plan.in_deg
    mx = int(jnp.max(deg)) if g.n_nodes else 0
    return jnp.bincount(deg, length=mx + 1)


# ---------------------------------------------------------------------------
# additional centrality / community measures (SNAP-style extensions)
# ---------------------------------------------------------------------------


def _eigen_body(ex, v):
    nv = ex.pull(v, "sum")
    nv = nv + 0.01 * v   # regularizer: convergence on DAG-like graphs
    return nv / jnp.maximum(jnp.linalg.norm(nv), 1e-30)


@track("algorithms.eigenvector_centrality", "A.eigenvector_centrality")
def eigenvector_centrality(g: Graph, n_iter: int = 50, *,
                           backend: Optional[str] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Power-iteration eigenvector centrality over in-edges."""
    _, ex = _exec_for(g, backend, interpret)
    x0 = jnp.full((g.n_nodes,), 1.0 / jnp.sqrt(g.n_nodes), jnp.float32)
    return engine.fixpoint(ex, _eigen_body, x0, n_iter=n_iter)


def degree_centrality(g: Graph, direction: str = "out") -> jax.Array:
    plan = g.plan()
    deg = plan.out_deg if direction == "out" else plan.in_deg
    return deg.astype(jnp.float32) / jnp.maximum(g.n_nodes - 1, 1)


def _lp_body(ex, lab):
    """Hash-min label propagation step (min-of-mode relaxation).

    Converges to communities on modular graphs; exact CC on disconnected
    ones — the deterministic tie-break variant of synchronous LP.
    """
    m = ex.pull(lab, "min")
    return jnp.minimum(lab, m)


@track("algorithms.label_propagation", "A.label_propagation")
def label_propagation(g: Graph, n_iter: int = 20, *,
                      backend: Optional[str] = None,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Community labels by (min-)label propagation on the undirected view.

    Min-label propagation is a monotone relaxation, so the ``"frontier"``
    backend path is round-for-round identical to the dense iterate: a
    vertex whose label did not change has nothing new to propagate.
    """
    u = g.plan().undirected()
    uplan = u.plan()
    be = engine.select_backend(uplan, backend, op="label_propagation")
    labels0 = jnp.arange(u.n_nodes, dtype=jnp.int32)
    if be == "frontier" and u.n_nodes > 0:
        lab = engine.frontier_fixpoint(uplan, labels0,
                                       jnp.ones((u.n_nodes,), bool),
                                       caps=n_iter)
    else:
        ex = engine.get_exec(uplan, be, interpret=interpret)
        lab = engine.fixpoint(ex, _lp_body, labels0, n_iter=n_iter)
    return _undirected_ids_to_g(g, u, lab)


# ---------------------------------------------------------------------------
# incremental recomputation (delta-update path; see core/graph.EdgeDelta)
#
# Each helper answers "can the parent's result be reused?" and returns None
# with a logged reason when it cannot — callers fall back to a cold run.
# Soundness rests on monotonicity: for an *insert-only* delta the parent
# fixpoint is a valid upper bound of the child fixpoint under a min
# relaxation, so re-seeding the frontier with the inserted edges' endpoints
# converges to exactly the from-scratch result.  Deletions can raise values,
# which breaks the bound — they always fall back.
# ---------------------------------------------------------------------------


def _insert_only_info(g: Graph, op: str):
    info = getattr(g, "_delta", None)
    if info is None:
        _log.info("incremental.cold_fallback", op=op,
                  reason="no delta lineage")
        return None
    if not info.insert_only:
        _log.info("incremental.cold_fallback", op=op,
                  reason="delta deletes edges; parent result is no longer "
                         "an upper bound")
        return None
    return info


def incremental_sssp(g: Graph, source, parent_dist, *,
                     weights: Optional[jax.Array] = None,
                     n_iter=None) -> Optional[jax.Array]:
    """Warm single-source shortest paths after an insert-only delta.

    Re-seeds :func:`engine.frontier_fixpoint` from the parent's (fixpoint)
    distance vector with the inserted edges' sources as the frontier: only
    regions whose distance actually improves are re-relaxed.  Returns None
    (caller runs cold) when unsound: deletions, weighted edges (the parent
    vector's weight keying cannot be verified), a round cap (a capped run
    is not a fixpoint), or a batched source.
    """
    info = _insert_only_info(g, "sssp")
    if info is None:
        return None
    if weights is not None:
        _log.info("incremental.cold_fallback", op="sssp",
                  reason="weighted run")
        return None
    if n_iter is not None:
        _log.info("incremental.cold_fallback", op="sssp",
                  reason="capped run is not a fixpoint")
        return None
    if np.ndim(source) != 0:
        _log.info("incremental.cold_fallback", op="sssp",
                  reason="batched sources")
        return None
    if g.n_nodes == 0:
        return jnp.zeros((0,), jnp.float32)
    dist0 = jnp.asarray(parent_dist, jnp.float32)
    mask = np.zeros((g.n_nodes,), bool)
    mask[info.add_src] = True
    return engine.frontier_fixpoint(g.plan(), dist0, jnp.asarray(mask),
                                    weights=jnp.float32(1.0))


def incremental_bfs(g: Graph, source, parent_levels, *,
                    n_iter=None) -> Optional[jax.Array]:
    """Warm BFS levels (unweighted :func:`incremental_sssp`); -1 unreachable."""
    pd = jnp.asarray(parent_levels)
    dist = incremental_sssp(
        g, source, jnp.where(pd < 0, _INF, pd.astype(jnp.float32)),
        n_iter=n_iter)
    if dist is None:
        return None
    return jnp.where(jnp.isinf(dist), -1, dist.astype(jnp.int32))


def incremental_connected_components(g: Graph, parent_labels
                                     ) -> Optional[jax.Array]:
    """Warm WCC labels after an insert-only delta.

    Works in the undirected view's id space: the parent labels translate to
    a valid upper bound (each vertex's label is the u-id of a member of its
    own component), and the inserted edges' endpoints seed the frontier, so
    only merging components are re-labeled.  Requires the plan's undirected
    view to be a *patched* one (it carries its own delta lineage); when the
    patch fell back to a rebuild there is no per-edge delta to seed from.
    """
    info = _insert_only_info(g, "connected_components")
    if info is None:
        return None
    if g.n_nodes == 0:
        return jnp.zeros((0,), jnp.int32)
    u = g.plan().undirected()
    uinfo = getattr(u, "_delta", None)
    if uinfo is None:
        _log.info("incremental.cold_fallback", op="connected_components",
                  reason="undirected view was rebuilt (no delta lineage)")
        return None
    if u.n_nodes == 0:
        return _undirected_ids_to_g(g, u, jnp.zeros((0,), jnp.int32))
    # translate parent g-space labels to u-space: label -> original id ->
    # u-dense id; the min-id member of every component is present in u
    # (defensively: fall back to own id, still an upper bound)
    orig_u = u.node_ids[: u.n_nodes]
    gx = g.dense_of(orig_u)
    lab_orig = g.original_of(jnp.asarray(parent_labels, jnp.int32)[gx])
    pos = jnp.clip(u.dense_of(lab_orig), 0, u.n_nodes - 1)
    own = jnp.arange(u.n_nodes, dtype=jnp.int32)
    init_u = jnp.where(u.node_ids[pos] == lab_orig, pos, own).astype(jnp.int32)
    mask = np.zeros((u.n_nodes,), bool)
    mask[uinfo.add_src] = True
    mask[uinfo.add_dst] = True
    labels = engine.frontier_fixpoint(u.plan(), init_u, jnp.asarray(mask))
    return _undirected_ids_to_g(g, u, labels)


def incremental_label_propagation(g: Graph, parent_labels, n_iter: int = 20
                                  ) -> Optional[jax.Array]:
    """Warm min-label propagation after an insert-only delta.

    Only sound when the round cap cannot bind: a capped LP result is not a
    fixpoint (a label may travel further through an inserted edge than the
    parent run's cap allowed).  With ``n_iter >= |V|`` the run is the
    min-label fixpoint — component min-labels — which is exactly what
    :func:`incremental_connected_components` computes.
    """
    info = _insert_only_info(g, "label_propagation")
    if info is None:
        return None
    u = g.plan().undirected()
    if int(n_iter) < u.n_nodes:
        _log.info("incremental.cold_fallback", op="label_propagation",
                  reason="n_iter < |V| may cap the propagation",
                  n_iter=int(n_iter), n_nodes=u.n_nodes)
        return None
    return incremental_connected_components(g, parent_labels)


@track("algorithms.closeness_centrality", "A.closeness_centrality")
def closeness_centrality(g: Graph, sources: Optional[jax.Array] = None,
                         n_samples: int = 16, *,
                         backend: Optional[str] = None,
                         interpret: Optional[bool] = None) -> jax.Array:
    """Sampled closeness: average reciprocal distance over sampled sources
    (exact if sources covers all nodes).  Batched multi-source sssp."""
    n = g.n_nodes
    if sources is None:
        step = max(n // max(n_samples, 1), 1)
        sources = jnp.arange(0, n, step, dtype=jnp.int32)[: n_samples]
    dists = sssp(g, sources, backend=backend, interpret=interpret)    # (k, n)
    finite = jnp.isfinite(dists)
    recip = jnp.where(finite & (dists > 0), 1.0 / jnp.maximum(dists, 1e-9), 0.0)
    return jnp.sum(recip, axis=0) / jnp.maximum(jnp.sum(finite, axis=0), 1)
