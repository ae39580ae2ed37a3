"""Layer 1 of the unified traversal engine: the memoized :class:`GraphPlan`.

Ringo's interactive loop (§2.2) repeatedly runs algorithms against the same
in-memory graph; the representation is pre-optimized once so every call after
the first is pure traversal.  Our seed re-derived the access structures on
every invocation (``out_edges()`` / ``_row_of_edge`` / orientation /
re-blocking).  ``GraphPlan`` hoists all of that into a per-``Graph`` cache,
keyed by graph *identity* via :meth:`repro.core.graph.Graph.plan` — functional
updates (``add_edges`` / ``delete_edges``) return fresh ``Graph`` objects, so
a stale plan can never be observed.

Eagerly built (cheap, needed by every traversal):

    in_src / in_dst    edge arrays sorted by destination (pull order)
    out_src / out_dst  edge arrays sorted by source (push order)
    out_deg / in_deg   degree vectors
    inv_out_deg        1/out-degree (0 for sinks) — PageRank mass split
    dangling           out_deg == 0 mask

Lazily built and cached on first use:

    undirected()       symmetrized simple-graph view (CC / k-core / LP / tri)
    oriented()         degeneracy-oriented padded adjacency (triangles)
    csr_out()          trimmed out-CSR (ptr, idx, deg_pad) — the frontier
                       backend's push-side gather: adjacency slices of only
                       the active vertices (sparse BFS/SSSP)
    csr_in()           trimmed in-CSR, the pull-side dual
    in_perm_out()      permutation taking in-edge-order per-edge values
                       (the sssp weight convention) to out-edge order, so
                       the frontier push relaxes with the same weights
    bsr(block)         128x128 BSR tiles of M[dst, src] (SpMV pull backend)
    bsr_t(block)       transpose tiles M[src, dst] (SpMV push backend — the
                       HITS hub step and every other out-edge reduction)
    tri_triples(block) BSR tile triples for A.(A@A) triangle counting
    chunk_layout_in / chunk_layout_out
                       static chunk structure for the Pallas segment-sum
                       backend (pull / push reduction order respectively)
    sharded(d)         per-shard arrays for the multi-device "sharded"
                       backend: contiguous vertex-range partition of both
                       CSR orders, halo/boundary index sets for the cut
                       edges, and padded degree slices — one ShardPlan per
                       device count, placed on the 1-D graph mesh

The execution primitives that consume these live in
:mod:`repro.core.engine`; per-backend ``Exec`` pytrees are cached here in
``execs`` so repeated calls reuse both the arrays *and* the jit caches.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .graph import EdgeDelta, Graph, building
from ..kernels.segment_sum import DEFAULT_BLOCK, DEFAULT_CHUNK, chunk_layout

__all__ = ["GraphPlan", "ShardPlan", "EVICTABLE_FAMILIES"]

# Derived-array families a plan can drop and rebuild on next touch.  "base"
# (the eager sorted-edge/degree arrays) and the graph's own CSR storage are
# deliberately absent: they are the plan, not a cache over it.
EVICTABLE_FAMILIES: Tuple[str, ...] = (
    "undirected", "oriented", "csr", "perm", "bsr", "tri", "chunks",
    "sharded", "execs")


class _ShardDir(NamedTuple):
    """One direction (pull or push) of a vertex-range partition.

    All per-shard buffers use the flat ``(d * per_shard,)`` layout and are
    replicated on the graph mesh; the engine's manual regions slice shard
    ``i``'s block out via ``axis_index``.  ``gather_idx`` addresses the
    concatenation ``[local x (ns values), halo (d * halo values)]`` built
    inside each round's boundary exchange; ``seg_local`` maps each edge
    slot to its shard-local segment, with padding slots pointing at the
    overflow segment ``ns`` (sliced off after the reduction, so pad slots
    can never perturb a real vertex — not even by adding a signed zero).
    """

    es: int                 # padded edge slots per shard
    halo: int               # boundary slots per shard (max cut fan-in)
    gather_idx: jax.Array   # (d*es,) int32 into [local(ns) | halo(d*halo)]
    seg_local: jax.Array    # (d*es,) int32 local segment id, pad -> ns
    edge_slot: jax.Array    # (E,) int32: global edge order -> flat slot
    boundary: jax.Array     # (d*halo,) int32 local ids each shard exports


class ShardPlan(NamedTuple):
    """Per-device-count derived arrays for the "sharded" engine backend.

    ``pull`` partitions the dst-sorted in-edges by destination range (each
    vertex's whole in-segment stays on its owner, in order — this is what
    makes the shard-local segment reduction bit-identical to the global
    one); ``push`` partitions the src-sorted out-edges by source range.
    ``out_deg`` / ``in_deg`` are the degree vectors padded to ``d * ns``,
    replicated on the mesh like every other per-shard buffer.
    """

    d: int                  # shard / device count
    ns: int                 # vertices per shard (ceil(n / d), >= 1)
    axis: str               # mesh axis name
    mesh: object            # the 1-D jax Mesh (hashable, identity-cached)
    pull: _ShardDir
    push: _ShardDir
    out_deg: jax.Array      # (d*ns,) padded, mesh-replicated
    in_deg: jax.Array       # (d*ns,) padded, mesh-replicated

    def halo_bytes_per_round(self, itemsize: int = 4) -> int:
        """Bytes materialized per device by one pull-side halo all-gather."""
        return self.d * self.pull.halo * itemsize


def _build_shard_dir(key: np.ndarray, other: np.ndarray, d: int, ns: int,
                     spec) -> _ShardDir:
    """Partition one edge order by contiguous ``key`` ranges.

    ``key`` is the sorted segment endpoint (dst for pull, src for push),
    ``other`` the gathered endpoint.  Shard ``i`` owns vertices
    ``[i*ns, (i+1)*ns)`` and therefore the contiguous edge slice whose keys
    fall in that range.  Cut edges (``other`` owned elsewhere) index into
    the halo: owner ``o`` exports its sorted unique referenced vertices
    (its boundary set), and the flat halo position is
    ``ns + o*halo + rank``.
    """
    e = int(key.shape[0])
    key = key.astype(np.int64)
    other = other.astype(np.int64)
    starts = np.searchsorted(key, np.arange(d, dtype=np.int64) * ns,
                             side="left")
    ends = np.searchsorted(key, np.arange(1, d + 1, dtype=np.int64) * ns,
                           side="left")
    es = max(int((ends - starts).max()) if d else 0, 1)
    shard_of = key // ns
    owner_of = other // ns
    remote = owner_of != shard_of
    # owner o's boundary set: the vertices it owns that another shard's
    # edges reference, ascending (a presence table, not a sort)
    referenced = np.zeros((d * ns,), bool)
    referenced[other[remote]] = True
    bnd_sets = [np.flatnonzero(referenced[o * ns:(o + 1) * ns])
                for o in range(d)]
    halo = max(max((v.size for v in bnd_sets), default=0), 1)
    boundary = np.zeros((d, halo), np.int32)
    halo_pos = np.zeros((d * ns,), np.int64)
    for o, vs in enumerate(bnd_sets):
        boundary[o, : vs.size] = vs
        halo_pos[o * ns + vs] = ns + o * halo + np.arange(vs.size)
    gidx_e = np.where(remote, halo_pos[other], other - shard_of * ns)
    gidx = np.zeros((d, es), np.int32)
    seg = np.full((d, es), ns, np.int32)
    slot = np.zeros((e,), np.int32)
    for i in range(d):
        s0, s1 = int(starts[i]), int(ends[i])
        c = s1 - s0
        gidx[i, :c] = gidx_e[s0:s1]
        seg[i, :c] = key[s0:s1] - i * ns
        slot[s0:s1] = i * es + np.arange(c, dtype=np.int32)
    return _ShardDir(
        es=es, halo=halo,
        gather_idx=jax.device_put(jnp.asarray(gidx.reshape(-1)), spec),
        seg_local=jax.device_put(jnp.asarray(seg.reshape(-1)), spec),
        edge_slot=jnp.asarray(slot),
        boundary=jax.device_put(jnp.asarray(boundary.reshape(-1)), spec))


def _tree_bytes(obj, seen: set) -> int:
    """Sum array bytes in a nested structure, counting each buffer once.

    ``seen`` carries the ids of buffers already charged elsewhere (the
    graph's own CSR storage, the parent plan's arrays a patched member
    shares) so aliased members — ``csr_out()`` returning ``g.out_idx``, a
    patched BSR sharing the parent's ``rows``/``cols``, exec pytrees holding
    references into plan arrays — never double-count.
    """
    if obj is None:
        return 0
    if isinstance(obj, (tuple, list)):
        return sum(_tree_bytes(x, seen) for x in obj)
    if isinstance(obj, dict):
        return sum(_tree_bytes(x, seen) for x in obj.values())
    if isinstance(obj, Graph):
        total = _tree_bytes((obj.node_ids, obj.out_ptr, obj.out_idx,
                             obj.in_ptr, obj.in_idx), seen)
        if obj._plan is not None:
            total += sum(obj._plan.nbytes_by_family().values())
        return total
    if hasattr(obj, "dtype") and hasattr(obj, "size"):
        k = id(obj)
        if k in seen:
            return 0
        seen.add(k)
        return int(obj.size) * int(np.dtype(obj.dtype).itemsize)
    try:                               # exec pytrees and anything jax knows
        leaves = jax.tree_util.tree_leaves(obj)
    except Exception:
        return 0
    if len(leaves) == 1 and leaves[0] is obj:
        return 0                       # opaque scalar leaf, not a container
    return sum(_tree_bytes(x, seen) for x in leaves)


@dataclass
class GraphPlan:
    """Precomputed traversal arrays for one :class:`Graph` (identity-cached)."""

    graph: Graph
    n_nodes: int
    n_edges: int
    in_src: jax.Array
    in_dst: jax.Array
    out_src: jax.Array
    out_dst: jax.Array
    out_deg: jax.Array
    in_deg: jax.Array
    inv_out_deg: jax.Array
    dangling: jax.Array
    # lazy caches — never hashed/compared, filled on first use
    execs: Dict = field(default_factory=dict, repr=False, compare=False)
    _undirected: Optional[Graph] = field(default=None, repr=False, compare=False)
    _oriented: Optional[Tuple] = field(default=None, repr=False, compare=False)
    _csr_out: Optional[Tuple] = field(default=None, repr=False, compare=False)
    _csr_in: Optional[Tuple] = field(default=None, repr=False, compare=False)
    _in_perm_out: Optional[jax.Array] = field(default=None, repr=False,
                                              compare=False)
    _bsr: Dict = field(default_factory=dict, repr=False, compare=False)
    _bsr_t: Dict = field(default_factory=dict, repr=False, compare=False)
    _tri_triples: Dict = field(default_factory=dict, repr=False, compare=False)
    _chunks_in: Dict = field(default_factory=dict, repr=False, compare=False)
    _chunks_out: Dict = field(default_factory=dict, repr=False, compare=False)
    _sharded: Dict = field(default_factory=dict, repr=False, compare=False)
    # delta lineage (set by :meth:`patch` only): dense ids of the vertices
    # the delta touched, the parent's plan, and the _DeltaInfo it came from
    dirty_vertices: Optional[np.ndarray] = field(default=None, repr=False,
                                                 compare=False)
    _parent: Optional["GraphPlan"] = field(default=None, repr=False,
                                           compare=False)
    _info: Optional[object] = field(default=None, repr=False, compare=False)

    # -- construction -----------------------------------------------------------
    @classmethod
    def build(cls, g: Graph) -> "GraphPlan":
        in_src, in_dst = g.in_edges()
        out_src, out_dst = g.out_edges()
        out_deg = g.out_degrees()
        in_deg = g.in_degrees()
        out_deg_f = out_deg.astype(jnp.float32)
        inv_out_deg = jnp.where(out_deg > 0,
                                1.0 / jnp.maximum(out_deg_f, 1.0), 0.0)
        dangling = out_deg == 0
        return cls(graph=g, n_nodes=g.n_nodes, n_edges=g.n_edges,
                   in_src=in_src, in_dst=in_dst,
                   out_src=out_src, out_dst=out_dst,
                   out_deg=out_deg, in_deg=in_deg,
                   inv_out_deg=inv_out_deg, dangling=dangling)

    @classmethod
    def patch(cls, g: Graph, info) -> "GraphPlan":
        """Derive the plan from the parent's instead of re-sorting.

        ``info`` is the ``_DeltaInfo`` left by ``Graph.apply_delta``'s fast
        path: it already holds the merged edge lists in both CSR orders as
        host arrays, so the eager fields are direct uploads (no device
        lexsort, no ``_row_of_edge`` searchsorted), and degrees are cheap
        slices of the already-patched row pointers.  The lazy structures
        below patch the parent's cached versions where that is sound
        (undirected view, BSR tiles, weight permutation) and rebuild
        otherwise.  ``dirty_vertices`` feeds incremental recomputation in
        :mod:`repro.core.algorithms`.
        """
        parent = info.parent.plan()
        out_deg = g.out_degrees()
        in_deg = g.in_degrees()
        out_deg_f = out_deg.astype(jnp.float32)
        inv_out_deg = jnp.where(out_deg > 0,
                                1.0 / jnp.maximum(out_deg_f, 1.0), 0.0)
        return cls(graph=g, n_nodes=g.n_nodes, n_edges=g.n_edges,
                   in_src=jnp.asarray(info.in_src),
                   in_dst=jnp.asarray(info.in_dst),
                   out_src=jnp.asarray(info.out_src),
                   out_dst=jnp.asarray(info.out_dst),
                   out_deg=out_deg, in_deg=in_deg,
                   inv_out_deg=inv_out_deg, dangling=out_deg == 0,
                   dirty_vertices=info.dirty, _parent=parent, _info=info)

    # -- lazy derived structures -------------------------------------------------
    def undirected(self) -> Graph:
        """Symmetrized simple-graph view, built once per plan.

        For an insert-only delta child this *patches* the parent's
        undirected view via ``apply_delta`` (symmetrize the inserted
        non-loop edges in original-id space) instead of re-symmetrizing the
        whole graph — and the patched view carries its own delta lineage,
        which is what lets connected-components warm-start.  Deletions fall
        back to a full rebuild.
        """
        if self._undirected is None:
            with building("undirected"):
                info = self._info
                if info is not None and info.insert_only:
                    osrc = np.asarray(self.graph.original_of(info.add_src))
                    odst = np.asarray(self.graph.original_of(info.add_dst))
                    keep = osrc != odst
                    self._undirected = self._parent.undirected().apply_delta(
                        EdgeDelta.inserts(
                            np.concatenate([osrc[keep], odst[keep]]),
                            np.concatenate([odst[keep], osrc[keep]])))
                else:
                    self._undirected = self.graph.to_undirected()
        return self._undirected

    def oriented(self) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
        """Degeneracy-oriented padded adjacency ``(osrc, odst, nbr, odeg)``.

        Orient each undirected edge from its lower-(degree, id) endpoint to
        the higher one; every triangle then has exactly one "apex" and is
        counted once.  Max oriented out-degree is O(sqrt(E)) — this bounds
        the padded matrix width, the TPU dual of the paper's per-node
        adjacency vectors.
        """
        if self._oriented is None:
            with building("oriented"):
                # on the host, like the CSR build: the device sorts here
                # compile anew for every edge count
                src = np.asarray(self.out_src)
                dst = np.asarray(self.out_dst)
                deg = np.asarray(self.out_deg)
                n = self.n_nodes
                keep = (deg[src] < deg[dst]) | \
                    ((deg[src] == deg[dst]) & (src < dst))
                osrc, odst = src[keep], dst[keep]
                odeg = np.bincount(osrc, minlength=n)[:n].astype(np.int32)
                max_deg = int(odeg.max()) if osrc.size else 0
                order_ = np.lexsort((odst, osrc))
                s_sorted, d_sorted = osrc[order_], odst[order_]
                ptr = np.concatenate([[0], np.cumsum(odeg)])
                # scatter into (n, max_deg) padded matrix; pad with n
                # (sorts last)
                slot = np.arange(osrc.size) - ptr[s_sorted]
                nbr = np.full((n, max(max_deg, 1)), n, dtype=np.int32)
                nbr[s_sorted, slot] = d_sorted
                self._oriented = tuple(jnp.asarray(a, jnp.int32)
                                       for a in (osrc, odst, nbr, odeg))
        return self._oriented

    def csr_out(self) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Out-CSR for frontier gathers: ``(ptr, idx, deg_pad)``.

        ``ptr`` is the trimmed ``(n+1,)`` row-pointer prefix (``ptr[n]`` is
        the edge count), ``idx`` the capacity-padded neighbor array, and
        ``deg_pad`` an ``(n+1,)`` degree vector whose sentinel row ``n``
        (the frontier pad vertex) has degree 0 — padded frontier slots
        contribute no edges.
        """
        if self._csr_out is None:
            with building("csr_out"):
                g, n = self.graph, self.n_nodes
                deg_pad = jnp.concatenate(
                    [self.out_deg, jnp.zeros((1,), self.out_deg.dtype)])
                self._csr_out = (g.out_ptr[: n + 1], g.out_idx, deg_pad)
        return self._csr_out

    def csr_in(self) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """In-CSR ``(ptr, idx, deg_pad)`` — the pull-side frontier dual.

        Reserved for a sparse *pull* phase (gathering in-edges of only the
        unsettled vertices); today's direction-optimized dense pull reduces
        over the sorted edge arrays directly, so nothing in the engine
        consumes this yet.
        """
        if self._csr_in is None:
            with building("csr_in"):
                g, n = self.graph, self.n_nodes
                deg_pad = jnp.concatenate(
                    [self.in_deg, jnp.zeros((1,), self.in_deg.dtype)])
                self._csr_in = (g.in_ptr[: n + 1], g.in_idx, deg_pad)
        return self._csr_in

    def in_perm_out(self) -> jax.Array:
        """Permutation ``p`` with ``w_out = w_in[p]``.

        Per-edge values follow the sssp convention (in-edge order, sorted by
        dst); the frontier push walks out-edge CSR order (sorted by src).
        ``p[j]`` is the in-order position of the j-th out-order edge, so one
        gather re-keys weights once per call.
        """
        if self._in_perm_out is None:
            with building("in_perm_out"):
                info = self._info
                if info is not None:
                    p = _host_in_perm_out(info)
                    if p is not None:
                        self._in_perm_out = jnp.asarray(p)
                        return self._in_perm_out
                # sorting the in-order edge list by (src, dst) yields out
                # order; on the host, like the CSR build (a device sort of
                # every edge compiles anew per edge count, for minutes at
                # tens of millions)
                keys = ((np.asarray(self.in_src).astype(np.int64) << 32)
                        | np.asarray(self.in_dst).astype(np.int64))
                self._in_perm_out = jnp.asarray(
                    np.argsort(keys, kind="stable").astype(np.int32))
        return self._in_perm_out

    def bsr(self, block: int = DEFAULT_BLOCK
            ) -> Tuple[jax.Array, jax.Array, jax.Array, int]:
        """Unweighted BSR tiles of M[dst, src] (the pull/SpMV layout)."""
        if block not in self._bsr:
            with building("bsr"):
                patched = self._patched_bsr(block, transpose=False)
                if patched is not None:
                    self._bsr[block] = patched
                else:
                    from ..kernels.ops import edges_to_bsr
                    self._bsr[block] = edges_to_bsr(np.asarray(self.in_src),
                                                    np.asarray(self.in_dst),
                                                    self.n_nodes, block=block)
        return self._bsr[block]

    def bsr_t(self, block: int = DEFAULT_BLOCK
              ) -> Tuple[jax.Array, jax.Array, jax.Array, int]:
        """Transpose BSR tiles: M[src, dst] (the push/SpMV layout).

        ``engine.push(x, "sum")`` is ``y[u] = Σ_{u→v} x[v]`` — an SpMV with
        the edge matrix oriented source-major.  Without these tiles the
        "bsr" backend silently fell back to XLA for every push (the HITS hub
        step, SCC's backward pass); with them the push takes the same MXU
        path as the pull.
        """
        if block not in self._bsr_t:
            with building("bsr_t"):
                patched = self._patched_bsr(block, transpose=True)
                if patched is not None:
                    self._bsr_t[block] = patched
                else:
                    from ..kernels.ops import edges_to_bsr
                    # edges_to_bsr(a, b) builds M[b, a]: pass (dst, src) for
                    # M[src, dst]
                    self._bsr_t[block] = edges_to_bsr(
                        np.asarray(self.out_dst), np.asarray(self.out_src),
                        self.n_nodes, block=block)
        return self._bsr_t[block]

    def _patched_bsr(self, block: int, transpose: bool):
        """Parent tiles + scatter-add of the inserted edges, when sound.

        Sound iff the delta is insert-only (a deleted pair's tile decrement
        would need its parent multiplicity) and every inserted edge lands in
        a tile the parent already materialized (tile *structure* unchanged,
        so ``rows``/``cols`` and any derived triples are shared).  Inserts
        are deduped by ``apply_delta``, so each adds exactly 1.0.
        """
        info = self._info
        if info is None or not info.insert_only:
            return None
        parent = self._parent
        cache = parent._bsr_t if transpose else parent._bsr
        if block not in cache:
            return None
        tiles, rows, cols, nb = cache[block]
        if info.add_src.size == 0:
            return (tiles, rows, cols, nb)
        if transpose:
            rv, cv = info.add_src, info.add_dst   # M[src, dst]
        else:
            rv, cv = info.add_dst, info.add_src   # M[dst, src]
        want = (rv // block).astype(np.int64) * nb + (cv // block)
        pkeys = np.asarray(rows).astype(np.int64) * nb + np.asarray(cols)
        if pkeys.size == 0:
            return None
        order = np.argsort(pkeys, kind="stable")
        pos = np.minimum(np.searchsorted(pkeys[order], want), pkeys.size - 1)
        if not bool(np.all(pkeys[order][pos] == want)):
            return None  # an insert opens a brand-new tile -> rebuild
        tidx = order[pos]
        new_tiles = tiles.at[jnp.asarray(tidx),
                             jnp.asarray(rv % block),
                             jnp.asarray(cv % block)].add(1.0)
        return (new_tiles, rows, cols, nb)

    def tri_triples(self, block: int = DEFAULT_BLOCK
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Tile triples (I,J),(I,K),(K,J) for the BSR triangle kernel."""
        if block not in self._tri_triples:
            with building("tri_triples"):
                _, rows, cols, _ = self.bsr(block)
                parent = self._parent
                if parent is not None and block in parent._tri_triples \
                        and block in parent._bsr \
                        and parent._bsr[block][1] is rows:
                    # patched BSR kept the parent's tile structure -> the
                    # (I,J),(I,K),(K,J) triples are byte-identical
                    self._tri_triples[block] = parent._tri_triples[block]
                else:
                    from ..kernels.ops import build_block_triples
                    self._tri_triples[block] = build_block_triples(
                        np.asarray(rows), np.asarray(cols))
        return self._tri_triples[block]

    def chunk_layout_in(self, chunk: int = DEFAULT_CHUNK):
        """Pallas chunk structure for per-destination (pull) reductions."""
        if chunk not in self._chunks_in:
            with building("chunk_layout_in"):
                self._chunks_in[chunk] = _device_layout(chunk_layout(
                    np.asarray(self.in_dst), self.n_nodes, chunk))
        return self._chunks_in[chunk]

    def chunk_layout_out(self, chunk: int = DEFAULT_CHUNK):
        """Pallas chunk structure for per-source (push) reductions."""
        if chunk not in self._chunks_out:
            with building("chunk_layout_out"):
                self._chunks_out[chunk] = _device_layout(chunk_layout(
                    np.asarray(self.out_src), self.n_nodes, chunk))
        return self._chunks_out[chunk]

    def sharded(self, n_shards: int, axis: Optional[str] = None) -> ShardPlan:
        """Vertex-range partition over ``n_shards`` devices, memoized per count.

        Partitioning happens once on the host (numpy over the already-sorted
        edge arrays — contiguous range split is two searchsorteds per
        direction); the resulting buffers are placed on the cached 1-D graph
        mesh.  A delta child starts with an empty ``_sharded`` cache, so
        ``apply_delta`` invalidation falls out of plan identity exactly like
        every other family; :meth:`evict` can drop the whole dict and the
        next touch rebuilds bit-identically.
        """
        from ..launch.mesh import GRAPH_AXIS, graph_mesh
        from ..launch.sharding import graph_replicated_spec
        axis = GRAPH_AXIS if axis is None else axis
        d = int(n_shards)
        if d < 1:
            raise ValueError(f"sharded() needs >= 1 shard, got {d}")
        if d not in self._sharded:
            with building("sharded"):
                mesh = graph_mesh(d, axis)
                # replicated placement: the engine's manual regions take
                # every input full-shape (in_specs P()) and slice their own
                # shard via axis_index — see ShardedExec in core/engine.py
                # for why GSPMD is given no sharding decisions at all on
                # this path
                spec = graph_replicated_spec(mesh)
                n = self.n_nodes
                ns = max(-(-n // d) if d else 1, 1)
                pull = _build_shard_dir(np.asarray(self.in_dst),
                                        np.asarray(self.in_src), d, ns, spec)
                push = _build_shard_dir(np.asarray(self.out_src),
                                        np.asarray(self.out_dst), d, ns, spec)
                pad = d * ns - n
                out_deg = jax.device_put(
                    jnp.pad(self.out_deg, (0, pad)), spec)
                in_deg = jax.device_put(
                    jnp.pad(self.in_deg, (0, pad)), spec)
                self._sharded[d] = ShardPlan(d=d, ns=ns, axis=axis, mesh=mesh,
                                             pull=pull, push=push,
                                             out_deg=out_deg, in_deg=in_deg)
        return self._sharded[d]

    # -- byte accounting + eviction ----------------------------------------------
    def _families(self) -> Dict[str, object]:
        """Family name -> the cached member(s) it covers (None/{} = cold)."""
        return {
            "base": (self.in_src, self.in_dst, self.out_src, self.out_dst,
                     self.out_deg, self.in_deg, self.inv_out_deg,
                     self.dangling),
            "undirected": self._undirected,
            "oriented": self._oriented,
            "csr": (self._csr_out, self._csr_in),
            "perm": self._in_perm_out,
            "bsr": (self._bsr, self._bsr_t),
            "tri": self._tri_triples,
            "chunks": (self._chunks_in, self._chunks_out),
            "sharded": self._sharded,
            "execs": self.execs,
            "lineage": self._info,
        }

    def _shared_ids(self) -> set:
        """Buffer ids charged to someone else: the graph's CSR storage and —
        for a patched plan — everything the parent plan already owns."""
        g = self.graph
        seen = {id(a) for a in (g.node_ids, g.out_ptr, g.out_idx,
                                g.in_ptr, g.in_idx)}
        parent = self._parent
        if parent is not None:
            sink: set = set()
            for member in parent._families().values():
                _tree_bytes(member, sink)
            seen |= sink
            pg = parent.graph
            seen |= {id(a) for a in (pg.node_ids, pg.out_ptr, pg.out_idx,
                                     pg.in_ptr, pg.in_idx)}
        return seen

    def nbytes_by_family(self) -> Dict[str, int]:
        """Derived bytes this plan holds, per family, aliases excluded.

        ``base`` is the eager sorted-edge/degree arrays (never evictable —
        they *are* the plan); ``lineage`` the host-side ``_DeltaInfo`` merge
        arrays a patched plan keeps for retention/warm starts.  Families in
        :data:`EVICTABLE_FAMILIES` can be dropped via :meth:`evict` and
        re-derive bit-identically on next touch.
        """
        seen = self._shared_ids()
        out: Dict[str, int] = {}
        for name, member in self._families().items():
            if name == "lineage":
                info = member
                out[name] = 0 if info is None else sum(
                    a.nbytes for a in (info.add_src, info.add_dst,
                                       info.del_src, info.del_dst, info.dirty,
                                       info.out_src, info.out_dst,
                                       info.in_src, info.in_dst))
            else:
                out[name] = _tree_bytes(member, seen)
        return out

    def nbytes(self) -> int:
        """Total derived bytes held by this plan (aliases excluded)."""
        return sum(self.nbytes_by_family().values())

    def evictable_bytes(self) -> int:
        fams = self.nbytes_by_family()
        return sum(fams[f] for f in EVICTABLE_FAMILIES)

    def evict(self, family: str) -> int:
        """Drop one re-derivable family; returns the bytes it held.

        Transparent by construction: every lazy getter rebuilds from the
        graph/base arrays (deterministically, so results are bit-identical),
        and evicting any array family also clears the cached ``Exec``
        pytrees, whose leaves reference the evicted buffers and would
        otherwise keep them alive.
        """
        if family not in EVICTABLE_FAMILIES:
            raise ValueError(f"family {family!r} is not evictable; "
                             f"have {EVICTABLE_FAMILIES}")
        fams = self.nbytes_by_family()
        freed = fams[family]
        if family == "undirected":
            self._undirected = None
        elif family == "oriented":
            self._oriented = None
        elif family == "csr":
            self._csr_out = None
            self._csr_in = None
        elif family == "perm":
            self._in_perm_out = None
        elif family == "bsr":
            self._bsr = {}
            self._bsr_t = {}
        elif family == "tri":
            self._tri_triples = {}
        elif family == "chunks":
            self._chunks_in = {}
            self._chunks_out = {}
        elif family == "sharded":
            self._sharded = {}
        if family != "execs" and self.execs:
            freed += fams["execs"]
            self.execs = {}
        elif family == "execs":
            self.execs = {}
        return freed

    def evict_all(self) -> int:
        """Drop every re-derivable family; returns total bytes freed."""
        return sum(self.evict(f) for f in EVICTABLE_FAMILIES)


def _host_in_perm_out(info) -> Optional[np.ndarray]:
    """Host-side weight permutation from the delta's merged edge lists.

    The in-order list is ascending in ``(dst, src)``, so the in-order slot
    of each out-order edge is one searchsorted over 64-bit pair keys — no
    device lexsort.  Duplicate edges make the key->slot map ambiguous;
    return None so the caller falls back to the stable lexsort.
    """
    ki = (info.in_dst.astype(np.int64) << 32) | info.in_src.astype(np.int64)
    if ki.size and bool(np.any(ki[1:] == ki[:-1])):
        return None
    ko = (info.out_dst.astype(np.int64) << 32) | info.out_src.astype(np.int64)
    return np.searchsorted(ki, ko).astype(np.int32)


def _device_layout(layout):
    slot_entry, local_ids, chunk_block, nb, total = layout
    return (jnp.asarray(slot_entry), jnp.asarray(local_ids),
            jnp.asarray(chunk_block), nb, total)
