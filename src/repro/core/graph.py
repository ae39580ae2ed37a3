"""Graph data structure (Ringo §2.2) — static-shape dual CSR in JAX.

Ringo represents a directed graph as a hash table of nodes, each node holding
two *sorted adjacency vectors* (in- and out-neighbors).  The representation
targets (a) fast neighborhood access for traversal and (b) dynamism.

TPU/JAX adaptation (DESIGN.md §2): XLA has no pointer-stable hash tables, so
we keep the *logical* structure — per-node sorted neighbor lists, both
directions — in **padded CSR** form with densely renumbered node ids:

    node_ids : (node_cap,)   original ids, ascending (padding = INT32_MAX)
    out_ptr  : (node_cap+1,) CSR row pointers (out-adjacency)
    out_idx  : (edge_cap,)   dense dst ids, sorted within each row
    in_ptr   : (node_cap+1,)
    in_idx   : (edge_cap,)   dense src ids, sorted within each row

The hash-table lookup ``id -> node`` becomes ``searchsorted(node_ids, id)``
(log n, vectorized over queries); updates are functional rebuilds via sorted
merge (O(E log E), fully parallel) instead of O(deg) in-place edits.
Capacities are power-of-two bucketed like tables.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from .provenance import track, version_of
from .table import next_capacity

__all__ = ["Graph", "EdgeDelta", "INVALID_ID"]

INVALID_ID = np.iinfo(np.int32).max

_log = obs.get_logger(__name__)
_C_PLAN_HIT = obs.counter("engine.plan_cache.hits")
_C_PLAN_MISS = obs.counter("engine.plan_cache.misses")
_C_PLAN_PATCH = obs.counter("engine.plan_cache.patched")


@dataclass(frozen=True)
class EdgeDelta:
    """Batch of edge inserts/deletes in **original** node ids.

    The unit of incremental maintenance (Ringo's dynamism story): applying a
    delta via :meth:`Graph.apply_delta` yields a new graph whose traversal
    plan can be *patched* from the parent's instead of re-derived, and whose
    analytics can warm-start from the parent's results.  Deleting an edge
    that does not exist is a no-op; inserted duplicates are deduped.
    """

    add_src: np.ndarray
    add_dst: np.ndarray
    del_src: np.ndarray
    del_dst: np.ndarray

    def __post_init__(self):
        for name in ("add_src", "add_dst", "del_src", "del_dst"):
            a = np.asarray(getattr(self, name), dtype=np.int32).reshape(-1)
            object.__setattr__(self, name, a)
        if self.add_src.shape != self.add_dst.shape:
            raise ValueError("EdgeDelta add_src/add_dst length mismatch")
        if self.del_src.shape != self.del_dst.shape:
            raise ValueError("EdgeDelta del_src/del_dst length mismatch")

    @classmethod
    def inserts(cls, src, dst) -> "EdgeDelta":
        empty = np.empty((0,), np.int32)
        return cls(src, dst, empty, empty)

    @classmethod
    def deletes(cls, src, dst) -> "EdgeDelta":
        empty = np.empty((0,), np.int32)
        return cls(empty, empty, src, dst)

    @property
    def n_adds(self) -> int:
        return int(self.add_src.shape[0])

    @property
    def n_dels(self) -> int:
        return int(self.del_src.shape[0])

    @property
    def insert_only(self) -> bool:
        return self.n_dels == 0

    def __repr__(self) -> str:  # pragma: no cover
        return f"EdgeDelta(+{self.n_adds} edges, -{self.n_dels} edges)"


@dataclass
class _DeltaInfo:
    """How a Graph was derived from its parent — fuel for plan patching.

    Dense-id arrays in the **child** numbering (== parent numbering on the
    fast path, which is the only path that records one of these).  The merged
    edge lists are host-side copies of both CSR orders so the plan patch
    never re-sorts on device.
    """

    parent: "Graph"
    add_src: np.ndarray      # applied (deduped) inserts, out-order sorted
    add_dst: np.ndarray
    del_src: np.ndarray      # distinct deleted pairs
    del_dst: np.ndarray
    insert_only: bool        # no edge was actually removed
    dirty: np.ndarray        # dense vertex ids touched by the delta
    out_src: np.ndarray      # merged edges sorted by (src, dst)
    out_dst: np.ndarray
    in_src: np.ndarray       # merged edges sorted by (dst, src)
    in_dst: np.ndarray


@jax.tree_util.register_pytree_node_class
@dataclass
class Graph:
    """Directed graph with dense node ids [0, n_nodes) and dual CSR."""

    n_nodes: int
    n_edges: int
    node_ids: jax.Array
    out_ptr: jax.Array
    out_idx: jax.Array
    in_ptr: jax.Array
    in_idx: jax.Array
    # Identity-keyed traversal-plan cache (core/plan.py).  Not a pytree leaf:
    # a Graph reconstructed inside jit starts with a cold cache, and the
    # functional update methods return fresh Graph objects, so a stale plan
    # can never be observed.
    _plan: Optional[object] = field(default=None, repr=False, compare=False)
    # Delta lineage (set by apply_delta's fast path).  Also not a pytree
    # leaf: a Graph rebuilt inside jit loses its lineage and simply rebuilds
    # its plan from scratch — correct, just not incremental.
    _delta: Optional[_DeltaInfo] = field(default=None, repr=False, compare=False)

    # -- pytree ---------------------------------------------------------------
    def tree_flatten(self):
        leaves = (self.node_ids, self.out_ptr, self.out_idx, self.in_ptr, self.in_idx)
        return leaves, (self.n_nodes, self.n_edges)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        n_nodes, n_edges = aux
        return cls(n_nodes, n_edges, *leaves)

    # -- constructors ----------------------------------------------------------
    @classmethod
    def from_dense_edges(cls, src, dst, n_nodes: int,
                         node_ids=None) -> "Graph":
        """Build from dense-id edge arrays (valid length = full length).

        This is the core of the paper's **sort-first** algorithm (§2.4):
        (1) copy the columns, (2) sort them, (3) compute neighbor counts
        explicitly, (4) bulk-write adjacency — no contention, no estimates.
        The sorts run on the host: a device sort compiles anew for every
        edge count, and at tens of millions of edges that compile takes
        longer than the host sort it would replace.  The CSR arrays are
        uploaded once, built.
        """
        src = np.asarray(src, dtype=np.int32).reshape(-1)
        dst = np.asarray(dst, dtype=np.int32).reshape(-1)
        e = int(src.shape[0])
        node_cap = next_capacity(max(n_nodes, 1))
        edge_cap = next_capacity(max(e, 1))

        ids = np.full((node_cap,), INVALID_ID, np.int32)
        ids[:n_nodes] = (np.arange(n_nodes, dtype=np.int32)
                         if node_ids is None
                         else np.asarray(node_ids, np.int32)[:n_nodes])

        out_ptr, out_idx = _csr_from_pairs(src, dst, node_cap, edge_cap)
        in_ptr, in_idx = _csr_from_pairs(dst, src, node_cap, edge_cap)
        return cls(n_nodes=n_nodes, n_edges=e, node_ids=jnp.asarray(ids),
                   out_ptr=jnp.asarray(out_ptr), out_idx=jnp.asarray(out_idx),
                   in_ptr=jnp.asarray(in_ptr), in_idx=jnp.asarray(in_idx))

    @classmethod
    def from_edges(cls, src, dst, dedupe: bool = True,
                   drop_self_loops: bool = False) -> "Graph":
        """Build from raw (original-id) edge arrays; renumbers densely.

        Node set = union of endpoint ids (paper §2.4: "Nodes V are defined by
        unique values in columns S and D").
        """
        src = np.asarray(src, dtype=np.int32).reshape(-1)
        dst = np.asarray(dst, dtype=np.int32).reshape(-1)
        if drop_self_loops:
            keep = src != dst
            src, dst = src[keep], dst[keep]
        if src.shape[0] == 0:
            return cls.from_dense_edges(src, dst, 0)

        node_ids, src_d, dst_d = _renumber(src, dst)
        if dedupe:
            src_d, dst_d = _dedupe_pairs(src_d, dst_d)
        return cls.from_dense_edges(src_d, dst_d, int(node_ids.size),
                                    node_ids=node_ids)

    # -- accessors ---------------------------------------------------------------
    @property
    def node_capacity(self) -> int:
        return int(self.node_ids.shape[0])

    @property
    def edge_capacity(self) -> int:
        return int(self.out_idx.shape[0])

    def out_degrees(self) -> jax.Array:
        return (self.out_ptr[1:] - self.out_ptr[:-1])[: self.n_nodes]

    def in_degrees(self) -> jax.Array:
        return (self.in_ptr[1:] - self.in_ptr[:-1])[: self.n_nodes]

    def out_edges(self) -> Tuple[jax.Array, jax.Array]:
        """(src, dst) with edges sorted by src (dense ids, valid prefix)."""
        e = self.n_edges
        src = _row_of_edge(self.out_ptr, self.edge_capacity)[:e]
        return src, self.out_idx[:e]

    def in_edges(self) -> Tuple[jax.Array, jax.Array]:
        """(src, dst) with edges sorted by dst (dense ids, valid prefix)."""
        e = self.n_edges
        dst = _row_of_edge(self.in_ptr, self.edge_capacity)[:e]
        return self.in_idx[:e], dst

    def neighbors_out(self, dense_id: int) -> jax.Array:
        lo, hi = int(self.out_ptr[dense_id]), int(self.out_ptr[dense_id + 1])
        return self.out_idx[lo:hi]

    # -- traversal plan (the shared-substrate hook; Ringo §2.2) -----------------
    def plan(self):
        """Memoized :class:`repro.core.plan.GraphPlan` for this graph.

        Built on first use and cached by graph identity, so the paper's
        trial-and-error loop — many algorithm calls against one graph —
        pays the edge-sort / re-blocking cost exactly once.  The functional
        update methods (:meth:`add_edges`, :meth:`delete_edges`) return new
        ``Graph`` objects whose plan cache starts empty (invalidation by
        construction); call :meth:`invalidate_plan` only if the underlying
        buffers are mutated out-of-band (donated buffers etc.).
        """
        if self._plan is None:
            from .plan import GraphPlan  # local import: plan -> kernels -> graph
            _C_PLAN_MISS.inc()
            if self._delta is not None:
                _C_PLAN_PATCH.inc()
                self._plan = GraphPlan.patch(self, self._delta)
            else:
                self._plan = GraphPlan.build(self)
        else:
            _C_PLAN_HIT.inc()
        return self._plan

    def invalidate_plan(self) -> None:
        self._plan = None

    @property
    def version(self) -> str:
        """Provenance version token (Ringo §2.1 object metadata).

        Graphs are immutable and the update methods return fresh objects, so
        the token doubles as a cache key: any result computed against it stays
        valid forever — a functional update yields a new token, which is the
        service-layer mirror of the plan cache's invalidation-by-construction.
        """
        return version_of(self)

    def dense_of(self, original_ids) -> jax.Array:
        """Vectorized id lookup (the hash-probe dual)."""
        q = jnp.asarray(original_ids, dtype=jnp.int32)
        return jnp.searchsorted(self.node_ids[: self.n_nodes], q).astype(jnp.int32)

    def original_of(self, dense_ids) -> jax.Array:
        return self.node_ids[jnp.asarray(dense_ids, dtype=jnp.int32)]

    # -- functional updates (the dynamism story) -----------------------------------
    @track("graph.add_edges", "Graph.add_edges")
    def add_edges(self, src, dst, dedupe: bool = True) -> "Graph":
        """Merge new edges (original ids) — functional rebuild via sorted merge."""
        osrc = self.original_of(self.out_edges()[0])
        odst = self.original_of(self.out_edges()[1])
        src = jnp.concatenate([osrc, jnp.asarray(src, jnp.int32)])
        dst = jnp.concatenate([odst, jnp.asarray(dst, jnp.int32)])
        return Graph.from_edges(src, dst, dedupe=dedupe)

    @track("graph.delete_edges", "Graph.delete_edges")
    def delete_edges(self, src, dst) -> "Graph":
        """Remove the given (original-id) edges; sort-based anti-join.

        Host-side op (interactive path): exact 64-bit pair keys via numpy,
        since device int64 is disabled in 32-bit mode.
        """
        s, d = self.out_edges()
        os = np.asarray(self.original_of(s), dtype=np.int64)
        od = np.asarray(self.original_of(d), dtype=np.int64)
        keys = (os << np.int64(32)) | (od & np.int64(0xFFFFFFFF))
        dk = (np.asarray(src, dtype=np.int64) << np.int64(32)) | \
             (np.asarray(dst, dtype=np.int64) & np.int64(0xFFFFFFFF))
        keep = ~np.isin(keys, dk)
        return Graph.from_edges(os[keep].astype(np.int32),
                                od[keep].astype(np.int32), dedupe=False)

    @track("graph.apply_delta", "Graph.apply_delta")
    def apply_delta(self, delta: EdgeDelta) -> "Graph":
        """Batch edge inserts/deletes (original ids) -> new Graph.

        Fast path — every insert endpoint is already a node — performs a
        host-side sorted merge of both CSR orders (O(E + Δ log Δ) numpy
        passes, no device re-sort) and records a ``_DeltaInfo`` so
        :meth:`plan` can *patch* the parent's plan instead of re-deriving
        it.  Inserts are deduped against the kept edges and themselves;
        deleting a non-existent edge is a no-op (all duplicates of a
        matched pair are removed, like :meth:`delete_edges`).

        When an insert endpoint is a brand-new node the dense numbering
        shifts, so we fall back to a full rebuild with a logged reason; the
        child then carries no delta lineage and its plan is built cold.
        """
        n = self.n_nodes
        valid = np.asarray(self.node_ids[:n]) if n else np.empty((0,), np.int32)
        new_eps = np.concatenate([delta.add_src, delta.add_dst])
        _, known = _dense_lookup(valid, new_eps)
        if new_eps.size and not bool(np.all(known)):
            n_new = int(np.unique(new_eps[~known]).size)
            _log.info("apply_delta.full_rebuild", new_nodes=n_new,
                      reason="dense numbering shifts")
            return self._apply_delta_rebuild(delta)

        s, d = self.out_edges()
        s64 = np.asarray(s).astype(np.int64)
        d64 = np.asarray(d).astype(np.int64)
        keys = (s64 << 32) | d64  # dense ids are non-negative: sorted, exact

        # -- deletes: anti-join on dense pair keys (absent endpoints no-op) --
        if delta.n_dels:
            dp, ok_s = _dense_lookup(valid, delta.del_src)
            dq, ok_d = _dense_lookup(valid, delta.del_dst)
            ok = ok_s & ok_d
            dkeys = np.unique((dp[ok] << 32) | dq[ok])
            keep = ~_in_sorted(dkeys, keys)
        else:
            keep = np.ones(keys.shape, bool)
        kept = keys[keep]
        dropped = np.unique(keys[~keep])
        n_deleted = int(keys.size - kept.size)

        # -- inserts: dedupe, then merge into the sorted out-order list --
        if delta.n_adds:
            ai, _ = _dense_lookup(valid, delta.add_src)
            aj, _ = _dense_lookup(valid, delta.add_dst)
            akeys = np.unique((ai << 32) | aj)
            akeys = akeys[~_in_sorted(kept, akeys)]
        else:
            akeys = np.empty((0,), np.int64)
        merged = (np.insert(kept, np.searchsorted(kept, akeys), akeys)
                  if akeys.size else kept)

        # -- same merge in in-order (sorted by dst, then src) --
        si, di = self.in_edges()
        keys_in = (np.asarray(di).astype(np.int64) << 32) | \
                  np.asarray(si).astype(np.int64)
        if n_deleted:
            dkeys_in = np.sort(((dropped & 0xFFFFFFFF) << 32) | (dropped >> 32))
            kept_in = keys_in[~_in_sorted(dkeys_in, keys_in)]
        else:
            kept_in = keys_in
        if akeys.size:
            akeys_in = np.sort(((akeys & 0xFFFFFFFF) << 32) | (akeys >> 32))
            merged_in = np.insert(kept_in, np.searchsorted(kept_in, akeys_in),
                                  akeys_in)
        else:
            merged_in = kept_in

        # -- rebuild the padded CSR arrays from the merged host lists --
        e2 = int(merged.size)
        node_cap = self.node_capacity
        edge_cap = next_capacity(max(e2, 1))
        m_src = (merged >> 32).astype(np.int32)
        m_dst = (merged & 0xFFFFFFFF).astype(np.int32)
        mi_dst = (merged_in >> 32).astype(np.int32)
        mi_src = (merged_in & 0xFFFFFFFF).astype(np.int32)
        out_idx = np.zeros((edge_cap,), np.int32)
        out_idx[:e2] = m_dst
        in_idx = np.zeros((edge_cap,), np.int32)
        in_idx[:e2] = mi_src

        child = Graph(n_nodes=n, n_edges=e2, node_ids=self.node_ids,
                      out_ptr=jnp.asarray(_host_ptr(m_src, node_cap)),
                      out_idx=jnp.asarray(out_idx),
                      in_ptr=jnp.asarray(_host_ptr(mi_dst, node_cap)),
                      in_idx=jnp.asarray(in_idx))
        dirty = np.unique(np.concatenate([
            akeys >> 32, akeys & 0xFFFFFFFF,
            dropped >> 32, dropped & 0xFFFFFFFF])).astype(np.int32)
        child._delta = _DeltaInfo(
            parent=self,
            add_src=(akeys >> 32).astype(np.int32),
            add_dst=(akeys & 0xFFFFFFFF).astype(np.int32),
            del_src=(dropped >> 32).astype(np.int32),
            del_dst=(dropped & 0xFFFFFFFF).astype(np.int32),
            insert_only=(n_deleted == 0),
            dirty=dirty,
            out_src=m_src, out_dst=m_dst, in_src=mi_src, in_dst=mi_dst)
        return child

    def _apply_delta_rebuild(self, delta: EdgeDelta) -> "Graph":
        """Slow path: node set grows -> renumber and rebuild from scratch.

        Node set = parent nodes (isolated ones included) + new insert
        endpoints; delete/dedupe semantics match the fast path.
        """
        s, d = self.out_edges()
        os = np.asarray(self.original_of(s)).astype(np.int64)
        od = np.asarray(self.original_of(d)).astype(np.int64)
        # original ids may be any int32, so mask the low word (injective on
        # int32 pairs; only used for set membership, never for ordering)
        keys = (os << 32) | (od & 0xFFFFFFFF)
        if delta.n_dels:
            dk = (delta.del_src.astype(np.int64) << 32) | \
                 (delta.del_dst.astype(np.int64) & 0xFFFFFFFF)
            keep = ~np.isin(keys, dk)
        else:
            keep = np.ones(keys.shape, bool)

        valid = np.asarray(self.node_ids[: self.n_nodes]) \
            if self.n_nodes else np.empty((0,), np.int32)
        new_ids = np.union1d(valid, np.concatenate([delta.add_src,
                                                    delta.add_dst]))
        # orig -> dense is monotone, so the kept out-order list stays sorted
        ks = np.searchsorted(new_ids, os[keep].astype(np.int32)).astype(np.int64)
        kd = np.searchsorted(new_ids, od[keep].astype(np.int32)).astype(np.int64)
        kept_keys = (ks << 32) | kd
        ai = np.searchsorted(new_ids, delta.add_src).astype(np.int64)
        aj = np.searchsorted(new_ids, delta.add_dst).astype(np.int64)
        akeys = np.unique((ai << 32) | aj)
        akeys = akeys[~_in_sorted(kept_keys, akeys)]
        all_s = np.concatenate([ks, akeys >> 32]).astype(np.int32)
        all_d = np.concatenate([kd, akeys & 0xFFFFFFFF]).astype(np.int32)
        return Graph.from_dense_edges(
            jnp.asarray(all_s), jnp.asarray(all_d), int(new_ids.size),
            node_ids=jnp.asarray(new_ids.astype(np.int32)))

    @track("graph.to_undirected", "Graph.to_undirected")
    def to_undirected(self) -> "Graph":
        """Symmetrized simple graph (for triangles / k-core / WCC)."""
        s, d = self.out_edges()
        os, od = self.original_of(s), self.original_of(d)
        src = jnp.concatenate([os, od])
        dst = jnp.concatenate([od, os])
        return Graph.from_edges(src, dst, dedupe=True, drop_self_loops=True)

    def nbytes(self) -> int:
        total = 0
        for a in (self.node_ids, self.out_ptr, self.out_idx, self.in_ptr, self.in_idx):
            total += a.size * a.dtype.itemsize
        return int(total)

    def plan_nbytes(self) -> int:
        """Derived bytes held by this graph's plan (0 when the plan is cold)."""
        return 0 if self._plan is None else int(self._plan.nbytes())

    def lineage_depth(self) -> int:
        """Length of the ``apply_delta`` ancestry chain hanging off this graph."""
        depth, g = 0, self
        while g._delta is not None:
            depth += 1
            g = g._delta.parent
        return depth

    def prune_lineage(self, max_depth: int) -> int:
        """Cut the delta-ancestry chain ``max_depth`` links up; returns cuts.

        Every ``apply_delta`` child strongly references its parent graph (and,
        once its plan is built, the parent's plan) through ``_delta`` — a
        long-lived delta stream would otherwise pin every ancestor forever.
        Cutting clears the ancestor's ``_delta`` and its plan's
        ``_parent``/``_info`` back-references, releasing everything deeper.
        The cut ancestor (and anything that still reaches it) simply loses
        delta-aware retention/warm-starts for *future* deltas and falls back
        to cold recomputation — results are unaffected.
        """
        depth, g = 0, self
        while g._delta is not None and depth < max_depth:
            depth += 1
            g = g._delta.parent
        cuts = 0
        if g._delta is not None:
            g._delta = None
            cuts += 1
        if g._plan is not None and getattr(g._plan, "_parent", None) is not None:
            g._plan._parent = None
            g._plan._info = None
            cuts += 1
        return cuts

    def __repr__(self) -> str:  # pragma: no cover
        return f"Graph({self.n_nodes} nodes, {self.n_edges} edges)"


# ---------------------------------------------------------------------------
# internals — the sort-first building blocks
# ---------------------------------------------------------------------------


def _dense_lookup(valid: np.ndarray, q: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """(dense position, present?) of original ids in the sorted id table."""
    q = np.asarray(q)
    if valid.size == 0 or q.size == 0:
        return np.zeros(q.shape, np.int64), np.zeros(q.shape, bool)
    pos = np.minimum(np.searchsorted(valid, q), valid.size - 1)
    return pos.astype(np.int64), valid[pos] == q


def _in_sorted(haystack: np.ndarray, needles: np.ndarray) -> np.ndarray:
    """Membership of needles in an ascending (possibly duplicated) array."""
    if haystack.size == 0 or needles.size == 0:
        return np.zeros(needles.shape, bool)
    pos = np.minimum(np.searchsorted(haystack, needles), haystack.size - 1)
    return haystack[pos] == needles


def _host_ptr(rows: np.ndarray, node_cap: int) -> np.ndarray:
    """CSR row pointers from sorted row ids — host-side counts + cumsum."""
    counts = np.bincount(rows, minlength=node_cap)
    ptr = np.zeros((node_cap + 1,), np.int64)
    np.cumsum(counts, out=ptr[1:])
    return ptr.astype(np.int32)


def _csr_from_pairs(row: np.ndarray, col: np.ndarray, node_cap: int,
                    edge_cap: int) -> Tuple[np.ndarray, np.ndarray]:
    """Sort-first CSR: sort (row, col) -> counts -> ptr; no hash inserts."""
    e = int(row.shape[0])
    # one sort of 64-bit (row, col) keys: row primary, col secondary =>
    # sorted adjacency (dense ids are non-negative int32); deduped input
    # arrives sorted already, and checking is linear
    keys = (row.astype(np.int64) << 32) | col.astype(np.int64)
    if not np.all(keys[1:] >= keys[:-1]):
        keys.sort()
    idx = np.zeros((edge_cap,), np.int32)
    idx[:e] = (keys & 0xFFFFFFFF).astype(np.int32)
    return _host_ptr((keys >> 32).astype(np.int64), node_cap), idx


def _row_of_edge(ptr: jax.Array, edge_cap: int) -> jax.Array:
    """Row id of each CSR slot: searchsorted(ptr, e, 'right')-1, vectorized."""
    e_idx = jnp.arange(edge_cap, dtype=jnp.int32)
    return (jnp.searchsorted(ptr, e_idx, side="right") - 1).astype(jnp.int32)


def _renumber(src: np.ndarray, dst: np.ndarray
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(node_ids, src_d, dst_d): ascending unique ids and dense endpoints.

    The sort-based dual of Ringo's node hash table.  When the ids are
    non-negative and at most a few times the endpoint count, a presence
    table and an id -> dense lookup replace the sort and the binary
    searches (linear, and tens of times faster at millions of edges).
    """
    lo = min(int(src.min()), int(dst.min()))
    hi = max(int(src.max()), int(dst.max()))
    if lo < 0 or hi >= 4 * (src.size + dst.size) + 1024:
        node_ids = np.unique(np.concatenate([src, dst]))
        return (node_ids, np.searchsorted(node_ids, src).astype(np.int32),
                np.searchsorted(node_ids, dst).astype(np.int32))
    present = np.zeros((hi + 1,), bool)
    present[src] = True
    present[dst] = True
    node_ids = np.flatnonzero(present).astype(np.int32)
    dense = np.zeros((hi + 1,), np.int32)
    dense[node_ids] = np.arange(node_ids.size, dtype=np.int32)
    return node_ids, dense[src], dense[dst]


def _dedupe_pairs(src: np.ndarray, dst: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Remove duplicate (src, dst) pairs; returns them sorted by (src, dst)."""
    if int(src.shape[0]) == 0:
        return src, dst
    keys = np.unique((src.astype(np.int64) << 32) | dst.astype(np.int64))
    return (keys >> 32).astype(np.int32), (keys & 0xFFFFFFFF).astype(np.int32)
