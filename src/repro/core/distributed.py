"""Distributed graph engine — the pod as the big-memory machine.

Ringo argues a single 1 TB/80-core box beats clusters for all-but-largest
graphs.  A TPU pod *is* that box at 2025 scale: 256 chips × 16 GB HBM = 4 TB
of flat, fast memory behind an ICI mesh.  This module maps Ringo's OpenMP
loops onto `shard_map`:

* **node space** is range-partitioned into contiguous shards (the dual of
  Ringo's per-thread partitions in graph→table conversion, §2.4);
* **edges live with their destination's owner**, so the PageRank scatter is
  shard-local (contention-free, like the paper's thread-owned partitions)
  and the only collective is the rank-vector `all_gather`;
* **conversion** is the distributed sort-first: local bucket-sort by owner,
  one `all_to_all` to ship edges home, local CSR build — the same
  "sort, count explicitly, bulk copy" with the ICI doing the shuffle;
* results flow back to (sharded) tables, closing the paper's workflow loop.

Everything here also runs under the 512-device production mesh via
`launch/dryrun.py --arch ringo-graph` (see launch/ringo_cells.py).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..launch.sharding import graph_replicated_spec, graph_shard_spec
from .graph import Graph

__all__ = [
    "make_graph_mesh",
    "DistGraph",
    "shard_graph",
    "pagerank_distributed",
    "distributed_to_graph",
    "triangle_count_distributed",
    "degrees_distributed",
]


def make_graph_mesh(n_devices: Optional[int] = None, axis: str = "gp") -> Mesh:
    """1-D mesh over all (or the first n) devices for graph collectives.

    Delegates to :func:`repro.launch.mesh.graph_mesh`, so this module, the
    ``"sharded"`` engine backend, and the serving layer all share one cached
    ``Mesh`` object per device count (identity matters: it keys jit caches).
    """
    from ..launch.mesh import graph_mesh
    return graph_mesh(n_devices, axis)


# ---------------------------------------------------------------------------
# sharded graph container
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclass
class DistGraph:
    """Destination-partitioned edge shards + node-range shards.

    Node space [0, n_pad) is split into D contiguous ranges of ``ns`` nodes.
    Shard d owns nodes [d·ns, (d+1)·ns) and every in-edge pointing to them.

    Arrays (sharded along axis 0 of a (D·X)-leading layout):
      src:       (D·es,)  global src id per edge (dst-owner order)
      dst_local: (D·es,)  dst id *within* the owner's range
      evalid:    (D·es,)  edge validity (padding is False)
      out_deg:   (D·ns,)  out-degree per owned node
      nvalid:    (D·ns,)  node validity
    """

    n_nodes: int
    n_edges: int
    ns: int            # nodes per shard
    es: int            # edge slots per shard
    src: jax.Array
    dst_local: jax.Array
    evalid: jax.Array
    out_deg: jax.Array
    nvalid: jax.Array

    def tree_flatten(self):
        return ((self.src, self.dst_local, self.evalid, self.out_deg,
                 self.nvalid),
                (self.n_nodes, self.n_edges, self.ns, self.es))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        n_nodes, n_edges, ns, es = aux
        return cls(n_nodes, n_edges, ns, es, *leaves)


def shard_graph(g: Graph, mesh: Mesh, axis: str = "gp") -> DistGraph:
    """Partition a host Graph across the mesh (host-side, once per graph)."""
    d = mesh.shape[axis]
    n = g.n_nodes
    ns = -(-max(n, 1) // d)
    src, dst = (np.asarray(a) for a in g.in_edges())   # sorted by dst
    owner_starts = np.searchsorted(dst, np.arange(d) * ns, side="left")
    owner_ends = np.searchsorted(dst, np.minimum((np.arange(d) + 1) * ns, n),
                                 side="left")
    counts = owner_ends - owner_starts
    es = max(int(counts.max()) if d else 1, 1)
    src_sh = np.zeros((d, es), np.int32)
    dstl_sh = np.zeros((d, es), np.int32)
    ev_sh = np.zeros((d, es), bool)
    for i in range(d):
        lo, hi = int(owner_starts[i]), int(owner_ends[i])
        c = hi - lo
        src_sh[i, :c] = src[lo:hi]
        dstl_sh[i, :c] = dst[lo:hi] - i * ns
        ev_sh[i, :c] = True
    out_deg = np.zeros((d * ns,), np.float32)
    out_deg[:n] = np.asarray(g.out_degrees(), np.float32)
    nvalid = np.zeros((d * ns,), bool)
    nvalid[:n] = True

    shard1 = graph_shard_spec(mesh, axis)
    put = lambda a: jax.device_put(jnp.asarray(a), shard1)
    return DistGraph(
        n_nodes=n, n_edges=g.n_edges, ns=ns, es=es,
        src=put(src_sh.reshape(-1)), dst_local=put(dstl_sh.reshape(-1)),
        evalid=put(ev_sh.reshape(-1)), out_deg=put(out_deg), nvalid=put(nvalid),
    )


# ---------------------------------------------------------------------------
# distributed PageRank
# ---------------------------------------------------------------------------


def pagerank_distributed(dg: DistGraph, mesh: Mesh, n_iter: int = 10,
                         damping: float = 0.85, axis: str = "gp",
                         compress_bf16: bool = False) -> jax.Array:
    """Edge-partitioned PageRank.

    Per iteration: `all_gather` the rank shard (N floats over ICI), gather
    contributions from global sources, `segment_sum` into the locally-owned
    destination range (contention-free — the owner writes its own nodes,
    exactly the paper's thread-partitioned scatter).

    ``compress_bf16`` halves all_gather bytes (beyond-paper optimization,
    recorded in EXPERIMENTS.md §Perf).
    """
    n, ns = dg.n_nodes, dg.ns

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=P(axis),
    )
    def run(src, dst_local, evalid, out_deg, nvalid):
        inv_deg = jnp.where(out_deg > 0, 1.0 / jnp.maximum(out_deg, 1.0), 0.0)
        inv_full = jax.lax.all_gather(inv_deg, axis, tiled=True)
        dangling = (out_deg == 0.0) & nvalid
        pr0 = jnp.where(nvalid, 1.0 / n, 0.0)

        def body(_, pr_shard):
            msg = pr_shard.astype(jnp.bfloat16) if compress_bf16 else pr_shard
            pr_full = jax.lax.all_gather(msg, axis, tiled=True).astype(jnp.float32)
            contrib = jnp.where(evalid, pr_full[src] * inv_full[src], 0.0)
            local = jax.ops.segment_sum(contrib, dst_local, num_segments=ns,
                                        indices_are_sorted=True)
            dang = jax.lax.psum(jnp.sum(jnp.where(dangling, pr_shard, 0.0)), axis)
            new = (1.0 - damping) / n + damping * (local + dang / n)
            return jnp.where(nvalid, new, 0.0)

        return jax.lax.fori_loop(0, n_iter, body, pr0)

    pr = run(dg.src, dg.dst_local, dg.evalid, dg.out_deg, dg.nvalid)
    return pr[: n]


# ---------------------------------------------------------------------------
# distributed sort-first conversion (edge table -> DistGraph)
# ---------------------------------------------------------------------------


def distributed_to_graph(src: jax.Array, dst: jax.Array, n_nodes: int,
                         mesh: Mesh, axis: str = "gp") -> DistGraph:
    """The paper's sort-first conversion, distributed.

    Rows (edges) arrive sharded arbitrarily.  Each shard (1) bucket-sorts its
    rows by destination owner — a local lexsort, contention-free; (2) ships
    each bucket to its owner with **one all_to_all**; (3) the owner sorts its
    received edges by destination and counts neighbors explicitly.  This is
    §2.4 verbatim with the ICI playing the memory bus.
    """
    d = mesh.shape[axis]
    ns = -(-max(n_nodes, 1) // d)
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    e = int(src.shape[0])
    per = -(-e // d)
    pad = per * d - e
    src = jnp.concatenate([src, jnp.zeros((pad,), jnp.int32)])
    dst = jnp.concatenate([dst, jnp.full((pad,), -1, jnp.int32)])  # invalid
    valid = jnp.arange(per * d) < e

    # bucket capacity: worst-case rows one shard sends to one owner
    owner = jnp.where(valid, dst // ns, d)  # invalid -> bucket d (dropped)
    owner_2d = owner.reshape(d, per)
    counts = jax.vmap(lambda o: jnp.bincount(o, length=d + 1))(owner_2d)
    cap = int(jnp.max(counts[:, :d]))
    cap = max(cap, 1)

    shard1 = graph_shard_spec(mesh, axis)
    src_s = jax.device_put(src, shard1)
    dst_s = jax.device_put(dst, shard1)
    val_s = jax.device_put(valid, shard1)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P(axis), P(axis), P(axis)),
                       out_specs=(P(axis), P(axis), P(axis)))
    def exchange(s, t, v):
        own = jnp.where(v, t // ns, d)
        order = jnp.argsort(own, stable=True)          # local bucket sort
        s, t, own = s[order], t[order], own[order]
        starts = jnp.searchsorted(own, jnp.arange(d))
        # gather each bucket into its fixed-capacity slot
        idx = starts[:, None] + jnp.arange(cap)[None, :]
        in_bucket = idx < jnp.searchsorted(own, jnp.arange(d), side="right")[:, None]
        idx = jnp.minimum(idx, s.shape[0] - 1)
        sb = jnp.where(in_bucket, s[idx], 0)
        tb = jnp.where(in_bucket, t[idx], 0)
        vb = in_bucket
        # one all_to_all: bucket j of shard i -> shard j slot i
        sb = jax.lax.all_to_all(sb, axis, split_axis=0, concat_axis=0, tiled=True)
        tb = jax.lax.all_to_all(tb, axis, split_axis=0, concat_axis=0, tiled=True)
        vb = jax.lax.all_to_all(vb, axis, split_axis=0, concat_axis=0, tiled=True)
        return sb.reshape(-1), tb.reshape(-1), vb.reshape(-1)

    # the graph mesh has explicit axes: eager shard_map calls on it need the
    # mesh as ambient context (JAX >= 0.7 no longer infers it from inputs)
    with jax.set_mesh(mesh):
        sb, tb, vb = exchange(src_s, dst_s, val_s)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P(axis), P(axis), P(axis)),
                       out_specs=(P(axis), P(axis), P(axis), P(axis)))
    def finalize(s, t, v):
        # local sort-first: sort received edges by (dst, src); count explicitly
        me = jax.lax.axis_index(axis)
        tl = jnp.where(v, t - me * ns, ns)             # local dst; pad -> ns
        order = jnp.lexsort((s, tl))
        s, tl, v = s[order], tl[order], v[order]
        # out-degree: count srcs locally, reduce, slice the owned range
        # (invalid slots map to the overflow bucket ns*d)
        src_counts = jnp.bincount(jnp.where(v, s, ns * d),
                                  length=ns * d + 1)[: ns * d]
        out_deg_full = jax.lax.psum(src_counts, axis)
        out_deg = jax.lax.dynamic_slice_in_dim(out_deg_full, me * ns, ns)
        return s, tl, v, out_deg.astype(jnp.float32)

    with jax.set_mesh(mesh):
        s2, t2, v2, out_deg = finalize(sb, tb, vb)
    es = d * cap
    nvalid = jax.device_put(
        (jnp.arange(d * ns) < n_nodes), shard1)
    return DistGraph(n_nodes=n_nodes, n_edges=e, ns=ns, es=es,
                     src=s2, dst_local=jnp.where(v2, t2, 0), evalid=v2,
                     out_deg=out_deg, nvalid=nvalid)


# ---------------------------------------------------------------------------
# distributed triangle counting
# ---------------------------------------------------------------------------


def triangle_count_distributed(g: Graph, mesh: Mesh, axis: str = "gp",
                               edge_chunk: int = 1 << 14) -> int:
    """Oriented-edge-partitioned triangle counting.

    Each shard intersects the neighborhoods of its share of oriented edges
    (same binary-search core as `algorithms.triangle_count`) against the
    replicated oriented adjacency; `psum` merges the counts.  The adjacency
    is degeneracy-oriented, so its padded width is O(√E) — replication costs
    N·√E, acceptable through the low hundreds of millions of edges; beyond
    that the BSR kernel path shards tiles instead (see DESIGN.md).
    """
    if g.n_edges == 0:
        return 0
    osrc, odst, nbr, _ = g.plan().oriented()
    d = mesh.shape[axis]
    e = int(osrc.shape[0])
    per = -(-e // d)
    per = -(-per // edge_chunk) * edge_chunk   # full chunks: no slice clamping
    pad = per * d - e
    n = g.n_nodes
    osrc = jnp.concatenate([osrc, jnp.zeros((pad,), jnp.int32)])
    odst = jnp.concatenate([odst, jnp.zeros((pad,), jnp.int32)])
    evalid = jnp.arange(per * d) < e

    shard1 = graph_shard_spec(mesh, axis)
    osrc = jax.device_put(osrc, shard1)
    odst = jax.device_put(odst, shard1)
    evalid = jax.device_put(evalid, shard1)
    nbr_r = jax.device_put(nbr, graph_replicated_spec(mesh))  # replicated

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P(axis), P(axis), P(axis), P()),
                       out_specs=P())
    def count(u, v, ev, nbr_l):
        pad_val = nbr_l.shape[0]

        def chunk_body(i, acc):
            lo = i * edge_chunk
            uu = jax.lax.dynamic_slice_in_dim(u, lo, edge_chunk)
            vv = jax.lax.dynamic_slice_in_dim(v, lo, edge_chunk)
            ee = jax.lax.dynamic_slice_in_dim(ev, lo, edge_chunk)
            cand = nbr_l[uu]
            rows = nbr_l[vv]
            pos = jnp.clip(jax.vmap(jnp.searchsorted)(rows, cand), 0,
                           rows.shape[1] - 1)
            hit = (jnp.take_along_axis(rows, pos, axis=1) == cand) & \
                  (cand != pad_val) & ee[:, None]
            return acc + jnp.sum(hit, dtype=jnp.int32)

        n_chunks = u.shape[0] // edge_chunk   # exact by construction
        init = jax.lax.pcast(jnp.int32(0), (axis,), to="varying")
        total = jax.lax.fori_loop(0, n_chunks, chunk_body, init)
        return jax.lax.psum(total, axis)

    return int(count(osrc, odst, evalid, nbr_r))


def degrees_distributed(dg: DistGraph, mesh: Mesh, axis: str = "gp") -> jax.Array:
    """In-degrees from the sharded structure (sanity/benchmark helper)."""
    ns = dg.ns

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P(axis), P(axis)),
                       out_specs=P(axis))
    def run(dst_local, evalid):
        return jax.ops.segment_sum(evalid.astype(jnp.int32), dst_local,
                                   num_segments=ns, indices_are_sorted=True)

    with jax.set_mesh(mesh):
        return run(dg.dst_local, dg.evalid)[: dg.n_nodes]


# ---------------------------------------------------------------------------
# 2D (SUMMA-style) PageRank — §Perf optimization over the 1D baseline
# ---------------------------------------------------------------------------
#
# The 1D engine all-gathers the full rank vector every iteration (N floats
# per device).  A square 2D partition assigns device (r, c) the edges with
# dst ∈ block r and src ∈ block c; the rank vector lives in N/(d²)-sized
# "shuffle layout" slices.  Per iteration each device only needs
#   all_gather over rows  : its column block  (N/d values)
#   psum_scatter over cols: its partial sums  (N/d values)
# — Θ(N/d) communication instead of Θ(N): a d-fold reduction (16× on the
# 16×16 pod).  This is the vertex-cut insight of PowerGraph re-expressed as
# a dense 2D SpMV decomposition, applied beyond the paper's single machine.


@jax.tree_util.register_pytree_node_class
@dataclass
class DistGraph2D:
    """Square 2D edge partition. Device (r,c): dst ∈ block r, src ∈ block c."""

    n_nodes: int
    n_edges: int
    nb: int            # nodes per block  (N padded to d·nb)
    es: int            # edge slots per device
    d: int             # grid side
    src_local: jax.Array   # (d*d*es,) src offset within col block
    dst_local: jax.Array   # (d*d*es,) dst offset within row block
    evalid: jax.Array      # (d*d*es,)
    inv_deg_col: jax.Array  # (d*nb,) 1/outdeg in column-block layout (P(col))

    def tree_flatten(self):
        return ((self.src_local, self.dst_local, self.evalid,
                 self.inv_deg_col),
                (self.n_nodes, self.n_edges, self.nb, self.es, self.d))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        n_nodes, n_edges, nb, es, d = aux
        return cls(n_nodes, n_edges, nb, es, d, *leaves)


def shard_graph_2d(g: Graph, mesh: Mesh, row_axis: str = "data",
                   col_axis: str = "model") -> DistGraph2D:
    dr, dc = mesh.shape[row_axis], mesh.shape[col_axis]
    if dr != dc:
        raise ValueError(f"2D pagerank needs a square grid, got {dr}x{dc}")
    d = dr
    n = g.n_nodes
    nb = -(-max(n, 1) // d)
    src, dst = (np.asarray(a) for a in g.in_edges())
    rb, cb = dst // nb, src // nb
    dev = rb * d + cb
    order = np.argsort(dev, kind="stable")
    src, dst, dev = src[order], dst[order], dev[order]
    starts = np.searchsorted(dev, np.arange(d * d))
    ends = np.searchsorted(dev, np.arange(d * d), side="right")
    es = max(int((ends - starts).max()), 1)
    src_l = np.zeros((d * d, es), np.int32)
    dst_l = np.zeros((d * d, es), np.int32)
    ev = np.zeros((d * d, es), bool)
    for i in range(d * d):
        lo, hi = int(starts[i]), int(ends[i])
        c = hi - lo
        src_l[i, :c] = src[lo:hi] % nb
        dst_l[i, :c] = dst[lo:hi] % nb
        ev[i, :c] = True
    inv = np.zeros((d * nb,), np.float32)
    outdeg = np.asarray(g.out_degrees(), np.float32)
    inv[:n] = np.where(outdeg > 0, 1.0 / np.maximum(outdeg, 1.0), 0.0)

    grid = NamedSharding(mesh, P((row_axis, col_axis)))
    col_sh = NamedSharding(mesh, P(col_axis))
    put = jax.device_put
    return DistGraph2D(
        n_nodes=n, n_edges=g.n_edges, nb=nb, es=es, d=d,
        src_local=put(jnp.asarray(src_l.reshape(-1)), grid),
        dst_local=put(jnp.asarray(dst_l.reshape(-1)), grid),
        evalid=put(jnp.asarray(ev.reshape(-1)), grid),
        inv_deg_col=put(jnp.asarray(inv), col_sh),
    )


def pagerank_distributed_2d(dg: DistGraph2D, mesh: Mesh, n_iter: int = 10,
                            damping: float = 0.85, row_axis: str = "data",
                            col_axis: str = "model",
                            compress_bf16: bool = False,
                            unshuffle: bool = True) -> jax.Array:
    """2D PageRank; returns the rank vector in natural node order.

    ``unshuffle=False`` returns the internal shuffle-layout vector —
    iterations compose in that layout, so steady-state use (and the dry-run
    step) skips the one-time reorder epilogue."""
    n, nb, d = dg.n_nodes, dg.nb, dg.d
    slice_len = nb // d if nb % d == 0 else -(-nb // d)
    nb_pad = slice_len * d  # pad block so it splits evenly into d slices

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P((row_axis, col_axis)), P((row_axis, col_axis)),
                  P((row_axis, col_axis)), P(col_axis)),
        out_specs=P((row_axis, col_axis)))
    def run(src_l, dst_l, ev, inv_c):
        r = jax.lax.axis_index(row_axis)
        c = jax.lax.axis_index(col_axis)
        inv_pad = jnp.zeros((nb_pad,), jnp.float32).at[:nb].set(inv_c)
        # x slice for device (r,c): block c, sub-slice r  (shuffle layout)
        gstart = c * nb + r * slice_len
        valid = (jnp.arange(slice_len) + gstart) < n
        x0 = jnp.where(valid, 1.0 / n, 0.0)

        def body(_, x_slice):
            if compress_bf16:
                # barriers on BOTH sides keep the bf16 payload on the wire
                # (XLA otherwise folds the converts through the collective)
                msg = jax.lax.optimization_barrier(
                    x_slice.astype(jnp.bfloat16))
                x_c = jax.lax.optimization_barrier(
                    jax.lax.all_gather(msg, row_axis, tiled=True)
                ).astype(jnp.float32)                           # (nb_pad,)
            else:
                x_c = jax.lax.all_gather(x_slice, row_axis, tiled=True)
            contrib = jnp.where(ev, x_c[src_l] * inv_pad[src_l], 0.0)
            partial = jax.ops.segment_sum(contrib, dst_l, num_segments=nb_pad)
            # inv==0 marks both dangling and padding; mask the padding
            node_ok = (jnp.arange(nb) + c * nb) < n
            dang_local = jnp.sum(jnp.where((inv_pad[:nb] == 0.0) & node_ok,
                                           x_c[:nb], 0.0))
            # column block c is gathered by every row: scale by 1/d once
            dang = jax.lax.psum(jax.lax.psum(dang_local, col_axis),
                                row_axis) / d
            if compress_bf16:
                msg2 = jax.lax.optimization_barrier(
                    partial.astype(jnp.bfloat16))
                y = jax.lax.optimization_barrier(
                    jax.lax.psum_scatter(msg2, col_axis,
                                         scatter_dimension=0, tiled=True)
                ).astype(jnp.float32)
            else:
                y = jax.lax.psum_scatter(partial, col_axis,
                                         scatter_dimension=0, tiled=True)
            # y = slice [r*nb + c*slice_len, +slice_len) — the (c,r)-site
            # x-slot: transpose device grid to restore the shuffle layout
            y_t = _ppermute_2d(y, row_axis, col_axis, d)
            new_valid = (jnp.arange(slice_len) + gstart) < n
            return jnp.where(new_valid,
                             (1.0 - damping) / n + damping * (y_t + dang / n),
                             0.0)

        x = jax.lax.fori_loop(0, n_iter, body, x0)
        return x

    x = run(dg.src_local, dg.dst_local, dg.evalid, dg.inv_deg_col)
    if not unshuffle:
        return x
    # undo the shuffle layout: slice (r,c) holds [c*nb + r*slice_len ...);
    # each block's d slices span nb_pad >= nb, so truncate per block
    slices = jnp.reshape(x, (d, d, slice_len))
    blocks = [slices[:, c, :].reshape(-1)[:nb] for c in range(d)]
    return jnp.concatenate(blocks)[:n]


def _ppermute_2d(y: jax.Array, row_axis: str, col_axis: str, d: int
                 ) -> jax.Array:
    """Transpose the device grid: (r, c) receives from (c, r).

    Two ppermutes (a cyclic shift decomposition of the transpose would be
    cheaper on a real torus; point-to-point pairs express intent and XLA
    maps them onto the ICI)."""
    pairs = []
    for rr in range(d):
        for cc in range(d):
            src_lin = cc * d + rr
            dst_lin = rr * d + cc
            pairs.append((src_lin, dst_lin))
    return jax.lax.ppermute(y, (row_axis, col_axis), pairs)
