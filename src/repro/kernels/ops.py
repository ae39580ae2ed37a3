"""Host-side re-blocking helpers + compat shims for the BSR graph kernels.

The host-side helpers perform the *re-blocking* that adapts Ringo's per-edge
algorithms to MXU tiles: edges → 128×128 BSR tiles / 128-wide chunked
segments.  They are conversion-time work, invoked once per graph by
:class:`repro.core.plan.GraphPlan` and cached there.

``pagerank_bsr`` / ``triangle_count_bsr`` are retained as thin compatibility
shims: the BSR kernels are now a *backend* of the unified traversal engine
(``core/engine.py``), so these simply run the shared algorithm with
``backend="bsr"`` instead of maintaining a rival implementation.  On non-TPU
backends the kernels run in interpret mode (``interpret=None`` → auto).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.graph import Graph
from .bsr_tricount import bsr_tricount
from .segment_sum import (DEFAULT_BLOCK, DEFAULT_CHUNK, chunk_layout,
                          chunk_values, segment_sum_chunked)

__all__ = [
    "auto_interpret",
    "edges_to_bsr",
    "build_block_triples",
    "pagerank_bsr",
    "triangle_count_bsr",
    "segment_sum_sorted",
]


def auto_interpret(interpret: Optional[bool]) -> bool:
    """Resolve a kernel's interpret flag: ``None`` interprets off-TPU only.

    On a TPU the kernels always compile: an interpreted kernel there would
    quietly emulate on the host what the chip is meant to run.
    """
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("interpret=True on a TPU backend: the Pallas kernels "
                         "compile for the chip there")
    return interpret


# ---------------------------------------------------------------------------
# host-side re-blocking (numpy; conversion-time work, done once per graph)
# ---------------------------------------------------------------------------


def edges_to_bsr(src: np.ndarray, dst: np.ndarray, n: int,
                 values: Optional[np.ndarray] = None,
                 block: int = DEFAULT_BLOCK
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, int]:
    """Build (tiles, rows, cols, n_blocks) BSR with every row-block present.

    Matrix semantics: M[dst, src] = value  (the PageRank pull layout:
    y = M @ x gathers from sources into destinations).
    """
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    vals = np.ones_like(src, dtype=np.float32) if values is None \
        else np.asarray(values, dtype=np.float32)
    # nb >= 1 even for empty graphs: the "every row block appears" pass then
    # emits one zero tile, so the SpMV kernel grid is never empty (the
    # degenerate dual of build_block_triples' non-empty-grid guard)
    nb = max((n + block - 1) // block, 1)
    rb, cb = dst // block, src // block
    key = rb * nb + cb
    uniq, inv = np.unique(key, return_inverse=True)
    # ensure every row block appears (zero tile on the diagonal)
    present = np.unique(uniq // nb)
    missing = np.setdiff1d(np.arange(nb), present)
    n_tiles = len(uniq) + len(missing)
    tiles = np.zeros((max(n_tiles, 1), block, block), np.float32)
    ri = (dst % block).astype(np.int64)
    ci = (src % block).astype(np.int64)
    np.add.at(tiles, (inv, ri, ci), vals)
    rows = np.concatenate([uniq // nb, missing])
    cols = np.concatenate([uniq % nb, missing])
    order = np.argsort(rows, kind="stable")
    tiles = tiles[order] if n_tiles else tiles
    rows, cols = rows[order], cols[order]
    return (jnp.asarray(tiles), jnp.asarray(rows.astype(np.int32)),
            jnp.asarray(cols.astype(np.int32)), nb)


def build_block_triples(rows: np.ndarray, cols: np.ndarray
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Enumerate tile triples (I,J),(I,K),(K,J) all nonzero.

    Block-level analogue of "for each edge, intersect the two endpoint
    neighborhoods": the (I,J) tile plays the edge, K sweeps the common
    block-neighborhood.
    """
    rows = np.asarray(rows)
    cols = np.asarray(cols)
    nnzb = len(rows)
    tile_of = {(int(r), int(c)): t for t, (r, c) in enumerate(zip(rows, cols))}
    by_row: dict = {}
    for t, r in enumerate(rows):
        by_row.setdefault(int(r), []).append(t)
    t_ij, t_ik, t_kj = [], [], []
    for ij in range(nnzb):
        i, j = int(rows[ij]), int(cols[ij])
        for ik in by_row.get(i, ()):        # tiles (i, k)
            k = int(cols[ik])
            kj = tile_of.get((k, j))
            if kj is not None:
                t_ij.append(ij)
                t_ik.append(ik)
                t_kj.append(kj)
    if not t_ij:  # keep grid non-empty
        t_ij, t_ik, t_kj = [0], [0], [0]
    return (jnp.asarray(t_ij, jnp.int32), jnp.asarray(t_ik, jnp.int32),
            jnp.asarray(t_kj, jnp.int32))


# ---------------------------------------------------------------------------
# graph-level entry points — compat shims over the unified engine
# ---------------------------------------------------------------------------


def pagerank_bsr(g: Graph, n_iter: int = 10, damping: float = 0.85,
                 interpret: Optional[bool] = None,
                 block: int = DEFAULT_BLOCK) -> jax.Array:
    """PageRank on the engine's "bsr" backend (BSR SpMV inner contraction)."""
    from ..core import algorithms, engine
    if g.n_nodes == 0:
        return jnp.zeros((0,), jnp.float32)
    plan = g.plan()
    ex = engine.get_exec(plan, "bsr", interpret=interpret, block=block)
    pr0 = jnp.full((g.n_nodes,), 1.0 / g.n_nodes, dtype=jnp.float32)
    return engine.fixpoint(ex, algorithms._pagerank_body, pr0, n_iter=n_iter,
                           args=(jnp.float32(damping), plan.inv_out_deg,
                                 plan.dangling))


def triangle_count_bsr(g: Graph, interpret: Optional[bool] = None,
                       block: int = DEFAULT_BLOCK) -> int:
    """Triangle count via the A∘(A·A) MXU kernel (g must be undirected)."""
    from ..core.algorithms import triangle_count
    if block == DEFAULT_BLOCK:
        return triangle_count(g, backend="bsr", interpret=interpret)
    if g.n_edges == 0 or g.n_nodes == 0:
        return 0
    plan = g.plan()
    tiles, _, _, _ = plan.bsr(block)
    t_ij, t_ik, t_kj = plan.tri_triples(block)
    six_t = bsr_tricount(jnp.minimum(tiles, 1.0), t_ij, t_ik, t_kj,
                         interpret=auto_interpret(interpret))
    return int(round(float(six_t) / 6.0))


def segment_sum_sorted(vals: jax.Array, seg_ids: jax.Array, n_segments: int,
                       chunk: int = DEFAULT_CHUNK,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Segment-sum of values whose ``seg_ids`` are sorted ascending.

    Host-side chunking via :func:`kernels.segment_sum.chunk_layout` (fully
    vectorized; the same structure GraphPlan caches per graph): group by
    128-wide id block, split each group into ``chunk``-long chunks, scatter
    the values in and run the one-hot-matmul kernel.  Returns (n_segments,)
    f32.
    """
    interpret = auto_interpret(interpret)
    slot_entry, lids, cblk, nb, _ = chunk_layout(
        np.asarray(seg_ids), n_segments, chunk)
    cvals = chunk_values(jnp.asarray(vals), jnp.asarray(slot_entry))
    out = segment_sum_chunked(cvals, jnp.asarray(lids), jnp.asarray(cblk),
                              nb, interpret=interpret)
    return out.reshape(-1)[: n_segments]
