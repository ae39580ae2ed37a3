"""Unified traversal engine: backend parity, plan caching, batched traversal.

Covers the plan/engine layering (core/plan.py + core/engine.py): the three
backends must agree bit-for-bit-ish on real algorithms over RMAT graphs, a
second call on the same Graph must reuse the cached plan (zero re-sorting),
and functional updates must invalidate by construction.
"""

import inspect

import jax
import numpy as np
import pytest
import jax.numpy as jnp

from repro import obs
from repro.core import algorithms as A
from repro.core import engine
from repro.core.graph import EdgeDelta, Graph
from repro.data.rmat import rmat_edges
from repro.kernels.segment_sum import (chunk_layout, chunk_values,
                                       segment_sum_chunked)

BACKENDS = ["xla", "pallas", "bsr", "frontier"]


def rmat_graph(scale=6, edge_factor=4, seed=0):
    s, d = rmat_edges(scale, edge_factor=edge_factor, seed=seed)
    return Graph.from_edges(s, d)


# ---------------------------------------------------------------------------
# primitive parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_pull_sum_matches_oracle(backend):
    g = rmat_graph(seed=1)
    plan = g.plan()
    x = jnp.arange(g.n_nodes, dtype=jnp.float32) + 1.0
    got = np.asarray(engine.pull(plan, x, "sum", backend=backend,
                                 interpret=True))
    s, d = (np.asarray(a) for a in g.in_edges())
    want = np.zeros(g.n_nodes, np.float32)
    np.add.at(want, d, np.asarray(x)[s])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_push_sum_matches_oracle(backend):
    g = rmat_graph(seed=2)
    plan = g.plan()
    x = jnp.arange(g.n_nodes, dtype=jnp.float32) + 1.0
    got = np.asarray(engine.push(plan, x, "sum", backend=backend,
                                 interpret=True))
    s, d = (np.asarray(a) for a in g.out_edges())
    want = np.zeros(g.n_nodes, np.float32)
    np.add.at(want, s, np.asarray(x)[d])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_pull_sum_integer_dtype_neutral():
    # f32-only kernel paths must fall back so backend choice never changes
    # the result dtype or integer exactness
    g = rmat_graph(seed=41)
    plan = g.plan()
    x = jnp.ones((g.n_nodes,), jnp.int32)
    ref = engine.pull(plan, x, "sum", backend="xla")
    for be in ("pallas", "bsr"):
        got = engine.pull(plan, x, "sum", backend=be, interpret=True)
        assert got.dtype == ref.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_pull_min_max_all_backends_agree():
    g = rmat_graph(seed=3)
    plan = g.plan()
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=g.n_nodes).astype(np.float32))
    ref = np.asarray(engine.pull(plan, x, "min", backend="xla"))
    for be in ("pallas", "bsr"):   # non-sum combines fall back, same result
        np.testing.assert_array_equal(
            np.asarray(engine.pull(plan, x, "min", backend=be,
                                   interpret=True)), ref)


# ---------------------------------------------------------------------------
# algorithm parity across backends (RMAT graphs)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_pagerank_backend_parity(backend):
    g = rmat_graph(scale=7, edge_factor=4, seed=5)
    ref = np.asarray(A.pagerank(g, n_iter=8, backend="xla"))
    got = np.asarray(A.pagerank(g, n_iter=8, backend=backend, interpret=True))
    np.testing.assert_allclose(got, ref, atol=1e-5)
    assert abs(got.sum() - 1.0) < 1e-4


@pytest.mark.parametrize("backend", BACKENDS)
def test_connected_components_backend_parity(backend):
    g = rmat_graph(scale=6, edge_factor=1, seed=7)   # sparse -> many comps
    ref = np.asarray(A.connected_components(g, backend="xla"))
    got = np.asarray(A.connected_components(g, backend=backend,
                                            interpret=True))
    np.testing.assert_array_equal(got, ref)


def test_triangle_count_backend_parity():
    u = rmat_graph(scale=6, edge_factor=4, seed=11).to_undirected()
    ref = A.triangle_count(u)
    assert A.triangle_count(u, backend="bsr", interpret=True) == ref


@pytest.mark.parametrize("backend", BACKENDS)
def test_hits_backend_parity(backend):
    g = rmat_graph(seed=13)
    hub_ref, auth_ref = (np.asarray(x) for x in A.hits(g, n_iter=10,
                                                       backend="xla"))
    hub, auth = (np.asarray(x) for x in A.hits(g, n_iter=10, backend=backend,
                                               interpret=True))
    np.testing.assert_allclose(hub, hub_ref, atol=1e-5)
    np.testing.assert_allclose(auth, auth_ref, atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_k_core_backend_parity(backend):
    g = rmat_graph(seed=17)
    ref = np.asarray(A.k_core(g, 3, backend="xla"))
    got = np.asarray(A.k_core(g, 3, backend=backend, interpret=True))
    np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------------------
# plan caching: repeated calls pay the sort cost once
# ---------------------------------------------------------------------------


def test_plan_is_memoized_by_identity():
    g = rmat_graph(seed=19)
    assert g.plan() is g.plan()
    ex = engine.get_exec(g.plan(), "xla")
    assert engine.get_exec(g.plan(), "xla") is ex


def test_repeated_pagerank_does_zero_resorting(monkeypatch):
    g = rmat_graph(seed=23)
    first = np.asarray(A.pagerank(g, n_iter=5))

    def boom(*a, **kw):  # any re-derivation of edge arrays would call these
        raise AssertionError("plan cache miss: graph re-sorted on 2nd call")

    monkeypatch.setattr(Graph, "in_edges", boom)
    monkeypatch.setattr(Graph, "out_edges", boom)
    monkeypatch.setattr(Graph, "out_degrees", boom)
    second = np.asarray(A.pagerank(g, n_iter=5))
    np.testing.assert_array_equal(first, second)


def test_plan_caches_undirected_and_oriented():
    g = rmat_graph(seed=29)
    plan = g.plan()
    assert plan.undirected() is plan.undirected()
    assert plan.oriented() is plan.oriented()
    assert plan.bsr() is plan.bsr()
    assert plan.bsr_t() is plan.bsr_t()
    assert plan.chunk_layout_in() is plan.chunk_layout_in()


def test_bsr_push_uses_transpose_tiles(monkeypatch):
    """push on "bsr" must take the SpMV path, not fall back to XLA."""
    g = rmat_graph(seed=43)
    plan = g.plan()
    ex = engine.get_exec(plan, "bsr", interpret=True)
    x = jnp.arange(g.n_nodes, dtype=jnp.float32)
    want = np.asarray(engine.push(plan, x, "sum", backend="xla"))

    def boom(self, edge_vals, combine="sum"):
        raise AssertionError("bsr push fell back to the XLA reduction")

    monkeypatch.setattr(engine.XlaExec, "reduce_out", boom)
    got = np.asarray(ex.push(x, "sum"))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_functional_updates_invalidate_plan():
    g = Graph.from_edges([1, 2], [2, 3])
    p = g.plan()
    g2 = g.add_edges([3], [1])
    assert g2.plan() is not p
    # results reflect the new edge (3->1 closes the cycle)
    lab = np.asarray(A.connected_components(g2))
    assert len(set(lab.tolist())) == 1
    g3 = g2.delete_edges([3], [1])
    assert g3.plan() is not g2.plan()
    assert g3.n_edges == 2
    g.invalidate_plan()
    assert g.plan() is not p


# ---------------------------------------------------------------------------
# plan-cache semantics under deltas
# ---------------------------------------------------------------------------


def _known_id_delta(g, k=6, seed=0):
    r = np.random.default_rng(seed)
    ids = np.asarray(g.node_ids)[:g.n_nodes]
    return EdgeDelta.inserts(ids[r.integers(0, g.n_nodes, k)],
                             ids[r.integers(0, g.n_nodes, k)])


def test_delta_child_plan_patched_without_resorting(monkeypatch):
    """The child's plan derives from the parent's: memoized per child,
    linked to the parent plan, and built with zero edge re-derivation."""
    g = rmat_graph(seed=83)
    p = g.plan()
    child = g.apply_delta(_known_id_delta(g))
    assert child._delta is not None

    def boom(*a, **kw):
        raise AssertionError("patched plan re-derived edge arrays")

    monkeypatch.setattr(Graph, "in_edges", boom)
    monkeypatch.setattr(Graph, "out_edges", boom)
    cp = child.plan()
    assert child.plan() is cp                     # memoized per child
    assert cp._parent is p                        # lineage points at parent
    assert cp.dirty_vertices is not None and len(cp.dirty_vertices) > 0


def test_delta_leaves_parent_plan_untouched():
    g = rmat_graph(seed=89)
    p = g.plan()
    in_src0 = np.asarray(p.in_src).copy()
    child = g.apply_delta(_known_id_delta(g))
    child.plan()
    assert g.plan() is p                          # identity preserved
    assert g.n_edges == p.n_edges                 # parent graph unchanged
    np.testing.assert_array_equal(np.asarray(p.in_src), in_src0)


def test_patched_plan_matches_rederived():
    """Patched CSR arrays and degrees are bit-identical to a plan derived
    from scratch over the same edge set (insert-only and mixed)."""
    g = rmat_graph(seed=97)
    ids = np.asarray(g.node_ids)[:g.n_nodes]
    es, ed = (np.asarray(x) for x in g.out_edges())
    ins = _known_id_delta(g, seed=1)
    mixed = EdgeDelta(ins.add_src, ins.add_dst,
                      ids[es[:3]], ids[ed[:3]])
    for delta in (ins, mixed):
        child = g.apply_delta(delta)
        assert child._delta is not None
        cp = child.plan()
        ref = Graph.from_dense_edges(*child.out_edges(), child.n_nodes).plan()
        assert cp.n_edges == ref.n_edges
        for fld in ("in_src", "in_dst", "out_src", "out_dst",
                    "out_deg", "in_deg", "dangling"):
            np.testing.assert_array_equal(
                np.asarray(getattr(cp, fld))[:cp.n_edges],
                np.asarray(getattr(ref, fld))[:cp.n_edges],
                err_msg=f"{fld} (insert_only={delta.insert_only})")


def test_second_update_gets_its_own_plan():
    """A second delta on the child yields a fresh plan chained to the
    child's — earlier plans stay valid and unmodified."""
    g = rmat_graph(seed=101)
    c1 = g.apply_delta(_known_id_delta(g, seed=2))
    p1 = c1.plan()
    c2 = c1.apply_delta(_known_id_delta(g, seed=3))
    p2 = c2.plan()
    assert p2 is not p1 and c1.plan() is p1
    assert p2._parent is p1
    # results through the chained patch match a from-scratch derivation
    fresh = Graph.from_dense_edges(*c2.out_edges(), c2.n_nodes)
    np.testing.assert_array_equal(
        np.asarray(A.connected_components(c2)),
        np.asarray(A.connected_components(fresh)))


# ---------------------------------------------------------------------------
# batched multi-source traversal (vmap over the engine)
# ---------------------------------------------------------------------------


def test_batched_bfs_matches_single_source():
    g = rmat_graph(seed=31)
    sources = jnp.asarray([0, 1, 5], dtype=jnp.int32)
    batched = np.asarray(A.bfs(g, sources))
    assert batched.shape == (3, g.n_nodes)
    for i, s in enumerate([0, 1, 5]):
        np.testing.assert_array_equal(batched[i], np.asarray(A.bfs(g, s)))


def test_batched_sssp_weighted():
    g = Graph.from_edges([0, 1, 0], [1, 2, 2])
    # in-edge order (sorted by dst, then src): (0->1), (0->2), (1->2)
    w = jnp.asarray([1.0, 5.0, 1.0])
    d = np.asarray(A.sssp(g, jnp.asarray([0], dtype=jnp.int32), weights=w))
    assert d.shape == (1, 3)
    assert d[0, 2] == pytest.approx(2.0)   # 0->1->2 beats the heavy 0->2


# ---------------------------------------------------------------------------
# fixpoint driver + layering invariants
# ---------------------------------------------------------------------------


def _collatz_ish_body(ex, v):
    return jnp.minimum(v, ex.pull(v, "min"))


def test_fixpoint_max_iter_caps_rounds():
    g = Graph.from_edges(list(range(9)), list(range(1, 10)))  # path graph
    plan = g.plan()
    v0 = jnp.arange(g.n_nodes, dtype=jnp.int32)
    one = engine.fixpoint(plan, _collatz_ish_body, v0, max_iter=1)
    full = engine.fixpoint(plan, _collatz_ish_body, v0)
    assert int(np.asarray(one).max()) > 0       # capped: not yet converged
    assert np.asarray(full).max() == 0          # converged: all labels 0


def test_fixpoint_terminates_on_nan_state():
    # NaN != NaN must not spin the until-unchanged loop forever
    g = Graph.from_edges([0, 1], [1, 0])
    d = np.asarray(A.sssp(g, 0, weights=jnp.asarray([jnp.nan, 1.0])))
    assert d.shape == (2,)          # terminating at all is the assertion


def test_triangle_count_rejects_unknown_backend():
    u = Graph.from_edges([0, 1, 2], [1, 2, 0]).to_undirected()
    with pytest.raises(ValueError):
        A.triangle_count(u, backend="pallas")


def test_algorithms_route_through_engine_only():
    """Acceptance: no direct jax.ops.segment_* call sites in algorithms.py."""
    src = inspect.getsource(A)
    assert "jax.ops.segment_" not in src
    assert "segment_sum(" not in src


def test_select_backend_override_and_validation():
    g = rmat_graph(seed=37)
    assert engine.select_backend(g.plan(), "bsr") == "bsr"
    assert engine.select_backend(g.plan()) in engine.BACKENDS
    with pytest.raises(ValueError):
        engine.select_backend(g.plan(), "tpu_magic")


def test_select_backend_op_aware_fallback():
    """Unsupported op/backend combinations resolve to "xla", never fail."""
    plan = rmat_graph(seed=47).plan()
    for op in ("bfs", "sssp", "connected_components", "label_propagation"):
        assert engine.select_backend(plan, "frontier", op=op) == "frontier"
    for op in ("pagerank", "hits", "k_core", "triangle_count"):
        assert engine.select_backend(plan, "frontier", op=op) == "xla"
    # op-awareness never touches backends with generic primitives
    assert engine.select_backend(plan, "bsr", op="pagerank") == "bsr"
    assert engine.select_backend(plan, "xla", op="anything") == "xla"


# ---------------------------------------------------------------------------
# frontier backend: plan-cache structure + sparse/dense agreement
# ---------------------------------------------------------------------------


def test_frontier_csr_is_memoized_on_plan():
    plan = rmat_graph(seed=53).plan()
    assert plan.csr_out() is plan.csr_out()
    assert plan.csr_in() is plan.csr_in()
    assert plan.in_perm_out() is plan.in_perm_out()
    ex = engine.get_exec(plan, "frontier")
    assert engine.get_exec(plan, "frontier") is ex


def test_frontier_csr_invalidated_by_functional_update():
    g = Graph.from_edges([0, 1, 2], [1, 2, 3])
    ptr0, _, _ = g.plan().csr_out()
    g2 = g.add_edges([3], [0])
    assert g2.plan() is not g.plan()
    ptr2, _, _ = g2.plan().csr_out()
    assert ptr2 is not ptr0
    assert int(ptr2[-1]) == 4      # the fresh plan sees the new edge
    # and results computed through the frontier path reflect it
    assert np.asarray(A.bfs(g2, 3, backend="frontier"))[0] == 1


def test_frontier_weight_permutation_rekeys_in_order_weights():
    g = Graph.from_edges([0, 1, 0], [1, 2, 2])
    # in-edge order (sorted by dst, then src): (0->1), (0->2), (1->2)
    w = jnp.asarray([1.0, 5.0, 1.0])
    d = np.asarray(A.sssp(g, 0, weights=w, backend="frontier"))
    assert d[2] == pytest.approx(2.0)   # 0->1->2 beats the heavy 0->2


@pytest.mark.parametrize("seed,edge_factor", [(61, 1), (67, 4), (71, 8)])
def test_frontier_bfs_sssp_match_dense(seed, edge_factor):
    """Sparse push + direction-optimized dense pull == dense relaxation."""
    g = rmat_graph(scale=7, edge_factor=edge_factor, seed=seed)
    for src in (0, 3):
        np.testing.assert_array_equal(
            np.asarray(A.bfs(g, src, backend="frontier")),
            np.asarray(A.bfs(g, src, backend="xla")))
    w = jnp.asarray(np.random.default_rng(seed).uniform(
        0.1, 2.0, g.n_edges).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(A.sssp(g, 1, weights=w, backend="frontier")),
        np.asarray(A.sssp(g, 1, weights=w, backend="xla")))


def test_frontier_batched_multi_source_matches_single():
    g = rmat_graph(seed=73)
    sources = jnp.asarray([0, 2, 9], dtype=jnp.int32)
    batched = np.asarray(A.bfs(g, sources, backend="frontier"))
    assert batched.shape == (3, g.n_nodes)
    for i, s in enumerate([0, 2, 9]):
        np.testing.assert_array_equal(
            batched[i], np.asarray(A.bfs(g, s, backend="frontier")))


def test_capped_n_iter_matches_per_row_runs():
    g = rmat_graph(seed=79)
    sources = jnp.asarray([0, 4, 8], dtype=jnp.int32)
    caps = np.asarray([1, 3, 50], np.int32)
    for backend in ("xla", "frontier"):
        rows = np.asarray(A.bfs(g, sources, n_iter=caps, backend=backend))
        for i, (s, c) in enumerate(zip([0, 4, 8], caps)):
            np.testing.assert_array_equal(
                rows[i], np.asarray(A.bfs(g, int(s), n_iter=int(c),
                                          backend=backend)),
                err_msg=f"{backend} row {i}")


@pytest.mark.parametrize("m", [0, 1, 1024, 1025, 5000, (1 << 21) + 3])
def test_int_cumsum_matches_cumsum(m):
    from repro.core.engine import _int_cumsum
    x = np.random.default_rng(m).integers(0, 50, m).astype(np.int32)
    got = np.asarray(_int_cumsum(jnp.asarray(x)))
    assert got.dtype == np.int32 and np.array_equal(got, np.cumsum(x))


# ---------------------------------------------------------------------------
# Pallas sum pulls: one gather through the composed slot -> vertex index
# ---------------------------------------------------------------------------


def _chunked_graph():
    """A graph whose chunk layout has uneven 128-blocks (the last one is
    partial), pad slots, and more than one chunk in some block."""
    g = rmat_graph(scale=9, edge_factor=8, seed=3)
    ex = engine.get_exec(g.plan(), "pallas", interpret=True)
    for vsrc, blk in ((ex.p_vsrc, ex.p_blk), (ex.q_vsrc, ex.q_blk)):
        assert np.bincount(np.asarray(blk)).max() >= 2
        assert (np.asarray(vsrc) == g.n_nodes).any()
    assert g.n_nodes % 128 != 0
    return g, ex


def _two_gather(ex, x, direction):
    """The reduction composed as two gathers, from the host chunk layout:
    gather edge-order values, then gather those into the chunk buffer."""
    if direction == "pull":
        ev, seg, lids, blk, nb = (x[ex.in_src], ex.in_dst, ex.p_lids,
                                  ex.p_blk, ex.nb_in)
    else:
        ev, seg, lids, blk, nb = (x[ex.out_dst], ex.out_src, ex.q_lids,
                                  ex.q_blk, ex.nb_out)
    slot_entry = jnp.asarray(chunk_layout(np.asarray(seg), ex.n_nodes)[0])
    out = segment_sum_chunked(chunk_values(ev, slot_entry), lids, blk, nb,
                              interpret=True)
    return np.asarray(out.reshape(-1)[: ex.n_nodes])


def _one_gather(ex, x, direction):
    return np.asarray(ex.pull(x, "sum") if direction == "pull"
                      else ex.push(x, "sum"))


@pytest.mark.parametrize("direction", ["pull", "push"])
def test_pallas_one_gather_matches_two_gather_bitwise(direction):
    g, ex = _chunked_graph()
    x = jnp.asarray(np.random.default_rng(7).normal(
        size=g.n_nodes).astype(np.float32))
    np.testing.assert_array_equal(_one_gather(ex, x, direction),
                                  _two_gather(ex, x, direction))


@pytest.mark.parametrize("direction", ["pull", "push"])
def test_pallas_one_gather_pads_read_zero_with_inf(direction):
    """An inf in x spoils only the output blocks of chunks that hold one
    of its edges (the one-hot matmul multiplies every slot by 0 or 1): a
    pad slot that read it would spoil every block with pads."""
    g, ex = _chunked_graph()
    if direction == "pull":
        src, vsrc, blk = ex.in_src, ex.p_vsrc, ex.p_blk
    else:
        src, vsrc, blk = ex.out_dst, ex.q_vsrc, ex.q_blk
    fan = np.bincount(np.asarray(src), minlength=g.n_nodes)
    hot = int(np.flatnonzero(fan == fan[fan > 0].min())[0])
    x = np.random.default_rng(8).random(g.n_nodes).astype(np.float32)
    x[hot] = np.inf
    x = jnp.asarray(x)
    got = _one_gather(ex, x, direction)
    np.testing.assert_array_equal(got, _two_gather(ex, x, direction))
    pads = np.asarray(vsrc) == g.n_nodes
    assert (np.asarray(chunk_values(x, vsrc))[pads] == 0.0).all()
    blk = np.asarray(blk)
    spoiled = np.unique(blk[(np.asarray(vsrc) == hot).any(axis=1)])
    clean = np.setdiff1d(blk[pads.any(axis=1)], spoiled)
    assert clean.size, "some block with pads must hold none of hot's edges"
    block_of = np.arange(g.n_nodes) // 128
    assert np.isfinite(got[np.isin(block_of, clean)]).all()
    assert not np.isfinite(got[np.isin(block_of, spoiled)]).all()


@pytest.mark.parametrize("direction", ["pull", "push"])
def test_pallas_one_gather_vmapped_rows(direction):
    g, ex = _chunked_graph()
    xs = jnp.asarray(np.random.default_rng(9).random(
        (3, g.n_nodes)).astype(np.float32))
    fn = (lambda r: ex.pull(r, "sum")) if direction == "pull" else \
        (lambda r: ex.push(r, "sum"))
    batched = np.asarray(jax.vmap(fn)(xs))
    for i in range(3):
        np.testing.assert_array_equal(batched[i], np.asarray(fn(xs[i])))


def _one_gather_count():
    return obs.counter("engine.pallas.one_gather_pulls").value


def test_pallas_edge_value_reductions_fall_back_to_xla():
    """Weighted pulls and reductions of caller-given edge values take the
    XLA segment reductions, bit for bit, and no Pallas pull is counted."""
    g, ex = _chunked_graph()
    xla = engine.get_exec(g.plan(), "xla")
    x = jnp.asarray(np.random.default_rng(10).random(
        g.n_nodes).astype(np.float32))
    w = jnp.asarray(np.random.default_rng(11).random(
        g.n_edges).astype(np.float32))
    n0 = _one_gather_count()
    for got, want in (
            (ex.pull(x, "sum", edge_values=w),
             xla.pull(x, "sum", edge_values=w)),
            (ex.push(x, "sum", edge_values=w),
             xla.push(x, "sum", edge_values=w)),
            (ex.reduce_in(w), xla.reduce_in(w)),
            (ex.reduce_out(w), xla.reduce_out(w))):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert _one_gather_count() == n0


def test_pallas_gather_counter_counts_traced_sites():
    g, ex = _chunked_graph()
    x = jnp.ones((g.n_nodes,), jnp.float32)
    n0 = _one_gather_count()
    ex.pull(x, "sum")
    ex.push(x, "sum")
    assert _one_gather_count() == n0 + 2
    # non-sum, integer and batched operands fall back to XLA: no count
    ex.pull(x, "max")
    ex.pull(x.astype(jnp.int32), "sum")
    ex.pull(jnp.ones((g.n_nodes, 2), jnp.float32), "sum")
    assert _one_gather_count() == n0 + 2
    # a jitted body counts once, when traced, however often it runs
    f = jax.jit(lambda ex, v: ex.pull(v, "sum"))
    f(ex, x)
    f(ex, x + 1.0)
    assert _one_gather_count() == n0 + 3


def test_pallas_pagerank_takes_one_gather_path():
    g = rmat_graph(scale=8, edge_factor=4, seed=12)
    n0 = _one_gather_count()
    A.pagerank(g, n_iter=3, backend="pallas", interpret=True)
    assert _one_gather_count() - n0 >= 1
    assert "engine.pallas.one_gather_pulls" in obs.profile_report()
