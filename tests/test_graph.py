"""Graph structure: sort-first construction, conversions, functional updates."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # pragma: no cover - depends on environment
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core.graph import Graph, INVALID_ID
from repro.core.table import Table, INT
from repro.core.convert import (to_graph, graph_to_edge_table,
                                graph_to_node_table, table_from_map)
from conftest import random_digraph


def test_construction_and_degrees():
    g = Graph.from_edges([10, 10, 20, 30], [20, 30, 30, 10])
    assert g.n_nodes == 3 and g.n_edges == 4
    assert np.asarray(g.out_degrees()).tolist() == [2, 1, 1]
    assert np.asarray(g.in_degrees()).tolist() == [1, 1, 2]


def test_adjacency_sorted_within_rows():
    g = Graph.from_edges([0, 0, 0, 1], [5, 3, 9, 7])
    nbrs = np.asarray(g.neighbors_out(0))
    assert nbrs.tolist() == sorted(nbrs.tolist())


def test_dense_renumbering_lookup():
    g = Graph.from_edges([100, 7, 100], [7, 55, 55])
    ids = np.asarray(g.node_ids[:g.n_nodes])
    assert ids.tolist() == [7, 55, 100]
    assert np.asarray(g.dense_of([55, 100, 7])).tolist() == [1, 2, 0]
    assert np.asarray(g.original_of([0, 1, 2])).tolist() == [7, 55, 100]


def test_dedupe_and_self_loops():
    g = Graph.from_edges([1, 1, 1, 2], [2, 2, 1, 1], dedupe=True,
                         drop_self_loops=True)
    assert g.n_edges == 2  # (1,2) and (2,1)


def test_edge_table_round_trip(rng):
    s, d = random_digraph(rng, n=80, m=500, seed=7)
    g = Graph.from_edges(s, d)
    et = graph_to_edge_table(g)
    got = set(zip(et.to_pydict()["src"], et.to_pydict()["dst"]))
    assert got == set(zip(s.tolist(), d.tolist()))


def test_to_graph_from_table():
    t = Table.from_columns({"s": INT, "d": INT},
                           {"s": [5, 5, 9], "d": [9, 6, 6]})
    g = to_graph(t, "s", "d")
    assert g.n_nodes == 3 and g.n_edges == 3


def test_to_graph_string_columns():
    from repro.core.table import STR
    t = Table.from_columns({"a": STR, "b": STR},
                           {"a": ["u1", "u2", "u1"], "b": ["u2", "u3", "u3"]})
    g = to_graph(t, "a", "b")
    assert g.n_nodes == 3 and g.n_edges == 3


def test_add_delete_edges():
    g = Graph.from_edges([1, 2], [2, 3])
    g2 = g.add_edges([3], [1])
    assert g2.n_edges == 3
    g3 = g2.delete_edges([3, 1], [1, 2])
    got = graph_to_edge_table(g3).to_pydict()
    assert list(zip(got["src"], got["dst"])) == [(2, 3)]


def test_to_undirected_symmetry(rng):
    s, d = random_digraph(rng, n=40, m=200, seed=3)
    u = Graph.from_edges(s, d).to_undirected()
    es, ed = (np.asarray(x) for x in u.out_edges())
    pairs = set(zip(es.tolist(), ed.tolist()))
    assert all((b, a) in pairs for a, b in pairs)
    assert not any(a == b for a, b in pairs)


def test_node_table_and_score_map():
    g = Graph.from_edges([10, 20], [20, 30])
    import jax.numpy as jnp
    scores = jnp.asarray([0.1, 0.9, 0.5])
    t = table_from_map(g, scores, "node", "score")
    d = t.to_pydict()
    assert d["node"] == [20, 30, 10]      # sorted by score desc
    assert d["score"] == pytest.approx([0.9, 0.5, 0.1])


def test_empty_graph():
    g = Graph.from_edges([], [])
    assert g.n_nodes == 0 and g.n_edges == 0


@settings(max_examples=20, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 30), st.integers(0, 30)),
                min_size=1, max_size=60))
def test_prop_construction_round_trip(edges):
    edges = [(a, b) for a, b in edges]
    s = np.asarray([e[0] for e in edges], np.int32)
    d = np.asarray([e[1] for e in edges], np.int32)
    g = Graph.from_edges(s, d, dedupe=True)
    et = graph_to_edge_table(g)
    got = set(zip(et.to_pydict()["src"], et.to_pydict()["dst"]))
    assert got == set(edges)
    # node set = union of endpoints
    assert g.n_nodes == len(set(s.tolist()) | set(d.tolist()))


@settings(max_examples=20, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 20), st.integers(0, 20)),
                min_size=1, max_size=40))
def test_prop_degree_sum_equals_edges(edges):
    s = np.asarray([e[0] for e in edges], np.int32)
    d = np.asarray([e[1] for e in edges], np.int32)
    g = Graph.from_edges(s, d, dedupe=True)
    assert int(np.asarray(g.out_degrees()).sum()) == g.n_edges
    assert int(np.asarray(g.in_degrees()).sum()) == g.n_edges


@pytest.mark.parametrize("offset", [0, 1 << 20, -5])
def test_renumbering_table_and_sort_paths_agree(rng, offset):
    """Compact ids take the lookup-table path, spread or negative ids the
    sort path; both give the same ascending ids and dense endpoints."""
    from repro.core.graph import _renumber
    src = rng.integers(0, 300, 2000).astype(np.int32) + offset
    dst = rng.integers(0, 300, 2000).astype(np.int32) + offset
    node_ids, src_d, dst_d = _renumber(src, dst)
    want = np.unique(np.concatenate([src, dst]))
    assert node_ids.dtype == np.int32 and np.array_equal(node_ids, want)
    assert np.array_equal(node_ids[src_d], src)
    assert np.array_equal(node_ids[dst_d], dst)


@pytest.mark.parametrize("block", [1 << 4, 1 << 9, 1 << 21])
def test_rmat_stream_independent_of_blocking(monkeypatch, block):
    """The threaded generator reproduces the sequential stream exactly."""
    from repro.data import rmat
    scale, ef, seed, a, b, c = 9, 8, 7, 0.57, 0.19, 0.19
    m = ef << scale
    r = np.random.default_rng(seed)
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for bit in range(scale):
        sb = r.random(m) >= a + b
        thresh = np.where(sb, c / (1.0 - (a + b)), a / (a + b))
        src |= sb.astype(np.int64) << bit
        dst |= (r.random(m) >= thresh).astype(np.int64) << bit
    perm = r.permutation(1 << scale)
    monkeypatch.setattr(rmat, "_BLOCK", block)
    got = rmat.rmat_edges(scale, ef, seed)
    assert np.array_equal(got[0], perm[src]) and np.array_equal(got[1], perm[dst])
