"""The NumPy references against plain loops, and their controls."""

import os
import sys

# the harness's modules import by their bare names, as bench/run.py does
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_BENCH, os.path.join(os.path.dirname(_BENCH), "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import collections

import numpy as np
import pytest

import byname
from reference import bfs, pagerank

KRON = byname.module("generators", "graph500-kronecker")
CFG = dict(scale=8, edge_factor=16, a=0.57, b=0.19, c=0.19, directed=True,
           graph_seed=22)


def _graph(directed=True, seed=2**31 + 5):
    return KRON.generate(dict(CFG, directed=directed), seed)


def _loop_bfs(g, source):
    adj = collections.defaultdict(list)
    for s, d in zip(g.src.tolist(), g.dst.tolist()):
        adj[s].append(d)
    level = [-1] * g.n
    level[source] = 0
    queue = collections.deque([source])
    while queue:
        u = queue.popleft()
        for v in adj[u]:
            if level[v] < 0:
                level[v] = level[u] + 1
                queue.append(v)
    return np.asarray(level, np.int32)


def _dense_pagerank(g, n_iter=10, d=0.85):
    n = g.n
    a = np.zeros((n, n))
    np.add.at(a, (g.dst, g.src), 1.0)
    deg = a.sum(axis=0)
    m = np.divide(a, deg, out=np.zeros_like(a), where=deg > 0)
    r = np.full(n, 1.0 / n)
    for _ in range(n_iter):
        r = d * m @ r + ((1 - d) + d * r[deg == 0].sum()) / n
    return r


def _edges(g):
    return sorted(zip(g.src.tolist(), g.dst.tolist()))


def test_generator_is_fixed_by_the_config_and_ordered_by_the_seed():
    a, b, c = _graph(), _graph(), _graph(seed=7)
    assert a.src.size == CFG["edge_factor"] << CFG["scale"]
    assert np.array_equal(a.src, b.src) and np.array_equal(a.dst, b.dst)
    # another seed hands over the same edges in another order
    assert not np.array_equal(a.src, c.src)
    assert _edges(a) == _edges(c)
    assert _edges(_graph_from(dict(CFG, graph_seed=23), 7)) != _edges(a)
    u = _graph(directed=False)
    assert u.src.size == 2 * a.src.size
    fwd = collections.Counter(zip(u.src.tolist(), u.dst.tolist()))
    assert fwd == collections.Counter(zip(u.dst.tolist(), u.src.tolist()))


def _graph_from(cfg, seed):
    return KRON.generate(cfg, seed)


@pytest.mark.parametrize("directed", [True, False])
def test_bfs_levels_match_a_queue_search(directed):
    g = _graph(directed)
    ptr, idx = g.out_csr()
    for source in np.flatnonzero(np.diff(ptr))[:5]:
        assert np.array_equal(bfs.levels(ptr, idx, g.n, int(source)),
                              _loop_bfs(g, int(source)))


def test_pagerank_matches_dense_power_iteration():
    g = _graph()
    assert np.allclose(pagerank.ranks(g), _dense_pagerank(g), rtol=1e-12,
                       atol=0)


def test_checks_pass_the_reference_itself():
    g = _graph()
    ptr, idx = g.out_csr()
    s = int(np.argmax(np.diff(ptr)))
    assert bfs.check(g, [({"source": s}, bfs.levels(ptr, idx, g.n, s))]) \
        == {"bfs_level_mismatches": 0}
    r = pagerank.ranks(g).astype(np.float32)
    err = pagerank.check(g, [({"n_iter": 10}, r)])["pagerank_max_rel_err"]
    assert err < pagerank.LIMITS["pagerank_max_rel_err"]


def test_controls_fail_their_limits():
    g = _graph()
    s = int(np.argmax(np.diff(g.out_csr()[0])))
    for mod, params in ((bfs, {"source": s}), (pagerank, {"n_iter": 10})):
        for name, value in mod.control(g, [params]).items():
            assert value > mod.LIMITS[name], (name, value)


def test_wrong_shapes_and_values_count_as_wrong():
    g = _graph()
    s = int(np.argmax(np.diff(g.out_csr()[0])))
    assert bfs.check(g, [({"source": s}, np.zeros(3))])[
        "bfs_level_mismatches"] == g.n
    bad = pagerank.ranks(g)
    bad[0] = np.nan
    assert pagerank.check(g, [({}, bad)])["pagerank_max_rel_err"] == \
        float("inf")


def _cells():
    import run
    return [w["name"] for w in run.load_json(run.ROOT, "BENCHMARK.json")[
        "workloads"]]


@pytest.mark.parametrize("cell", _cells())
def test_control_script_fails_each_cell_at_a_small_size(cell):
    import control
    import run
    spec = run.load_json(run.ROOT, "BENCHMARK.json")
    cfg_name = run._by_name(spec["workloads"], cell, "workload")["config"]
    cfg = run.load_json(run.ROOT, run._by_name(spec["configs"], cfg_name,
                                               "config")["file"])
    got = control.readings(cell, 2**31 + 11, config=dict(cfg, scale=9))
    assert got and all(c["value"] > c["limit"] for c in got.values()), got
