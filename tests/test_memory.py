"""Bounded-memory serving (PR 8): byte accounting, eviction, leak soak.

Covers the memory-budget contract end to end:

* ``GraphPlan`` byte accounting per derived-array family, alias-safe, with
  transparent per-family eviction (re-derive on next touch, bit-identical);
* the service's byte-accounted cost-aware LRU result cache under
  :class:`~repro.serve.policy.MemoryPolicy` — tracked bytes never exceed the
  budget, LRU order holds, result entries evict before plan members, and a
  budgeted service answers every query bit-identically to an unbounded one
  (property-based, random submit/evict/delta sequences);
* concurrency: two workers hammered under a budget tight enough to force
  continuous eviction — no use-after-evict, no deadlock, counters exact
  (extends the PR 7 hammer-test pattern);
* leak soak: a long-lived service through many submit + ``apply_delta``
  cycles plateaus in tracked bytes, provenance-registry size and lineage
  depth (the unbounded strong-pin ring / ancestry-chain bugs this PR fixes).
"""

import gc
import threading
import time

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core import provenance as P
from repro.core.graph import EdgeDelta, Graph
from repro.core.plan import EVICTABLE_FAMILIES
from repro.data.rmat import rmat_edges
from repro.kernels.segment_sum import chunk_layout
from repro.serve.graph_service import GraphService, RejectedError, Workspace
from repro.serve.policy import AdmissionPolicy, MemoryPolicy, SchedulerPolicy


def small_graph(n=32, e=160, seed=0):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    return Graph.from_edges(src, dst)


def rmat_graph(scale=7, edge_factor=4, seed=0):
    s, d = rmat_edges(scale, edge_factor=edge_factor, seed=seed)
    return Graph.from_edges(s, d)


def budgeted_service(budget, graph=None, **kw):
    svc = GraphService(memory=MemoryPolicy(budget_bytes=budget), **kw)
    svc.workspace.put("g", graph if graph is not None else small_graph())
    return svc


# ---------------------------------------------------------------------------
# plan byte accounting + per-family eviction
# ---------------------------------------------------------------------------


def test_plan_nbytes_per_family_and_transparent_evict():
    g = small_graph()
    p = g.plan()
    cold = p.nbytes_by_family()
    assert cold["base"] > 0
    for fam in EVICTABLE_FAMILIES:
        assert cold[fam] == 0, f"{fam} should be cold"
    # materialize lazy members, capture values, evict, re-derive
    before = {
        "csr": tuple(np.asarray(a) for a in p.csr_out()),
        "perm": np.asarray(p.in_perm_out()),
        "oriented": tuple(np.asarray(a) for a in p.oriented()),
        "bsr": np.asarray(p.bsr()[0]),
        "und": np.asarray(p.undirected().out_edges()[0]),
    }
    warm = p.nbytes_by_family()
    for fam in ("csr", "perm", "oriented", "bsr", "undirected"):
        assert warm[fam] > 0, f"{fam} should be warm"
    assert p.evictable_bytes() > 0
    total = p.nbytes()
    assert total == sum(warm.values())
    freed = p.evict_all()
    assert freed > 0
    after_evict = p.nbytes_by_family()
    for fam in EVICTABLE_FAMILIES:
        assert after_evict[fam] == 0
    assert after_evict["base"] == cold["base"]  # base survives, by design
    # bit-identical re-derivation on next touch
    assert all(np.array_equal(a, b)
               for a, b in zip(before["csr"], p.csr_out()))
    assert np.array_equal(before["perm"], np.asarray(p.in_perm_out()))
    assert all(np.array_equal(a, b)
               for a, b in zip(before["oriented"], p.oriented()))
    assert np.array_equal(before["bsr"], np.asarray(p.bsr()[0]))
    assert np.array_equal(before["und"],
                          np.asarray(p.undirected().out_edges()[0]))


def _composed_slot_vertex(plan, seg, edge_vertex):
    """slot -> vertex as a gather of the host layout's slot -> edge: pads
    (edge E) read n."""
    slot_entry = chunk_layout(np.asarray(seg), plan.n_nodes)[0]
    return np.append(np.asarray(edge_vertex), plan.n_nodes)[slot_entry]


def test_chunk_slot_vertex_index_charged_evicted_and_rebuilt():
    g = rmat_graph()
    p = g.plan()
    lay_in, lay_out = p.chunk_layout_in(), p.chunk_layout_out()
    vin, vout = np.asarray(lay_in[0]), np.asarray(lay_out[0])
    np.testing.assert_array_equal(
        vin, _composed_slot_vertex(p, p.in_dst, p.in_src))
    np.testing.assert_array_equal(
        vout, _composed_slot_vertex(p, p.out_src, p.out_dst))
    assert (vin == p.n_nodes).any() and (vout == p.n_nodes).any()
    # charged to "chunks", in place of a slot -> edge index
    layout_bytes = sum(int(a.nbytes) for lay in (lay_in, lay_out)
                       for a in (lay[0], lay[1], lay[2]))
    assert p.nbytes_by_family()["chunks"] == layout_bytes
    assert p.evict("chunks") == layout_bytes
    assert p.nbytes_by_family()["chunks"] == 0
    np.testing.assert_array_equal(np.asarray(p.chunk_layout_in()[0]), vin)
    np.testing.assert_array_equal(np.asarray(p.chunk_layout_out()[0]), vout)
    # a patched plan builds its own, over its own edge arrays
    ids = np.asarray(g.node_ids)[:g.n_nodes]
    child = g.apply_delta(EdgeDelta.inserts(ids[:5], ids[7:12]))
    cp = child.plan()
    assert cp._parent is p and cp.n_edges > p.n_edges
    c_in, c_out = cp.chunk_layout_in(), cp.chunk_layout_out()
    np.testing.assert_array_equal(
        np.asarray(c_in[0]), _composed_slot_vertex(cp, cp.in_dst, cp.in_src))
    np.testing.assert_array_equal(
        np.asarray(c_out[0]),
        _composed_slot_vertex(cp, cp.out_src, cp.out_dst))
    assert cp.nbytes_by_family()["chunks"] == sum(
        int(a.nbytes) for lay in (c_in, c_out) for a in lay[:3])


def test_base_family_is_never_evictable():
    p = small_graph().plan()
    with pytest.raises(ValueError):
        p.evict("base")
    with pytest.raises(ValueError):
        p.evict("lineage")


def test_csr_family_does_not_double_count_graph_storage():
    g = small_graph()
    p = g.plan()
    p.csr_out()
    p.csr_in()
    # csr_out()/csr_in() mostly alias the graph's own ptr/idx arrays; only
    # the trimmed ptr slices and deg_pad vectors are fresh memory
    assert p.nbytes_by_family()["csr"] < g.nbytes() // 4


def test_evict_clears_exec_pytrees_that_reference_family_arrays():
    from repro.core.engine import get_exec
    p = small_graph().plan()
    get_exec(p, "xla")
    assert p.execs
    p.evict("csr")
    assert not p.execs  # execs hold refs into plan arrays; must go too


# ---------------------------------------------------------------------------
# MemoryPolicy validation
# ---------------------------------------------------------------------------


def test_memory_policy_validation():
    with pytest.raises(ValueError):
        MemoryPolicy(budget_bytes=-1)
    with pytest.raises(ValueError):
        MemoryPolicy(max_lineage_depth=0)
    with pytest.raises(ValueError):
        MemoryPolicy(max_provenance_pins=0)
    assert SchedulerPolicy().memory.budget_bytes is None  # default: unbounded


# ---------------------------------------------------------------------------
# property-based eviction invariants (random submit/delta sequences)
# ---------------------------------------------------------------------------

_PROP_GRAPH = small_graph(n=48, e=220, seed=3)


def _apply_op(svc, sess, code, step):
    """One random workload step; returns (tag, result-or-None)."""
    op = code % 5
    if op == 0:
        return ("bfs", svc.execute(sess, {"op": "bfs", "graph": "g",
                                          "params": {"source": code % 48}}))
    if op == 1:
        return ("sssp", svc.execute(sess, {"op": "sssp", "graph": "g",
                                           "params": {"source": code % 48}}))
    if op == 2:
        return ("pagerank", svc.execute(
            sess, {"op": "pagerank", "graph": "g", "params": {"n_iter": 5}}))
    if op == 3:
        return ("cc", svc.execute(sess, {"op": "connected_components",
                                         "graph": "g", "params": {}}))
    # insert-only delta: deterministic edge derived from (code, step)
    u, v = (code * 7 + step) % 48, (code * 13 + 3 * step + 1) % 48
    svc.workspace.apply_delta("g", EdgeDelta.inserts([u], [v]))
    return ("delta", None)


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(0, 99), min_size=3, max_size=7),
       st.integers(20, 120))
def test_eviction_invariants_random_sequences(codes, budget_kb):
    """Budgeted vs unbounded differential run over a random op sequence.

    After every step: tracked bytes <= budget, and every query result is
    bit-identical to the unbounded service's (evicted members re-derive,
    evicted cache entries re-execute — transparently).
    """
    budget = budget_kb * 1024
    bud = budgeted_service(budget, graph=_PROP_GRAPH)
    unb = GraphService()
    unb.workspace.put("g", _PROP_GRAPH)
    sb, su = bud.session("s"), unb.session("s")
    for step, code in enumerate(codes):
        tag_b, out_b = _apply_op(bud, sb, code, step)
        tag_u, out_u = _apply_op(unb, su, code, step)
        assert tag_b == tag_u
        if out_b is not None:
            assert np.array_equal(np.asarray(out_b), np.asarray(out_u)), \
                f"divergence at step {step} ({tag_b})"
        assert bud.memory_stats()["tracked_bytes"] <= budget
    # accounting consistency: the running byte counter matches a recompute
    from repro.serve.graph_service import _value_nbytes
    with bud._lock:
        recomputed = sum(_value_nbytes(v) for v in bud._cache.values())
        assert bud._cache_bytes == recomputed
        assert set(bud._cache_cost) == set(bud._cache)


def test_result_cache_evicts_before_plan_members():
    g = rmat_graph()          # big enough that plan families carry weight
    svc = GraphService()
    svc.workspace.put("g", g)
    s = svc.session("s")
    # cc/triangles materialize the undirected + oriented plan families
    svc.execute(s, {"op": "connected_components", "graph": "g", "params": {}})
    svc.execute(s, {"op": "triangle_count", "graph": "g", "params": {}})
    for i in range(8):
        svc.execute(s, {"op": "bfs", "graph": "g", "params": {"source": i}})
    ms = svc.memory_stats()
    assert ms["plan_evictable_bytes"] > 0 and ms["result_cache_bytes"] > 0
    # budget admits all plan members but not the whole result cache: only
    # result entries may be evicted
    svc._mem.policy = MemoryPolicy(
        budget_bytes=ms["plan_evictable_bytes"] + ms["result_cache_bytes"] // 2)
    svc._mem.maybe_evict()
    assert svc.stats["evicted_results"] > 0
    assert svc.stats["evicted_plan_families"] == 0
    assert svc.memory_stats()["tracked_bytes"] \
        <= svc._mem.policy.budget_bytes
    # budget below the plan's evictable bytes: the result cache must be
    # fully spent before any plan member goes
    svc._mem.policy = MemoryPolicy(
        budget_bytes=max(svc.memory_stats()["plan_evictable_bytes"] // 2, 1))
    svc._mem.maybe_evict()
    assert len(svc._cache) == 0
    assert svc.stats["evicted_plan_families"] > 0
    assert svc.memory_stats()["tracked_bytes"] \
        <= svc._mem.policy.budget_bytes
    # ...and the evicted members re-derive bit-identically on next touch
    out = svc.execute(s, {"op": "bfs", "graph": "g", "params": {"source": 0}})
    svc2 = GraphService()
    svc2.workspace.put("g", g)
    ref = svc2.execute(svc2.session("s"),
                       {"op": "bfs", "graph": "g", "params": {"source": 0}})
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_lru_order_holds_under_byte_eviction():
    svc = budgeted_service(None)   # start unbounded to fill deterministically
    s = svc.session("s")
    keys = []
    for i in range(10):
        svc.execute(s, {"op": "bfs", "graph": "g", "params": {"source": i}})
    with svc._lock:
        keys = list(svc._cache)
    # touch sources 0/1 (MRU), then shrink the budget to roughly half
    svc.execute(s, {"op": "bfs", "graph": "g", "params": {"source": 0}})
    svc.execute(s, {"op": "bfs", "graph": "g", "params": {"source": 1}})
    ms = svc.memory_stats()
    svc._mem.policy = MemoryPolicy(
        budget_bytes=ms["plan_evictable_bytes"]
        + ms["result_cache_bytes"] // 2)
    svc._mem.maybe_evict()
    with svc._lock:
        survivors = list(svc._cache)
    assert survivors, "eviction should not empty the cache at this budget"
    # the survivors must be exactly the most-recently-used suffix:
    # re-touched 0/1 last, before them the newest of the original fill
    expected_order = [k for k in keys if k not in (keys[0], keys[1])] \
        + [keys[0], keys[1]]
    assert survivors == expected_order[-len(survivors):]


def test_retention_and_warm_starts_respect_budget():
    budget = 40 * 1024
    svc = budgeted_service(budget)
    s = svc.session("s")
    for i in range(12):
        svc.execute(s, {"op": "bfs", "graph": "g", "params": {"source": i}})
    # deltas drive retention / warm starts; the budget must hold throughout
    for k in range(6):
        svc.workspace.apply_delta("g", EdgeDelta.inserts([k], [(k + 9) % 32]))
        svc.execute(s, {"op": "bfs", "graph": "g", "params": {"source": k}})
        assert svc.memory_stats()["tracked_bytes"] <= budget


# ---------------------------------------------------------------------------
# concurrency: continuous eviction under two workers (PR 7 hammer pattern)
# ---------------------------------------------------------------------------


def test_stats_exact_and_no_deadlock_under_budgeted_hammer():
    g = small_graph(n=64, e=320, seed=1)
    svc = GraphService(memory=MemoryPolicy(budget_bytes=24 * 1024),
                       policy=SchedulerPolicy(
                           admission=AdmissionPolicy(max_inflight=16,
                                                     max_queue_depth=256)),
                       workers=2)
    svc.workspace.put("g", g)
    n_threads, per_thread = 2, 150
    errors, done = [], []
    done_lock = threading.Lock()

    def hammer(tid):
        sess = svc.session(f"s{tid}")
        for i in range(per_thread):
            req = {"op": "bfs", "graph": "g",
                   "params": {"source": (tid * 31 + i) % 64}}
            while True:
                try:
                    p = sess.submit(req)
                    break
                except RejectedError as e:
                    time.sleep(min(e.retry_after, 0.005))
            try:
                p.result(timeout=30.0)
                with done_lock:
                    done.append(p)
            except Exception as e:   # pragma: no cover - failure detail
                errors.append((tid, i, e))

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120.0)
    assert not any(t.is_alive() for t in threads), \
        "hammer threads wedged: deadlock between eviction and scheduler"
    svc.close()
    assert not errors, errors[:3]
    assert len(done) == n_threads * per_thread
    st_ = svc.stats
    served = st_["cache_hits"] + st_["engine_calls"] \
        + st_["fused_requests"] - st_["fused_calls"] + st_["retained"]
    assert served >= st_["engine_calls"]
    assert st_["requests"] == len(done) + st_["rejected"]
    # continuous eviction actually happened, the budget held, and the byte
    # ledger is exact (no use-after-evict would leave it consistent)
    assert st_["evicted_results"] > 0
    assert svc.memory_stats()["tracked_bytes"] <= 24 * 1024
    from repro.serve.graph_service import _value_nbytes
    with svc._lock:
        assert svc._cache_bytes == sum(_value_nbytes(v)
                                       for v in svc._cache.values())


# ---------------------------------------------------------------------------
# leak soak: tracked bytes / provenance registry / lineage must plateau
# ---------------------------------------------------------------------------


def test_leak_soak_plateaus():
    budget = 48 * 1024
    svc = budgeted_service(budget, graph=small_graph(n=40, e=180, seed=2))
    s = svc.session("s")
    depth = svc.memory.max_lineage_depth

    def sample():
        gc.collect()
        ms = svc.memory_stats()
        with P._LOCK:
            reg = len(P._BY_VERSION)
        return ms["tracked_bytes"], reg, ms["provenance_pins"]

    cycles, mid = 300, None
    for i in range(cycles):
        u, v = (3 * i) % 40, (7 * i + 1) % 40
        svc.workspace.apply_delta("g", EdgeDelta.inserts([u], [v]))
        svc.execute(s, {"op": "bfs", "graph": "g",
                        "params": {"source": i % 40}})
        if i % 3 == 0:
            svc.execute(s, {"op": "pagerank", "graph": "g",
                            "params": {"n_iter": 3}})
        assert svc.workspace.get("g").lineage_depth() <= depth
        if i == cycles // 2:
            mid = sample()
    end = sample()
    tracked_mid, reg_mid, pins_mid = mid
    tracked_end, reg_end, pins_end = end
    assert tracked_end <= budget
    # plateau: the second half of the soak must not keep growing the
    # registry or the tracked footprint (generous 25% slack + constant)
    assert tracked_end <= tracked_mid * 1.25 + 8192, (mid, end)
    assert reg_end <= reg_mid * 1.25 + 64, (mid, end)
    assert pins_end <= P.pin_stats()["capacity"]


def test_strong_pin_ring_bounded_and_registry_cleaned():
    baseline = P.pin_stats()
    try:
        P.set_pin_capacity(32)
        tokens = []
        for i in range(200):
            # tuples refuse both attributes and weakrefs -> pinned path
            tokens.append(P.version_of((i, "pin-me")))
        stats = P.pin_stats()
        assert stats["pinned"] <= 32
        assert stats["capacity"] == 32
        with P._LOCK:
            pinned_entries = sum(
                1 for v in P._BY_VERSION.values()
                if isinstance(v, tuple) and v[0] is P._PINNED)
        # pre-fix, every evicted pin leaked its _BY_VERSION entry: 200 here
        assert pinned_entries <= 32
        # evicted tokens resolve to nothing; the youngest still resolve
        assert P.object_for_version(tokens[0]) is None
        assert P.object_for_version(tokens[-1]) == (199, "pin-me")
    finally:
        P.set_pin_capacity(max(baseline["capacity"], 1))


# ---------------------------------------------------------------------------
# telemetry: gauges + session_stats + stats surfaces
# ---------------------------------------------------------------------------


def test_memory_telemetry_surfaces():
    import repro.obs as obs
    svc = budgeted_service(64 * 1024)
    s = svc.session("s")
    svc.execute(s, {"op": "bfs", "graph": "g", "params": {"source": 1}})
    ms = svc.memory_stats()
    for k in ("tracked_bytes", "budget_bytes", "result_cache_bytes",
              "plan_bytes", "plan_evictable_bytes", "provenance_pins"):
        assert k in ms
    assert ms["budget_bytes"] == 64 * 1024
    # the same numbers ride session_stats (mem_ prefix, flat scalars)...
    ss = svc.session_stats("s")
    assert ss["mem_tracked_bytes"] == svc.memory_stats()["tracked_bytes"]
    assert all(isinstance(ss[k], (int, float))
               for k in ss if k.startswith("mem_"))
    # ...and the obs gauges the metrics RPC ships are populated
    snap = obs.REGISTRY.snapshot()
    if snap:   # obs may be disabled via env in exotic CI configs
        for gname in ("mem.tracked_bytes", "mem.result_cache_bytes",
                      "mem.plan_bytes", "mem.budget_bytes"):
            assert gname in snap, sorted(snap)[:10]
        assert snap["mem.budget_bytes"]["value"] == 64 * 1024
