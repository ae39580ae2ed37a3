"""The readers of the program's own instruments and the reduction of the
program's spans in a trace, on hand-made inputs and on the recorded chip
traces (``bench/testdata``)."""

import os
import sys

# the harness's modules import by their bare names, as bench/run.py does
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_BENCH, os.path.join(os.path.dirname(_BENCH), "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

import program_spans as ps
import run
import trace_reduce as tr

MS = 1_000_000  # ns


def _hist(count, total):
    return {"type": "histogram", "buckets": [1.0], "counts": [0, count],
            "sum": total, "count": count}


def _counter(v):
    return {"type": "counter", "value": v}


def _ctx():
    """A BFS window: 3 push rounds of 1,000 edges, 2 pull rounds of 64,000
    edges, 4 replies, 12.5 s of plan builds in set-up."""
    before = {"serve.reply_ms": _hist(8, 100.0),
              "plan.build_ms": _hist(5, 12500.0),
              "engine.frontier.push_edges": _counter(500),
              "engine.frontier.pull_edges": _counter(0)}
    after = {"serve.reply_ms": _hist(12, 180.0),
             "plan.build_ms": _hist(5, 12500.0),
             "engine.frontier.push_edges": _counter(3500),
             "engine.frontier.pull_edges": _counter(128000)}
    trace = {"op_seconds": {
        "jit__frontier_push_step:%fusion.22": 20e-6,
        "jit__compact:%fusion.10": 10e-6,
        "jit__frontier_dense_step:%fusion.1": 640e-6,
        "jit__stats_of:%reduce.1": 1e-6,
        "jit_fixpoint_pagerank_fori:%fusion.7": 1.0}}
    return run.Context(before=before, after=after, trace=trace, records=[])


@pytest.mark.parametrize("metric,want", [
    ("engine.frontier.push_ns_per_edge", 30e-6 / 3000 * 1e9),
    ("engine.frontier.pull_ns_per_edge", 640e-6 / 128000 * 1e9),
    ("serve.reply_ms.query", 80.0 / 4),
    ("serve.reply_ms.job", 80.0 / 4),
    ("plan.build_s.query", 12.5),
    ("plan.build_s.job", 12.5),
])
def test_reader_on_hand_made_context(metric, want):
    assert run.load_reader(metric).read(_ctx()) == pytest.approx(want)


NEW = ("engine.frontier.push_ns_per_edge", "engine.frontier.pull_ns_per_edge",
       "serve.reply_ms.query", "serve.reply_ms.job", "plan.build_s.query",
       "plan.build_s.job")


@pytest.mark.parametrize("metric", NEW)
def test_reader_reads_nothing_without_the_instrument(metric):
    """A program without the instrument (an older commit) reads as no
    metric, and no reader raises."""
    ctx = _ctx()
    ctx.before, ctx.after = {}, {}
    assert run.load_reader(metric).read(ctx) is None
    ctx = _ctx()
    ctx.trace = None
    if metric.endswith("_ns_per_edge"):
        assert run.load_reader(metric).read(ctx) is None


def test_new_metrics_are_declared_for_the_cells_they_read():
    spec = run.load_json(run.ROOT, "BENCHMARK.json")
    by = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW:
        cell = ("kron22.pagerank-jobs" if name.endswith(".job")
                else "kron22u.g500-bfs")
        assert by[name]["workloads"] == [cell]
        assert name in {m["name"] for m in run.cell_metrics(spec, cell, True)}


def _events():
    devices = {"/device:TPU:0": [
        ("jit__compact:%f", 10 * MS, 20 * MS),
        ("jit__frontier_push_step:%f", 22 * MS, 40 * MS),
        ("jit__frontier_dense_step:%f", 60 * MS, 80 * MS),
    ]}
    bench = [("bench.window", 0, 100 * MS), ("bench.call.bfs", 5 * MS, 95 * MS)]
    program = [
        ("engine.frontier_fixpoint", 8 * MS, 90 * MS),
        ("engine.frontier.push", 9 * MS, 45 * MS),
        ("engine.frontier.sync", 38 * MS, 45 * MS),
        ("engine.frontier.pull", 55 * MS, 85 * MS),
        ("engine.frontier.sync", 70 * MS, 85 * MS),
        ("serve.reply.write", 92 * MS, 94 * MS),
        ("engine.frontier.push", 150 * MS, 160 * MS),   # after the window
    ]
    return devices, bench, program


def test_program_span_seconds_and_device_busy_inside_them():
    r = ps.reduce_program_spans(*_events())
    assert r["span_s"]["engine.frontier.push"] == pytest.approx(0.036)
    assert r["span_s"]["engine.frontier.sync"] == pytest.approx(0.022)
    # device busy [10,20] + [22,40] inside push, [60,80] inside pull
    assert r["span_busy_s"]["engine.frontier.push"] == pytest.approx(0.028)
    assert r["span_busy_s"]["engine.frontier.pull"] == pytest.approx(0.020)
    assert r["span_busy_s"]["engine.frontier.sync"] == pytest.approx(0.012)
    assert r["span_busy_s"]["serve.reply.write"] == 0


def test_program_gaps_are_named_by_the_innermost_program_span():
    r = ps.reduce_program_spans(*_events())
    gaps = dict(r["program_gaps"])
    # [0,10]: mid 5 in no program span; [20,22] in push; [40,60]: mid 50
    # in the fixpoint; [80,100]: mid 90 outside (the fixpoint ends at 90)
    assert gaps[ps.OUTSIDE] == pytest.approx(0.030)
    assert gaps["engine.frontier.push"] == pytest.approx(0.002)
    assert gaps["engine.frontier_fixpoint"] == pytest.approx(0.020)
    assert sum(gaps.values()) == pytest.approx(0.1 - 0.048)


def test_program_spans_refuse_a_trace_without_window_or_device():
    devices, bench, program = _events()
    with pytest.raises(ValueError, match="bench.window"):
        ps.reduce_program_spans(devices, bench[1:], program)
    with pytest.raises(ValueError, match="TPU"):
        ps.reduce_program_spans({}, bench, program)


def _recorded(name):
    return os.path.join(_BENCH, "testdata", name)


@pytest.mark.parametrize("path", [
    _recorded("pagerank_scale14_v5e.xplane.pb"),
    _recorded("pagerank_kron22_v5e.xplane.pb")])
def test_recorded_trace_before_the_bridge_has_no_program_spans(path):
    """Traces recorded before the program mirrored its spans: every idle
    second is outside program spans, and the device reduction is what
    ``trace_reduce`` reads."""
    r = ps.reduce(path)
    assert r["span_s"] == {} and r["span_busy_s"] == {}
    base = tr.reduce(path)
    idle = base["window_s"] - base["busy_s"]
    assert [n for n, _ in r["program_gaps"]] == [ps.OUTSIDE]
    assert r["program_gaps"][0][1] == pytest.approx(idle, rel=1e-6)


def test_recorded_trace_with_program_spans():
    """Three PageRank jobs on the SCALE 22 Kronecker graph on one v5e chip,
    traced with the program's spans mirrored: the engine span holds the
    device work, and the replies' writes hold most of the idle time."""
    r = ps.reduce(_recorded("pagerank_kron22_spans_v5e.xplane.pb"))
    base = tr.reduce(_recorded("pagerank_kron22_spans_v5e.xplane.pb"))
    assert r["span_busy_s"]["engine.pagerank"] == \
        pytest.approx(base["busy_s"], rel=1e-9)
    assert r["span_busy_s"]["serve.reply.write"] == 0
    gaps = dict(r["program_gaps"])
    assert set(gaps) == {"serve.reply.write", "engine.pagerank"}
    assert gaps["serve.reply.write"] == pytest.approx(0.1069, abs=1e-4)
    assert sum(gaps.values()) == \
        pytest.approx(base["window_s"] - base["busy_s"], rel=1e-6)
    assert base["device_ops"][0][0] == "jit_fixpoint_pagerank_fori:%fusion.7"
