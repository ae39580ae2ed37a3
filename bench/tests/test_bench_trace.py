"""The trace reduction, on hand-made events and on a small trace recorded on
a TPU v5e chip (``bench/testdata``)."""

import os
import sys

# the harness's modules import by their bare names, as bench/run.py does
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_BENCH, os.path.join(os.path.dirname(_BENCH), "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import glob

import pytest

import trace_reduce as tr

MS = 1_000_000  # ns


def _events():
    devices = {"/device:TPU:0": [
        ("while.1", 10 * MS, 40 * MS),
        ("fusion.2", 20 * MS, 30 * MS),        # the loop's body
        ("segsum_kernel", 60 * MS, 70 * MS),
        ("fusion.1", 95 * MS, 130 * MS),       # runs past the window
    ]}
    spans = [("bench.window", 0, 100 * MS),
             ("bench.call.bfs", 6 * MS, 90 * MS),
             ("bench.call.bfs", 92 * MS, 100 * MS)]
    return devices, spans


def test_busy_is_the_union_clipped_to_the_window():
    r = tr.reduce_events(*_events())
    assert r["window_s"] == pytest.approx(0.1)
    # [10, 40] + [60, 70] + [95, 100]
    assert r["busy_s"] == pytest.approx(0.045)
    # self time: the loop is charged its own 20 ms, not its body's 10
    assert r["op_seconds"]["while.1"] == pytest.approx(0.020)
    assert r["op_seconds"]["fusion.2"] == pytest.approx(0.010)
    assert r["op_seconds"]["fusion.1"] == pytest.approx(0.005)
    assert r["op_counts"] == {"while.1": 1, "fusion.1": 1, "fusion.2": 1,
                              "segsum_kernel": 1}
    assert r["device_ops"][0][0] == "while.1"
    assert sum(r["op_seconds"].values()) == pytest.approx(r["busy_s"])


def test_gaps_are_named_by_the_innermost_bench_span():
    r = tr.reduce_events(*_events())
    gaps = dict(r["idle_gaps"])
    # [0,10] outside any call; [40,60] + [70,90] inside the first call;
    # [90,95]: midpoint 92.5 inside the second call
    assert gaps["outside bench spans"] == pytest.approx(0.010)
    assert gaps["bench.call.bfs"] == pytest.approx(0.045)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_a_trace_without_window_or_device_is_refused():
    devices, spans = _events()
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce_events(devices, spans[1:])
    with pytest.raises(ValueError, match="TPU"):
        tr.reduce_events({}, spans)


RECORDED = glob.glob(os.path.join(os.path.dirname(tr.__file__), "testdata",
                                  "*.xplane.pb"))


@pytest.mark.parametrize("path", RECORDED)
def test_recorded_chip_trace(path):
    r = tr.reduce(path)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert sum(s for _, s in r["idle_gaps"]) + r["busy_s"] == \
        pytest.approx(r["window_s"], rel=1e-6)
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])


def test_recorded_pagerank_trace_reads_as_measured():
    """Scale-14 PageRank jobs on one v5e chip (the BSR pull; 9 jobs of 10
    iterations in a 0.54 s window), read by hand when it was recorded."""
    path = os.path.join(os.path.dirname(tr.__file__), "testdata",
                        "pagerank_scale14_v5e.xplane.pb")
    r = tr.reduce(path)
    assert r["window_s"] == pytest.approx(0.537201467)
    assert r["busy_s"] == pytest.approx(0.479480317)
    name, secs = r["device_ops"][0]
    assert name == "jit_run_py:%bsr_spmv.6"
    assert r["op_counts"][name] == 90
    assert secs == pytest.approx(0.4792582, rel=1e-6)
    assert r["idle_gaps"][0][0].startswith("bench.")


def test_kernel_bytes_come_from_the_recorded_call_shapes():
    """The BSR kernel's call in the recorded trace: two (16375,) int32 block
    tables, the (16375, 128, 128) f32 tiles and the (128, 1, 128) f32 vector
    read, the (128, 1, 128) f32 product written."""
    import kernel_cost
    path = os.path.join(os.path.dirname(tr.__file__), "testdata",
                        "pagerank_scale14_v5e.xplane.pb")
    hlo = tr.reduce(path)["op_hlo"]["jit_run_py:%bsr_spmv.6"]
    want = 2 * 4 * 16375 + 4 * 16375 * 128 * 128 + 2 * 4 * 128 * 128
    assert kernel_cost.hlo_bytes(hlo) == want


@pytest.mark.parametrize("hlo,want", [
    ("%k = f32[256,128]{1,0:T(8,128)} custom-call(f32[40,512]{1,0} %a, "
     "s32[40,512]{1,0} %b, s32[40]{0:T(1024)} %c), custom_call_target="
     "\"tpu_custom_call\", operand_layout_constraints={f32[40,512]{1,0}}",
     4 * 256 * 128 + 8 * 40 * 512 + 4 * 40),
    ("%t = (bf16[8]{0}, pred[16]{0}) fusion(u8[4]{0} %x), kind=kLoop",
     2 * 8 + 16 + 4),
    ("%k = f32[256,128]{1,0} custom-call(f32[40,512]{1,0} %a, s32[4", None),
    ("fusion.3", None),
])
def test_hlo_bytes(hlo, want):
    import kernel_cost
    assert kernel_cost.hlo_bytes(hlo) == want


def test_recorded_kron22_trace_reads_as_the_chip_run_did():
    """Three PageRank jobs on the SCALE 22 Kronecker graph on one v5e chip
    (the Pallas pull): the readers give what the run printed."""
    import run
    path = os.path.join(os.path.dirname(tr.__file__), "testdata",
                        "pagerank_kron22_v5e.xplane.pb")
    ctx = run.Context(trace=tr.reduce(path),
                      peaks={"hbm_bytes_per_s": 819e9})
    assert run.load_reader("segment_sum_roofline").read(ctx) == \
        pytest.approx(1.3729095726376341, rel=1e-9)
    assert run.load_reader("device.idle_share.job").read(ctx) == \
        pytest.approx(0.17673876305245306, rel=1e-9)
