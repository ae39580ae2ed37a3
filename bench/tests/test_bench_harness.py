"""The harness on the CPU at a small size: the real socket path, the
reference check, faults planted under it, and the refusal without a chip."""

import os
import sys

# the harness's modules import by their bare names, as bench/run.py does
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_BENCH, os.path.join(os.path.dirname(_BENCH), "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json

import jax.numpy as jnp
import numpy as np
import pytest

import run

SPEC = run.load_json(run.ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in SPEC["workloads"]]


def _small(cell, scale=9):
    cfg_name = next(w["config"] for w in SPEC["workloads"]
                    if w["name"] == cell)
    file = next(c["file"] for c in SPEC["configs"] if c["name"] == cfg_name)
    cfg = run.load_json(run.ROOT, file)
    return dict(cfg, scale=scale)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_over_the_socket(cell):
    res = run.run_cell(SPEC, cell, 2**31 + 3, 1.0, False, config=_small(cell))
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    want = {m["name"] for m in run.cell_metrics(SPEC, cell, False)}
    assert set(res["metrics"]) == want
    assert "setup_s" in want and len(want) >= 2
    assert res["device"]["platform"] == "cpu"
    json.dumps(res)


def _altered(fn):
    """The op's answer with one vertex's value changed where it is made."""
    def bad(*args, **kwargs):
        out = fn(*args, **kwargs)
        return out.at[..., 0].set(out[..., 0] + 1)
    return bad


@pytest.mark.parametrize("cell", CELLS)
def test_an_altered_answer_reads_not_correct(cell, monkeypatch):
    from repro.serve import graph_service
    op = run.load_json(run.BENCH, "traffic", next(
        w["traffic"] for w in SPEC["workloads"] if w["name"] == cell)
        + ".json")["op"]
    fn, slots = graph_service._OPS[op]
    monkeypatch.setitem(graph_service._OPS, op, (_altered(fn), slots))
    res = run.run_cell(SPEC, cell, 2**31 + 4, 1.0, False, config=_small(cell))
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_no_tpu_means_no_result(capsys):
    assert run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                     "--trace", "0"]) != 0
    assert capsys.readouterr().out == ""


def test_every_metric_has_a_reader_and_every_cell_its_files():
    import byname
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert hasattr(run.load_reader(m["name"]), "read"), m["name"]
    for w in SPEC["workloads"]:
        mix = run.load_json(run.BENCH, "traffic", w["traffic"] + ".json")
        __import__(f"reference.{mix['op']}")
        assert hasattr(byname.module("loops", mix["loop"]), "Loop")
        assert run.cell_metrics(SPEC, w["name"], True), w["name"]
    for c in SPEC["configs"]:
        cfg = run.load_json(run.ROOT, c["file"])
        assert cfg["name"] == c["name"]
        assert hasattr(byname.module("generators", cfg["generator"]),
                       "generate")


def test_a_missing_part_is_named():
    import byname
    with pytest.raises(FileNotFoundError, match="no loops module named"):
        byname.module("loops", "open-loop-at-a-rate")


@pytest.mark.parametrize("cell", CELLS)
def test_every_seed_sends_the_same_work(cell):
    """Two seeds: the same graph, in another order, and the same requests."""
    import byname
    import loadgen
    w = run._by_name(SPEC["workloads"], cell, "workload")
    mix = run.load_json(run.BENCH, "traffic", w["traffic"] + ".json")
    cfg = _small(cell)
    gen = byname.module("generators", cfg["generator"])
    a, b = (gen.generate(cfg, seed) for seed in (2**31 + 21, 9))
    assert not np.array_equal(a.src, b.src)
    assert sorted(zip(a.src.tolist(), a.dst.tolist())) == \
        sorted(zip(b.src.tolist(), b.dst.tolist()))
    assert loadgen.session_lists(mix, a) == loadgen.session_lists(mix, b)


def test_compile_counter_counts_only_while_active():
    import jax
    counter = run.CompileCounter.get()
    x = jnp.arange(7).block_until_ready()
    counter.count, counter.active = 0, True
    jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
    counter.active = False
    jax.jit(lambda x: x * 5 + 2)(x).block_until_ready()
    assert counter.count == 1
    assert counter.take_seconds() and not counter.take_seconds()
