"""Sharding rules + HLO cost model unit tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_config, SHAPES
from repro.launch import sharding as shlib
from repro.launch.hlo_cost import analyze_hlo
from repro.models import transformer as model


def _mesh11():
    return jax.make_mesh((1, 1), ("data", "model"))


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "qwen3-moe-235b-a22b",
                                  "jamba-1.5-large-398b", "xlstm-350m",
                                  "whisper-small", "grok-1-314b"])
def test_param_specs_cover_every_leaf(arch):
    """Every param leaf gets a spec whose axes fit its rank and divide the
    production dims (checked symbolically on full-size shapes)."""
    cfg = get_config(arch)
    shapes = jax.eval_shape(lambda: model.init_params(
        cfg, jax.random.PRNGKey(0)))
    # production EP policy: experts shard over model only when divisible
    eap = cfg.n_experts > 0 and cfg.n_experts % 16 == 0
    rules = shlib.default_rules(_mesh11(), expert_axis_parallel=eap)
    specs = shlib.param_specs(shapes, rules)
    prod = {"data": 16, "model": 16, None: 1}

    def check(path, leaf, spec):
        assert len(spec) <= leaf.ndim, (path, spec, leaf.shape)
        for i, ax in enumerate(spec):
            dim = leaf.shape[i + leaf.ndim - len(spec)] \
                if len(spec) < leaf.ndim else leaf.shape[i]
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                if a is not None:
                    assert dim % prod[a] == 0, \
                        f"{jax.tree_util.keystr(path)}: {leaf.shape} vs {spec}"

    jax.tree_util.tree_map_with_path(check, shapes, specs)


def test_no_duplicate_axes_in_specs():
    for arch in ("qwen3-moe-235b-a22b", "jamba-1.5-large-398b"):
        cfg = get_config(arch)
        shapes = jax.eval_shape(lambda c=cfg: model.init_params(
            c, jax.random.PRNGKey(0)))
        rules = shlib.default_rules(_mesh11(), two_d_weights=True,
                                    expert_axis_parallel=True)
        specs = shlib.param_specs(shapes, rules)
        for spec in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
            flat = [a for ax in spec
                    for a in (ax if isinstance(ax, tuple) else (ax,))
                    if a is not None]
            assert len(flat) == len(set(flat)), spec


def test_shard_is_identity_without_rules():
    x = jnp.ones((4, 4))
    assert shlib.shard(x, ("batch", None)) is x


# ---------------------------------------------------------------------------
# HLO cost model (the §Roofline measurement tool)
# ---------------------------------------------------------------------------


def _xla_flops(compiled):
    return float(compiled.cost_analysis()["flops"])


def test_hlo_cost_matches_xla_without_scans():
    def f(x, y):
        return jnp.tanh(x @ y) @ y

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    y = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c = jax.jit(f).lower(x, y).compile()
    mine = analyze_hlo(c.as_text())
    assert mine.flops == pytest.approx(_xla_flops(c), rel=1e-6)


def test_hlo_cost_multiplies_scan_bodies():
    def g(x, w):
        def body(h, _):
            return jnp.tanh(h @ w), None
        return jax.lax.scan(body, x, None, length=16)[0]

    c = jax.jit(g).lower(jax.ShapeDtypeStruct((32, 64), jnp.float32),
                         jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    mine = analyze_hlo(c.as_text())
    assert mine.flops == pytest.approx(2 * 32 * 64 * 64 * 16, rel=1e-6)
    # XLA counts the body once (± the loop counter) — our reason for existing
    assert _xla_flops(c) == pytest.approx(2 * 32 * 64 * 64, rel=1e-3)


def test_hlo_cost_nested_scans():
    def nested(x, w):
        def outer(h, _):
            def inner(h2, _):
                return jnp.tanh(h2 @ w), None
            return jax.lax.scan(inner, h, None, length=4)[0], None
        return jax.lax.scan(outer, x, None, length=3)[0]

    c = jax.jit(nested).lower(
        jax.ShapeDtypeStruct((16, 32), jnp.float32),
        jax.ShapeDtypeStruct((32, 32), jnp.float32)).compile()
    assert analyze_hlo(c.as_text()).flops == pytest.approx(
        2 * 16 * 32 * 32 * 12, rel=1e-6)


def test_hlo_cost_counts_collectives_inside_scans():
    import functools
    mesh = jax.make_mesh((1,), ("d",))

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("d"),
                       out_specs=P("d"))
    def h(x):
        def body(carry, _):
            gathered = jax.lax.all_gather(carry, "d", tiled=True)
            return carry + gathered.reshape(1, -1).sum(0), None
        return jax.lax.scan(body, x, None, length=5)[0]

    with mesh:
        c = jax.jit(h).lower(
            jax.ShapeDtypeStruct((256,), jnp.float32)).compile()
    cost = analyze_hlo(c.as_text())
    assert cost.collective_bytes.get("all-gather", 0) == \
        pytest.approx(256 * 4 * 5)


# ---------------------------------------------------------------------------
# 1-D graph mesh (PR 9): shared cached mesh + placement specs
#
# These run on the ambient device pool — a single real CPU device is enough
# for the identity/spec assertions, and the multi-device legs execute for
# real under the `sharded-sim` CI lane's simulated 8-device host mesh
# instead of being skipped.
# ---------------------------------------------------------------------------


def test_graph_mesh_cached_identity_and_axis():
    from repro.launch.mesh import GRAPH_AXIS, graph_mesh
    m = graph_mesh(1)
    assert graph_mesh(1) is m          # lru-cached: identity keys jit caches
    assert m.axis_names == (GRAPH_AXIS,)
    assert m.devices.shape == (1,)


def test_graph_mesh_rejects_oversubscription():
    from repro.launch.mesh import graph_mesh
    with pytest.raises(ValueError, match="device"):
        graph_mesh(len(jax.devices()) + 1)


def test_graph_specs_place_arrays():
    from repro.launch.mesh import GRAPH_AXIS, graph_mesh
    from repro.launch.sharding import (graph_replicated_spec,
                                       graph_shard_spec)
    d = min(2, len(jax.devices()))
    if d < 2:
        pytest.skip("needs >= 2 devices (simulated host mesh); the "
                    "sharded-sim CI lane runs this leg")
    mesh = graph_mesh(d)
    sh = graph_shard_spec(mesh)
    rep = graph_replicated_spec(mesh)
    assert sh.spec == P(GRAPH_AXIS) and rep.spec == P()
    x = jax.device_put(jnp.arange(8, dtype=jnp.float32), sh)
    r = jax.device_put(jnp.arange(8, dtype=jnp.float32), rep)
    assert len(x.sharding.device_set) == d
    assert x.sharding.is_equivalent_to(sh, x.ndim)
    assert r.sharding.is_fully_replicated
    np.testing.assert_array_equal(np.asarray(x), np.arange(8))


def test_shard_plan_uses_shared_mesh():
    # the engine's per-shard plan family must ride the same cached mesh as
    # launch-layer consumers, or jit caches fragment per-mesh-object
    from repro.core.graph import Graph
    from repro.launch.mesh import graph_mesh
    g = Graph.from_edges(np.asarray([0, 1, 2], np.int32),
                         np.asarray([1, 2, 0], np.int32))
    sp = g.plan().sharded(1)
    assert sp.mesh is graph_mesh(1)


def test_runnable_vs_skip_matrix_documented():
    """Dry-run skip policy matches DESIGN §Arch-applicability."""
    from repro.configs.base import runnable_shapes, list_archs
    skip_long = {"whisper-small", "qwen1.5-4b", "qwen2.5-3b",
                 "starcoder2-15b", "mistral-nemo-12b", "grok-1-314b",
                 "qwen3-moe-235b-a22b", "internvl2-26b"}
    for arch in list_archs():
        if arch == "ringo-graph":
            continue
        has_long = "long_500k" in runnable_shapes(get_config(arch))
        assert has_long == (arch not in skip_long), arch
