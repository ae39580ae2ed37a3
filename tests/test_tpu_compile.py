"""Compile the graph kernels for a described TPU v5e at served sizes.

Nothing runs: each test lowers and compiles for one chip of a described
``v5e:2x2`` topology, which is where the TPU compiler refuses block shapes,
scalar stores to VMEM or over-full SMEM that interpret mode accepts.  Shapes
are the upper bounds of the plans the served path builds:

* RMAT scale 22, edge factor 16 (the Pallas segment-sum backend): the plan
  of ``rmat_edges(22, 16, seed=0)``, ``n = 2395447`` vertices and
  ``E = 65242279`` edges after renumbering and dedupe, in ``C = E / L + n /
  128`` chunks of ``L = 512``.  Sizes that are not powers of two matter: a
  batched gather that compiles in a second at ``2^22`` took five minutes
  at these;
* RMAT scale 14 (the BSR backend): 128 row blocks, every 128x128 tile
  present, and up to ``2^21`` block triples for triangle counting.

The topology is described inside a fixture (only the worker that runs this
file loads the TPU compiler), and the persistent compilation cache is off
around these compiles: entries for a described chip cannot be read back.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import algorithms as A
from repro.core import engine
from repro.kernels.bsr_spmv import bsr_spmv
from repro.kernels.bsr_tricount import bsr_tricount
from repro.kernels.segment_sum import (DEFAULT_BLOCK, DEFAULT_CHUNK,
                                       segment_sum_chunked)

N22 = 2395447
E22 = 65242279
NB22 = -(-N22 // DEFAULT_BLOCK)
C22 = E22 // DEFAULT_CHUNK + NB22
NB14 = (1 << 14) // DEFAULT_BLOCK
TILES14 = NB14 * NB14
TRIPLES14 = 1 << 21


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory placed on one described chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_segment_sum_chunked_scale22(sds):
    _compile(lambda v, l, b: segment_sum_chunked(v, l, b, NB22),
             sds((C22, DEFAULT_CHUNK), jnp.float32),
             sds((C22, DEFAULT_CHUNK), jnp.int32), sds((C22,), jnp.int32))


def test_bsr_spmv_scale14(sds):
    _compile(lambda t, r, c, x: bsr_spmv(t, r, c, x, NB14),
             sds((TILES14, DEFAULT_BLOCK, DEFAULT_BLOCK), jnp.float32),
             sds((TILES14,), jnp.int32), sds((TILES14,), jnp.int32),
             sds((NB14, DEFAULT_BLOCK), jnp.float32))


def test_bsr_tricount_scale14(sds):
    idx = sds((TRIPLES14,), jnp.int32)
    _compile(bsr_tricount,
             sds((TILES14, DEFAULT_BLOCK, DEFAULT_BLOCK), jnp.float32),
             idx, idx, idx)


def _pallas_exec(sds):
    e = sds((E22,), jnp.int32)
    cl, blk = sds((C22, DEFAULT_CHUNK), jnp.int32), sds((C22,), jnp.int32)
    return engine.PallasExec(N22, E22, e, e, e, e, cl, cl, blk, cl, cl, blk,
                             nb_in=NB22, nb_out=NB22, interpret=False)


def _gathers(compiled):
    """Result shapes of the gathers in a compiled program."""
    return re.findall(r"= (\w+\[[\d,]*\])\S* gather\(", compiled.as_text())


# a Pallas sum pull gathers the vertex vector straight into the chunk
# buffer: no edge-order f32[E22] intermediate, and no second gather
ONE_CHUNK_GATHER = [f"f32[{C22},{DEFAULT_CHUNK}]"]


def test_pallas_pagerank_fixpoint_scale22(sds):
    """The served PageRank program: ten pulls through the Pallas kernel."""
    run = engine._runner(A._pagerank_body, True)
    v = sds((N22,), jnp.float32)
    compiled = _compile(run, _pallas_exec(sds), v, sds((), jnp.int32),
                        sds((), jnp.float32), v, sds((N22,), jnp.bool_))
    assert _gathers(compiled) == ONE_CHUNK_GATHER


def test_pallas_batched_pull_scale22(sds):
    """A fused multi-source burst vmaps the Pallas pull over sources."""
    compiled = _compile(
        lambda ex, x: jax.vmap(lambda r: ex.pull(r, "sum"))(x),
        _pallas_exec(sds), sds((3, N22), jnp.float32))
    assert _gathers(compiled) == ONE_CHUNK_GATHER


def test_pallas_push_scale22(sds):
    """A Pallas push (HITS' hub step) gathers through the push layout."""
    compiled = _compile(lambda ex, x: ex.push(x, "sum"), _pallas_exec(sds),
                        sds((N22,), jnp.float32))
    assert _gathers(compiled) == ONE_CHUNK_GATHER


def test_frontier_round_scale22(sds):
    """A large sparse BFS round: compaction plus the push step.  Their
    prefix sums are what a TPU compiled slowly (a scan over millions of
    elements), once per frontier bucket."""
    b, eb = 1 << 20, 1 << 24
    ex = engine.FrontierExec(
        N22, E22, *(sds((E22,), jnp.int32),) * 4, sds((N22 + 1,), jnp.int32),
        sds((1 << 26,), jnp.int32), sds((N22 + 1,), jnp.int32),
        sds((E22,), jnp.int32))
    jax.jit(lambda m: engine._compact(m, b=b)).lower(
        sds((N22,), jnp.bool_)).compile()
    jax.jit(lambda ex, st, f, caps, t: engine._frontier_push_step(
        ex, st, f, jnp.float32(1.0), caps, t, e_budget=eb)).lower(
        ex, sds((1, N22), jnp.float32), sds((b,), jnp.int32),
        sds((1,), jnp.int32), sds((), jnp.int32)).compile()
