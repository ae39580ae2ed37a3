"""Triangle counting as block-sparse A∘(A·A) on the MXU — Pallas TPU kernel.

Ringo counts triangles by intersecting per-node *sorted adjacency vectors*
(scalar compares, OpenMP).  A systolic array cannot branch per element, but
set intersection over a 128-node tile IS a matmul:  for symmetric 0/1
adjacency A,

    #triangles = (1/6) Σ_{I,J} sum( A_IJ ∘ (Σ_K A_IK · A_KJ) )

so we enumerate nonzero **block triples** (I,K)(K,J) with (I,J) nonzero —
the block-level analogue of "for each edge, intersect neighborhoods" — and
feed 128×128×128 dense products to the MXU (2·B³ useful flops each).  The
elementwise mask ∘A_IJ and the global reduction run on the VPU while the
next triple's tiles stream HBM→VMEM (grid is sequential, the (1, B) count
block stays in VMEM the whole kernel).

This is the hardware adaptation documented in DESIGN.md §2: per-edge
branching → re-blocked arithmetic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["bsr_tricount"]


# triples per pallas_call: the three prefetched index arrays of one call live
# in SMEM (1 MiB on v5e), so longer triple streams run as a loop of calls
TRIPLES_PER_CALL = 1 << 15


def _tricount_kernel(nvalid_ref, tij_ref, tik_ref, tkj_ref, a1_ref, a2_ref,
                     a3_ref, acc_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t < nvalid_ref[0])
    def _count():
        prod = jnp.dot(a2_ref[...], a3_ref[...],
                       preferred_element_type=jnp.float32)
        masked = a1_ref[...].astype(jnp.float32) * prod
        # per-column partial counts: exact small integers, kept in int32
        # lanes (a lane-dense (1, B) block: TPUs cannot store a VMEM scalar)
        acc_ref[...] += jnp.sum(masked, axis=0, keepdims=True
                                ).astype(jnp.int32)


def _tricount_call(tiles, nvalid, t_ij, t_ik, t_kj, interpret):
    piece = t_ij.shape[0]
    _, b, _ = tiles.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(piece,),
        in_specs=[
            pl.BlockSpec((None, b, b), lambda t, nv, ij, ik, kj: (ij[t], 0, 0)),
            pl.BlockSpec((None, b, b), lambda t, nv, ij, ik, kj: (ik[t], 0, 0)),
            pl.BlockSpec((None, b, b), lambda t, nv, ij, ik, kj: (kj[t], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, b), lambda t, nv, ij, ik, kj: (0, 0)),
    )
    return pl.pallas_call(
        _tricount_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, b), jnp.int32),
        interpret=interpret,
    )(nvalid, t_ij, t_ik, t_kj, tiles, tiles, tiles)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bsr_tricount(tiles: jax.Array, t_ij: jax.Array, t_ik: jax.Array,
                 t_kj: jax.Array, interpret: bool = False) -> jax.Array:
    """Ordered-triple count = 6 × #triangles.

    Args:
      tiles: (nnzb, B, B) symmetric 0/1 adjacency tiles.
      t_ij, t_ik, t_kj: (n_triples,) int32 tile indices per block triple.

    Returns: scalar int32 — divide by 6 for the triangle count.
    """
    n = t_ij.shape[0]
    piece = min(n, TRIPLES_PER_CALL)
    n_calls = -(-n // piece)
    # pad with tile 0 to whole calls; padded steps are skipped in-kernel
    idx = jnp.pad(jnp.stack([t_ij, t_ik, t_kj]),
                  ((0, 0), (0, n_calls * piece - n)))

    def one(i, acc):
        lo = i * piece
        ij, ik, kj = (jax.lax.dynamic_slice_in_dim(idx[r], lo, piece)
                      for r in range(3))
        nvalid = jnp.minimum(n - lo, piece).astype(jnp.int32).reshape(1)
        return acc + _tricount_call(tiles, nvalid, ij, ik, kj, interpret)

    acc = jax.lax.fori_loop(0, n_calls, one,
                            jnp.zeros((1, tiles.shape[1]), jnp.int32))
    return jnp.sum(acc)
