"""Block-sparse SpMV Pallas TPU kernel — PageRank's hot loop.

Ringo's PageRank inner loop is a per-edge gather/scatter over the CSR
(OpenMP on 80 hyperthreads).  A TPU has no scatter hardware and wants
128-aligned dense tiles on the MXU, so we re-block the hypersparse adjacency
into **BSR**: 128×128 dense tiles stored only where the graph has edges
(DESIGN.md §2).  One PageRank iteration is then

    y[R] += Σ_{tiles t in row-block R}  A_t @ x[col_block(t)]

with the tile stream sorted by row-block so each output block stays resident
in VMEM across consecutive grid steps (zero HBM round-trips for partial
sums).  Tile indices arrive via scalar prefetch so the DMA pipeline can look
ahead through the sparse structure.

VMEM working set per grid step: one (B,B) tile + one (1,B) x block + one
(1,B) y accumulator = B²+2B floats ≈ 64 KiB + 1 KiB at B=128/f32 — comfortably
inside the ~16 MiB VMEM with double buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["bsr_spmv"]

DEFAULT_BLOCK = 128


def _bsr_spmv_kernel(rows_ref, cols_ref, a_ref, x_ref, y_ref):
    t = pl.program_id(0)
    first = t == 0
    prev_row = rows_ref[jnp.maximum(t, 1) - 1]
    row_changed = rows_ref[t] != prev_row

    @pl.when(first | row_changed)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    # MXU tile contraction y (1, B) += x (1, B) . A^T; accumulate in f32
    # regardless of tile dtype (f32 tiles at full f32 precision)
    a = a_ref[...]
    y_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(a.dtype), a, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_row_blocks", "interpret"))
def bsr_spmv(tiles: jax.Array, rows: jax.Array, cols: jax.Array,
             x_blocks: jax.Array, n_row_blocks: int,
             interpret: bool = False) -> jax.Array:
    """y = A @ x for BSR ``A``.

    Args:
      tiles: (nnzb, B, B) dense tiles (f32 or bf16).
      rows:  (nnzb,) int32 row-block ids, **sorted ascending**, covering
             every row block at least once (use a zero tile for empty rows).
      cols:  (nnzb,) int32 col-block ids.
      x_blocks: (n_col_blocks, B) input vector, blocked.
      n_row_blocks: static output row-block count.

    Returns: (n_row_blocks, B) f32.

    The vector blocks ride a leading squeezed axis — ``(n_col_blocks, 1,
    B)`` in, ``(n_row_blocks, 1, B)`` out — so every block's last two dims
    equal the array's, as the TPU lowering requires of a one-row block.
    """
    nnzb, b, _ = tiles.shape
    ncb = x_blocks.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nnzb,),
        in_specs=[
            pl.BlockSpec((None, b, b), lambda t, rows, cols: (t, 0, 0)),
            pl.BlockSpec((None, 1, b), lambda t, rows, cols: (cols[t], 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, b),
                               lambda t, rows, cols: (rows[t], 0, 0)),
    )
    y = pl.pallas_call(
        _bsr_spmv_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_row_blocks, 1, b), jnp.float32),
        interpret=interpret,
    )(rows, cols, tiles, x_blocks.reshape(ncb, 1, b))
    return y.reshape(n_row_blocks, b)
