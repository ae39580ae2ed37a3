"""Sorted segmented reduction — Pallas TPU kernel for the conversion hot loop.

The paper's sort-first table→graph conversion (§2.4) reduces to: *after
sorting edges by destination, sum/count contributions per destination*.  On
CPU Ringo does atomic-free writes because each thread owns a partition; on
TPU the scatter itself must become arithmetic.  The trick: a segment-sum of a
chunk whose segment ids all fall in one 128-wide id block is a **one-hot
matmul**

    partial[s] = Σ_e vals[e]·[seg(e) == s]   ⇔   onehotᵀ(L×B) · vals(L)

which the MXU executes at full rate.  The host groups edges by 128-wide
destination block (they are already sorted — zero cost), pads each group to
the chunk length L, and the kernel accumulates chunks into the owning output
block, which stays in VMEM across the consecutive chunks of one block.

VMEM per step: L ids + L vals + L×B one-hot + B accumulator ≈ 0.27 MiB at
L=512, B=128, f32.  Also the group-by/aggregate hot loop (relational.py).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["segment_sum_chunked", "chunk_layout", "chunk_values"]

DEFAULT_CHUNK = 512
DEFAULT_BLOCK = 128


def chunk_layout(seg_ids: np.ndarray, n_segments: int,
                 chunk: int = DEFAULT_CHUNK
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int, int]:
    """Static chunking structure for **sorted** segment ids (host-side).

    Groups entries by 128-wide output block and splits each group into
    ``chunk``-long chunks (every block gets >= 1 chunk so the kernel's
    accumulator init fires).  The structure depends only on ``seg_ids``, so
    callers (``GraphPlan``) compute it once per graph and gather fresh
    values into it on every reduction (:func:`chunk_values`).  A gather, not
    a scatter: an unsorted scatter into an operand this large, or a batched
    scatter feeding the kernel, compiles for minutes on a TPU.

    Returns ``(slot_entry, local_ids, chunk_block, nb, C)`` where
    ``slot_entry`` is (C, L) int32, the entry each slot holds (pad = E),
    ``local_ids`` is (C, L) int32 with pad id = 128, ``chunk_block`` is (C,)
    sorted ascending, ``nb`` the output block count and ``C`` the total
    chunk count.
    """
    b = DEFAULT_BLOCK
    nb = max((n_segments + b - 1) // b, 1)
    seg = np.asarray(seg_ids, dtype=np.int64)
    e = int(seg.shape[0])
    blocks = seg // b
    starts = np.searchsorted(blocks, np.arange(nb), side="left")
    ends = np.searchsorted(blocks, np.arange(nb), side="right")
    counts = ends - starts
    n_chunks = np.maximum((counts + chunk - 1) // chunk, 1)
    base = np.concatenate([[0], np.cumsum(n_chunks)[:-1]])
    total = int(n_chunks.sum())
    # block k's entries fill its chunks' slots in order from base[k] * chunk
    entry_pos = np.arange(e) + np.repeat(base * chunk - starts, counts)
    slot_entry = np.full((total * chunk,), e, np.int32)
    slot_entry[entry_pos] = np.arange(e, dtype=np.int32)
    local_ids = np.full((total * chunk,), b, np.int32)
    local_ids[entry_pos] = (seg % b).astype(np.int32)
    chunk_block = np.repeat(np.arange(nb), n_chunks).astype(np.int32)
    return (slot_entry.reshape(total, chunk), local_ids.reshape(total, chunk),
            chunk_block, nb, total)


@jax.custom_batching.custom_vmap
def chunk_values(vals: jax.Array, slot_entry: jax.Array) -> jax.Array:
    """Gather per-entry values into the (C, L) chunk buffer (pads = 0)."""
    return jnp.concatenate([vals.astype(jnp.float32),
                            jnp.zeros((1,), jnp.float32)])[slot_entry]


@chunk_values.def_vmap
def _chunk_values_batched(axis_size, in_batched, vals, slot_entry):
    # one row gather per batch row: vmap's own rule makes a single gather
    # of (k, 1) column slices, which a TPU took minutes to compile at tens
    # of millions of entries (the row loop: about a second)
    vals_b, slot_b = in_batched
    if not vals_b:
        vals = jnp.broadcast_to(vals, (axis_size,) + vals.shape)
    if slot_b:
        return jax.lax.map(lambda a: chunk_values(*a), (vals, slot_entry)), True
    return jax.lax.map(lambda v: chunk_values(v, slot_entry), vals), True


def _segsum_kernel(outblk_ref, vals_ref, lids_ref, out_ref):
    t = pl.program_id(0)
    first = t == 0
    prev = outblk_ref[jnp.maximum(t, 1) - 1]
    changed = outblk_ref[t] != prev

    @pl.when(first | changed)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    b = out_ref.shape[-1]
    l = lids_ref.shape[-1]
    # transposed one-hot (B, L): row s marks the slots whose id is s; the
    # lane-major (1, L) ids broadcast down the sublanes, so no relayout
    onehot_t = (lids_ref[...] == jax.lax.broadcasted_iota(jnp.int32, (b, l), 0)
                ).astype(jnp.float32)
    # (1, L) x (B, L)^T -> (1, B) on the MXU, f32 kept exact
    out_ref[...] += jax.lax.dot_general(
        vals_ref[...].astype(jnp.float32), onehot_t,
        (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_out_blocks", "interpret"))
def segment_sum_chunked(vals: jax.Array, local_ids: jax.Array,
                        chunk_block: jax.Array, n_out_blocks: int,
                        interpret: bool = False) -> jax.Array:
    """Segment-sum of pre-chunked sorted data.

    Args:
      vals:       (C, L) chunked values (padding entries may hold anything).
      local_ids:  (C, L) int32 segment id *within* the owning 128-block;
                  padding entries must be >= B (one-hot row of zeros).
      chunk_block:(C,) int32 owning output block per chunk, sorted ascending,
                  covering every output block at least once.
      n_out_blocks: static number of 128-wide output blocks.

    Returns: (n_out_blocks, B) f32 segment sums.

    Every per-step block gets its own leading (squeezed) axis, so the last
    two block dims equal the array's — ``(C, 1, L)`` in, ``(nb, 1, B)`` out —
    which is what the TPU lowering requires of a one-row block.
    """
    c, l = vals.shape
    b = DEFAULT_BLOCK
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(c,),
        in_specs=[
            pl.BlockSpec((None, 1, l), lambda t, blk: (t, 0, 0)),
            pl.BlockSpec((None, 1, l), lambda t, blk: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, b), lambda t, blk: (blk[t], 0, 0)),
    )
    out = pl.pallas_call(
        _segsum_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_out_blocks, 1, b), jnp.float32),
        interpret=interpret,
    )(chunk_block, vals.reshape(c, 1, l), local_ids.reshape(c, 1, l))
    return out.reshape(n_out_blocks, b)
