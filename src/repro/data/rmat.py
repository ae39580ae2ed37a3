"""R-MAT graph generator (Chakrabarti et al.) — LiveJournal/Twitter-like
synthetic power-law graphs for the paper-table benchmarks.

The SNAP datasets themselves aren't shipped in this container; R-MAT with
(a,b,c,d) = (0.57, 0.19, 0.19, 0.05) gives the community structure +
heavy-tail degree distribution these benchmarks care about.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Tuple

import numpy as np

__all__ = ["rmat_edges"]

# edges per generation block: blocks run on a thread pool (numpy releases
# the GIL inside these array operations)
_BLOCK = 1 << 21


def rmat_edges(scale: int, edge_factor: int = 16, seed: int = 0,
               a: float = 0.57, b: float = 0.19, c: float = 0.19
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Generate 2^scale nodes and edge_factor·2^scale directed edges.

    The stream is fixed by ``seed``: per bit level, one uniform draw per
    edge picks the source half and a second the destination half, in that
    order over all edges; then a label permutation.  Each block of edges
    reads its stretch of that stream by advancing a copy of the generator
    (PCG64 draws one 64-bit word per float64), so the output does not
    depend on how the work is split.
    """
    n = 1 << scale
    m = edge_factor * n
    rng = np.random.default_rng(seed)
    start = rng.bit_generator.state
    src = np.zeros(m, dtype=np.int32)
    dst = np.zeros(m, dtype=np.int32)
    ab = a + b
    t_top, t_bottom = a / ab, c / (1.0 - ab)

    def uniform(offset: int, count: int) -> np.ndarray:
        bg = np.random.PCG64()
        bg.state = start
        return np.random.Generator(bg.advance(offset)).random(count)

    def fill(lo: int) -> None:
        hi = min(lo + _BLOCK, m)
        for bit in range(scale):
            src_bit = uniform(2 * bit * m + lo, hi - lo) >= ab
            # within the chosen half, pick the column quadrant
            thresh = np.where(src_bit, t_bottom, t_top)
            dst_bit = uniform((2 * bit + 1) * m + lo, hi - lo) >= thresh
            src[lo:hi] |= src_bit.astype(np.int32) << bit
            dst[lo:hi] |= dst_bit.astype(np.int32) << bit

    with ThreadPoolExecutor(max(1, min(os.cpu_count() or 1, 16))) as pool:
        list(pool.map(fill, range(0, m, _BLOCK)))
    rng.bit_generator.advance(2 * scale * m)
    # permute labels to kill the bit-pattern locality artifact
    perm = rng.permutation(n)
    return perm[src].astype(np.int32), perm[dst].astype(np.int32)
