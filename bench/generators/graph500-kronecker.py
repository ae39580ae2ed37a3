"""Graph500 Kronecker graphs (``"generator": "graph500-kronecker"``).

The generator is the Graph500 one (specification, section 3): for each of
``scale`` bit levels every edge draws one uniform number that picks the
source half (``>= a + b`` sets the bit) and a second that picks the
destination half inside it (threshold ``a / (a + b)`` in the top half,
``c / (1 - a - b)`` in the bottom half).  Vertex labels are then permuted.
Duplicates and self-loops are kept as generated, so every graph has exactly
``edge_factor * 2^scale`` edges over ``2^scale`` vertices.

The draws and the vertex permutation come from the configuration's fixed
``graph_seed``, so every run serves the same graph; ``--seed`` shuffles the
order of the edge list the program is handed (the specification's own edge
shuffle).  The work does not change with the seed: a vertex permutation
alone moved PageRank's job time by 8% on a v5e chip.  The draws run in one
jitted call on the device; ``jax.random`` gives the same numbers on every
backend, so a CPU test and a chip run see the same graph.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import HostGraph
from seeds import seed_words


@functools.partial(jax.jit, static_argnames=("scale", "m", "a", "b", "c"))
def _draw(key_data, perm, *, scale: int, m: int, a: float, b: float,
          c: float):
    key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
    ab = a + b
    t_top, t_bottom = a / ab, c / (1.0 - ab)

    def level(bit, sd):
        s, d = sd
        u = jax.random.uniform(jax.random.fold_in(key, bit), (2, m))
        s_bit = u[0] >= ab
        d_bit = u[1] >= jnp.where(s_bit, t_bottom, t_top)
        return (s | (s_bit.astype(jnp.int32) << bit),
                d | (d_bit.astype(jnp.int32) << bit))

    zero = jnp.zeros((m,), jnp.int32)
    s, d = jax.lax.fori_loop(0, scale, level, (zero, zero))
    return perm[s], perm[d]


def generate(cfg: dict, seed: int) -> HostGraph:
    """The configuration's graph, its edges in ``seed``'s order.
    ``cfg["directed"]`` false adds every edge's reverse."""
    scale, ef = int(cfg["scale"]), int(cfg["edge_factor"])
    n, m = 1 << scale, ef << scale
    graph_seed = int(cfg["graph_seed"])
    perm = np.random.default_rng(seed_words(graph_seed, 1)).permutation(n)
    s, d = _draw(jnp.asarray(seed_words(graph_seed)),
                 jnp.asarray(perm, jnp.int32), scale=scale, m=m,
                 a=float(cfg["a"]), b=float(cfg["b"]), c=float(cfg["c"]))
    src, dst = np.asarray(s), np.asarray(d)
    del s, d
    if not cfg["directed"]:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    order = np.random.default_rng(seed_words(seed, 3)).permutation(src.size)
    return HostGraph(src[order], dst[order], n)
