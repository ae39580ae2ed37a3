"""Plain NumPy references of the served operations, one module per op.

Each module is found by the op's name and gives:

* ``LIMITS``: ``{check name: limit}``; a run is correct when every number
  compared is at or under its limit;
* ``check(graph, answers)``: the numbers compared, for the answers the
  client received (``[(params, array), ...]``);
* ``control(graph, params_list)``: the same numbers for the control, the
  reference in a lower precision or with one guarantee broken, put in the
  program's place.

``graph`` is a :class:`HostGraph`: the generator's own edge list, in the
order the program was handed it.  Nothing here imports the program or reads
what it built.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import numpy as np


class HostGraph(NamedTuple):
    src: np.ndarray      # (E,) int32
    dst: np.ndarray      # (E,) int32
    n: int

    def out_csr(self) -> Tuple[np.ndarray, np.ndarray]:
        """(ptr, idx): the out-neighbours of vertex v are
        ``idx[ptr[v]:ptr[v + 1]]``."""
        ptr = np.concatenate([[0], np.cumsum(
            np.bincount(self.src, minlength=self.n))]).astype(np.int64)
        return ptr, self.dst[np.argsort(self.src, kind="stable")]
