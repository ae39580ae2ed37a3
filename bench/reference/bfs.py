"""BFS levels by level-synchronous search over the out-edges."""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from . import HostGraph

# Levels are integers: the served answer must equal the reference exactly.
LIMITS = {"bfs_level_mismatches": 0}


def levels(ptr: np.ndarray, idx: np.ndarray, n: int, source: int,
           max_depth: int = -1) -> np.ndarray:
    """Hop level of every vertex from ``source``; -1 where unreachable.
    ``max_depth >= 0`` stops the search after that many levels."""
    level = np.full(n, -1, np.int32)
    level[source] = 0
    frontier = np.array([source], np.int64)
    depth = 0
    while frontier.size and depth != max_depth:
        starts, lens = ptr[frontier], ptr[frontier + 1] - ptr[frontier]
        pos = np.repeat(starts - np.cumsum(lens) + lens, lens) \
            + np.arange(int(lens.sum()), dtype=np.int64)
        nbrs = idx[pos]
        nbrs = nbrs[level[nbrs] < 0]
        depth += 1
        level[nbrs] = depth
        frontier = np.flatnonzero(level == depth) if nbrs.size else nbrs
    return level


def _mismatches(got, want: np.ndarray) -> int:
    got = np.asarray(got)
    if got.shape != want.shape:
        return int(want.size)
    return int(np.count_nonzero(got != want))


def _by_source(answers) -> Dict[int, List]:
    out: Dict[int, List] = {}
    for params, got in answers:
        out.setdefault(int(params["source"]), []).append(got)
    return out


def check(graph: HostGraph, answers: List[Tuple[dict, object]]
          ) -> Dict[str, float]:
    ptr, idx = graph.out_csr()
    bad = 0
    for source, gots in _by_source(answers).items():
        want = levels(ptr, idx, graph.n, source)
        bad += sum(_mismatches(g, want) for g in gots)
    return {"bfs_level_mismatches": bad}


def control(graph: HostGraph, params_list: List[dict]) -> Dict[str, float]:
    """The search stopped one level early: the deepest level's vertices
    read as unreachable (the guarantee broken: every reachable vertex)."""
    ptr, idx = graph.out_csr()
    bad = 0
    for source in sorted({int(p["source"]) for p in params_list}):
        want = levels(ptr, idx, graph.n, source)
        early = levels(ptr, idx, graph.n, source,
                       max_depth=max(int(want.max()) - 1, 0))
        bad += _mismatches(early, want)
    return {"bfs_level_mismatches": bad}
