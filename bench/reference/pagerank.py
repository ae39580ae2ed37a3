"""PageRank by float64 power iteration, as ``algorithms.pagerank`` states it:
start from the uniform vector; each iteration every vertex passes its rank,
split evenly over its out-edges (duplicates counted), and the rank of
vertices without out-edges is spread over all vertices:
``r' = d * (A r/deg) + ((1 - d) + d * dangling(r)) / n``."""

from __future__ import annotations

from typing import Callable, Dict, List, Tuple

import ml_dtypes
import numpy as np

from . import HostGraph

# Largest relative error of any vertex's rank over every answer compared.
# Set from the chip readings in PERF.md (sound runs against the bfloat16
# control): float32 arithmetic lands near 1e-6, bfloat16 near 1e-2.
LIMITS = {"pagerank_max_rel_err": 1e-4}


def _f64(x: np.ndarray) -> np.ndarray:
    return x


def _bf16(x: np.ndarray) -> np.ndarray:
    return x.astype(ml_dtypes.bfloat16).astype(np.float64)


def ranks(graph: HostGraph, n_iter: int = 10, damping: float = 0.85,
          rnd: Callable[[np.ndarray], np.ndarray] = _f64) -> np.ndarray:
    """``rnd`` rounds every stored vector (ranks, shares, sums): the
    identity for the reference, bfloat16 for the control."""
    n = graph.n
    deg = np.bincount(graph.src, minlength=n).astype(np.float64)
    inv = rnd(np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0))
    dangling = deg == 0
    r = rnd(np.full(n, 1.0 / n))
    for _ in range(int(n_iter)):
        share = rnd(r * inv)
        summed = rnd(np.bincount(graph.dst, weights=share[graph.src],
                                 minlength=n))
        dang = float(r[dangling].sum())
        r = rnd(damping * summed + ((1.0 - damping) + damping * dang) / n)
    return r


def _rel_err(got, want: np.ndarray) -> float:
    got = np.asarray(got, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want) / want))


def _key(params: dict) -> Tuple[int, float]:
    return int(params.get("n_iter", 10)), float(params.get("damping", 0.85))


def check(graph: HostGraph, answers: List[Tuple[dict, object]]
          ) -> Dict[str, float]:
    refs: Dict[Tuple[int, float], np.ndarray] = {}
    err = 0.0
    for params, got in answers:
        k = _key(params)
        if k not in refs:
            refs[k] = ranks(graph, *k)
        err = max(err, _rel_err(got, refs[k]))
    return {"pagerank_max_rel_err": err}


def control(graph: HostGraph, params_list: List[dict]) -> Dict[str, float]:
    err = 0.0
    for k in sorted({_key(p) for p in params_list}):
        err = max(err, _rel_err(ranks(graph, *k, rnd=_bf16),
                                ranks(graph, *k)))
    return {"pagerank_max_rel_err": err}
