"""Bytes that kernels and whole steps move.

A kernel's bytes come from its own HLO text in the profiler trace: the
operand and result shapes of the call as it ran, so the count follows
whatever the kernel reads and writes.  A whole step's least bytes come from
the graph's sizes alone, whatever implements it.  ``graph`` is the
generator's edge list (``reference.HostGraph``).
"""

from __future__ import annotations

import re
from typing import Optional

import numpy as np

_SEGSUM = re.compile(r"segsum|segment_sum", re.IGNORECASE)
_SHAPE = re.compile(r"\b(pred|[su](?:4|8|16|32|64)|bf16|f(?:8\w*|16|32|64))"
                    r"\[([0-9,]*)\]")


def _bits(dtype: str) -> int:
    if dtype == "pred" or dtype.startswith("f8"):
        return 8
    return int(dtype[2:] if dtype == "bf16" else dtype[1:])


def is_segment_sum(op_name: str) -> bool:
    """Whether a device op of the trace is the segment-sum kernel."""
    return bool(_SEGSUM.search(op_name))


def _balanced(text: str, i: int) -> Optional[int]:
    """Index just past the bracket group that opens at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        if text[j] in "({[":
            depth += 1
        elif text[j] in ")}]":
            depth -= 1
            if depth == 0:
                return j + 1
    return None


def shape_bytes(text: str) -> int:
    """Bytes of every array shape (``f32[128,512]{...}``) written in
    ``text``."""
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        total += int(np.prod([int(d) for d in dims.split(",") if d],
                             dtype=np.int64)) * _bits(dtype) // 8
    return total


def hlo_bytes(hlo: str) -> Optional[int]:
    """Bytes an op reads and writes once: its operands' and its result's
    shapes, from the HLO text ``%name = <result> <opcode>(<operands>), ...``.
    None where the text is not whole."""
    if " = " not in hlo:
        return None
    rest = hlo.split(" = ", 1)[1]
    if rest.startswith("("):                      # a tuple result
        end = _balanced(rest, 0)
    else:
        end = rest.find(" ")
        end = None if end < 0 else end
    if end is None:
        return None
    m = re.match(r"\s*[\w\-]+\(", rest[end:])
    if not m:
        return None
    open_at = end + m.end() - 1
    close = _balanced(rest, open_at)
    if close is None:
        return None
    return shape_bytes(rest[:end]) + shape_bytes(rest[open_at:close])


def pagerank_iter_bytes(graph) -> int:
    """Least bytes of one PageRank iteration: the source index of every edge
    (4 B), and rank, inverse degree and new rank of every vertex (16 B with
    the dangling mask)."""
    return 4 * int(graph.src.shape[0]) + 16 * int(graph.n)
