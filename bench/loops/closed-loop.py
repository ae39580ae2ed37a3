"""Closed loops with no think time: each session sends its next request
when the reply to its last is in, cycling through its list in order.

Warm-up sends every session's whole list once, one request at a time, so
every shape the window meets is compiled (or loaded from the cache) in
set-up."""

from __future__ import annotations

import threading
import time
from typing import List, Optional, Tuple

import jax

from loadgen import Record, send


class Loop:
    def __init__(self, client, mix: dict, graph: str,
                 lists: List[List[dict]]):
        self.op, self.graph, self.lists = mix["op"], graph, lists
        self.sessions = [client.session(f"analyst{i}")
                         for i in range(len(lists))]

    def _send(self, i: int, params: dict) -> Record:
        return send(self.sessions[i], i, self.op, self.graph, params)

    def warm_up(self) -> List[Record]:
        with jax.profiler.TraceAnnotation("bench.warmup"):
            return [self._send(i, p) for i, lst in enumerate(self.lists)
                    for p in lst]

    def window(self, seconds: float) -> Tuple[float, List[Record]]:
        records: List[List[Record]] = [[] for _ in self.lists]
        start = time.perf_counter()
        end = start + seconds
        errors: List[Optional[BaseException]] = [None] * len(self.lists)

        def loop(i: int) -> None:
            try:
                lst, j = self.lists[i], 0
                while time.perf_counter() < end:
                    records[i].append(self._send(i, lst[j % len(lst)]))
                    j += 1
            except BaseException as e:  # re-raised in the caller
                errors[i] = e

        threads = [threading.Thread(target=loop, args=(i,), daemon=True)
                   for i in range(len(self.lists))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for e in errors:
            if e is not None:
                raise e
        return start, [r for rs in records for r in rs]
