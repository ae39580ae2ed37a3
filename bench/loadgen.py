"""The one load generator: turns a traffic mix (``bench/traffic/<mix>.json``)
into per-session request lists, and sends them through the mix's loop
(``bench/loops/<loop>.py``).

A mix is data only.  Its keys:

``loop``
    the name of the loop that sends the lists (``closed-loop``: each session
    sends its next request when the reply to the last is in);
``op``, ``params``
    the request every session sends, and its fixed parameters;
``sessions``
    analyst sessions, one list of requests each;
``sources``
    ``null`` for whole-graph jobs; else ``{"param", "count", "seed"}``:
    ``count`` distinct roots per session, drawn from the fixed ``seed``
    among the vertices with an edge to another vertex, so that every
    ``--seed`` sends the same searches; no root is shared between sessions;
``service``
    the ``GraphService`` settings: scheduler ``mode``, ``workers``, result
    ``cache``.

A loop module gives ``Loop(client, mix, graph_name, lists)`` with
``warm_up()``, which sends every request shape the window will send and
returns its records, and ``window(seconds)``, which returns the window's
start on the host clock and the records of every request sent inside it,
each waited for.
"""

from __future__ import annotations

import time
from typing import List, NamedTuple

import jax
import numpy as np

import byname
from reference import HostGraph
from seeds import seed_words


class Record(NamedTuple):
    session: int
    params: dict
    start: float          # host clock, request sent
    end: float            # host clock, reply received
    ok: bool
    answer: object        # what the client received; None on an error


def session_lists(mix: dict, graph: HostGraph) -> List[List[dict]]:
    """Each session's request parameters, in the order it sends them."""
    base = dict(mix.get("params") or {})
    k = int(mix["sessions"])
    spec = mix.get("sources")
    if not spec:
        return [[base] for _ in range(k)]
    count = int(spec["count"])
    loops = graph.src != graph.dst
    pool = np.flatnonzero(np.bincount(graph.src[loops], minlength=graph.n))
    rng = np.random.default_rng(seed_words(spec["seed"], 2))
    roots = rng.choice(pool, k * count, replace=False)
    return [[dict(base, **{spec["param"]: int(r)})
             for r in roots[i * count:(i + 1) * count]] for i in range(k)]


def send(session, i: int, op: str, graph: str, params: dict) -> Record:
    """One request of session ``i``, inside a ``bench.call.<op>`` span."""
    with jax.profiler.TraceAnnotation(f"bench.call.{op}"):
        t0 = time.perf_counter()
        try:
            out = session.execute({"op": op, "graph": graph,
                                   "params": params})
            ok = True
        except Exception as e:  # an error frame: a failed request
            out, ok = repr(e), False
        t1 = time.perf_counter()
    return Record(i, params, t0, t1, ok, np.asarray(out) if ok else None)


def loop(client, mix: dict, graph: str, lists: List[List[dict]]):
    """The mix's loop, found by name, over ``lists``."""
    return byname.module("loops", mix["loop"]).Loop(
        client, mix, graph, lists)
