"""Whole decomposes: each request is a graph, its answer the core and cnt of
every node.  The warm-up decompose compiles and warms every program the
window uses.  Afterwards the core and cnt of every decompose are compared
with the reference's, computed once on the same graph.
"""
from __future__ import annotations

import numpy as np

from bench.reference import cores

UNIT = "bench.decompose"


def setup(run) -> None:
    run.results = []


def request(run, g) -> dict:
    r = run.program.decompose(g)
    return {"passes": r.passes, "ops": 1, "core": r.core, "cnt": r.cnt}


def observe(run, reply) -> None:
    run.results.append((reply["core"].astype(np.int32),
                        reply["cnt"].astype(np.int32)))


def close(run) -> None:
    pass


def check(run) -> list:
    """``(name, value, op, limit)`` of each number compared."""
    core = cores.peel(run.graph)
    cnt = cores.cnt(run.graph, core)
    return [
        ("decomposes_checked", len(run.results), ">=", 1),
        ("core_mismatch_nodes",
         sum(int((c != core).sum()) for c, _ in run.results), "<=", 0),
        ("cnt_mismatch_nodes",
         sum(int((k != cnt).sum()) for _, k in run.results), "<=", 0),
    ]
