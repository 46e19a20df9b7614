"""The plain reference: exact core numbers by peeling, and cnt by its
definition.  Imports nothing of the program.

``peel`` strips the graph level by level, as Batagelj–Zaversnik's bin-sort
peeling does: at level k every node of remaining degree at most k gets core
k and is removed, its neighbours' degrees drop, and the nodes that fall to
k or below go next.  ``cnt(v)`` is the number of neighbours u of v with
``core(u) >= core(v)``, the count SemiCore* keeps (the paper's Eq. 2).
"""
from __future__ import annotations

import numpy as np


def neighbours(g, f: np.ndarray) -> np.ndarray:
    """The neighbour lists of the nodes ``f``, concatenated."""
    lo = g.indptr[f]
    lens = g.indptr[f + 1] - lo
    start = np.repeat(lo - (np.cumsum(lens) - lens), lens)
    return g.adj[np.arange(lens.sum()) + start]


def peel(g) -> np.ndarray:
    """Exact core number of every node of ``g``."""
    deg = g.degrees().astype(np.int64)
    core = np.zeros(g.n, dtype=np.int64)
    alive = np.ones(g.n, dtype=bool)
    k = 0
    while alive.any():
        k = max(k, int(deg[alive].min()))
        f = np.flatnonzero(alive & (deg <= k))
        while len(f):
            core[f] = k
            alive[f] = False
            nb = neighbours(g, f)
            nb = nb[alive[nb]]
            deg -= np.bincount(nb, minlength=g.n)
            nb = np.unique(nb)
            f = nb[deg[nb] <= k]
    return core


def cnt(g, core: np.ndarray) -> np.ndarray:
    """``cnt(v) = |{u in nbr(v) : core(u) >= core(v)}|``."""
    src = g.src()
    ge = core[g.adj] >= core[src]
    return np.bincount(src[ge], minlength=g.n).astype(np.int64)


def top_k(core: np.ndarray, k: int) -> np.ndarray:
    """The k nodes of highest core number, ties by lower id first."""
    order = np.lexsort((np.arange(len(core)), -core))
    return order[:k].astype(np.int64)
