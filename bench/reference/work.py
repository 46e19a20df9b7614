"""The work of a SemiCore* decompose, counted independently of the program.

SemiCore* (the paper's Algorithm 5) run as Jacobi passes, vectorised in
numpy: every node starts at its degree, and a pass recomputes, at once and
from the pass-start values, every node whose cnt has fallen below its core.
A node's new value is the h-index of its neighbours' cores capped by its
own (the locality property).  The frontiers F_p of the passes are the work
any implementation of the fixpoint has to do; ``pass_bytes`` turns them
into the bytes a pass must move at the least.
"""
from __future__ import annotations

import numpy as np

from bench.reference.cores import neighbours

#: bytes per neighbour read in a pass: its id and its core, 4 B each
EDGE_BYTES = 8
#: bytes per frontier node: its core and cnt read, its new core written
NODE_BYTES = 12


def semicore_star(g, max_passes: int | None = None):
    """Run SemiCore* to its fixpoint, or for at most ``max_passes`` passes.
    Returns ``(core, frontiers)``: the core numbers and, per pass, the sorted
    ids it recomputed."""
    src = g.src()
    adj = g.adj.astype(np.int64)
    core = g.degrees().astype(np.int64)
    frontiers = []
    active = core > 0  # cnt starts at 0
    while active.any() and (max_passes is None
                            or len(frontiers) < max_passes):
        f = np.flatnonzero(active)
        frontiers.append(f)
        vals = core[neighbours(g, f)]  # row by row
        lens = g.indptr[f + 1] - g.indptr[f]
        # h = max h <= core(v) with |{u : core(u) >= h}| >= h, by bisection
        lo_h = np.zeros(len(f), dtype=np.int64)
        hi_h = core[f]
        pos = np.repeat(np.arange(len(f)), lens)
        while (lo_h < hi_h).any():
            mid = (lo_h + hi_h + 1) // 2
            ok = np.bincount(pos, weights=vals >= mid[pos],
                             minlength=len(f)) >= mid
            lo_h = np.where(ok, mid, lo_h)
            hi_h = np.where(ok, hi_h, mid - 1)
        core[f] = lo_h
        cnt = np.bincount(src[core[adj] >= core[src]], minlength=g.n)
        active = (cnt < core) & (core > 0)
    return core, frontiers


def pass_bytes(g, frontiers) -> list[int]:
    """Least bytes each pass moves: a neighbour's id and core for every edge
    of the frontier, a node's core and cnt read and core written."""
    deg = g.degrees()
    return [int(EDGE_BYTES * deg[f].sum() + NODE_BYTES * len(f))
            for f in frontiers]
