"""Synthetic mixed update streams for benchmarks, examples, and tests.

Generates an always-valid insert/delete stream against the *evolving* edge
set (deletes pick a live edge, inserts pick a fresh non-edge), deterministic
in ``seed``.  Deleted-edge selection uses swap-remove over a mirrored edge
list, so generation is O(1) per op.
"""
from __future__ import annotations

import numpy as np

__all__ = ["mixed_stream", "sampled_stream"]


def mixed_stream(g, num_updates: int, seed: int = 0, p_delete: float = 0.45):
    """Return ``(ops, final_edges)``: the op list and the resulting edge set."""
    rng = np.random.default_rng(seed)
    present = {tuple(e) for e in g.edge_list().tolist()}
    ordered = sorted(present)
    ops = []
    for _ in range(num_updates):
        if ordered and rng.random() < p_delete:
            i = rng.integers(len(ordered))
            u, v = ordered[i]
            ordered[i] = ordered[-1]
            ordered.pop()
            present.discard((u, v))
            ops.append(("-", u, v))
        else:
            while True:
                u, v = int(rng.integers(g.n)), int(rng.integers(g.n))
                lo, hi = min(u, v), max(u, v)
                if u != v and (lo, hi) not in present:
                    break
            present.add((lo, hi))
            ordered.append((lo, hi))
            ops.append(("+", lo, hi))
    return ops, present


def sampled_stream(g, num_updates: int, seed: int = 0,
                   p_delete: float = 0.45):
    """Return a mixed op list without materializing the edge set.

    For graphs whose edge set does not fit a Python set: deletes draw a
    uniform edge slot of the CSR and inserts a uniform non-edge, each
    checked against the graph and against the ops drawn so far, so every
    op changes the graph.  O(num_updates) memory beyond the graph.
    """
    rng = np.random.default_rng(seed)
    deleted: set = set()
    inserted: set = set()
    ops = []
    for _ in range(num_updates):
        if rng.random() < p_delete:
            while True:
                slot = int(rng.integers(len(g.adj)))
                u = int(np.searchsorted(g.indptr, slot, side="right")) - 1
                e = tuple(sorted((u, int(g.adj[slot]))))
                if e not in deleted:
                    break
            deleted.add(e)
            ops.append(("-",) + e)
        else:
            while True:
                u, v = int(rng.integers(g.n)), int(rng.integers(g.n))
                e = (min(u, v), max(u, v))
                if u != v and e not in inserted and not g.has_edge(*e):
                    break
            inserted.add(e)
            ops.append(("+",) + e)
    return ops
