"""Chung–Lu power-law graphs: the program's generator, copied here so that
no change to the program can change the inputs it is measured on.

One departure: the graph has exactly ``edges`` undirected edges.  Pairs are
drawn as the program draws them, self loops and repeats dropped in draw
order, and the first ``edges`` distinct pairs kept.  The spec gives
``gamma`` and ``structure_seed``.
"""
from __future__ import annotations

import numpy as np

from bench.graph import Graph


def chung_lu(n: int, edges: int, gamma: float, seed: int) -> Graph:
    """Chung–Lu expected-degree graph with exactly ``edges`` edges:
    weight of node i is ``(i + i0) ** (-1 / (gamma - 1))``, node ids
    permuted at random."""
    rng = np.random.default_rng(seed)
    i0 = n ** (1.0 / (gamma - 1.0)) / 10.0 + 1.0
    w = (np.arange(n) + i0) ** (-1.0 / (gamma - 1.0))
    p = w / w.sum()
    perm = rng.permutation(n)
    kept = np.empty(0, dtype=np.int64)
    draws = edges + edges // 64 + 16
    while len(kept) < edges:
        a = perm[rng.choice(n, size=draws, p=p)]
        b = perm[rng.choice(n, size=draws, p=p)]
        lo, hi = np.minimum(a, b), np.maximum(a, b)
        key = np.concatenate([kept, (lo * np.int64(n) + hi)[lo != hi]])
        _, first = np.unique(key, return_index=True)
        kept = key[np.sort(first)]
    return Graph.from_keys(n, kept[:edges])


def structure(spec: dict, nodes: int, edges: int) -> Graph:
    return chung_lu(nodes, edges, spec["gamma"], spec["structure_seed"])
