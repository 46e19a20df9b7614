"""The benchmark's graph type, and the graph of a configuration made from the
seed.

A configuration's ``graph.generator`` names a file of its own,
``bench/graphs/<generator>.py``, whose ``structure(spec, nodes, edges)``
returns the graph from the spec (its ``structure_seed``); ``--seed`` draws
the node labels.  Every seed so gets the same graph up to the order of its
nodes: the same table sizes, compiled programs, memory and fixpoint passes,
laid out differently in the tables.
"""
from __future__ import annotations

import numpy as np


class Graph:
    """Undirected graph in CSR form: each edge in both endpoints' lists,
    neighbour lists sorted."""

    def __init__(self, n: int, indptr: np.ndarray, adj: np.ndarray):
        self.n = int(n)
        self.indptr = indptr
        self.adj = adj

    @classmethod
    def from_pairs(cls, n: int, lo: np.ndarray, hi: np.ndarray) -> "Graph":
        """From distinct undirected pairs with ``lo < hi``."""
        key = np.concatenate([lo * np.int64(n) + hi, hi * np.int64(n) + lo])
        key.sort()
        src = key // n
        indptr = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
        return cls(n, indptr, (key - src * np.int64(n)).astype(np.int32))

    @classmethod
    def from_keys(cls, n: int, keys: np.ndarray) -> "Graph":
        """From distinct keys ``lo * n + hi``."""
        return cls.from_pairs(n, keys // n, keys % n)

    @property
    def m(self) -> int:
        return len(self.adj) // 2

    def degrees(self) -> np.ndarray:
        return np.diff(self.indptr)

    def src(self) -> np.ndarray:
        """Source node of every directed edge slot."""
        return np.repeat(np.arange(self.n, dtype=np.int64), self.degrees())

    def pairs(self) -> tuple[np.ndarray, np.ndarray]:
        """Each undirected edge once, as ``(lo, hi)`` with ``lo < hi``."""
        src, dst = self.src(), self.adj.astype(np.int64)
        keep = src < dst
        return src[keep], dst[keep]

    def keys(self) -> np.ndarray:
        """Sorted ``lo * n + hi`` of every undirected edge."""
        lo, hi = self.pairs()
        return lo * np.int64(self.n) + hi


def relabel(g: Graph, perm: np.ndarray) -> Graph:
    """``g`` with node ``v`` renamed ``perm[v]``."""
    lo, hi = g.pairs()
    a, b = perm[lo], perm[hi]
    return Graph.from_pairs(g.n, np.minimum(a, b), np.maximum(a, b))


def apply_ops(g: Graph, batches) -> Graph:
    """``g`` with every ``("+"|"-", lo, hi)`` op of ``batches`` applied."""
    keys = g.keys()
    n = np.int64(g.n)
    dels = [u * n + v for b in batches for k, u, v in b if k == "-"]
    ins = [u * n + v for b in batches for k, u, v in b if k == "+"]
    if dels:
        keys = keys[~np.isin(keys, np.asarray(dels, dtype=np.int64))]
    if ins:
        keys = np.union1d(keys, np.asarray(ins, dtype=np.int64))
    return Graph.from_keys(g.n, keys)


def make_graph(config: dict, seed: int, generator) -> tuple:
    """``(base, perm, graph)``: the configuration's structure from its
    ``generator`` module, the node labels drawn from ``seed``, and the graph
    under those labels."""
    base = generator.structure(config["graph"], config["nodes"],
                               config["edges"])
    perm = np.random.default_rng([seed, 0]).permutation(base.n)
    return base, perm, relabel(base, perm)
