"""Batches of edge updates, driven by a traffic file's numbers.

The traffic file gives ``batch_ops`` and ``p_delete``.  Every batch holds
exactly ``round(p_delete * batch_ops)`` deletes and the rest inserts, in an
order drawn from the seed, so every seed sends the same sizes.  The op draws
are the program's ``sampled_stream``, copied: a delete names a uniform edge
slot of the starting graph that no earlier op deleted, an insert a uniform
pair of distinct nodes that is neither an edge of the starting graph nor
inserted before.  Every op therefore changes the graph, and the stream stays
valid however long it runs.

The ops are drawn on the configuration's structure from its
``structure_seed``, then named under the run's labels: every seed sends the
same ops, batch by batch, on a differently labelled graph.  ``prepared``
batches are drawn in set-up, the rest when asked for.
"""
from __future__ import annotations

import numpy as np


class UpdateStream:
    """Batches of ``("+"|"-", u, v)`` ops against a starting graph."""

    def __init__(self, g, traffic: dict, structure_seed: int,
                 perm: np.ndarray, seed: int):
        self.g = g
        self.perm = perm
        self.order = np.random.default_rng([seed, 2])
        self.batch_ops = int(traffic["batch_ops"])
        self.deletes = int(round(float(traffic["p_delete"]) * self.batch_ops))
        if not 0 <= self.deletes <= self.batch_ops:
            raise ValueError(f"p_delete out of range in {traffic}")
        self.rng = np.random.default_rng(structure_seed)
        self.deleted: set = set()
        self.inserted: set = set()
        self.batches: list = []

    def _has_edge(self, u: int, v: int) -> bool:
        g = self.g
        row = g.adj[g.indptr[u]:g.indptr[u + 1]]
        i = np.searchsorted(row, v)
        return bool(i < len(row) and row[i] == v)

    def _delete(self):
        g, rng = self.g, self.rng
        if len(self.deleted) == g.m:
            raise RuntimeError("the stream has deleted every edge")
        while True:
            slot = int(rng.integers(len(g.adj)))
            u = int(np.searchsorted(g.indptr, slot, side="right")) - 1
            v = int(g.adj[slot])
            e = (min(u, v), max(u, v))
            if e not in self.deleted:
                self.deleted.add(e)
                return ("-",) + e

    def _insert(self):
        g, rng = self.g, self.rng
        while True:
            u, v = int(rng.integers(g.n)), int(rng.integers(g.n))
            e = (min(u, v), max(u, v))
            if u != v and e not in self.inserted and not self._has_edge(*e):
                self.inserted.add(e)
                return ("+",) + e

    def request(self, i: int) -> list:
        """Batch ``i`` under the run's labels; batches are drawn in order
        and kept."""
        p = self.perm
        while len(self.batches) <= i:
            kinds = np.zeros(self.batch_ops, dtype=bool)
            kinds[:self.deletes] = True
            self.rng.shuffle(kinds)
            ops = [self._delete() if d else self._insert() for d in kinds]
            named = [(k, int(min(p[u], p[v])), int(max(p[u], p[v])))
                     for k, u, v in ops]
            self.batches.append([named[j] for j in
                                 self.order.permutation(len(named))])
        return self.batches[i]


def make(run) -> UpdateStream:
    stream = UpdateStream(run.base, run.traffic,
                          run.config["graph"]["structure_seed"], run.perm,
                          run.seed)
    for i in range(int(run.traffic.get("warmup", 0))
                   + int(run.traffic.get("prepared", 0))):
        stream.request(i)
    return stream
