"""Every request is the run's graph: a whole decompose of it."""
from __future__ import annotations


class SameGraph:
    def __init__(self, graph):
        self.graph = graph

    def request(self, i: int):
        return self.graph


def make(run) -> SameGraph:
    return SameGraph(run.graph)
