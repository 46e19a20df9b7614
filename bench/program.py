"""The system under test, as the benchmark drives it.

Everything the benchmark takes from the program passes through here: the
decompose entry point, the streaming writer, the metrics registry and the
span collector.  The rest of the benchmark sees only numpy arrays.  Tests
and the control replace this object to break or substitute the timed path.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Decomposition:
    core: np.ndarray
    cnt: np.ndarray
    passes: int


@dataclass
class Ack:
    """An acknowledged batch: logged, settled and published."""
    passes: int


class Writer:
    """A ``CoreWriter`` with the queries the client sends."""

    def __init__(self, writer):
        self._w = writer

    def ingest(self, ops) -> Ack:
        return Ack(passes=int(self._w.ingest(ops).iterations))

    def state(self) -> tuple[np.ndarray, np.ndarray]:
        """The published core and the cnt behind it."""
        m = self._w.maintainer
        return np.asarray(m.core), np.asarray(m.cnt)

    def coreness(self, nodes: np.ndarray) -> np.ndarray:
        return np.asarray(self._w.coreness(nodes))

    def top_k(self, k: int) -> np.ndarray:
        return np.asarray(self._w.top_k(k))

    def degeneracy(self) -> int:
        return int(self._w.degeneracy())

    def close(self) -> None:
        self._w.close()


class Program:
    """The program built from a configuration's backend and algorithm."""

    def __init__(self, config: dict):
        self.backend = config["backend"]
        self.algorithm = config.get("algorithm", "semicore*")
        self.schedule = config.get("schedule", "batch")
        self.wal_fsync = bool(config.get("wal_fsync", False))

    @staticmethod
    def _csr(g):
        """A fresh program graph object over copies of the arrays, so that no
        cache keyed on the object can skip the work a user pays for."""
        from repro.graph.storage import CSRGraph

        return CSRGraph(g.indptr.copy(), g.adj.copy())

    def decompose(self, g) -> Decomposition:
        from repro.core.semicore import decompose

        r = decompose(self._csr(g), self.algorithm, self.schedule,
                      backend=self.backend)
        return Decomposition(np.asarray(r.core), np.asarray(r.cnt),
                             int(r.iterations))

    def open_writer(self, g, core, cnt, wal_path: str) -> Writer:
        from repro.stream import CoreWriter

        return Writer(CoreWriter(self._csr(g), backend=self.backend,
                                 state=(core, cnt), wal_path=wal_path,
                                 wal_fsync=self.wal_fsync))

    # ------------------------------------------------------------ telemetry
    @staticmethod
    def registry() -> dict:
        """Flat snapshot of the program's counters and histogram sums."""
        from repro.obs import metrics

        return metrics.get_registry().snapshot()

    @staticmethod
    def start_spans() -> None:
        """Start the program's span collector; its timestamps count from
        this call."""
        from repro.obs import trace

        trace.get_collector().clear()
        trace.start_trace()

    @staticmethod
    def stop_spans() -> list:
        """Stop collecting; the complete spans as Chrome-trace events."""
        from repro.obs import trace

        trace.stop_trace()
        return [e for e in trace.get_collector().events if e["ph"] == "X"]
