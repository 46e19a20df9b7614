"""Graph storage: CSR node/edge tables with blocked, I/O-accounted access.

Mirrors the paper's disk layout (§II *Graph Storage*): the **edge table** stores
``nbr(v_1), nbr(v_2), ...`` consecutively as adjacency lists; the **node table**
stores the offset and degree of every node.  The edge table is partitioned into
fixed-size blocks of ``block_size`` edges — the unit of I/O accounting under the
external-memory model of Aggarwal & Vitter [1].

Two backings are provided:
  * in-memory numpy arrays (tests, benchmarks, generators), and
  * on-disk ``.npy`` files opened with ``np.memmap`` (true out-of-core runs),
both behind the same :class:`CSRGraph` interface.

Graphs too large for ``CSRGraph.from_edges`` (whole-array sorts) are built by
the external-memory pipeline in :mod:`repro.graph.build`, which emits this
exact on-disk layout with O(n) + O(chunk) peak memory (DESIGN.md §10).

:class:`BlockReader` models the paper's single in-memory block buffer; the
``pool_blocks`` parameter generalizes it to an LRU buffer pool (a realistic
page cache) while keeping ``pool_blocks=1`` bit-identical to the paper's
accounting — see DESIGN.md §10 for the exact semantics.
"""
from __future__ import annotations

import json
import os
from collections import OrderedDict
from dataclasses import dataclass, field

import numpy as np

from ..faults import fs as _faults
from ..obs import metrics as _metrics

__all__ = [
    "CSRGraph",
    "BlockReader",
    "paper_example_graph",
    "DEFAULT_BLOCK_EDGES",
]

# Registry mirrors of the paper's I/O accounting (DESIGN.md §14).  Incremented
# at the same source lines as the reader's own counters so a registry delta
# around any run reconciles exactly with its DecompResult / reader fields.
_IO_READS = _metrics.counter(
    "repro_io_edge_block_reads_total",
    "Edge-table block read I/Os under the paper's blocked access model",
).labels()
_IO_HITS = _metrics.counter(
    "repro_io_edge_block_pool_hits_total",
    "Edge-table block reads answered from a resident buffer-pool block",
).labels()
_IO_EVICTIONS = _metrics.counter(
    "repro_io_edge_block_evictions_total",
    "LRU buffer-pool evictions of edge-table blocks",
).labels()
_IO_NODE_READS = _metrics.counter(
    "repro_io_node_table_reads_total",
    "Node-table block read I/Os (sequential node scans)",
).labels()
_IO_BYTES = _metrics.counter(
    "repro_io_bytes_read_total",
    "Bytes read under the blocked I/O model (edge + node table)",
).labels()

# 4096 edges * 4 bytes = 16 KiB per block: one DMA/disk-friendly tile.
DEFAULT_BLOCK_EDGES = 4096


@dataclass
class CSRGraph:
    """Undirected graph in CSR form (each edge stored in both endpoint lists).

    ``indptr``  -- int64 array of shape (n + 1,): the node table offsets.
    ``adj``     -- int32 array of shape (2m,): the edge table.
    """

    indptr: np.ndarray
    adj: np.ndarray

    def __post_init__(self) -> None:
        self.indptr = np.asarray(self.indptr, dtype=np.int64)
        # adj may be a memmap; only coerce dtype when needed.
        if self.adj.dtype != np.int32:
            self.adj = np.asarray(self.adj, dtype=np.int32)

    # ------------------------------------------------------------------ basic
    @property
    def n(self) -> int:
        return len(self.indptr) - 1

    @property
    def m(self) -> int:
        """Number of *undirected* edges."""
        return len(self.adj) // 2

    @property
    def num_directed(self) -> int:
        return len(self.adj)

    def degrees(self) -> np.ndarray:
        return np.diff(self.indptr).astype(np.int64)

    def degree(self, v: int) -> int:
        return int(self.indptr[v + 1] - self.indptr[v])

    def neighbors(self, v: int) -> np.ndarray:
        return self.adj[self.indptr[v] : self.indptr[v + 1]]

    def has_edge(self, u: int, v: int) -> bool:
        return bool(np.isin(v, self.neighbors(u)).item())

    # ------------------------------------------------------------ construction
    @classmethod
    def from_edges(cls, n: int, edges: np.ndarray, *, dedup: bool = True) -> "CSRGraph":
        """Build from an (E, 2) array of undirected edges (any orientation).

        Self loops are dropped; parallel edges are deduplicated when ``dedup``.
        """
        edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
        if len(edges):
            edges = edges[edges[:, 0] != edges[:, 1]]
        if dedup and len(edges):
            lo = np.minimum(edges[:, 0], edges[:, 1])
            hi = np.maximum(edges[:, 0], edges[:, 1])
            key = lo * np.int64(n) + hi
            _, idx = np.unique(key, return_index=True)
            edges = np.stack([lo[idx], hi[idx]], axis=1)
        # symmetrize, then one sort by (src, dst): rows in order, neighbor
        # lists sorted, for deterministic layouts
        key = np.concatenate([edges[:, 0] * np.int64(n) + edges[:, 1],
                              edges[:, 1] * np.int64(n) + edges[:, 0]])
        key.sort()
        src = key // max(n, 1)
        counts = np.bincount(src, minlength=n).astype(np.int64)
        indptr = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(counts, out=indptr[1:])
        out = (key - src * np.int64(n)).astype(np.int32)
        return cls(indptr=indptr, adj=out)

    def edge_list(self) -> np.ndarray:
        """Return (m, 2) array with each undirected edge once (u < v)."""
        src = np.repeat(np.arange(self.n, dtype=np.int64), np.diff(self.indptr))
        dst = self.adj.astype(np.int64)
        mask = src < dst
        return np.stack([src[mask], dst[mask]], axis=1)

    def directed_pairs(self) -> tuple[np.ndarray, np.ndarray]:
        """(src, dst) for every directed copy (2m entries), src sorted."""
        src = np.repeat(np.arange(self.n, dtype=np.int32), np.diff(self.indptr))
        return src, self.adj

    # ------------------------------------------------------------- subgraphs
    def induced_subgraph(self, nodes: np.ndarray) -> "CSRGraph":
        """Induced subgraph with nodes relabeled 0..len(nodes)-1."""
        nodes = np.asarray(nodes, dtype=np.int64)
        remap = np.full(self.n, -1, dtype=np.int64)
        remap[nodes] = np.arange(len(nodes))
        e = self.edge_list()
        keep = (remap[e[:, 0]] >= 0) & (remap[e[:, 1]] >= 0)
        e = remap[e[keep]]
        return CSRGraph.from_edges(len(nodes), e, dedup=False)

    def sample_edges(self, frac: float, seed: int = 0) -> "CSRGraph":
        """Keep a random fraction of edges (incident nodes kept; §VI-C)."""
        e = self.edge_list()
        rng = np.random.default_rng(seed)
        keep = rng.random(len(e)) < frac
        return CSRGraph.from_edges(self.n, e[keep], dedup=False)

    def sample_nodes(self, frac: float, seed: int = 0) -> "CSRGraph":
        """Induced subgraph of a random node sample (§VI-C)."""
        rng = np.random.default_rng(seed)
        nodes = np.flatnonzero(rng.random(self.n) < frac)
        return self.induced_subgraph(nodes)

    def relabel(self, perm: np.ndarray) -> "CSRGraph":
        """Relabel node ids: new id of old node v is perm[v]."""
        e = self.edge_list()
        perm = np.asarray(perm, dtype=np.int64)
        return CSRGraph.from_edges(self.n, perm[e], dedup=False)

    # ------------------------------------------------------------------- disk
    def save(self, path: str) -> None:
        os.makedirs(path, exist_ok=True)
        np.save(os.path.join(path, "indptr.npy"), self.indptr)
        np.save(os.path.join(path, "adj.npy"), self.adj)
        with open(os.path.join(path, "meta.json"), "w") as f:
            json.dump({"n": self.n, "m": self.m}, f)

    @classmethod
    def load(cls, path: str, *, mmap: bool = True) -> "CSRGraph":
        mode = "r" if mmap else None
        indptr = np.load(os.path.join(path, "indptr.npy"), mmap_mode=mode)
        adj = np.load(os.path.join(path, "adj.npy"), mmap_mode=mode)
        g = cls.__new__(cls)
        g.indptr = np.asarray(indptr, dtype=np.int64)
        g.adj = adj  # keep memmapped: the "edge table on disk"
        return g


class BlockReader:
    """Block-granular, I/O-accounted access to the edge table.

    Models the paper's sequential-scan access: a single in-memory block buffer;
    reading edge positions within the currently buffered block is free, any
    other block costs one read I/O.  Sequential full scans therefore cost
    ``ceil(2m / B)`` I/Os, and skip-heavy scans (SemiCore+/SemiCore*) cost one
    I/O per *distinct* block actually touched, exactly as in the paper.

    ``pool_blocks`` generalizes the single buffer to an LRU buffer pool
    (DESIGN.md §10): a read of a pool-resident block is a hit (free), a miss
    costs one read I/O and evicts the least-recently-used block.
    ``pool_blocks=1`` degenerates to exactly the paper's single-buffer model —
    every existing I/O trace is preserved bit-for-bit.
    """

    def __init__(
        self,
        graph: CSRGraph,
        block_edges: int = DEFAULT_BLOCK_EDGES,
        pool_blocks: int = 1,
        retry=None,
    ):
        self.graph = graph
        self.block_edges = int(block_edges)
        self.pool_blocks = max(1, int(pool_blocks))
        self.retry = retry  # optional faults.RetryPolicy for block fills
        self.reads = 0  # edge-table block read I/Os
        self.node_table_reads = 0  # node-table block read I/Os
        self.hits = 0  # pool hits (reads answered from a resident block)
        self._pool: OrderedDict[int, None] = OrderedDict()  # resident blocks, LRU order
        # node-table entries per block: entries are (offset 8B, degree 4B) =
        # 12 bytes; one block is block_edges * 4 bytes of edge data.
        self._node_entries_per_block = max(1, (self.block_edges * 4) // 12)

    # -- accounting ---------------------------------------------------------
    @property
    def num_blocks(self) -> int:
        return -(-self.graph.num_directed // self.block_edges)

    def invalidate(self) -> None:
        """Drop every resident block (the backing CSR was rewritten)."""
        self._pool.clear()

    def reset_io(self) -> None:
        self.reads = 0
        self.node_table_reads = 0
        self.hits = 0
        self.invalidate()

    @property
    def bytes_read(self) -> int:
        return self.reads * self.block_edges * 4 + self.node_table_reads * self.block_edges * 4

    @property
    def resident_blocks(self) -> tuple[int, ...]:
        """Resident block ids, least- to most-recently used."""
        return tuple(self._pool)

    # -- access -------------------------------------------------------------
    def _touch(self, block: int) -> None:
        pool = self._pool
        if block in pool:
            pool.move_to_end(block)
            self.hits += 1
            _IO_HITS.inc()
            return
        self.reads += 1
        _IO_READS.inc()
        _IO_BYTES.inc(self.block_edges * 4)
        pool[block] = None
        while len(pool) > self.pool_blocks:
            pool.popitem(last=False)
            _IO_EVICTIONS.inc()

    def charge_pass(self, blocks: np.ndarray) -> None:
        """Account one batch-schedule pass touching ``blocks`` (distinct,
        ascending ids).

        With ``pool_blocks == 1`` this reproduces the paper's single-buffer
        accounting exactly: a batch pass streams the covered blocks through
        the buffer in ascending order, so every distinct covered block costs
        one read I/O per pass and the buffer state is left untouched (the
        original implementation).  With a larger pool, blocks still resident
        from earlier passes hit for free; LRU's inclusion property makes the
        total read count non-increasing in ``pool_blocks``.

        The pool>1 path simulates LRU exactly without touching every block in
        Python: only blocks resident at pass start can hit (a once-evicted
        block always has ≥ pool_blocks fresher distinct blocks until it is
        re-read), and for a resident block at pass position ``i`` with
        pass-start LRU rank ``rho`` the number of distinct fresher blocks at
        its touch is ``i + (|resident| - 1 - rho) - #(prior pass touches of
        residents fresher than rho)`` — so the hit test loops over at most
        ``pool_blocks`` candidates while everything else stays vectorized.
        """
        blocks = np.asarray(blocks, dtype=np.int64)
        k = len(blocks)
        if self.pool_blocks == 1:
            self.reads += k
            _IO_READS.inc(k)
            _IO_BYTES.inc(k * self.block_edges * 4)
            return
        if k == 0:
            return
        pool = self._pool
        P = self.pool_blocks
        hits = 0
        resident = np.fromiter(pool.keys(), np.int64, len(pool))  # LRU -> MRU
        if len(resident):
            order = np.argsort(resident)
            pos = np.searchsorted(resident[order], blocks)
            pos = np.minimum(pos, len(resident) - 1)
            cand = np.flatnonzero(resident[order][pos] == blocks)
            rhos = order[pos[cand]]  # pass-start LRU rank of each candidate
            nres = len(resident)
            seen: list[int] = []
            for i, rho in zip(cand.tolist(), rhos.tolist()):
                fresher = i + (nres - 1 - rho) - sum(1 for r in seen if r > rho)
                if fresher < P:
                    hits += 1
                seen.append(rho)
        self.reads += k - hits
        self.hits += hits
        _IO_READS.inc(k - hits)
        _IO_HITS.inc(hits)
        _IO_BYTES.inc((k - hits) * self.block_edges * 4)
        # post-pass pool: the P most recently touched distinct blocks =
        # untouched residents (old recency order) then the pass tail
        if len(resident):
            untouched = resident[~np.isin(resident, blocks)]
        else:
            untouched = resident
        # evictions a per-block LRU simulation would have made this pass:
        # misses minus the pool-size growth
        end_size = min(len(untouched) + k, P)
        _IO_EVICTIONS.inc((k - hits) - (end_size - len(resident)))
        pool.clear()
        for b in untouched[max(0, len(untouched) + k - P):].tolist():
            pool[b] = None
        for b in blocks[max(0, k - P):].tolist():
            pool[b] = None

    def _fill_span(self, first: int, last: int) -> list[int]:
        """Touch blocks ``first..last``, fetching the missing ones.

        The fetch point (the fault hook, standing in for the disk read) runs
        *before* a missing block is charged or made resident, so a failed
        fill leaves no pool entry and no I/O charge behind — a retried read
        misses again, is charged exactly once, and the
        hits + evictions = reads - pool-growth reconciliation stays exact.
        Blocks already filled earlier in the span stay resident across a
        mid-span failure: their data really did arrive, and the retry
        legitimately hits them.
        """
        filled: list[int] = []
        for b in range(first, last + 1):
            if b not in self._pool:
                _faults.on_op("block.read")  # may raise a transient IOError
                filled.append(b)
            self._touch(b)
        return filled

    def load_neighbors(self, v: int) -> np.ndarray:
        """Load nbr(v), touching every block the adjacency list spans."""
        lo = int(self.graph.indptr[v])
        hi = int(self.graph.indptr[v + 1])
        if hi > lo:
            first = lo // self.block_edges
            last = (hi - 1) // self.block_edges
            if self.retry is None:
                filled = self._fill_span(first, last)
            else:
                filled = self.retry.call(
                    self._fill_span, first, last, op="block.read")
            try:
                return self.graph.adj[lo:hi]
            except OSError:
                # a block charged as read never delivered its bytes (memmap
                # page-in failure): invalidate this call's fills and undo
                # their charges so residency never lies about disk state.
                for b in filled:
                    if b in self._pool:
                        del self._pool[b]
                        self.reads -= 1
                raise
        return self.graph.adj[lo:hi]

    def account_node_table_scan(self, v_lo: int, v_hi: int) -> None:
        """Charge node-table I/O for sequentially scanning nodes [v_lo, v_hi]."""
        if v_hi < v_lo:
            return
        span = v_hi - v_lo + 1
        blocks = -(-span // self._node_entries_per_block)
        self.node_table_reads += blocks
        _IO_NODE_READS.inc(blocks)
        _IO_BYTES.inc(blocks * self.block_edges * 4)


def paper_example_graph() -> CSRGraph:
    """The 9-node, 15-edge running example of the paper (Fig. 1).

    Reconstructed from the degree row of Fig. 2 (Init = deg) and the traces of
    Examples 4.1 (nbr(v3) values {3,3,3,3,5,3}), 4.2 (v5's larger neighbors are
    v6, v7, v8), and 5.3 (v2's status flip decrements cnt(v4), so (v2,v4) ∈ E):
    cores are {v0..v3: 3, v4..v7: 2, v8: 1}; deleting (v0, v1) drops v0..v3 to
    2; then inserting (v4, v6) lifts {v3,v4,v5,v6} to 3.
    """
    edges = np.array(
        [
            (0, 1), (0, 2), (0, 3), (1, 2), (1, 3), (2, 3),  # K4: the 3-core
            (2, 4),
            (3, 4), (3, 5), (3, 6),
            (4, 5),
            (5, 6), (5, 7), (5, 8),
            (6, 7),
        ],
        dtype=np.int64,
    )
    return CSRGraph.from_edges(9, edges)
