"""Sharded graph layout for the mesh backend (DESIGN.md §5, §13).

The paper's memory contract maps onto a device mesh as:

  * edge table  -> per-device CSR shards of *contiguous node ranges* balanced
    by edge count (the paper's sequential adjacency layout, so every owned
    node's LocalCore needs only local edges — no cross-device count reduction);
  * node state  -> the replicated ``core`` array, O(n) per device — the
    semi-external memory bound (Clueweb: 978M * 4B = 3.9 GB/device, the
    paper's "< 4.2 GB" headline number);
  * one pass    -> one superstep: local h-index refresh of owned nodes
    (Jacobi), then an ``all_gather`` of the owned slices (O(n) over ICI,
    the read-only-I/O discipline: edge shards never move).

This module is the *layout* half of that contract: :func:`shard_arrays` cuts
a flat CSR into stacked per-shard arrays (minimax-balanced contiguous ranges,
int32-validated like ``resident.build_structure``), :func:`shard_graph` wraps
it for a plain :class:`CSRGraph`, and :func:`sharded_graph_specs` produces
the matching ShapeDtypeStructs for the dry-run cost-analysis path.

The *execution* half lives in the engine since the shard ComputeBackend
landed (DESIGN.md §13): :class:`repro.core.engine.ShardedBackend` binds a
:class:`~repro.core.resident.ShardedStructure` built from these arrays and
runs the whole fixpoint on-mesh through the shared fused superstep bodies
(``resident.fused_hindex`` / ``fused_counts``), pass-for-pass identical to
the numpy backend.  :func:`distributed_decompose` is kept as a thin wrapper
over that backend — its old private superstep builders are gone.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax

from ..graph.storage import CSRGraph

__all__ = [
    "ShardedGraph",
    "shard_arrays",
    "shard_graph",
    "balanced_bounds",
    "sharded_graph_specs",
    "distributed_decompose",
]


@dataclass
class ShardedGraph:
    """Stacked per-shard CSR arrays (leading dim = number of shards).

    ``lsegptr`` holds each shard's *local* CSR offsets over its padded edge
    axis (empty segments for padding slots), so the on-mesh superstep can run
    its segment reductions as sorted prefix sums instead of scatters, and
    spread owned values to edge slots from the same offsets: no per-slot
    owner table is built.
    ``pad_edges`` / ``per_shard_edges`` surface the padding cost of the
    rectangular (S, E) layout; the minimax balance below keeps it minimal
    for contiguous ranges.
    """

    dst: np.ndarray        # (S, E) int32  — edge targets, padded
    edge_mask: np.ndarray  # (S, E) bool
    owned_ids: np.ndarray  # (S, V) int32  — global node id per local slot (pad -> n)
    owned_mask: np.ndarray # (S, V) bool
    lsegptr: np.ndarray    # (S, V+1) int32 — local flat-table offsets
    bounds: np.ndarray     # (S+1,) int64  — contiguous node-range cuts
    deg: np.ndarray        # (n,)  int32   — global degrees (core init)
    n: int
    num_probes: int        # binary-search probes = ceil(log2(max_deg + 2))
    pad_edges: int         # S * E - total directed edges (wasted slots)
    per_shard_edges: np.ndarray  # (S,) int64 — real edges per shard


def _validate_int32(total_edges: int, n: int) -> None:
    """The device shard tables are int32 end-to-end (ids, local offsets;
    jax x64 is off) — fail loudly instead of wrapping offsets negative and
    converging to a silently-wrong core array (the same guard
    ``resident.build_structure`` applies to the flat table)."""
    if total_edges >= (1 << 31) or n >= (1 << 31):
        raise ValueError(
            f"sharded edge table needs int32 offsets: 2m={total_edges} "
            f"n={n} exceeds 2**31; raise num_shards only splits the edge "
            "axis, not the id space — use the numpy backend for this graph")


def balanced_bounds(seg_ptr: np.ndarray, num_shards: int) -> np.ndarray:
    """Contiguous node-range cuts minimizing the max per-shard edge count.

    The rectangular (S, E) device layout pads every shard to the heaviest
    shard's edge count, so the balance objective is *minimax*, not
    mean-squared: binary-search the smallest feasible load L, with greedy
    feasibility via ``searchsorted`` (each range takes the longest prefix
    fitting in L; a node's adjacency never splits, and L >= max degree
    guarantees progress).  O(S log n log m).
    """
    n = len(seg_ptr) - 1
    S = max(1, int(num_shards))
    total = int(seg_ptr[-1])
    if n == 0:
        return np.zeros(S + 1, dtype=np.int64)
    deg = np.diff(seg_ptr)
    lo = max(int(deg.max()) if n else 0, -(-total // S))
    hi = total

    def cuts(L):
        bounds = np.empty(S + 1, dtype=np.int64)
        bounds[0] = 0
        cur = 0
        for s in range(S):
            if cur >= n:
                bounds[s + 1] = n
                continue
            nxt = int(np.searchsorted(seg_ptr, seg_ptr[cur] + L,
                                      side="right")) - 1
            bounds[s + 1] = cur = max(min(nxt, n), cur + 1)
        return bounds if bounds[-1] >= n else None

    while lo < hi:
        mid = (lo + hi) // 2
        if cuts(mid) is not None:
            hi = mid
        else:
            lo = mid + 1
    return cuts(lo)


def shard_arrays(adj: np.ndarray, seg_ptr: np.ndarray, num_shards: int,
                 n: int | None = None) -> ShardedGraph:
    """Cut a flat CSR (``adj`` targets, ``seg_ptr`` offsets) into stacked
    per-shard arrays over minimax-balanced contiguous node ranges."""
    n = len(seg_ptr) - 1 if n is None else int(n)
    seg_ptr = np.asarray(seg_ptr, dtype=np.int64)
    total = int(seg_ptr[-1])
    _validate_int32(total, n)
    S = max(1, int(num_shards))
    bounds = balanced_bounds(seg_ptr, S)
    per_shard = (seg_ptr[bounds[1:]] - seg_ptr[bounds[:-1]]).astype(np.int64)
    max_nodes = int(max(1, np.diff(bounds).max() if n else 1))
    max_edges = int(max(1, per_shard.max()))
    dst = np.zeros((S, max_edges), dtype=np.int32)
    emask = np.zeros((S, max_edges), dtype=bool)
    owned = np.full((S, max_nodes), n, dtype=np.int32)
    omask = np.zeros((S, max_nodes), dtype=bool)
    lseg = np.zeros((S, max_nodes + 1), dtype=np.int32)
    for s in range(S):
        lo_v, hi_v = int(bounds[s]), int(bounds[s + 1])
        e0, e1 = int(seg_ptr[lo_v]), int(seg_ptr[hi_v])
        ne, nv = e1 - e0, hi_v - lo_v
        dst[s, :ne] = adj[e0:e1]
        emask[s, :ne] = True
        owned[s, :nv] = np.arange(lo_v, hi_v, dtype=np.int32)
        omask[s, :nv] = True
        lseg[s, : nv + 1] = (seg_ptr[lo_v: hi_v + 1] - e0).astype(np.int32)
        lseg[s, nv + 1:] = ne  # padding slots: empty trailing segments
    deg = np.diff(seg_ptr).astype(np.int32)
    dmax = int(deg.max()) if n else 0
    return ShardedGraph(
        dst=dst, edge_mask=emask, owned_ids=owned,
        owned_mask=omask, lsegptr=lseg, bounds=bounds, deg=deg, n=n,
        num_probes=max(1, int(np.ceil(np.log2(dmax + 2)))),
        pad_edges=S * max_edges - total, per_shard_edges=per_shard,
    )


def shard_graph(graph: CSRGraph, num_shards: int) -> ShardedGraph:
    """Contiguous node-range shards of a plain CSR, balanced by edge count."""
    return shard_arrays(np.asarray(graph.adj), graph.indptr, num_shards,
                        n=graph.n)


def sharded_graph_specs(
    n: int, m_directed: int, num_shards: int, max_deg: int
) -> tuple[dict, int, int]:
    """ShapeDtypeStructs matching the shard chunk-fn signature (dry-run path:
    ``resident.build_shard_chunk_fn``)."""
    import jax.numpy as jnp

    V = -(-n // num_shards) + 1
    E = int(m_directed / num_shards * 1.05) + 8  # balanced-cut slack
    S = num_shards
    sds = jax.ShapeDtypeStruct
    specs = dict(
        dst=sds((S, E), jnp.int32),
        edge_mask=sds((S, E), jnp.bool_),
        lsegptr=sds((S, V + 1), jnp.int32),
        owned_ids=sds((S, V), jnp.int32),
        owned_mask=sds((S, V), jnp.bool_),
        cnt=sds((S, V), jnp.int32),
        active=sds((S, V), jnp.bool_),
        nactive=sds((), jnp.int32),
    )
    probes = max(1, int(np.ceil(np.log2(max_deg + 2))))
    return specs, probes, V


def distributed_decompose(
    graph: CSRGraph,
    mesh=None,
    star_gating: bool = True,
    core0: np.ndarray | None = None,
    max_supersteps: int | None = None,
):
    """Thin wrapper over the ``shard`` ComputeBackend (DESIGN.md §13):
    shard, run the on-mesh fixpoint, return (core, supersteps).

    With ``core0`` given (e.g. a checkpointed intermediate state or the
    post-deletion upper bounds), performs a warm restart: monotone
    convergence makes any upper-bound state a valid init, and the exact-cnt
    prologue (the warm-settle discipline) re-derives cnt on the mesh.
    ``max_supersteps`` budgets the run exactly — the returned core is then
    a valid upper-bound checkpoint rather than the fixpoint.
    """
    from .engine import ShardedBackend
    from .resident import run_resident
    from .semicore import HostEngine

    if mesh is not None:
        devices = list(mesh.devices.flat)  # honor the caller's device pick
        S = len(devices)
    else:
        devices = None
        S = len(jax.devices())
    backend = ShardedBackend(num_shards=S, devices=devices)
    eng = HostEngine(graph)
    if core0 is not None:
        warm = np.minimum(np.asarray(core0, dtype=np.int64),
                          eng.degrees()).astype(np.int64)
        r = run_resident(eng, "semicore*", backend, core=warm,
                         initial_cnt_scan=True, max_supersteps=max_supersteps)
    else:
        algo = "semicore*" if star_gating else "semicore"
        r = run_resident(eng, algo, backend, max_supersteps=max_supersteps)
    return np.asarray(r.core), int(r.iterations)
