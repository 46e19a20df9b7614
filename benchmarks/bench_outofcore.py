"""Out-of-core benchmark: external-memory build throughput + buffer pool.

Three measurements (JSON artifact: ``benchmarks/results/outofcore.json``):

1. **Build** — stream a ≥10M-edge synthetic web (R-MAT chunks) through
   ``build_csr`` into on-disk node/edge tables, recording wall time, edge
   throughput, and peak memory (tracemalloc tracks numpy allocations; the
   point is O(n) + O(chunk), never O(m)).
2. **Fidelity** — memmap-load the disk build and decompose it; the core
   array must be bit-identical to decomposing an in-memory ``from_edges``
   build of the same stream (``--quick`` only shrinks the graph, the check
   always runs).
3. **Pool sweep** — a skip-heavy SemiCore* run per ``pool_blocks`` setting:
   block reads must decrease monotonically as the pool grows (LRU inclusion).

Usage:
    PYTHONPATH=src python benchmarks/bench_outofcore.py [--quick] [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
import tracemalloc

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.semicore import decompose  # noqa: E402
from repro.graph import CSRGraph, build_csr, chung_lu, rmat_chunks  # noqa: E402
from repro.obs import metrics as obs_metrics  # noqa: E402
from repro.obs.bench import shared_result  # noqa: E402

RESULTS = os.path.join(os.path.dirname(__file__), "results")


def bench_build(scale: int, edge_factor: int, chunk_edges: int, workdir: str) -> dict:
    """Out-of-core build of an R-MAT stream; peak memory + throughput."""
    out = os.path.join(workdir, "graph")
    tracemalloc.start()
    t0 = time.perf_counter()
    stats = build_csr(
        rmat_chunks(scale, edge_factor, seed=7, chunk_edges=chunk_edges),
        out,
        n=1 << scale,
        chunk_edges=chunk_edges,
        tmp_dir=workdir,
    )
    build_s = time.perf_counter() - t0
    _, peak_bytes = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    raw = (1 << scale) * edge_factor
    return {
        "n": stats.n,
        "m": stats.m,
        "edges_ingested": stats.edges_ingested,
        "runs": stats.runs,
        "merge_rounds": stats.merge_rounds,
        "chunk_edges": chunk_edges,
        "build_seconds": round(build_s, 3),
        "edges_per_second": round(raw / build_s),
        "peak_traced_bytes": peak_bytes,
        "node_state_bytes": stats.node_state_bytes,
        # the O(n) + O(chunk) contract, with headroom for numpy temporaries:
        # sort/unique/scatter stages each hold a small constant number of
        # int64 views of one chunk (measured ~26x8 bytes/chunk edge)
        "memory_bound_bytes": stats.node_state_bytes + 32 * 8 * chunk_edges,
        "within_bound": peak_bytes <= stats.node_state_bytes + 32 * 8 * chunk_edges,
        # what an in-memory from_edges would hold just for the raw edge array
        "inmemory_edge_array_bytes": stats.edges_ingested * 16,
        "graph_dir": out,
    }


def bench_fidelity(graph_dir: str, scale: int, edge_factor: int,
                   chunk_edges: int) -> dict:
    """decompose(memmap build) must equal decompose(in-memory build)."""
    g_disk = CSRGraph.load(graph_dir, mmap=True)
    edges = np.concatenate(
        list(rmat_chunks(scale, edge_factor, seed=7, chunk_edges=chunk_edges))
    )
    g_mem = CSRGraph.from_edges(1 << scale, edges)
    del edges
    assert np.array_equal(np.asarray(g_disk.indptr), g_mem.indptr)
    assert np.array_equal(np.asarray(g_disk.adj), g_mem.adj)
    t0 = time.perf_counter()
    r_disk = decompose(g_disk, "semicore*", "batch")
    t_disk = time.perf_counter() - t0
    r_mem = decompose(g_mem, "semicore*", "batch")
    identical = bool(np.array_equal(r_disk.core, r_mem.core))
    assert identical, "memmap decomposition diverged from in-memory build"
    return {
        "kmax": r_disk.kmax,
        "iterations": r_disk.iterations,
        "decompose_seconds_memmap": round(t_disk, 3),
        "edge_block_reads": r_disk.edge_block_reads,
        "bit_identical_to_inmemory": identical,
    }


def bench_pool_sweep(quick: bool) -> dict:
    """Skip-heavy SemiCore* (seq): block reads vs pool size, monotone."""
    n, m = (1200, 5000) if quick else (4000, 16000)
    g = chung_lu(n, m, seed=6)
    block_edges = 32
    pools = [1, 16, 64, 256, 1024]
    rows = []
    core0 = None
    s = obs_metrics.sum_by_name
    for pool in pools:
        snap = obs_metrics.get_registry().snapshot()
        t0 = time.perf_counter()
        r = decompose(g, "semicore*", "seq", block_edges=block_edges,
                      pool_blocks=pool)
        wall = time.perf_counter() - t0
        delta = obs_metrics.get_registry().delta(snap)
        if core0 is None:
            core0 = r.core
        else:
            assert np.array_equal(r.core, core0)
        # reads come from the telemetry registry, cross-checked against the
        # DecompResult; hits/evictions exist only in the registry — the
        # reader's paper accounting never needed them until the pool sweep
        reads = int(s(delta, "repro_io_edge_block_reads_total"))
        if obs_metrics.obs_enabled():
            assert reads == r.edge_block_reads, (pool, reads,
                                                 r.edge_block_reads)
        else:
            reads = r.edge_block_reads
        rows.append({
            "pool_blocks": pool,
            "edge_block_reads": reads,
            "pool_hits": int(s(delta, "repro_io_edge_block_pool_hits_total")),
            "evictions": int(s(delta, "repro_io_edge_block_evictions_total")),
            "obs": shared_result(f"outofcore/pool-sweep[pool={pool}]",
                                 wall, delta),
        })
    reads = [row["edge_block_reads"] for row in rows]
    monotone = all(a >= b for a, b in zip(reads, reads[1:]))
    assert monotone, f"pool sweep not monotone: {reads}"
    return {
        "graph": {"n": g.n, "m": g.m, "block_edges": block_edges,
                  "num_blocks": -(-g.num_directed // block_edges)},
        "sweep": rows,
        "monotone_decreasing": monotone,
        "reads_reduction": round(1 - reads[-1] / reads[0], 4),
    }


def smoke(workdir: str) -> None:
    """CI smoke: ~1M-edge chunked build == in-memory build, end to end."""
    scale, ef, chunk = 16, 16, 1 << 17  # 2^16 nodes, ~1M raw edges
    out = os.path.join(workdir, "smoke")
    build_csr(rmat_chunks(scale, ef, seed=7, chunk_edges=chunk), out,
              n=1 << scale, chunk_edges=chunk, tmp_dir=workdir)
    g_disk = CSRGraph.load(out, mmap=True)
    edges = np.concatenate(list(rmat_chunks(scale, ef, seed=7, chunk_edges=chunk)))
    g_mem = CSRGraph.from_edges(1 << scale, edges)
    assert np.array_equal(np.asarray(g_disk.indptr), g_mem.indptr)
    assert np.array_equal(np.asarray(g_disk.adj), g_mem.adj)
    r_disk = decompose(g_disk, "semicore*", "batch")
    r_mem = decompose(g_mem, "semicore*", "batch")
    assert np.array_equal(r_disk.core, r_mem.core)
    print(f"out-of-core smoke OK: n={g_disk.n:,} m={g_disk.m:,} "
          f"kmax={r_disk.kmax} (disk == memory)")


def main() -> None:
    from repro.runtime import enable_compile_cache

    enable_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small graph (CI-friendly); skips the 10M-edge build")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke only: ~1M-edge disk-vs-memory check")
    args = ap.parse_args()

    workdir = tempfile.mkdtemp(prefix="bench_ooc_")
    try:
        if args.smoke:
            smoke(workdir)
            return
        if args.quick:
            scale, ef, chunk = 16, 16, 1 << 17
        else:
            # 2M nodes, 16.8M raw edges, 1M-edge chunks: scratch is 1/16 of
            # the stream, so the O(chunk) bound is visibly decoupled from m
            scale, ef, chunk = 21, 8, 1 << 20
        result = {"mode": "quick" if args.quick else "full"}
        print(f"building 2^{scale} x {ef} R-MAT out of core ...")
        result["build"] = bench_build(scale, ef, chunk, workdir)
        b = result["build"]
        print(f"  n={b['n']:,} m={b['m']:,} in {b['build_seconds']}s "
              f"({b['edges_per_second']:,} edges/s), peak "
              f"{b['peak_traced_bytes']/1e6:.1f} MB "
              f"(bound {b['memory_bound_bytes']/1e6:.1f} MB)")
        print("checking memmap decomposition == in-memory build ...")
        result["fidelity"] = bench_fidelity(b.pop("graph_dir"), scale, ef, chunk)
        print(f"  kmax={result['fidelity']['kmax']} bit-identical: "
              f"{result['fidelity']['bit_identical_to_inmemory']}")
        print("pool sweep (skip-heavy SemiCore*, seq) ...")
        result["pool_sweep"] = bench_pool_sweep(args.quick)
        for row in result["pool_sweep"]["sweep"]:
            print(f"  pool={row['pool_blocks']:>5}  reads={row['edge_block_reads']}")
        os.makedirs(RESULTS, exist_ok=True)
        path = os.path.join(RESULTS, "outofcore.json")
        with open(path, "w") as f:
            json.dump(result, f, indent=2)
        print(f"wrote {path}")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    main()
