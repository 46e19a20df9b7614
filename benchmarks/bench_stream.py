"""Streaming core service benchmark: sustained updates/s + query QPS.

A mixed workload on a Chung–Lu graph: micro-batches of edge inserts/deletes
ingested through ``CoreService`` interleaved with bursts of read queries
(coreness lookups, k-core membership, top-k) against the committed epoch
view.  Reports updates/s, query QPS, edge-block reads per batch, cache hit
rate, and the cost of a WAL+snapshot recovery vs. a cold decomposition.
Always verifies the streamed ``core`` against ``decompose`` on the final
graph.

  PYTHONPATH=src python benchmarks/bench_stream.py --quick
  REPRO_BENCH_FULL=1 PYTHONPATH=src python benchmarks/bench_stream.py
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "../src"))

from repro.core import CoreMaintainer, decompose  # noqa: E402
from repro.graph import chung_lu  # noqa: E402
from repro.graph.updates import BufferedGraph  # noqa: E402
from repro.obs import metrics as obs_metrics  # noqa: E402
from repro.obs.bench import shared_result  # noqa: E402
from repro.runtime import Settings  # noqa: E402
from repro.stream import (CoreService, CoreWriter, Overloaded,  # noqa: E402
                          UpdateBatch, mixed_stream)

RESULTS = os.path.join(os.path.dirname(__file__), "results")

#: maint-scaling cell (PR 10 acceptance): sustained updates/s, parallel
#: grouped settle vs the serial oracle, as a function of micro-batch size.
#: The sizes are fixed — the parallel win needs a graph large enough that
#: the serial warm settle's full-graph passes dominate, so --quick only
#: trims repeats, never the graph.
MAINT_CELL = dict(n=10_000, m=60_000, seed=3)
MAINT_BATCH_SIZES = (16, 64, 128)
#: the gate: at batch size 64 on xla the parallel settle must sustain at
#: least this multiple of the serial oracle's updates/s (ISSUE acceptance
#: asks >= 2x; measured ~2.7x on the reference runner).
MAINT_GATE_BATCH = 64
MAINT_GATE_SPEEDUP = 2.0


def _maint_batches(n, live, rng, bsz, count):
    """Deterministic mixed micro-batches over a shared live-edge set."""
    out = []
    for _ in range(count):
        ops = []
        for _ in range(bsz):
            if rng.random() < 0.5 and live:
                e = sorted(live)[int(rng.integers(len(live)))]
                live.discard(e)
                ops.append(("-",) + e)
            else:
                while True:
                    u, v = int(rng.integers(n)), int(rng.integers(n))
                    e = (min(u, v), max(u, v))
                    if u != v and e not in live:
                        break
                live.add(e)
                ops.append(("+",) + e)
        out.append(UpdateBatch.from_wire(ops))
    return out


def run_maint_scaling(quick: bool) -> tuple[dict, int]:
    """Sustained-updates/s-vs-batch-size cell + the >=2x trajectory gate.

    For each batch size, the same deterministic update stream is applied
    twice from the same initial graph — once through the parallel grouped
    settle, once through the serial oracle (``parallel_maint=False``) —
    and the two must land bit-identical (core and cnt).  Reports medians,
    p99 settle latency and the parallel/serial updates-per-second ratio;
    returns exit code 1 if the gate batch size misses the speedup floor.
    The ratio is same-machine, so the gate is machine-speed independent.
    """
    n, m = MAINT_CELL["n"], MAINT_CELL["m"]
    warmup, repeats = (2, 6) if quick else (2, 10)
    rows = []
    failures = 0
    for bsz in MAINT_BATCH_SIZES:
        walls = {}
        state = {}
        for parallel in (True, False):
            g = chung_lu(n, m, seed=MAINT_CELL["seed"])
            live = set(map(tuple, np.sort(g.edge_list(), axis=1)))
            batches = _maint_batches(
                n, live, np.random.default_rng(5), bsz, warmup + repeats)
            mt = CoreMaintainer(
                BufferedGraph(g),
                settings=Settings(backend="xla", parallel_maint=parallel))
            times = []
            for i, batch in enumerate(batches):
                t0 = time.perf_counter()
                mt.apply(batch)
                dt = time.perf_counter() - t0
                if i >= warmup:  # skip jit/compile warmup batches
                    times.append(dt)
            walls[parallel] = np.asarray(times)
            state[parallel] = (mt.core.copy(), mt.cnt.copy())
        # the differential contract, re-checked inside the bench
        assert np.array_equal(state[True][0], state[False][0]), \
            f"parallel core != serial core at batch={bsz}"
        assert np.array_equal(state[True][1], state[False][1]), \
            f"parallel cnt != serial cnt at batch={bsz}"
        par_med = float(np.median(walls[True]))
        ser_med = float(np.median(walls[False]))
        row = {
            "batch": bsz,
            "parallel_updates_per_s": bsz / par_med,
            "serial_updates_per_s": bsz / ser_med,
            "speedup": ser_med / par_med,
            "parallel_p50_ms": par_med * 1e3,
            "parallel_p99_ms": float(np.percentile(walls[True], 99)) * 1e3,
            "serial_p50_ms": ser_med * 1e3,
            "serial_p99_ms": float(np.percentile(walls[False], 99)) * 1e3,
        }
        gated = bsz == MAINT_GATE_BATCH
        row["gated"] = gated
        if gated and row["speedup"] < MAINT_GATE_SPEEDUP:
            failures += 1
        rows.append(row)
    return {"cell": dict(MAINT_CELL), "rows": rows,
            "gate_batch": MAINT_GATE_BATCH,
            "gate_speedup": MAINT_GATE_SPEEDUP}, failures


def run_overload(quick: bool) -> dict:
    """Admission-control cell (DESIGN.md §17): oversized bursts against a
    budgeted writer.  Bursts cycle through the three admission stages —
    under the soft budget (apply now), between soft and hard (bounded-
    staleness deferral) and over the hard budget (typed ``Overloaded``
    shed) — and the cell reports accepted-updates/s, the shed rate and the
    p99 admission latency.  Ends with the usual correctness gate: after a
    draining snapshot the streamed ``core`` must equal a fresh decompose.
    """
    if quick:
        n, m, budget, bursts = 3_000, 12_000, 240, 45
    else:
        n, m, budget, bursts = 10_000, 60_000, 400, 90
    # stage-0 / stage-1 / shed burst sizes, cycled in that order
    sizes = [budget // 3, (budget * 4) // 5, (budget * 3) // 2]
    g = chung_lu(n, m, seed=1)
    ops, _ = mixed_stream(g, sum(sizes) * (bursts // 3 + 1), seed=2)

    with tempfile.TemporaryDirectory() as tmp:
        w = CoreWriter(
            g,
            wal_path=os.path.join(tmp, "wal.jsonl"),
            snapshot_dir=os.path.join(tmp, "snaps"),
            admission_budget=budget,
            admission_soft_ratio=0.5,
            admission_max_defer=4,
        )
        walls = []
        offered = accepted_updates = deferred_batches = 0
        pos = 0
        ingest_s = 0.0
        for b in range(bursts):
            size = sizes[b % len(sizes)]
            chunk = ops[pos : pos + size]
            pos += size
            offered += len(chunk)
            t0 = time.perf_counter()
            try:
                stats = w.ingest(chunk)
                wall = time.perf_counter() - t0
                accepted_updates += len(chunk)
                deferred_batches += stats.deferred
            except Overloaded:
                wall = time.perf_counter() - t0
            walls.append(wall)
            ingest_s += wall
        w.snapshot()  # drain the pending pool: epoch catches the WAL tip
        assert w.epoch == w._wal_tip
        health = w.health()
        assert health["status"] == "ok", health

        final = w.bg.materialize()
        ref = decompose(final, "semicore*", "batch")
        assert np.array_equal(w.maintainer.core, ref.core), \
            "overloaded stream != decompose"

        adm = w.admission.state()
        wq = np.asarray(walls)
        shed = adm["rejected_updates"]
        row = {
            "n": n, "m": m, "budget": budget, "bursts": bursts,
            "burst_sizes": sizes,
            "offered_updates": offered,
            "accepted_updates": accepted_updates,
            "accepted_updates_per_s": accepted_updates / ingest_s,
            "shed_updates": shed,
            "shed_batches": adm["rejected_batches"],
            "shed_rate": shed / max(offered, 1),
            "deferred_batches": deferred_batches,
            "coalesced_updates": adm["coalesced"],
            "admission_p50_ms": float(np.percentile(wq, 50) * 1e3),
            "admission_p99_ms": float(np.percentile(wq, 99) * 1e3),
            "final_epoch": int(w.epoch),
        }
        w.close()
    return row


def query_burst(svc: CoreService, rng, num_queries: int) -> int:
    """A read burst against the current epoch; returns #queries served."""
    kmax = svc.degeneracy()
    served = 1  # the degeneracy lookup above is a served query too
    for _ in range(num_queries // 4):
        svc.coreness(int(rng.integers(svc.bg.n)))
        svc.in_kcore(int(rng.integers(svc.bg.n)), max(kmax - 1, 1))
        svc.top_k(100)
        svc.kcore_members(max(kmax - 1, 1))
        served += 4
    return served


def main() -> None:
    from repro.runtime import enable_compile_cache

    enable_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small sizes for CI smoke runs")
    ap.add_argument("--overload", action="store_true",
                    help="admission-backpressure cell only: oversized "
                         "bursts against a budgeted writer")
    ap.add_argument("--maint-scaling", action="store_true",
                    help="sustained updates/s vs batch size: parallel "
                         "grouped settle vs serial oracle (gated)")
    args = ap.parse_args()
    full = os.environ.get("REPRO_BENCH_FULL") == "1" and not args.quick

    if args.maint_scaling:
        cell, failures = run_maint_scaling(quick=args.quick or not full)
        print("name,us_per_call,derived")
        for row in cell["rows"]:
            print(f"stream/maint_scaling/batch={row['batch']},"
                  f"{row['parallel_p50_ms'] * 1e3:.1f},"
                  f"updates_per_s={row['parallel_updates_per_s']:.0f};"
                  f"serial_updates_per_s={row['serial_updates_per_s']:.0f};"
                  f"speedup={row['speedup']:.2f};"
                  f"p99_settle_ms={row['parallel_p99_ms']:.1f}"
                  f"{';GATED' if row['gated'] else ''}")
        os.makedirs(RESULTS, exist_ok=True)
        path = os.path.join(RESULTS, "stream.json")
        merged = {}
        if os.path.exists(path):  # ride alongside the mixed-workload rows
            with open(path) as f:
                merged = json.load(f)
        merged["maint_scaling"] = cell
        with open(path, "w") as f:
            json.dump(merged, f, indent=1)
        if failures:
            gated = next(r for r in cell["rows"] if r["gated"])
            print(f"TRAJECTORY FAIL: parallel settle speedup "
                  f"{gated['speedup']:.2f}x at batch={gated['batch']} is "
                  f"below the {MAINT_GATE_SPEEDUP:.1f}x floor",
                  file=sys.stderr)
            sys.exit(1)
        print("# verified: parallel settle bit-identical to serial oracle "
              "at every batch size; gate passed", file=sys.stderr)
        return

    if args.overload:
        row = run_overload(quick=args.quick or not full)
        print("name,us_per_call,derived")
        print(f"stream/overload,{row['admission_p50_ms'] * 1e3:.1f},"
              f"accepted_per_s={row['accepted_updates_per_s']:.0f};"
              f"shed_rate={row['shed_rate']:.3f};"
              f"p99_admission_ms={row['admission_p99_ms']:.2f}")
        os.makedirs(RESULTS, exist_ok=True)
        path = os.path.join(RESULTS, "stream.json")
        merged = {}
        if os.path.exists(path):  # ride alongside the mixed-workload rows
            with open(path) as f:
                merged = json.load(f)
        merged["overload"] = row
        with open(path, "w") as f:
            json.dump(merged, f, indent=1)
        print(f"# verified: overloaded stream == decompose(final) with "
              f"{row['shed_batches']} shed and {row['deferred_batches']} "
              f"deferred batches", file=sys.stderr)
        return

    if full:  # the ISSUE acceptance workload
        n, m, num_updates, batch = 30_000, 200_000, 10_000, 200
    elif args.quick:
        n, m, num_updates, batch = 3_000, 12_000, 600, 100
    else:
        n, m, num_updates, batch = 10_000, 60_000, 3_000, 150
    queries_per_batch = 200

    g = chung_lu(n, m, seed=1)
    ops, _ = mixed_stream(g, num_updates, seed=2)
    rng = np.random.default_rng(3)

    with tempfile.TemporaryDirectory() as tmp:
        svc = CoreService(
            g,
            wal_path=os.path.join(tmp, "wal.jsonl"),
            snapshot_dir=os.path.join(tmp, "snaps"),
        )
        # telemetry baseline *after* construction: the delta below is pure
        # workload cost (initial decompose + WAL truncate excluded)
        obs_snap = obs_metrics.get_registry().snapshot()
        num_batches = -(-len(ops) // batch)
        snapshot_at = max((2 * num_batches) // 3, 1)  # leaves a WAL tail
        update_s = query_s = 0.0
        queries = 0
        for b, i in enumerate(range(0, len(ops), batch)):
            t0 = time.perf_counter()
            svc.ingest(ops[i : i + batch])
            update_s += time.perf_counter() - t0
            if b + 1 == snapshot_at:
                svc.snapshot()
            t0 = time.perf_counter()
            queries += query_burst(svc, rng, queries_per_batch)
            query_s += time.perf_counter() - t0

        # workload numbers now come from the telemetry registry: the ingest
        # latency histogram supplies the percentiles and the service
        # counters supply the served-query and io totals.  The delta is taken
        # *before* the correctness-gate decompose below so it covers exactly
        # the streamed workload.
        delta = obs_metrics.get_registry().delta(obs_snap)

        # correctness gate: the stream must equal a fresh decomposition
        final = svc.bg.materialize()
        ref = decompose(final, "semicore*", "batch")
        assert np.array_equal(svc.maintainer.core, ref.core), "stream != decompose"

        log = svc.batch_log
        stats = svc.service_stats()
        applied = stats["updates_applied"]
        cache_total = stats["cache_hits"] + stats["cache_misses"]
        s = obs_metrics.sum_by_name
        ingest_hist = obs_metrics.get_registry().get(
            "repro_service_ingest_seconds")
        queries_served = int(s(delta, "repro_service_queries_total"))
        io_reads = int(s(delta, "repro_io_edge_block_reads_total"))
        nt_reads = int(s(delta, "repro_io_node_table_reads_total"))
        if obs_metrics.obs_enabled():  # registry must reconcile exactly
            assert queries_served == queries, (queries_served, queries)
            assert io_reads == sum(x.edge_block_reads for x in log), io_reads
            assert nt_reads == sum(x.node_table_reads for x in log), nt_reads
        else:  # silent registry: fall back to the hand-tracked numbers
            queries_served = queries
            io_reads = sum(x.edge_block_reads for x in log)
            nt_reads = sum(x.node_table_reads for x in log)
        rows = {
            "n": n, "m": m, "num_updates": num_updates, "batch": batch,
            "epochs": svc.epoch,
            "updates_per_s": applied / update_s,
            "query_qps": queries_served / query_s,
            "edge_block_reads_per_batch": io_reads / max(len(log), 1),
            "node_table_reads_per_batch": nt_reads / max(len(log), 1),
            "node_computations_per_update": float(
                sum(x.node_computations for x in log) / max(applied, 1)
            ),
            "p50_batch_ms": ingest_hist.quantile(0.50) * 1e3,
            "p99_batch_ms": ingest_hist.quantile(0.99) * 1e3,
            "cache_hit_rate": stats["cache_hits"] / max(cache_total, 1),
            "degeneracy": stats["degeneracy"],
            "obs": shared_result("stream/mixed-workload",
                                 update_s + query_s, delta),
        }

        # recovery cost vs a cold decomposition of the final graph
        svc.close()
        t0 = time.perf_counter()
        _, rec = CoreService.recover(
            wal_path=os.path.join(tmp, "wal.jsonl"),
            snapshot_dir=os.path.join(tmp, "snaps"),
        )
        rows["recovery_s"] = time.perf_counter() - t0
        rows["recovery_replayed_updates"] = rec.replayed_updates
        rows["recovery_settle_computations"] = rec.settle_node_computations
        rows["cold_decompose_computations"] = ref.node_computations

    print("name,us_per_call,derived")
    print(f"stream/ingest,{update_s / max(applied, 1) * 1e6:.1f},"
          f"updates_per_s={rows['updates_per_s']:.0f};"
          f"io_blocks_per_batch={rows['edge_block_reads_per_batch']:.1f};"
          f"p99_batch_ms={rows['p99_batch_ms']:.1f}")
    print(f"stream/query,{query_s / max(queries, 1) * 1e6:.1f},"
          f"qps={rows['query_qps']:.0f};"
          f"cache_hit_rate={rows['cache_hit_rate']:.3f}")
    print(f"stream/recovery,{rows['recovery_s'] * 1e6:.1f},"
          f"settle_comp={rows['recovery_settle_computations']};"
          f"cold_comp={rows['cold_decompose_computations']}")

    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, "stream.json")
    if os.path.exists(path):  # keep the overload / maint_scaling cells
        with open(path) as f:
            prior = json.load(f)
        for key in ("overload", "maint_scaling"):
            if key in prior:
                rows.setdefault(key, prior[key])
    with open(path, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"# verified: streamed core == decompose(final) on n={n}, "
          f"m={final.m}, {num_updates} updates", file=sys.stderr)


if __name__ == "__main__":
    main()
