"""CQRS replication benchmark: a single writer under sustained ingest with
K read replicas tailing the WAL and serving queries concurrently.

One ``CoreWriter`` ingests micro-batches (WAL append -> apply -> publish,
snapshot+rotation every few batches); K ``CoreReplica``s poll the WAL on
staggered cadences, replay newly durable batches into their own epoch-view
chains, and serve read bursts between syncs.  A late replica joins mid-run
to exercise the snapshot+tail catch-up protocol, and the periodic rotations
exercise the tailers' re-seek path.

Reports sustained writer updates/s, replica query p50/p99, and the observed
replica-lag distribution (sampled before every sync) into
``results/replication.json``.  Always verifies that every replica is
bit-identical to the writer at the final epoch — same ``core``/``cnt``,
same watermarked query replies.

  PYTHONPATH=src python benchmarks/bench_replication.py --smoke
  REPRO_BENCH_FULL=1 PYTHONPATH=src python benchmarks/bench_replication.py
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "../src"))

from repro.core import decompose  # noqa: E402
from repro.faults import FaultPlan, FaultRule, inject  # noqa: E402
from repro.graph import chung_lu  # noqa: E402
from repro.obs import metrics as obs_metrics  # noqa: E402
from repro.obs.bench import shared_result  # noqa: E402
from repro.stream import CoreReplica, CoreService, mixed_stream  # noqa: E402

RESULTS = os.path.join(os.path.dirname(__file__), "results")


def replica_burst(rep: CoreReplica, rng, num_queries: int) -> list:
    """A read burst against the replica's committed view; per-query walls."""
    walls = []
    kmax = max(int(rep.degeneracy()) - 1, 1)
    n = rep.bg.n
    for _ in range(num_queries // 4):
        for call in (
            lambda: rep.coreness(int(rng.integers(n))),
            lambda: rep.in_kcore(int(rng.integers(n)), kmax),
            lambda: rep.top_k(100),
            lambda: rep.kcore_members(kmax),
        ):
            t0 = time.perf_counter()
            call()
            walls.append(time.perf_counter() - t0)
    return walls


def main() -> None:
    from repro.runtime import enable_compile_cache

    enable_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="1 writer + 2 replicas, bounded-lag assertion (CI)")
    ap.add_argument("--replicas", type=int, default=None)
    ap.add_argument("--wal-append-latency-ms", type=float, default=0.0,
                    help="inject this much latency into every WAL append "
                         "(seeded FaultPlan); the lag and bit-identity "
                         "gates must hold with slow appends too")
    args = ap.parse_args()
    full = os.environ.get("REPRO_BENCH_FULL") == "1" and not args.smoke

    if full:
        n, m, num_updates, batch, replicas = 30_000, 200_000, 10_000, 200, 4
        snapshot_every, queries_per_burst = 12, 400
    elif args.smoke:
        n, m, num_updates, batch, replicas = 2_000, 8_000, 600, 60, 2
        snapshot_every, queries_per_burst = 4, 80
    else:
        n, m, num_updates, batch, replicas = 10_000, 60_000, 3_000, 150, 3
        snapshot_every, queries_per_burst = 6, 200
    if args.replicas is not None:
        replicas = args.replicas
    # replica r syncs every (r + 2) batches: staggered cadences make the lag
    # distribution non-trivial and bound it by the slowest cadence.
    cadences = [r + 2 for r in range(replicas)]

    g = chung_lu(n, m, seed=1)
    ops, _ = mixed_stream(g, num_updates, seed=2)
    chunks = [ops[i:i + batch] for i in range(0, len(ops), batch)]
    rng = np.random.default_rng(3)

    plan = None
    fault_ctx = contextlib.nullcontext()
    if args.wal_append_latency_ms > 0:
        plan = FaultPlan([FaultRule("wal.append", "latency", every=1,
                                    arg=args.wal_append_latency_ms / 1e3)])
        fault_ctx = inject(plan)

    with fault_ctx, tempfile.TemporaryDirectory() as tmp:
        wal = os.path.join(tmp, "wal.jsonl")
        snaps = os.path.join(tmp, "snaps")
        writer = CoreService(g, wal_path=wal, snapshot_dir=snaps,
                             snapshot_every=snapshot_every)
        writer.snapshot()  # epoch-0 snapshot so replicas can bootstrap
        obs_snap = obs_metrics.get_registry().snapshot()

        t0 = time.perf_counter()
        reps = [CoreReplica(snapshot_dir=snaps, wal_path=wal, replica_id=r)
                for r in range(replicas)]
        bootstrap_s = time.perf_counter() - t0

        late_at = len(chunks) // 2  # joins mid-run: snapshot+tail catch-up
        lag_samples: list[int] = []
        query_walls: list[float] = []
        update_s = sync_s = query_s = 0.0
        for b, chunk in enumerate(chunks):
            t0 = time.perf_counter()
            writer.ingest(chunk)
            update_s += time.perf_counter() - t0
            if b == late_at:
                t0 = time.perf_counter()
                reps.append(CoreReplica(snapshot_dir=snaps, wal_path=wal,
                                        replica_id=len(reps)))
                cadences.append(2)
                sync_s += time.perf_counter() - t0
            for rep, cadence in zip(reps, cadences):
                lag_samples.append(rep.lag(writer.epoch))
                if (b + 1) % cadence == 0:
                    t0 = time.perf_counter()
                    rep.sync()
                    sync_s += time.perf_counter() - t0
                    t0 = time.perf_counter()
                    query_walls.extend(
                        replica_burst(rep, rng, queries_per_burst))
                    query_s += time.perf_counter() - t0

        # drain every replica to the writer's tip
        t0 = time.perf_counter()
        for rep in reps:
            rep.sync()
        sync_s += time.perf_counter() - t0
        delta = obs_metrics.get_registry().delta(obs_snap)

        # correctness gates ------------------------------------------------
        final = writer.bg.materialize()
        ref = decompose(final, "semicore*", "batch")
        np.testing.assert_array_equal(writer.maintainer.core, ref.core)
        all_nodes = np.arange(n)
        wm_core = writer.coreness(all_nodes)
        for rep in reps:
            assert rep.epoch == writer.epoch, (rep.epoch, writer.epoch)
            assert rep.lag(writer.epoch) == 0
            np.testing.assert_array_equal(rep.maintainer.core,
                                          writer.maintainer.core)
            np.testing.assert_array_equal(rep.maintainer.cnt,
                                          writer.maintainer.cnt)
            r_core = rep.coreness(all_nodes)  # bit-identical watermarked reply
            np.testing.assert_array_equal(r_core, wm_core)
            assert r_core.epoch == wm_core.epoch == writer.epoch
            np.testing.assert_array_equal(rep.top_k(100), writer.top_k(100))
            assert int(rep.degeneracy()) == int(writer.degeneracy())
        if args.smoke:  # bounded lag: never worse than the slowest cadence
            assert max(lag_samples) <= max(cadences) + 1, max(lag_samples)

        applied = sum(
            s.num_applied_deletes + s.num_applied_inserts
            for s in writer.batch_log)
        qw = np.asarray(query_walls)
        lags = np.asarray(lag_samples)
        s = obs_metrics.sum_by_name
        rows = {
            "n": n, "m": m, "num_updates": num_updates, "batch": batch,
            "replicas": len(reps), "cadences": cadences,
            "epochs": writer.epoch,
            "writer_updates_per_s": applied / update_s,
            "writer_rotations": writer.wal.rotations,
            "replica_bootstrap_s": bootstrap_s,
            "replica_sync_s_total": sync_s,
            "replica_batches_applied": int(
                s(delta, "repro_replica_batches_applied_total")),
            "replica_rotations_detected": sum(
                r.tailer.rotations_detected for r in reps),
            "replica_bootstraps": sum(r.bootstraps for r in reps),
            "queries_served": len(qw),
            "query_qps": len(qw) / query_s if query_s else 0.0,
            "query_p50_us": float(np.percentile(qw, 50) * 1e6),
            "query_p99_us": float(np.percentile(qw, 99) * 1e6),
            "lag_samples": len(lags),
            "lag_mean": float(lags.mean()),
            "lag_p50": float(np.percentile(lags, 50)),
            "lag_p95": float(np.percentile(lags, 95)),
            "lag_max": int(lags.max()),
            "obs": shared_result("replication/writer+replicas",
                                 update_s + sync_s + query_s, delta),
        }
        rows["wal_append_latency_ms"] = args.wal_append_latency_ms
        rows["faults_injected_total"] = plan.total_injected if plan else 0
        rows["faults_injected"] = (
            {f"{op}/{kind}": cnt for (op, kind), cnt in plan.injected.items()}
            if plan else {})
        if plan is not None:  # every append was slowed, and all were counted
            assert plan.total_injected == writer.wal.appends, \
                (plan.total_injected, writer.wal.appends)
        writer.close()

    print("name,us_per_call,derived")
    print(f"replication/ingest,{update_s / max(applied, 1) * 1e6:.1f},"
          f"updates_per_s={rows['writer_updates_per_s']:.0f};"
          f"rotations={rows['writer_rotations']}")
    print(f"replication/query,{qw.mean() * 1e6:.1f},"
          f"qps={rows['query_qps']:.0f};p50_us={rows['query_p50_us']:.1f};"
          f"p99_us={rows['query_p99_us']:.1f}")
    print(f"replication/lag,{rows['lag_mean']:.2f},"
          f"p95={rows['lag_p95']:.1f};max={rows['lag_max']};"
          f"bootstraps={rows['replica_bootstraps']}")
    if plan is not None:
        print(f"replication/faults,{rows['faults_injected_total']},"
              f"wal_append_latency_ms={args.wal_append_latency_ms:g}")

    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "replication.json"), "w") as f:
        json.dump(rows, f, indent=1)
    print(f"# verified: {len(reps)} replicas bit-identical to the writer at "
          f"epoch {rows['epochs']} (core, cnt, watermarked replies) under "
          f"{num_updates} streamed updates", file=sys.stderr)


if __name__ == "__main__":
    main()
