"""Backend matrix benchmark: one superstep core, four compute substrates.

Runs every batch-schedule algorithm on every compute backend (DESIGN.md §11,
§13) over the same graphs and records pass counts, wall time (cold = first
call including jit compiles, warm = steady state on the device-resident
caches), jit trace counts, planner I/O, and the pallas backend's kernel-block
skip counts to ``benchmarks/results/backends.json``.  All backends must
converge through identical passes to the identical core array — the script
asserts it.

Two graphs: the PR 3 comparison cell (n=4k, the history in CHANGES.md) and a
``large`` ≥200k-directed-edge cell (numpy vs xla vs pallas vs shard) where
the device-resident speedup-vs-numpy is the headline number.

Perf-trajectory gate (scripts/ci.sh):

    python benchmarks/bench_backends.py --emit-trajectory   # refresh baseline
    python benchmarks/bench_backends.py --check-trajectory  # CI regression gate
    python benchmarks/bench_backends.py --summary           # markdown table

``--emit-trajectory`` measures the trajectory cell (warm walls best-of-3,
cold walls, jit-trace counts, numpy-normalized ratios) and writes/updates the
section for the current device count in ``BENCH_backends.json`` at the repo
root — the committed baseline.  ``--check-trajectory`` re-measures and fails
on a warm-wall regression beyond the tolerance band or on *any* jit-trace
count increase (the O(passes)-retrace regression), replacing the old one-off
"xla ≤ 40× numpy + 2s" smoke hack.  Warm walls are compared as ratios to the
same run's numpy wall, so the gate is machine-speed independent; the band is
``ratio <= 1.5 × baseline_ratio + 1.0`` per backend (summed over the three
algorithms to damp small-cell noise).

Usage:
    PYTHONPATH=src python benchmarks/bench_backends.py [--quick]
    REPRO_BACKEND=shard PYTHONPATH=src python benchmarks/bench_backends.py --smoke
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import resident  # noqa: E402
from repro.core.imcore import imcore_bz  # noqa: E402
from repro.core.semicore import decompose  # noqa: E402
from repro.graph import chung_lu  # noqa: E402
from repro.obs import metrics as obs_metrics  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402
from repro.obs.bench import shared_result  # noqa: E402

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
RESULTS = os.path.join(os.path.dirname(__file__), "results")
TRAJECTORY_BASELINE = os.path.join(REPO_ROOT, "BENCH_backends.json")
TRAJECTORY_CURRENT = os.path.join(RESULTS, "BENCH_backends_current.json")
ALGORITHMS = ("semicore", "semicore+", "semicore*")
BACKENDS = ("numpy", "xla", "pallas", "shard")

# trajectory gate: per-backend warm-wall ratio vs numpy (summed over the
# three algorithms) may grow at most BAND x the committed baseline ratio
# plus FLOOR; jit-trace counts may never grow at all.  The large cell rides
# along with fewer warm repeats (walls are seconds, not milliseconds) and
# without shard (the small cell already gates it; the full --bench matrix
# still records it) — its job is gating the pallas fused-superstep ratio at
# a size where per-kernel overheads can't hide.
TRAJECTORY_CELL = dict(n=1200, m=4800, seed=6, block_edges=128)
TRAJECTORY_LARGE_CELL = dict(n=25_000, m=110_000, seed=8, block_edges=4096)
TRAJECTORY_LARGE_BACKENDS = ("numpy", "xla", "pallas")
TRAJECTORY_WALL_BAND = 1.5
TRAJECTORY_RATIO_FLOOR = 1.0
TRAJECTORY_WARM_REPEATS = 3


def _timed(g, algo, backend, block_edges, warm_repeats: int = 1):
    """(cold_s, warm_s, jit_traces, result, obs_delta) for one config.

    ``obs_delta`` is the telemetry-registry delta around the *last* warm run
    (one full decompose), the registry-sourced mirror of the DecompResult
    accounting — reconciled loudly by the callers.
    """
    t0 = resident.trace_count()
    w0 = time.perf_counter()
    r = decompose(g, algo, "batch", block_edges=block_edges, backend=backend)
    cold = time.perf_counter() - w0
    traces = resident.trace_count() - t0
    warm = float("inf")
    delta = {}
    for i in range(max(1, warm_repeats)):
        snap = obs_metrics.get_registry().snapshot()
        w1 = time.perf_counter()
        r2 = decompose(g, algo, "batch", block_edges=block_edges,
                       backend=backend)
        wall = time.perf_counter() - w1
        if wall < warm or not delta:
            delta = obs_metrics.get_registry().delta(snap)
        warm = min(warm, wall)
        assert np.array_equal(r.core, r2.core)
    return cold, warm, traces, r, delta


def _reconcile(delta: dict, r, where) -> dict:
    """Registry-sourced I/O numbers for one decompose, asserted == DecompResult.

    This is the migration contract: benches now *source* their io columns
    from the metrics registry, and the old hand-tracked DecompResult numbers
    become the cross-check instead of the source.  Under ``REPRO_OBS=0`` the
    registry is silent, so the DecompResult numbers are used directly.
    """
    if not obs_metrics.obs_enabled():
        return {
            "edge_block_reads": r.edge_block_reads,
            "node_table_reads": r.node_table_reads,
            "iterations": r.iterations,
            "kernel_blocks_active": r.kernel_blocks_active,
            "kernel_blocks_skipped": r.kernel_blocks_skipped,
        }
    s = obs_metrics.sum_by_name
    out = {
        "edge_block_reads": int(s(delta, "repro_io_edge_block_reads_total")),
        "node_table_reads": int(s(delta, "repro_io_node_table_reads_total")),
        "iterations": int(s(delta, "repro_engine_passes_total")),
        "kernel_blocks_active": int(
            s(delta, "repro_kernel_blocks_active_total")),
        "kernel_blocks_skipped": int(
            s(delta, "repro_kernel_blocks_skipped_total")),
    }
    assert out["edge_block_reads"] == r.edge_block_reads, \
        (where, out["edge_block_reads"], r.edge_block_reads)
    assert out["node_table_reads"] == r.node_table_reads, \
        (where, out["node_table_reads"], r.node_table_reads)
    assert out["iterations"] == r.iterations, \
        (where, out["iterations"], r.iterations)
    assert out["kernel_blocks_active"] == r.kernel_blocks_active, \
        (where, out["kernel_blocks_active"], r.kernel_blocks_active)
    assert out["kernel_blocks_skipped"] == r.kernel_blocks_skipped, \
        (where, out["kernel_blocks_skipped"], r.kernel_blocks_skipped)
    return out


def smoke() -> None:
    """CI backend-matrix smoke: decompose under the REPRO_BACKEND env default
    and check against the BZ oracle (scripts/ci.sh runs one per backend).
    Wall-clock regressions are gated separately by --check-trajectory."""
    backend = os.environ.get("REPRO_BACKEND", "numpy")
    g = chung_lu(400, 1600, seed=3)
    expect = imcore_bz(g)
    wall = 0.0
    for algo in ALGORITHMS:
        rn = decompose(g, algo, "batch", block_edges=64, backend="numpy")
        assert np.array_equal(rn.core, expect), ("numpy", algo)
        r = decompose(g, algo, "batch", block_edges=64)  # backend from env
        t0 = time.perf_counter()
        r = decompose(g, algo, "batch", block_edges=64)  # warm: jits cached
        wall += time.perf_counter() - t0
        assert np.array_equal(r.core, expect), (backend, algo)
        assert r.backend == backend, (r.backend, backend)
        # identical passes + planner trace is the layer's core invariant
        assert r.iterations == rn.iterations, (backend, algo)
        assert r.edge_block_reads == rn.edge_block_reads, (backend, algo)
    skipped = r.kernel_blocks_skipped  # last run: semicore*
    print(f"backend smoke OK: backend={backend} kmax={r.kmax} "
          f"iters={r.iterations} io_blocks={r.edge_block_reads} "
          f"kernel_blocks_skipped={skipped} num_shards={r.num_shards} "
          f"wall={wall:.3f}s")
    if backend == "pallas":
        assert skipped > 0, "SemiCore* frontier shrinkage must skip blocks"
    if backend == "shard":
        import jax

        assert r.num_shards == len(jax.devices()), r.num_shards


def _bench_graph(g, block_edges, backends, label):
    rows = []
    cores: dict = {}
    warm_numpy: dict = {}
    for backend in backends:
        for algo in ALGORITHMS:
            cold, warm, traces, r, delta = _timed(g, algo, backend,
                                                  block_edges)
            cores.setdefault(algo, r.core)
            assert np.array_equal(r.core, cores[algo]), (backend, algo)
            if backend == "numpy":
                warm_numpy[algo] = warm
            # io columns come from the telemetry registry, cross-checked
            # against the DecompResult accounting they mirror
            rec = _reconcile(delta, r, (label, backend, algo))
            row = {
                "backend": backend,
                "algorithm": algo,
                "wall_seconds": round(warm, 4),
                "wall_seconds_cold": round(cold, 4),
                "jit_traces": traces,
                "speedup_vs_numpy": round(warm_numpy[algo] / warm, 2),
                "iterations": rec["iterations"],
                "node_computations": r.node_computations,
                "edge_block_reads": rec["edge_block_reads"],
                "node_table_reads": rec["node_table_reads"],
                "kernel_blocks_active": rec["kernel_blocks_active"],
                "kernel_blocks_skipped": rec["kernel_blocks_skipped"],
                "num_shards": r.num_shards,
                "shard_pad_edges": r.shard_pad_edges,
            }
            rows.append(row)
            print(f"[{label}] {backend:>6} {algo:<10} warm={warm:7.3f}s "
                  f"cold={cold:7.3f}s traces={traces} "
                  f"passes={r.iterations:<3} io={r.edge_block_reads:<5} "
                  f"skipped={r.kernel_blocks_skipped}")
    # identical passes across backends is the layer's core invariant
    by_algo: dict = {}
    for row in rows:
        by_algo.setdefault(row["algorithm"], set()).add(
            (row["iterations"], row["edge_block_reads"],
             row["node_table_reads"]))
    assert all(len(v) == 1 for v in by_algo.values()), by_algo
    return rows


# ============================================================= trajectory
def _trajectory_rows(cell, backends, warm_repeats, label) -> list[dict]:
    g = chung_lu(cell["n"], cell["m"], seed=cell["seed"])
    rows = []
    warm_numpy: dict = {}
    for backend in backends:
        for algo in ALGORITHMS:
            cold, warm, traces, r, delta = _timed(
                g, algo, backend, cell["block_edges"],
                warm_repeats=warm_repeats)
            if backend == "numpy":
                warm_numpy[algo] = warm
            # keep the committed BENCH_backends.json schema byte-compatible:
            # iterations are registry-sourced but the row keys are unchanged
            rec = _reconcile(delta, r, (label, backend, algo))
            rows.append({
                "backend": backend,
                "algorithm": algo,
                "wall_seconds": round(warm, 4),
                "wall_seconds_cold": round(cold, 4),
                "jit_traces": traces,
                "ratio_vs_numpy": round(warm / warm_numpy[algo], 3),
                "speedup_vs_numpy": round(warm_numpy[algo] / warm, 3),
                "iterations": rec["iterations"],
                "num_shards": r.num_shards,
            })
            print(f"[{label}] {backend:>6} {algo:<10} warm={warm:7.3f}s "
                  f"cold={cold:7.3f}s traces={traces}")
    return rows


def _measure_trajectory() -> dict:
    """One trajectory section: the 4-backend × 3-algorithm matrix on the
    trajectory cell (warm walls best-of-N, numpy-normalized ratios) plus the
    3-backend matrix on the large cell (single warm repeat)."""
    import jax

    rows = _trajectory_rows(TRAJECTORY_CELL, BACKENDS,
                            TRAJECTORY_WARM_REPEATS, "traj")
    large_rows = _trajectory_rows(TRAJECTORY_LARGE_CELL,
                                  TRAJECTORY_LARGE_BACKENDS, 1, "traj-large")
    return {
        "device_count": len(jax.devices()),
        "python": f"{sys.version_info[0]}.{sys.version_info[1]}",
        "rows": rows,
        "large_rows": large_rows,
    }


def _backend_aggregate(rows):
    """{backend: (sum warm, sum numpy warm, sum traces)} over the algos."""
    numpy_wall = {r["algorithm"]: r["wall_seconds"] for r in rows
                  if r["backend"] == "numpy"}
    agg: dict = {}
    for r in rows:
        w, nw, t = agg.get(r["backend"], (0.0, 0.0, 0))
        agg[r["backend"]] = (w + r["wall_seconds"],
                             nw + numpy_wall[r["algorithm"]],
                             t + r["jit_traces"])
    return agg


def emit_trajectory() -> None:
    """Measure and write/update this device count's baseline section in the
    repo-root ``BENCH_backends.json`` (commit the result)."""
    section = _measure_trajectory()
    data = {"schema": 1, "cell": TRAJECTORY_CELL, "device_counts": {}}
    if os.path.exists(TRAJECTORY_BASELINE):
        with open(TRAJECTORY_BASELINE) as f:
            data = json.load(f)
    data["cell"] = TRAJECTORY_CELL
    data["large_cell"] = TRAJECTORY_LARGE_CELL
    data.setdefault("device_counts", {})[str(section["device_count"])] = \
        section
    with open(TRAJECTORY_BASELINE, "w") as f:
        json.dump(data, f, indent=2)
        f.write("\n")
    print(f"wrote {TRAJECTORY_BASELINE} "
          f"(device_count={section['device_count']})")


def check_trajectory() -> int:
    """Measure fresh, write the candidate next to the other CI artifacts,
    and gate against the committed baseline."""
    section = _measure_trajectory()
    os.makedirs(RESULTS, exist_ok=True)
    with open(TRAJECTORY_CURRENT, "w") as f:
        json.dump({"schema": 1, "cell": TRAJECTORY_CELL,
                   "large_cell": TRAJECTORY_LARGE_CELL,
                   "device_counts": {str(section["device_count"]): section}},
                  f, indent=2)
        f.write("\n")
    if not os.path.exists(TRAJECTORY_BASELINE):
        print("WARN: no committed BENCH_backends.json baseline; "
              "run --emit-trajectory and commit it", file=sys.stderr)
        return 0
    with open(TRAJECTORY_BASELINE) as f:
        baseline = json.load(f)
    base = baseline.get("device_counts", {}).get(
        str(section["device_count"]))
    if base is None:
        print(f"WARN: baseline has no section for device_count="
              f"{section['device_count']}; skipping the gate",
              file=sys.stderr)
        return 0
    failures = []
    for key, tag in (("rows", "gate"), ("large_rows", "gate-large")):
        if key not in base:
            print(f"WARN: baseline has no {key!r} section; skipping "
                  "(re-emit the baseline to gate it)", file=sys.stderr)
            continue
        cand_agg = _backend_aggregate(section[key])
        base_agg = _backend_aggregate(base[key])
        for backend, (w, nw, traces) in sorted(cand_agg.items()):
            if backend not in base_agg:
                continue
            bw, bnw, btraces = base_agg[backend]
            if traces > btraces:
                failures.append(
                    f"{tag}/{backend}: jit traces grew {btraces} -> "
                    f"{traces} (O(passes)-retrace regression)")
            if backend == "numpy":
                continue  # numpy is the normalizer
            ratio = w / max(nw, 1e-9)
            base_ratio = bw / max(bnw, 1e-9)
            limit = TRAJECTORY_WALL_BAND * base_ratio \
                + TRAJECTORY_RATIO_FLOOR
            status = "ok" if ratio <= limit else "FAIL"
            print(f"[{tag}] {backend:>6} warm-vs-numpy ratio {ratio:6.2f} "
                  f"(baseline {base_ratio:6.2f}, limit {limit:6.2f}) "
                  f"{status}")
            if ratio > limit:
                failures.append(
                    f"{tag}/{backend}: warm-wall ratio {ratio:.2f} exceeds "
                    f"{TRAJECTORY_WALL_BAND}x baseline {base_ratio:.2f} + "
                    f"{TRAJECTORY_RATIO_FLOOR}")
    if failures:
        print("perf-trajectory gate FAILED:", file=sys.stderr)
        for msg in failures:
            print(f"  - {msg}", file=sys.stderr)
        return 1
    print("perf-trajectory gate OK "
          f"(device_count={section['device_count']})")
    return 0


def summary() -> None:
    """Render the backend × algorithm wall-clock table as GitHub-flavored
    markdown (for $GITHUB_STEP_SUMMARY) from the freshest trajectory file."""
    path = TRAJECTORY_CURRENT if os.path.exists(TRAJECTORY_CURRENT) \
        else TRAJECTORY_BASELINE
    if not os.path.exists(path):
        print("(no trajectory data)")
        return
    with open(path) as f:
        data = json.load(f)
    for dc, section in sorted(data.get("device_counts", {}).items()):
        sources = [(data.get("cell", {}), section.get("rows", []))]
        if section.get("large_rows"):
            sources.append((data.get("large_cell", {}),
                            section["large_rows"]))
        for cell, sec_rows in sources:
            print(f"### Backend × algorithm warm wall-clock "
                  f"({dc} device(s), python {section.get('python', '?')}, "
                  f"n={cell.get('n', '?')} cell)\n")
            print("| backend | " + " | ".join(ALGORITHMS) +
                  " | jit traces | speedup vs numpy |")
            print("|---|" + "---|" * (len(ALGORITHMS) + 2))
            by_backend: dict = {}
            for r in sec_rows:
                by_backend.setdefault(r["backend"], {})[r["algorithm"]] = r
            numpy_total = sum(r["wall_seconds"]
                              for r in by_backend.get("numpy", {}).values())
            for backend in BACKENDS:
                rows = by_backend.get(backend)
                if not rows:
                    continue
                walls = " | ".join(
                    f"{rows[a]['wall_seconds']:.3f}s" if a in rows else "-"
                    for a in ALGORITHMS)
                traces = sum(r["jit_traces"] for r in rows.values())
                total_w = sum(r["wall_seconds"] for r in rows.values())
                speed = numpy_total / max(total_w, 1e-9)
                print(f"| {backend} | {walls} | {traces} | {speed:.2f}x |")
            print()


# ================================================================= obs cell
OBS_CELL = dict(n=25_000, m=110_000, seed=8, block_edges=4096)
OBS_OVERHEAD_BAND = 0.05      # instrumented warm wall <= 1.05x base ...
OBS_OVERHEAD_FLOOR_S = 0.05   # ... plus an absolute floor for tiny walls
OBS_WARM_REPEATS = 3


def obs_cell(quick: bool = False) -> int:
    """CI observability leg: the large bench cell with tracing on.

    Writes three artifacts to ``benchmarks/results/``:

    * ``superstep_trace.json`` — Chrome-trace (Perfetto-loadable) timeline of
      every superstep/chunk/prologue span of the instrumented runs;
    * ``metrics.prom`` — the full registry in Prometheus text exposition;
    * ``obs_summary.md`` — markdown summary for ``$GITHUB_STEP_SUMMARY``.

    Gate: the instrumented warm wall must stay within
    ``(1 + OBS_OVERHEAD_BAND) x`` the ``REPRO_OBS=0`` wall (+ an absolute
    floor so sub-100ms cells don't flake).  Returns a process exit code.
    """
    cell = dict(OBS_CELL)
    if quick:
        cell.update(n=3_000, m=13_000, block_edges=512)
    g = chung_lu(cell["n"], cell["m"], seed=cell["seed"])
    algo, backend = "semicore*", "xla"

    def warm_wall(repeats: int = OBS_WARM_REPEATS) -> float:
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            r = decompose(g, algo, "batch",
                          block_edges=cell["block_edges"], backend=backend)
            best = min(best, time.perf_counter() - t0)
        return best

    decompose(g, algo, "batch", block_edges=cell["block_edges"],
              backend=backend)  # jit warm-up, outside both measurements

    prev = os.environ.get(obs_metrics.OBS_ENV_VAR)
    os.environ[obs_metrics.OBS_ENV_VAR] = "0"
    try:
        base = warm_wall()
    finally:
        if prev is None:
            os.environ.pop(obs_metrics.OBS_ENV_VAR, None)
        else:
            os.environ[obs_metrics.OBS_ENV_VAR] = prev

    obs_trace.clear_trace()
    obs_trace.start_trace()
    snap = obs_metrics.get_registry().snapshot()
    instrumented = warm_wall()
    delta = obs_metrics.get_registry().delta(snap)
    obs_trace.stop_trace()

    os.makedirs(RESULTS, exist_ok=True)
    trace_path = os.path.join(RESULTS, "superstep_trace.json")
    obs_trace.get_collector().save(trace_path)
    n_events = len(obs_trace.get_collector().events)
    obs_trace.clear_trace()
    prom_path = os.path.join(RESULTS, "metrics.prom")
    with open(prom_path, "w") as f:
        f.write(obs_metrics.get_registry().to_prometheus())

    result = shared_result(f"backends/obs-cell[{backend}/{algo}]",
                           instrumented, delta,
                           extra={"wall_seconds_base": round(base, 4),
                                  "trace_events": n_events,
                                  "cell": cell})
    limit = (1.0 + OBS_OVERHEAD_BAND) * base + OBS_OVERHEAD_FLOOR_S
    ok = instrumented <= limit
    overhead_pct = 100.0 * (instrumented - base) / max(base, 1e-9)

    md_path = os.path.join(RESULTS, "obs_summary.md")
    s = obs_metrics.sum_by_name
    with open(md_path, "w") as f:
        f.write("### Telemetry cell (instrumented superstep, "
                f"{backend}/{algo}, n={cell['n']})\n\n")
        f.write("| metric | value |\n|---|---|\n")
        f.write(f"| warm wall (REPRO_OBS=0) | {base:.3f}s |\n")
        f.write(f"| warm wall (instrumented + tracing) | "
                f"{instrumented:.3f}s |\n")
        f.write(f"| instrumentation overhead | {overhead_pct:.1f}% "
                f"(limit {100 * OBS_OVERHEAD_BAND:.0f}% + "
                f"{OBS_OVERHEAD_FLOOR_S:.2f}s floor) |\n")
        f.write(f"| passes | "
                f"{int(s(delta, 'repro_engine_passes_total'))} |\n")
        f.write(f"| edge-block reads | "
                f"{int(s(delta, 'repro_io_edge_block_reads_total'))} |\n")
        f.write(f"| bytes read | "
                f"{int(s(delta, 'repro_io_bytes_read_total')):,} |\n")
        f.write(f"| trace events | {n_events} |\n")
        f.write(f"| gate | {'ok' if ok else 'FAIL'} |\n")
    with open(os.path.join(RESULTS, "obs_cell.json"), "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")

    print(f"[obs] base={base:.3f}s instrumented={instrumented:.3f}s "
          f"({overhead_pct:+.1f}%, limit {limit:.3f}s) "
          f"events={n_events} -> {trace_path}")
    if not ok:
        print(f"obs overhead gate FAILED: {instrumented:.3f}s > "
              f"{limit:.3f}s", file=sys.stderr)
        return 1
    print("obs overhead gate OK")
    return 0


def main() -> None:
    from repro.runtime import enable_compile_cache

    enable_compile_cache(os.path.join(os.path.dirname(__file__), ".."))
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small graphs, skip the large cell")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: REPRO_BACKEND env decides the backend")
    ap.add_argument("--emit-trajectory", action="store_true",
                    help="refresh this device count's committed baseline "
                    "section in BENCH_backends.json")
    ap.add_argument("--check-trajectory", action="store_true",
                    help="CI gate: fail on warm-wall or jit-trace regression "
                    "vs the committed baseline")
    ap.add_argument("--summary", action="store_true",
                    help="markdown wall-clock table (for "
                    "$GITHUB_STEP_SUMMARY)")
    ap.add_argument("--obs-cell", action="store_true",
                    help="CI observability leg: traced large cell + "
                    "Prometheus/Chrome-trace artifacts + overhead gate")
    args = ap.parse_args()
    if args.smoke:
        smoke()
        return
    if args.emit_trajectory:
        emit_trajectory()
        return
    if args.check_trajectory:
        raise SystemExit(check_trajectory())
    if args.summary:
        summary()
        return
    if args.obs_cell:
        raise SystemExit(obs_cell(quick=args.quick))

    n, m = (800, 3200) if args.quick else (4000, 16000)
    block_edges = 256
    g = chung_lu(n, m, seed=6)
    result = {
        "graph": {"n": g.n, "m": g.m, "block_edges": block_edges,
                  "num_blocks": -(-g.num_directed // block_edges)},
        "runs": _bench_graph(g, block_edges, BACKENDS, "small"),
        "identical_passes_across_backends": True,
    }
    if not args.quick:
        # >= 200k directed edges: the host reference vs the device-resident
        # xla loop, the fused single-kernel pallas superstep (DESIGN.md §16,
        # still interpret-emulated on CPU), and the on-mesh shard loop
        gl = chung_lu(25_000, 110_000, seed=8)
        assert gl.num_directed >= 200_000
        result["large"] = {
            "graph": {"n": gl.n, "m": gl.m, "block_edges": 4096,
                      "num_blocks": -(-gl.num_directed // 4096)},
            "runs": _bench_graph(gl, 4096,
                                 ("numpy", "xla", "pallas", "shard"),
                                 "large"),
        }
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, "backends.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
