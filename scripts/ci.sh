#!/usr/bin/env bash
# Tier-1 CI: full test suite, streaming bench smoke.  The workflow installs
# the pinned runtime and dev dependencies (.github/workflows/ci.yml).
#
# The workflow matrix runs this leg at python 3.12 x device-count {1, 8};
# the 8-device legs export
# XLA_FLAGS=--xla_force_host_platform_device_count=8 so the shard backend
# (engine.ShardedBackend, DESIGN.md §13) exercises a real 8-way mesh on the
# CPU runner end to end — pytest sweep, backend smoke, and trajectory gate.
set -euo pipefail
cd "$(dirname "$0")/.."

PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q

# backend-matrix smoke: the same batch superstep on every compute substrate
# (engine.py, DESIGN.md §11/§13), selected through the REPRO_BACKEND env
# default.  Exactness + trace parity only; wall-clock is gated below by the
# perf-trajectory harness.
for backend in numpy xla pallas shard; do
  REPRO_BACKEND=$backend PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python benchmarks/bench_backends.py --smoke
done

# legacy per-pass loop (REPRO_DEVICE_RESIDENT=0, DESIGN.md §12) must stay
# exact: same fixpoint, same planner trace as the resident default
REPRO_DEVICE_RESIDENT=0 REPRO_BACKEND=xla \
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python benchmarks/bench_backends.py --smoke

# pallas-fused leg (DESIGN.md §16): the pallas smoke above already runs the
# fused single-kernel superstep (the default); this one pins the per-probe
# segment_sum_active oracle path (REPRO_PALLAS_FUSED=0) so the fallback and
# its accounting parity stay exact too
REPRO_PALLAS_FUSED=0 REPRO_BACKEND=pallas \
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python benchmarks/bench_backends.py --smoke

# perf-trajectory regression gate: measure the 4-backend matrix on the small
# cell plus numpy/xla/pallas on the large cell (interpret-mode fused-superstep
# decompose) and compare warm-wall ratios + jit-trace counts against the
# committed BENCH_backends.json baseline (fails on >1.5x warm-wall regression
# or any jit-trace-count increase; replaces the old "xla <= 40x numpy + 2s"
# hack).  The candidate lands in
# benchmarks/results/BENCH_backends_current.json for the artifact upload.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python benchmarks/bench_backends.py --check-trajectory

# maintenance-scaling trajectory gate (DESIGN.md §18): sustained updates/s
# of the parallel grouped settle vs the serial oracle across batch sizes on
# the fixed 10k/60k cell.  Same-machine ratio, so machine-speed independent;
# fails if the batch=64 speedup drops below 2x.  Also re-asserts the
# differential contract (parallel state bit-identical to serial) inside the
# bench itself.  Rows merge into results/stream.json under "maint_scaling".
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python benchmarks/bench_stream.py --quick --maint-scaling

# update-API deprecation lint (DESIGN.md §18): no internal caller may use a
# deprecated spelling (apply_batch, 3-arg wal.append) — shims exist for
# external callers only.
python scripts/check_deprecations.py

# telemetry leg (DESIGN.md §14): run the large bench cell with tracing on,
# emitting a Perfetto-loadable Chrome trace (superstep_trace.json), the full
# registry in Prometheus text exposition (metrics.prom) and a markdown
# summary (obs_summary.md).  Gates on instrumentation overhead: the traced
# warm wall must stay within 5% (+50ms floor) of the REPRO_OBS=0 wall.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python benchmarks/bench_backends.py --obs-cell

# registry-sourced superstep roofline: achieved-vs-peak bytes/s where the
# numerator is the repro_io_bytes_read_total delta, never hand math
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python benchmarks/roofline.py --superstep --quick

# fused-superstep roofline (DESIGN.md §16): same registry-sourced sweep with
# the pallas single-kernel backend included; writes
# results/fused_superstep_roofline.{json,md} (the .md feeds the step summary)
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python benchmarks/roofline.py --fused-superstep --quick

# CI observability: render the backend x algorithm wall-clock table and the
# telemetry-cell summary into the workflow step summary (no-op outside
# GitHub Actions)
if [ -n "${GITHUB_STEP_SUMMARY:-}" ]; then
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python benchmarks/bench_backends.py --summary >> "$GITHUB_STEP_SUMMARY"
  cat benchmarks/results/obs_summary.md >> "$GITHUB_STEP_SUMMARY"
  cat benchmarks/results/fused_superstep_roofline.md >> "$GITHUB_STEP_SUMMARY"
fi

PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python benchmarks/bench_stream.py --quick

# parallel-maint oracle smoke (DESIGN.md §18): the same mixed streaming
# workload forced onto the serial parity oracle — REPRO_PARALLEL_MAINT=0
# must stay a working end-to-end configuration, since it is how the
# differential battery pins bit-identity.
REPRO_PARALLEL_MAINT=0 PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python benchmarks/bench_stream.py --quick

# updates/s cell into the workflow step summary
if [ -n "${GITHUB_STEP_SUMMARY:-}" ]; then
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - >> "$GITHUB_STEP_SUMMARY" <<'PYEOF'
import json
cell = json.load(open("benchmarks/results/stream.json"))["maint_scaling"]
print("\n### Maintenance scaling (parallel grouped settle vs serial oracle)\n")
print("| batch | parallel upd/s | serial upd/s | speedup | p99 settle ms | gated |")
print("|---|---|---|---|---|---|")
for r in cell["rows"]:
    print(f"| {r['batch']} | {r['parallel_updates_per_s']:.0f} "
          f"| {r['serial_updates_per_s']:.0f} | {r['speedup']:.2f}x "
          f"| {r['parallel_p99_ms']:.1f} | {'yes' if r['gated'] else ''} |")
PYEOF
fi

# replication leg (DESIGN.md §15): 1 writer + 2 replicas (+1 late joiner)
# tailing the WAL under sustained ingest with rotation every few batches.
# Asserts bounded replica lag and bit-identical watermarked replies at the
# final epoch; results/replication.json rides the artifact upload.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python benchmarks/bench_replication.py --smoke

# chaos leg (DESIGN.md §17): the fixed-seed fault-injection soak — a writer
# plus two replicas under a seeded randomized schedule of injected I/O
# errors, torn writes, bit flips, lying fsyncs and ENOSPC; every seed must
# end bit-identical to the in-memory oracle with every injected fault
# visible in repro_faults_injected_total.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python -m pytest tests/test_faults.py -q -k chaos_soak

# the replication smoke re-run with 2ms of injected WAL-append latency: the
# bounded-lag and bit-identity gates must hold while appends are slow, and
# the run must account every slowed append in the fault counters (asserted
# inside the bench); the admission-backpressure overload cell rides along,
# merging into results/stream.json.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python benchmarks/bench_replication.py --smoke --wal-append-latency-ms 2
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python benchmarks/bench_stream.py --quick --overload
if [ -n "${GITHUB_STEP_SUMMARY:-}" ]; then
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python scripts/fault_summary.py >> "$GITHUB_STEP_SUMMARY"
fi

# out-of-core smoke: build a ~1M-edge graph from chunks in a temp dir,
# memmap-load it, decompose, and compare against the in-memory build
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python benchmarks/bench_outofcore.py --smoke
