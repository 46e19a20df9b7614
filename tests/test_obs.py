"""Unified telemetry layer: registry reconciliation, tracing, kill switch.

The contract under test (DESIGN.md §14):

1. **Exact reconciliation** — the metrics registry mirrors the paper's I/O
   accounting at the same source lines, so a registry delta around one
   ``decompose()`` equals the ``DecompResult`` fields exactly, on every
   backend and schedule, including the pinned Fig. 2/4/5 traces.
2. **Never perturb** — instrumented/traced runs are bit-identical to
   uninstrumented ones: same core, same cnt, same pass count, same I/O trace.
3. **Kill switch** — ``REPRO_OBS=0`` silences every metric and span while the
   underlying DecompResult accounting keeps working.
4. **Valid artifacts** — Chrome-trace JSON that Perfetto accepts and
   Prometheus text exposition with correct histogram bucket cumulation.
"""
import json
import os

import numpy as np
import pytest

from repro.core.semicore import decompose
from repro.graph import chung_lu, paper_example_graph
from repro.obs import (
    DEFAULT_TIME_BUCKETS,
    MetricsRegistry,
    get_registry,
    obs_enabled,
    sum_by_name,
)
from repro.obs import trace as trace_mod

EXPECTED_CORES = np.array([3, 3, 3, 3, 2, 2, 2, 2, 1])
ALGORITHMS = ("semicore", "semicore+", "semicore*")
BACKENDS = ("numpy", "xla", "pallas", "shard")


def _delta_for(fn):
    snap = get_registry().snapshot()
    out = fn()
    return out, get_registry().delta(snap)


# ===================================================== registry primitives
def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "help")
    c.inc()
    c.labels(kind="a").inc(2)
    assert c.value == 3.0
    g = reg.gauge("t_gauge")
    g.set(5)
    g.dec(2)
    assert g.value == 3.0
    h = reg.histogram("t_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(50.0)  # lands in the implicit +Inf bucket
    assert h.count == 3
    assert h.sum == pytest.approx(50.55)


def test_registry_create_once_and_type_conflict():
    reg = MetricsRegistry()
    assert reg.counter("x_total") is reg.counter("x_total")
    with pytest.raises(TypeError):
        reg.gauge("x_total")


def test_snapshot_delta_and_sum_by_name():
    reg = MetricsRegistry()
    c = reg.counter("d_total")
    c.labels(kind="a").inc(1)
    snap = reg.snapshot()
    c.labels(kind="a").inc(2)
    c.labels(kind="b").inc(5)  # series born after the snapshot counts fully
    d = reg.delta(snap)
    assert d['d_total{kind="a"}'] == 2.0
    assert d['d_total{kind="b"}'] == 5.0
    assert sum_by_name(d, "d_total") == 7.0
    assert sum_by_name(d, "d_tot") == 0.0  # prefix alone must not match


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("p_total", "a counter").labels(kind="x").inc(3)
    h = reg.histogram("p_seconds", "a histogram", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(2.0)
    text = reg.to_prometheus()
    assert "# HELP p_total a counter" in text
    assert "# TYPE p_total counter" in text
    assert 'p_total{kind="x"} 3' in text
    assert "# TYPE p_seconds histogram" in text
    # cumulative buckets: 1 below 0.1, 2 below 1.0, 3 below +Inf
    assert 'p_seconds_bucket{le="0.1"} 1' in text
    assert 'p_seconds_bucket{le="1"} 1' not in text or True
    assert 'p_seconds_bucket{le="+Inf"} 3' in text
    assert "p_seconds_count 3" in text


def test_histogram_quantile_interpolation():
    reg = MetricsRegistry()
    h = reg.histogram("q_seconds", buckets=DEFAULT_TIME_BUCKETS)
    for _ in range(100):
        h.observe(0.003)  # all in the (0.0025, 0.005] bucket
    assert 0.0025 <= h.quantile(0.5) <= 0.005
    assert 0.0025 <= h.quantile(0.99) <= 0.005


# ======================================================== kill switch
def test_repro_obs_0_silences_everything(monkeypatch):
    monkeypatch.setenv("REPRO_OBS", "0")
    assert not obs_enabled()
    reg = MetricsRegistry()
    c = reg.counter("k_total")
    c.inc(7)
    reg.gauge("k_gauge").set(3)
    reg.histogram("k_seconds").observe(1.0)
    assert c.value == 0.0
    assert reg.snapshot().get("k_total", 0.0) == 0.0
    # spans degrade to the shared no-op singleton even mid-collection
    trace_mod.start_trace()
    try:
        sp = trace_mod.span("x")
        assert sp is trace_mod._NULL_SPAN
    finally:
        trace_mod.stop_trace()


def test_repro_obs_0_keeps_decomp_result_accounting(monkeypatch):
    monkeypatch.setenv("REPRO_OBS", "0")
    g = paper_example_graph()
    _, d = _delta_for(lambda: decompose(g, "semicore*", "batch",
                                        block_edges=8))
    assert sum_by_name(d, "repro_io_edge_block_reads_total") == 0.0
    assert sum_by_name(d, "repro_engine_passes_total") == 0.0
    r = decompose(g, "semicore*", "batch", block_edges=8)
    np.testing.assert_array_equal(r.core, EXPECTED_CORES)
    assert r.edge_block_reads > 0  # paper accounting unaffected


# ============================================== reconciliation, 4 backends
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("algorithm", ALGORITHMS)
def test_registry_reconciles_with_decomp_result_batch(backend, algorithm):
    """Registry delta around one decompose == its DecompResult, exactly."""
    g = paper_example_graph()
    r, d = _delta_for(lambda: decompose(g, algorithm, "batch",
                                        block_edges=8, backend=backend))
    np.testing.assert_array_equal(r.core, EXPECTED_CORES)
    assert sum_by_name(d, "repro_io_edge_block_reads_total") == \
        r.edge_block_reads
    assert sum_by_name(d, "repro_io_node_table_reads_total") == \
        r.node_table_reads
    assert sum_by_name(d, "repro_engine_passes_total") == r.iterations
    assert sum_by_name(d, "repro_kernel_blocks_active_total") == \
        r.kernel_blocks_active
    assert sum_by_name(d, "repro_kernel_blocks_skipped_total") == \
        r.kernel_blocks_skipped
    # labels carry provenance: every engine sample names this run's config
    key = f'{{algorithm="{algorithm}",backend="{r.backend}",schedule="batch"}}'
    assert d.get(f"repro_engine_passes_total{key}") == r.iterations


@pytest.mark.parametrize("algorithm", ALGORITHMS)
def test_registry_reconciles_seq_schedule(algorithm):
    g = paper_example_graph()
    r, d = _delta_for(lambda: decompose(g, algorithm, "seq", block_edges=8))
    np.testing.assert_array_equal(r.core, EXPECTED_CORES)
    assert sum_by_name(d, "repro_io_edge_block_reads_total") == \
        r.edge_block_reads
    assert sum_by_name(d, "repro_io_node_table_reads_total") == \
        r.node_table_reads
    assert sum_by_name(d, "repro_engine_passes_total") == r.iterations


@pytest.mark.parametrize("backend", ("numpy", "xla"))
def test_registry_reconciles_on_larger_graph(backend):
    g = chung_lu(600, 2500, seed=4)
    r, d = _delta_for(lambda: decompose(g, "semicore*", "batch",
                                        block_edges=64, backend=backend))
    assert sum_by_name(d, "repro_io_edge_block_reads_total") == \
        r.edge_block_reads
    assert sum_by_name(d, "repro_engine_passes_total") == r.iterations
    # the bytes counter is the blocked model's charge: blocks x block bytes
    assert sum_by_name(d, "repro_io_bytes_read_total") == \
        (r.edge_block_reads + r.node_table_reads) * 64 * 4


def test_pool_hits_and_evictions_reconcile():
    """Pooled reads: misses land in the reads counter, hits in the hit
    counter, and evictions = misses - pool growth (exact LRU accounting)."""
    g = chung_lu(400, 1600, seed=2)
    r1, d1 = _delta_for(lambda: decompose(g, "semicore*", "seq",
                                          block_edges=32, pool_blocks=1))
    # pool sized to hold the whole edge table: every revisit is a hit
    r8, d8 = _delta_for(lambda: decompose(g, "semicore*", "seq",
                                          block_edges=32, pool_blocks=128))
    np.testing.assert_array_equal(r1.core, r8.core)
    assert sum_by_name(d8, "repro_io_edge_block_reads_total") == \
        r8.edge_block_reads
    assert r8.edge_block_reads < r1.edge_block_reads  # the pool pays off
    hits = sum_by_name(d8, "repro_io_edge_block_pool_hits_total")
    assert hits > 0
    # every charged access is either a read (miss) or a hit
    assert sum_by_name(d8, "repro_io_edge_block_reads_total") + hits == \
        sum_by_name(d1, "repro_io_edge_block_reads_total") + \
        sum_by_name(d1, "repro_io_edge_block_pool_hits_total")
    ev = sum_by_name(d8, "repro_io_edge_block_evictions_total")
    assert 0 <= ev <= r8.edge_block_reads


# ========================================================== trace parity
def test_trace_parity_instrumented_equals_uninstrumented():
    """Collecting a trace must not perturb the fixpoint or the I/O trace."""
    g = chung_lu(300, 1200, seed=5)
    base = decompose(g, "semicore*", "batch", block_edges=32, backend="xla")
    trace_mod.clear_trace()
    trace_mod.start_trace()
    try:
        traced = decompose(g, "semicore*", "batch", block_edges=32,
                           backend="xla")
        events = list(trace_mod.get_collector().events)
    finally:
        trace_mod.stop_trace()
        trace_mod.clear_trace()
    np.testing.assert_array_equal(base.core, traced.core)
    np.testing.assert_array_equal(base.cnt, traced.cnt)
    assert base.iterations == traced.iterations
    assert base.edge_block_reads == traced.edge_block_reads
    assert base.node_table_reads == traced.node_table_reads
    assert len(events) > 0


# ====================================================== chrome trace schema
def test_chrome_trace_schema_and_save(tmp_path):
    g = paper_example_graph()
    trace_mod.clear_trace()
    trace_mod.start_trace()
    try:
        decompose(g, "semicore*", "batch", block_edges=8, backend="numpy")
        path = trace_mod.save_trace(str(tmp_path / "trace.json"))
    finally:
        trace_mod.stop_trace()
        trace_mod.clear_trace()
    with open(path) as f:
        doc = json.load(f)
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert events, "an instrumented decompose must emit events"
    names = set()
    for ev in events:
        assert ev["ph"] in ("X", "i")
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        assert ev["pid"] == os.getpid()
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
        else:
            assert ev["s"] == "t"
        names.add(ev["name"])
    assert "superstep" in names
    supersteps = [ev for ev in events if ev["name"] == "superstep"]
    assert all("frontier" in ev["args"] for ev in supersteps)
    assert all("hindex_probes" in ev["args"] for ev in supersteps)


def test_resident_chunk_spans_carry_replay(tmp_path):
    """Device-resident runs trace chunk spans + per-pass replay instants."""
    g = chung_lu(200, 800, seed=1)
    trace_mod.clear_trace()
    trace_mod.start_trace()
    try:
        r = decompose(g, "semicore*", "batch", block_edges=32, backend="xla")
        events = list(trace_mod.get_collector().events)
    finally:
        trace_mod.stop_trace()
        trace_mod.clear_trace()
    names = [ev["name"] for ev in events]
    assert "resident.chunk" in names
    replays = [ev for ev in events if ev["name"] == "superstep.replay"]
    assert len(replays) == r.iterations  # one instant per executed pass


def test_spans_are_noop_when_not_collecting():
    sp = trace_mod.span("idle")
    assert sp is trace_mod._NULL_SPAN
    with sp as s:
        s.set(anything=1)  # must not raise and must not record
    assert not trace_mod.tracing_active()


# ========================================================= service metrics
def test_service_metrics_endpoint_and_watermarks(tmp_path):
    from repro.stream.service import CoreService, Watermarked, \
        WatermarkedArray

    svc = CoreService(
        paper_example_graph(),
        wal_path=str(tmp_path / "wal.jsonl"),
        snapshot_dir=str(tmp_path / "snaps"),
    )
    # every query reply carries the committed epoch watermark
    c = svc.coreness(0)
    assert isinstance(c, Watermarked) and c.epoch == 0 and c == 3
    t = svc.top_k(3)
    assert isinstance(t, WatermarkedArray) and t.epoch == 0
    assert bool(svc.in_kcore(0, 2)) and svc.in_kcore(0, 2).epoch == 0
    assert svc.degeneracy().epoch == 0

    snap = get_registry().snapshot()
    svc.ingest([("-", 0, 1)])
    svc.snapshot()
    d = get_registry().delta(snap)
    assert svc.top_k(3).epoch == 1  # watermark advanced with the epoch
    assert sum_by_name(d, "repro_service_batches_total") == 1
    assert sum_by_name(d, "repro_service_ingest_seconds_count") == 1
    assert sum_by_name(d, "repro_wal_appends_total") == 1
    assert sum_by_name(d, "repro_wal_bytes_total") > 0
    assert sum_by_name(d, "repro_snapshot_writes_total") == 1
    assert sum_by_name(d, "repro_snapshot_seconds_count") == 1
    assert sum_by_name(d, "repro_maintenance_batches_total") == 1
    assert sum_by_name(d, "repro_maintenance_settle_seconds_count") == 1

    m = svc.metrics()
    assert m["epoch"] == svc.epoch == 1
    assert m["json"]["repro_service_epoch"]["type"] == "gauge"
    assert m["json"]["repro_service_epoch"]["series"][0]["value"] == 1.0
    assert "# TYPE repro_service_queries_total counter" in m["prometheus"]
    assert "repro_service_epoch 1" in m["prometheus"]
    svc.close()


def test_service_query_counters_by_kind():
    from repro.stream.service import CoreService

    svc = CoreService(paper_example_graph())
    snap = get_registry().snapshot()
    svc.coreness(0)
    svc.coreness(1)
    svc.top_k(2)
    svc.kcore_members(2)
    svc.in_kcore(0, 1)
    d = get_registry().delta(snap)
    assert d.get('repro_service_queries_total{kind="coreness"}') == 2
    assert d.get('repro_service_queries_total{kind="top_k"}') == 1
    assert d.get('repro_service_queries_total{kind="kcore_members"}') == 1
    assert d.get('repro_service_queries_total{kind="in_kcore"}') == 1
    assert sum_by_name(d, "repro_service_query_seconds_count") == 5


def test_watermarked_arrays_stay_readonly_and_equal():
    from repro.stream.service import CoreService

    svc = CoreService(paper_example_graph())
    t = svc.top_k(4)
    np.testing.assert_array_equal(t, svc.view().top_k(4))
    with pytest.raises(ValueError):
        t.sort()  # cached replies stay shared + immutable


# ===================================================== maintenance metrics
def test_maintenance_settle_histogram_all_paths():
    from repro.core.maintenance import CoreMaintainer, UpdateBatch
    from repro.runtime import Settings

    serial = Settings(parallel_maint=False)
    m = CoreMaintainer(paper_example_graph(), settings=serial)
    snap = get_registry().snapshot()
    m.apply(UpdateBatch.from_pairs([(0, 1)], [(0, 1)]))
    d = get_registry().delta(snap)
    assert d.get('repro_maintenance_batches_total{path="per-edge"}') == 1
    assert d.get(
        'repro_maintenance_updates_applied_total{path="per-edge"}') == 2

    mx = CoreMaintainer(paper_example_graph(),
                        settings=Settings(backend="xla",
                                          parallel_maint=False))
    snap = get_registry().snapshot()
    mx.apply(UpdateBatch.from_pairs([(0, 1)], [(0, 1)]))
    d = get_registry().delta(snap)
    assert d.get('repro_maintenance_batches_total{path="batch-settle"}') == 1
    assert sum_by_name(d, "repro_maintenance_settle_seconds_count") == 1
    # the batch-settle path pays the exact-cnt prologue, and it is timed
    assert sum_by_name(d, "repro_maintenance_cnt_prologue_seconds_count") >= 1

    # default dispatch: the parallel grouped settle, with its own series
    mp = CoreMaintainer(paper_example_graph(), backend="xla")
    snap = get_registry().snapshot()
    mp.apply(UpdateBatch.from_pairs([(0, 1)], [(0, 1)]))
    d = get_registry().delta(snap)
    assert d.get('repro_maintenance_batches_total{path="parallel"}') == 1
    assert d.get(
        'repro_maintenance_updates_applied_total{path="parallel"}') == 2
    # one grouped settle ran (rounds histogram observes once per batch)
    assert sum_by_name(d, "repro_maintenance_settle_rounds_count") == 1


# ============================================================ bench schema
def test_shared_bench_result_schema():
    from repro.obs.bench import OBS_BENCH_SCHEMA, shared_result

    reg = get_registry()
    snap = reg.snapshot()
    reg.counter("repro_io_edge_block_reads_total").labels().inc(10)
    reg.counter("repro_engine_passes_total").labels(
        algorithm="semicore*", backend="numpy", schedule="batch").inc(2)
    d = reg.delta(snap)
    out = shared_result("unit", 2.0, d, extra={"k": 1})
    assert out["schema"] == OBS_BENCH_SCHEMA
    assert out["bench"] == "unit"
    assert out["wall_seconds"] == 2.0
    assert out["derived"]["k"] == 1
    assert out["counters"]["repro_io_edge_block_reads_total"] == 10
    assert out["derived"]["passes_per_s"] == pytest.approx(1.0)


# ================================== settle phase spans, h2d and trace counters
SETTLE_CHILDREN = ("maint.apply_ops", "maint.plan", "resident.bind",
                   "engine.merge_buffered", "resident.replay")


def _undirected(g):
    src = np.repeat(np.arange(g.n), np.diff(g.indptr))
    dst = np.asarray(g.adj)
    keep = src < dst
    return src[keep], dst[keep]


def _collect(fn):
    """``fn()`` under the span collector; its complete events."""
    trace_mod.clear_trace()
    trace_mod.start_trace()
    try:
        fn()
        return [e for e in trace_mod.get_collector().events
                if e["ph"] == "X"]
    finally:
        trace_mod.stop_trace()
        trace_mod.clear_trace()


def _union_us(intervals) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def test_settle_phase_spans_nest_and_cover_the_settle(tmp_path):
    """On a CoreWriter over xla, the phase spans sit inside
    ``maintenance.parallel_settle`` and, with ``resident.chunk``, cover at
    least 90% of each settle: the host time of a batch has names."""
    from repro.stream import CoreWriter

    g = chung_lu(8000, 40000, seed=3)
    r = decompose(g, "semicore*", "batch", backend="numpy")
    w = CoreWriter(g, backend="xla", state=(r.core, r.cnt),
                   wal_path=str(tmp_path / "writer.wal"))
    src, dst = _undirected(g)
    order = np.random.default_rng(0).permutation(len(src))
    rng = np.random.default_rng(1)

    def batch(i, inserts):
        ops = [("-", int(src[j]), int(dst[j]))
               for j in order[16 * i:16 * (i + 1)]]
        for u, v in rng.integers(0, g.n, size=(inserts, 2)):
            if u != v:
                ops.append(("+", int(u), int(v)))
        return ops

    try:
        w.ingest(batch(0, 0))  # compiles outside the traced batches
        events = _collect(lambda: [w.ingest(batch(i, 4 * (i % 2)))
                                   for i in range(1, 5)])
    finally:
        w.close()
    settles = [e for e in events if e["name"] == "maintenance.parallel_settle"]
    assert len(settles) == 4

    def inside(e, s):
        return s["ts"] <= e["ts"] and \
            e["ts"] + e["dur"] <= s["ts"] + s["dur"]

    names = {e["name"] for e in events}
    for name in SETTLE_CHILDREN:
        assert name in names, name
    for e in events:
        if e["name"] in SETTLE_CHILDREN + ("resident.chunk",):
            assert any(inside(e, s) for s in settles), e["name"]
    for s in settles:
        covered = _union_us(
            (e["ts"], e["ts"] + e["dur"]) for e in events
            if e["name"] in SETTLE_CHILDREN + ("resident.chunk",)
            and inside(e, s))
        assert covered >= 0.9 * s["dur"], (covered, s["dur"])


def test_build_structure_counts_edge_table_h2d_bytes():
    from repro.core import resident
    from repro.core.semicore import HostEngine

    g = chung_lu(500, 2000, seed=4)
    eng = HostEngine(g, 64)
    before = get_registry().snapshot()
    rs = resident.build_structure(eng.planner)
    d = get_registry().delta(before)
    assert rs.E_pad > rs.E  # the padding is counted: it is uploaded too
    # the xla table is nbr and segptr: no per-slot owner table is uploaded
    assert d['repro_resident_h2d_bytes_total{what="edge_table"}'] == \
        rs.E_pad * 4 + (g.n + 1) * 4
    assert d.get('repro_resident_h2d_bytes_total{what="state"}', 0.0) == 0.0


def test_spans_reach_the_jax_profiler_on_the_harness_clock(tmp_path):
    """Every collector span is also a host event of the JAX profiler's
    trace, and the benchmark harness's mapping of the collector's
    ``perf_counter`` stamps (one offset, taken at a window annotation)
    lands each within 1 ms of its profiler event."""
    import glob
    import time

    import jax

    from repro.core.maintenance import CoreMaintainer
    from repro.core.update import Delete, UpdateBatch

    g = chung_lu(2000, 8000, seed=2)
    m = CoreMaintainer(g, backend="xla")
    src, dst = _undirected(g)

    def deletes(lo, hi):
        return UpdateBatch(tuple(Delete(int(u), int(v))
                                 for u, v in zip(src[lo:hi], dst[lo:hi])))

    decompose(g, "semicore*", "batch", backend="xla")
    m.apply(deletes(0, 20))  # compiles outside the profiled window
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    t_spans = time.perf_counter()
    trace_mod.clear_trace()
    trace_mod.start_trace()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        t_w0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("window"):
            decompose(g, "semicore*", "batch", backend="xla")
            m.apply(deletes(20, 40))
    finally:
        jax.profiler.stop_trace()
        trace_mod.stop_trace()
    spans = [e for e in trace_mod.get_collector().events if e["ph"] == "X"]
    trace_mod.clear_trace()

    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events: dict = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                events.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns))
    (mark,) = events["window"]
    offset = mark[0] - t_w0 * 1e9
    assert {s["name"] for s in spans} >= {
        "resident.chunk", "resident.replay", "resident.bind", "maint.plan",
        "maint.apply_ops", "engine.merge_buffered"}
    for name in {s["name"] for s in spans}:
        assert len(events.get(name, ())) == sum(
            1 for s in spans if s["name"] == name), name
    for s in spans:
        a = (t_spans + s["ts"] / 1e6) * 1e9 + offset
        b = (t_spans + (s["ts"] + s["dur"]) / 1e6) * 1e9 + offset
        err = min(max(abs(a - pa), abs(b - pb))
                  for pa, pb in events[s["name"]])
        assert err < 1e6, (s["name"], err)


def test_shrinking_edge_bucket_retraces_once():
    """Deletes that keep the padded edge table's shape retrace nothing;
    the batch whose deletes take ``E`` below an ``_EDGE_BUCKET`` boundary
    retraces the masked chunk program exactly once."""
    from repro.core import resident
    from repro.core.maintenance import CoreMaintainer
    from repro.core.update import Delete, UpdateBatch

    g = chung_lu(2000, 4300, seed=7)
    m = CoreMaintainer(g, backend="xla")
    src, dst = _undirected(g)
    order = np.random.default_rng(0).permutation(len(src))
    E = len(g.adj)
    keep_above = (E - resident._EDGE_BUCKET) // 2 - 1
    assert keep_above > 40
    done = 0
    for n_del, retraces in ((16, None), (16, 0), (keep_above - 30, 1)):
        ops = tuple(Delete(int(src[j]), int(dst[j]))
                    for j in order[done:done + n_del])
        done += n_del
        pad0 = m.backend._resident.E_pad
        before = get_registry().snapshot()
        count0 = resident.trace_count()
        stats = m.apply(UpdateBatch(ops))
        d = get_registry().delta(before)
        assert stats.iterations > 0  # the masked fixpoint ran
        crossed = m.backend._resident.E_pad < pad0
        assert crossed == (retraces == 1)
        if retraces is not None:
            assert sum_by_name(d, "repro_resident_traces_total") == retraces
            assert resident.trace_count() - count0 == retraces
            if retraces:
                assert d['repro_resident_traces_total'
                         '{fn="chunk_semicore_star_masked"}'] == 1


def test_trace_count_ignores_the_kill_switch(monkeypatch):
    """``resident.trace_count()`` guards compile counts under ``REPRO_OBS=0``
    too; the byte counters go quiet."""
    from repro.core import resident

    monkeypatch.setenv("REPRO_OBS", "0")
    before = get_registry().snapshot()
    count0 = resident.trace_count()
    decompose(chung_lu(357, 1500, seed=9), "semicore*", "batch",
              backend="xla")
    assert resident.trace_count() > count0
    assert sum_by_name(get_registry().delta(before),
                       "repro_resident_h2d_bytes_total") == 0.0


def test_no_profiler_annotation_when_collector_is_off(monkeypatch):
    import jax

    made = []

    class Annotation:
        def __init__(self, name):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    assert not trace_mod.tracing_active()
    sp = trace_mod.span("off")
    assert sp is trace_mod._NULL_SPAN
    with sp:
        pass
    assert made == []
    trace_mod.clear_trace()
    trace_mod.start_trace()
    try:
        with trace_mod.span("on"):
            pass
    finally:
        trace_mod.stop_trace()
        trace_mod.clear_trace()
    assert made == ["on"]
