"""Streaming core service: admission, replay determinism, epoch isolation,
zero-I/O queries, WAL/snapshot crash recovery (warm restart)."""
import os

import numpy as np
import pytest

from repro.core import decompose, imcore_bz
from repro.graph import chung_lu, paper_example_graph
from repro.stream import (CoreService, UpdateBatch, WriteAheadLog,
                          admit_batch, mixed_stream)

make_stream = mixed_stream  # shared generator: repro.stream.workload


def batches(ops, size):
    return [ops[i : i + size] for i in range(0, len(ops), size)]


# ================================================================ admission
def test_admission_coalesces_last_op_wins():
    b = admit_batch([("+", 1, 2), ("-", 2, 1), ("+", 3, 4), ("+", 4, 3)])
    assert b.deletes == [(1, 2)]
    assert b.inserts == [(3, 4)]
    assert b.num_requested == 4
    assert b.num_coalesced == 2
    assert b.num_dropped == 0


def test_admission_drops_out_of_range_node_ids():
    g = paper_example_graph()  # n = 9
    svc = CoreService(g, block_edges=16)
    core0 = svc.view().core.copy()
    s = svc.ingest([("+", 0, 50), ("-", -3, 1), ("+", 0, 8)])
    assert s.num_dropped == 2 and s.num_applied_inserts == 1
    svc.ingest([("-", 0, 8)])  # buffer intact: stream keeps working
    np.testing.assert_array_equal(svc.view().core, core0)


def test_admission_counts_malformed_ops_as_dropped():
    g = paper_example_graph()
    svc = CoreService(g, block_edges=16)
    s = svc.ingest([("+", 3), ("+", "a", "b"), None, ("+", 0, 8)])
    assert s.num_dropped == 3 and s.num_applied_inserts == 1


def test_admission_drops_self_loops_and_orders_deletes_first():
    b = admit_batch([("+", 5, 5), ("+", 0, 9), ("-", 7, 3)])
    assert b.num_dropped == 1
    assert b.deletes == [(3, 7)] and b.inserts == [(0, 9)]


def test_admission_insert_then_delete_of_missing_edge_is_noop():
    """Stream says +e then -e on an absent edge: net nothing must change."""
    g = paper_example_graph()
    svc = CoreService(g, block_edges=16)
    core0 = svc.view().core.copy()
    s = svc.ingest([("+", 0, 8), ("-", 0, 8)])
    assert s.num_applied_inserts == 0 and s.num_applied_deletes == 0
    assert s.num_noops == 1 and s.num_coalesced == 1
    np.testing.assert_array_equal(svc.view().core, core0)


# ==================================================== stream == decompose
def test_stream_matches_full_decompose_exactly():
    g = chung_lu(1500, 6000, seed=3)
    ops, final_edges = make_stream(g, 800, seed=1)
    svc = CoreService(g, block_edges=128)
    for chunk in batches(ops, 80):
        svc.ingest(chunk)
    final = svc.bg.materialize()
    assert {tuple(e) for e in final.edge_list().tolist()} == final_edges
    np.testing.assert_array_equal(svc.maintainer.core, imcore_bz(final))
    r = decompose(final, "semicore*", "batch", block_edges=128)
    np.testing.assert_array_equal(svc.maintainer.core, r.core)
    np.testing.assert_array_equal(svc.maintainer.cnt, r.cnt)


def test_replay_determinism_same_stream_same_result():
    g = chung_lu(600, 2400, seed=5)
    ops, _ = make_stream(g, 300, seed=2)
    runs = []
    for _ in range(2):
        svc = CoreService(chung_lu(600, 2400, seed=5), block_edges=64)
        log = [svc.ingest(c) for c in batches(ops, 50)]
        runs.append((svc.view().core, [s.num_changed for s in log], svc.epoch))
    np.testing.assert_array_equal(runs[0][0], runs[1][0])
    assert runs[0][1] == runs[1][1]
    assert runs[0][2] == runs[1][2] == 6


# ======================================================== epochs + queries
def test_epoch_isolation_of_views():
    g = paper_example_graph()
    svc = CoreService(g, block_edges=16)
    v0 = svc.view()
    np.testing.assert_array_equal(v0.core, [3, 3, 3, 3, 2, 2, 2, 2, 1])
    svc.ingest([("-", 0, 1)])  # drops the 3-core to 2 (Example 5.1)
    v1 = svc.view()
    assert (v0.epoch, v1.epoch) == (0, 1)
    # the pre-batch view is frozen: still answers the old epoch's state
    np.testing.assert_array_equal(v0.core, [3, 3, 3, 3, 2, 2, 2, 2, 1])
    np.testing.assert_array_equal(v1.core, [2, 2, 2, 2, 2, 2, 2, 2, 1])
    assert v0.coreness(0) == 3 and v1.coreness(0) == 2
    with pytest.raises(ValueError):
        v0.core[0] = 99  # views are read-only


def test_queries_are_zero_edge_io_and_cached():
    g = chung_lu(1000, 5000, seed=4)
    svc = CoreService(g, block_edges=64)
    reader = svc.maintainer.engine.reader
    io0 = (reader.reads, reader.node_table_reads)
    top = svc.top_k(10)
    members = svc.kcore_members(2)
    assert svc.degeneracy() == svc.view().core.max()
    assert bool(svc.in_kcore(int(top[0]), svc.degeneracy()))
    # vectorized membership/coreness
    np.testing.assert_array_equal(svc.coreness(top), svc.view().core[top])
    assert (reader.reads, reader.node_table_reads) == io0  # zero edge-table I/O
    # second identical queries hit the epoch cache
    h0 = svc.cache.hits
    np.testing.assert_array_equal(svc.top_k(10), top)
    np.testing.assert_array_equal(svc.kcore_members(2), members)
    assert svc.cache.hits == h0 + 2
    # a new epoch invalidates: same query misses again
    svc.ingest([])
    m0 = svc.cache.misses
    svc.top_k(10)
    assert svc.cache.misses == m0 + 1


def test_top_k_is_sorted_and_deterministic():
    g = chung_lu(500, 2500, seed=9)
    svc = CoreService(g, block_edges=64)
    core = svc.view().core
    full = svc.view().top_k(g.n)
    # sorted by coreness desc, ties by id asc — and a permutation of all nodes
    np.testing.assert_array_equal(np.sort(full), np.arange(g.n))
    c = core[full]
    assert (np.diff(c) <= 0).all()
    for k in (1, 7, 50):
        np.testing.assert_array_equal(svc.view().top_k(k), full[:k])


def test_kcore_members_match_min_degree_property():
    g = chung_lu(400, 1600, seed=8)
    svc = CoreService(g, block_edges=64)
    k = max(svc.degeneracy() - 1, 1)
    members = svc.kcore_members(k)
    sub = g.induced_subgraph(members)
    assert sub.degrees().min() >= k
    assert svc.view().kcore_size(k) == len(members)


# ================================================================ recovery
def test_crash_recovery_from_snapshot_and_wal_tail(tmp_path):
    g = chung_lu(1200, 5000, seed=6)
    wal = str(tmp_path / "wal.jsonl")
    snaps = str(tmp_path / "snaps")
    svc = CoreService(g, block_edges=128, wal_path=wal, snapshot_dir=snaps,
                      snapshot_every=3)
    ops, _ = make_stream(g, 350, seed=3)
    for chunk in batches(ops, 50):  # 7 batches -> snapshots at epochs 3, 6
        svc.ingest(chunk)
    svc.close()  # "crash" after epoch 7: one un-snapshotted batch in the WAL

    svc2, rs = CoreService.recover(wal_path=wal, snapshot_dir=snaps,
                                   block_edges=128)
    assert rs.snapshot_epoch == 6 and rs.recovered_epoch == 7
    assert rs.replayed_batches == 1 and rs.warm_restart
    np.testing.assert_array_equal(svc2.maintainer.core, svc.maintainer.core)
    np.testing.assert_array_equal(svc2.maintainer.cnt, svc.maintainer.cnt)
    assert svc2.epoch == svc.epoch
    # the warm settle must beat recomputing the decomposition from scratch
    cold = decompose(svc.bg.materialize(), "semicore*", "batch", block_edges=128)
    assert 0 < rs.settle_node_computations < cold.node_computations
    # and the recovered service keeps serving the stream
    more, _ = make_stream(svc2.bg.materialize(), 40, seed=11)
    svc2.ingest(more)
    np.testing.assert_array_equal(
        svc2.maintainer.core, imcore_bz(svc2.bg.materialize())
    )


def test_recovery_without_tail_uses_snapshot_state_verbatim(tmp_path):
    g = chung_lu(500, 2000, seed=2)
    wal = str(tmp_path / "wal.jsonl")
    snaps = str(tmp_path / "snaps")
    svc = CoreService(g, block_edges=64, wal_path=wal, snapshot_dir=snaps,
                      snapshot_every=2)
    ops, _ = make_stream(g, 80, seed=7)
    for chunk in batches(ops, 40):  # snapshot lands exactly at the last epoch
        svc.ingest(chunk)
    svc.close()
    svc2, rs = CoreService.recover(wal_path=wal, snapshot_dir=snaps,
                                   block_edges=64)
    assert not rs.warm_restart and rs.settle_node_computations == 0
    assert svc2.epoch == svc.epoch == 2
    np.testing.assert_array_equal(svc2.maintainer.core, svc.maintainer.core)


def test_recovery_ignores_torn_wal_tail(tmp_path):
    g = chung_lu(400, 1600, seed=1)
    wal = str(tmp_path / "wal.jsonl")
    snaps = str(tmp_path / "snaps")
    svc = CoreService(g, block_edges=64, wal_path=wal, snapshot_dir=snaps,
                      snapshot_every=100)
    svc.snapshot()  # durable state at epoch 0
    ops, _ = make_stream(g, 60, seed=4)
    svc.ingest(ops[:30])
    svc.close()
    with open(wal, "a") as f:  # crash mid-append of batch 2: torn line
        f.write('{"epoch":2,"del":[[1,')
    svc2, rs = CoreService.recover(wal_path=wal, snapshot_dir=snaps,
                                   block_edges=64)
    assert rs.recovered_epoch == 1 and rs.replayed_batches == 1
    np.testing.assert_array_equal(svc2.maintainer.core, svc.maintainer.core)


def test_wal_appends_after_torn_tail_do_not_corrupt_next_recovery(tmp_path):
    """Reopening a torn WAL must truncate the partial line first; otherwise
    the next append concatenates onto it and a *second* recovery silently
    drops that acknowledged batch (or refuses to parse the log)."""
    g = chung_lu(300, 1200, seed=3)
    wal = str(tmp_path / "wal.jsonl")
    snaps = str(tmp_path / "snaps")
    svc = CoreService(g, block_edges=64, wal_path=wal, snapshot_dir=snaps)
    svc.snapshot()
    ops, _ = make_stream(g, 60, seed=4)
    svc.ingest(ops[:30])
    svc.close()
    with open(wal, "a") as f:
        f.write('{"epoch":2,"del":[[1,')  # crash mid-append
    svc2, _ = CoreService.recover(wal_path=wal, snapshot_dir=snaps,
                                  block_edges=64)
    svc2.ingest(ops[30:])  # epoch 2, appended to the reopened WAL
    svc2.close()
    svc3, rs = CoreService.recover(wal_path=wal, snapshot_dir=snaps,
                                   block_edges=64)
    assert rs.recovered_epoch == 2 and rs.replayed_batches == 2
    np.testing.assert_array_equal(svc3.maintainer.core, svc2.maintainer.core)


def test_cached_query_results_are_read_only():
    g = chung_lu(300, 1200, seed=9)
    svc = CoreService(g, block_edges=64)
    top = svc.top_k(5)
    with pytest.raises(ValueError):
        top[0] = -1  # a caller must not be able to poison later cache hits
    with pytest.raises(ValueError):
        svc.kcore_members(1).sort()
    np.testing.assert_array_equal(svc.top_k(5), svc.view().top_k(5))


def test_recovery_from_base_graph_without_snapshot(tmp_path):
    """No snapshot yet: replay the whole WAL onto the base graph, cold-init."""
    g = chung_lu(300, 1200, seed=5)
    wal = str(tmp_path / "wal.jsonl")
    svc = CoreService(g, block_edges=64, wal_path=wal)
    ops, _ = make_stream(g, 100, seed=6)
    for chunk in batches(ops, 25):
        svc.ingest(chunk)
    svc.close()
    svc2, rs = CoreService.recover(wal_path=wal, base_graph=g, block_edges=64)
    assert rs.replayed_batches == 4 and not rs.warm_restart
    np.testing.assert_array_equal(svc2.maintainer.core, svc.maintainer.core)


def test_wal_replay_filters_already_snapshotted_epochs(tmp_path):
    wal = str(tmp_path / "wal.jsonl")
    w = WriteAheadLog(wal)
    for e in range(1, 5):
        w.append(e, UpdateBatch.from_pairs([(0, e)], [(e, e + 1)]))
    w.close()
    got = list(WriteAheadLog.replay(wal, after_epoch=2))
    assert [e for e, _ in got] == [3, 4]
    assert got[0][1].deletes == [(0, 3)]
    assert got[0][1].inserts == [(3, 4)]


# ========================================================== integration bits
def test_buffer_flush_during_stream_keeps_state_exact():
    """A tiny buffer forces CSR rewrites mid-stream; flush hooks fire and the
    decomposition stays exact across the storage epoch turnover."""
    g = chung_lu(400, 1600, seed=7)
    from repro.graph import BufferedGraph

    bg = BufferedGraph(g, buffer_capacity=64)
    svc = CoreService(bg, block_edges=64)
    ops, _ = make_stream(g, 300, seed=8)
    for chunk in batches(ops, 60):
        svc.ingest(chunk)
    assert svc._flush_events > 0
    assert sum(s.flushes for s in svc.batch_log) == svc._flush_events
    np.testing.assert_array_equal(
        svc.maintainer.core, imcore_bz(svc.bg.materialize())
    )


def test_service_registry_exposes_core_stream():
    from repro.serve import (CoreService as Exported, available_services,
                             service_factory)

    assert "core-stream" in available_services()
    assert "lm" in available_services()
    assert service_factory("core-stream") is Exported is CoreService
    svc = service_factory("core-stream")(paper_example_graph(), block_edges=16)
    assert svc.degeneracy() == 3


# ================================================= watermark epoch semantics
def _wm(values, epoch):
    from repro.stream import WatermarkedArray

    a = np.asarray(values).view(WatermarkedArray)
    a.epoch = epoch
    return a


def test_watermark_views_and_slices_keep_source_epoch():
    a = _wm([3, 1, 4, 1, 5], epoch=7)
    assert a[1:4].epoch == 7
    assert a[a >= 3].epoch == 7
    assert a.reshape(5, 1).epoch == 7
    assert a.copy().epoch == 7


def test_watermark_derived_arrays_keep_source_epoch():
    """Deriving from one stamped reply keeps its epoch: `core >= k`,
    `core + 1`, reductions via ufunc — all still describe epoch-7 state."""
    a = _wm([3, 1, 4], epoch=7)
    assert (a + 1).epoch == 7
    assert (a >= 3).epoch == 7
    assert (-a).epoch == 7
    assert np.maximum(a, 2).epoch == 7  # plain operand doesn't constrain
    assert (a * np.array([1, 2, 3])).epoch == 7
    assert (2 ** a).epoch == 7  # reflected op keeps the stamp too


def test_watermark_same_epoch_operands_keep_epoch():
    a, b = _wm([1, 2, 3], epoch=4), _wm([4, 5, 6], epoch=4)
    assert (a + b).epoch == 4
    assert (a < b).epoch == 4


def test_watermark_mixed_epochs_drop_to_none():
    """The bugfix pin: combining replies from different epochs must not
    silently inherit one parent's watermark — the result describes no
    single consistent snapshot."""
    a, b = _wm([1, 2, 3], epoch=4), _wm([4, 5, 6], epoch=5)
    assert (a + b).epoch is None
    assert (a == b).epoch is None
    assert np.minimum(a, b).epoch is None


def test_watermark_unstamped_operand_does_not_constrain():
    a = _wm([1, 2, 3], epoch=9)
    from repro.stream import WatermarkedArray

    bare = np.array([7, 8, 9]).view(WatermarkedArray)  # never stamped
    assert bare.epoch is None
    assert (a + bare).epoch == 9
    assert (bare + 1).epoch is None


def test_watermark_inplace_ops_restamp_target():
    a, b = _wm([1, 2, 3], epoch=4), _wm([4, 5, 6], epoch=5)
    a += 1  # in-place with a constant: still epoch-4 data
    assert a.epoch == 4
    a += b  # in-place mix: target no longer describes one epoch
    assert a.epoch is None
    np.testing.assert_array_equal(np.asarray(a), [6, 8, 10])


def test_watermark_service_replies_compose():
    svc = CoreService(paper_example_graph(), block_edges=16)
    svc.ingest([("+", 0, 5)])
    core = svc.coreness(np.arange(svc.bg.n))
    assert core.epoch == svc.epoch == 1
    assert (core >= 2).epoch == 1
    stale = _wm(np.asarray(core).copy(), epoch=0)
    assert (core - stale).epoch is None


def test_sampled_stream_ops_are_valid_and_seeded():
    """The edge-set-free generator: every delete names a base edge, every
    insert a base non-edge, no edge twice, same seed same stream."""
    from repro.stream import sampled_stream

    g = chung_lu(400, 1600, seed=2)
    ops = sampled_stream(g, 300, seed=7)
    assert ops == sampled_stream(g, 300, seed=7)
    assert ops != sampled_stream(g, 300, seed=8)
    kinds = {k for k, _, _ in ops}
    assert kinds == {"+", "-"}
    seen = set()
    for kind, u, v in ops:
        assert u < v and (u, v) not in seen
        seen.add((u, v))
        assert g.has_edge(u, v) == (kind == "-")
    svc = CoreService(g, backend="numpy")
    for i in range(0, len(ops), 50):
        st = svc.ingest(ops[i:i + 50])
        assert st.num_noops == 0
