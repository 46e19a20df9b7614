"""Differential battery for the parallel grouped maintenance settle.

The contract under test (DESIGN.md §18): ``CoreMaintainer.apply`` with the
parallel path enabled lands on ``(core, cnt)`` **bit-identical** to the
serial oracle — the paper's per-edge seq maintenance — for every batch
shape and every compute backend, because both are exact algorithms for the
same fixpoint.  The battery runs 7 differential families × 4 backends,
plus adversarial batches, a candidate-bound soundness check, replica
replay parity, and the deprecation-shim equivalences.
"""
import warnings

import numpy as np
import pytest

import repro.core.parallel_maint as pm
from repro.core import CoreMaintainer, Delete, Insert, UpdateBatch
from repro.core.imcore import imcore_bz
from repro.graph import chung_lu, erdos_renyi
from repro.graph.updates import BufferedGraph
from repro.runtime import Settings
from repro.stream import CoreReplica, CoreService, WriteAheadLog

BACKENDS = ["numpy", "xla", "pallas-interpret", "shard"]

# the interpreter-mode pallas substrate is orders of magnitude slower than
# compiled paths; every family shrinks its graph for it.
_SIZES = {"pallas-interpret": (90, 300)}
_DEFAULT_SIZE = (250, 1000)


def _graph(backend, seed):
    n, m = _SIZES.get(backend, _DEFAULT_SIZE)
    return chung_lu(n, m, seed=seed), n


def _live_edges(g):
    return set(map(tuple, np.sort(g.edge_list(), axis=1)))


def _rand_missing(rng, n, live):
    while True:
        u, v = int(rng.integers(n)), int(rng.integers(n))
        if u == v:
            continue
        e = (min(u, v), max(u, v))
        if e not in live:
            return e


# --------------------------------------------------------------- families
def _fam_insert_sparse(g, n, rng):
    live = _live_edges(g)
    ops = []
    for _ in range(16):
        e = _rand_missing(rng, n, live)
        live.add(e)
        ops.append(Insert(*e))
    return [ops]


def _fam_delete_sparse(g, n, rng):
    live = sorted(_live_edges(g))
    idx = rng.choice(len(live), 16, replace=False)
    return [[Delete(*live[i]) for i in idx]]


def _fam_mixed(g, n, rng):
    live = _live_edges(g)
    out = []
    for _ in range(2):  # two consecutive batches: state carries over
        ops = []
        for _ in range(16):
            if rng.random() < 0.5 and live:
                e = sorted(live)[int(rng.integers(len(live)))]
                live.discard(e)
                ops.append(Delete(*e))
            else:
                e = _rand_missing(rng, n, live)
                live.add(e)
                ops.append(Insert(*e))
        out.append(ops)
    return out


def _fam_clique_lift(g, n, rng):
    """Complete a clique among low-degree nodes: multi-level rises that
    force saturation re-root rounds."""
    deg = np.zeros(n, dtype=int)
    e = g.edge_list()
    np.add.at(deg, e[:, 0], 1)
    np.add.at(deg, e[:, 1], 1)
    nodes = [int(v) for v in np.argsort(deg)[:7]]
    live = _live_edges(g)
    ops = []
    for i, u in enumerate(nodes):
        for v in nodes[i + 1:]:
            edge = (min(u, v), max(u, v))
            if edge not in live:
                ops.append(Insert(*edge))
                live.add(edge)
    return [ops]


def _fam_hub_churn(g, n, rng):
    """Every op incident to one hub: maximally-overlapping candidate sets
    (one big group, not many independent ones)."""
    deg = np.zeros(n, dtype=int)
    e = g.edge_list()
    np.add.at(deg, e[:, 0], 1)
    np.add.at(deg, e[:, 1], 1)
    hub = int(np.argmax(deg))
    live = _live_edges(g)
    hub_edges = sorted(e for e in live if hub in e)
    ops = [Delete(*e) for e in hub_edges[:6]]
    for e in hub_edges[:6]:
        live.discard(e)
    for _ in range(6):
        while True:
            v = int(rng.integers(n))
            edge = (min(hub, v), max(hub, v))
            if v != hub and edge not in live:
                break
        live.add(edge)
        ops.append(Insert(*edge))
    return [ops]


def _fam_cascade_delete(g, n, rng):
    """Delete edges of max-core nodes: the deepest drop cascades, the whole
    settle mass lands in the delete prefix masks."""
    core = imcore_bz(g)
    kmax = int(core.max())
    top = set(np.flatnonzero(core == kmax).tolist())
    live = sorted(_live_edges(g))
    ops = [Delete(*e) for e in live if e[0] in top or e[1] in top][:16]
    return [ops]


def _fam_reinsert(g, n, rng):
    """Delete edges and re-insert the same edges inside one batch (plus
    fresh inserts): the structural net effect interleaves with genuine
    changes — order-preserving application must still be exact."""
    live = sorted(_live_edges(g))
    idx = rng.choice(len(live), 8, replace=False)
    victims = [live[i] for i in idx]
    ops = [Delete(*e) for e in victims] + [Insert(*e) for e in victims]
    live_set = set(live)
    for _ in range(4):
        e = _rand_missing(rng, n, live_set)
        live_set.add(e)
        ops.append(Insert(*e))
    return [ops]


FAMILIES = {
    "insert_sparse": _fam_insert_sparse,
    "delete_sparse": _fam_delete_sparse,
    "mixed": _fam_mixed,
    "clique_lift": _fam_clique_lift,
    "hub_churn": _fam_hub_churn,
    "cascade_delete": _fam_cascade_delete,
    "reinsert": _fam_reinsert,
}


def _pair(g, backend):
    """(parallel maintainer on ``backend``, serial numpy per-edge oracle)."""
    par = CoreMaintainer(
        BufferedGraph(g),
        settings=Settings(backend=backend, parallel_maint=True))
    ser = CoreMaintainer(
        BufferedGraph(g), settings=Settings(parallel_maint=False))
    return par, ser


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_grouped_settle_matches_serial_oracle(family, backend):
    g, n = _graph(backend, seed=11 + len(family))
    rng = np.random.default_rng(29)
    batches = FAMILIES[family](g, n, rng)
    par, ser = _pair(g, backend)
    for ops in batches:
        sp = par.apply(UpdateBatch(ops))
        ser.apply(UpdateBatch(ops))
        assert sp.algorithm.startswith("parallel(")
        np.testing.assert_array_equal(par.core, ser.core)
        np.testing.assert_array_equal(par.cnt, ser.cnt)
    # and both equal recompute-from-scratch on the final graph
    np.testing.assert_array_equal(par.core, imcore_bz(par.bg.materialize()))


# ----------------------------------------------------------- adversarial
def test_adversarial_net_noop_batch():
    """delete(e) then insert(e) in one batch: the graph round-trips, so the
    settled state must equal the initial decomposition exactly."""
    g = chung_lu(200, 800, seed=5)
    par = CoreMaintainer(BufferedGraph(g), backend="xla")
    core0, cnt0 = par.core.copy(), par.cnt.copy()
    live = sorted(_live_edges(g))[:12]
    ops = [Delete(*e) for e in live] + [Insert(*e) for e in live]
    par.apply(UpdateBatch(ops))
    np.testing.assert_array_equal(par.core, core0)
    np.testing.assert_array_equal(par.cnt, cnt0)


def test_adversarial_duplicate_and_missing_ops():
    """Duplicate inserts, deletes of absent edges, and an empty batch are
    counted as no-ops, never corrupt state."""
    g = chung_lu(200, 800, seed=7)
    par, ser = _pair(g, "xla")
    e = _rand_missing(np.random.default_rng(0), 200, _live_edges(g))
    ops = [Insert(*e), Insert(*e), Delete(199, 198 if e != (198, 199) else 0)]
    sp = par.apply(UpdateBatch(ops))
    ser.apply(UpdateBatch(ops))
    assert sp.num_noops >= 1
    np.testing.assert_array_equal(par.core, ser.core)
    np.testing.assert_array_equal(par.cnt, ser.cnt)
    s_empty = par.apply(UpdateBatch())
    assert s_empty.num_deletes == s_empty.num_inserts == 0
    np.testing.assert_array_equal(par.core, ser.core)


def test_adversarial_isolated_nodes():
    """Edges among previously isolated nodes (degree 0 -> small core)."""
    base = erdos_renyi(60, 150, seed=3)
    # append 6 isolated nodes
    g = type(base).from_edges(base.n + 6, base.edge_list())
    par, ser = _pair(g, "xla")
    iso = list(range(base.n, base.n + 6))
    ops = [Insert(iso[0], iso[1]), Insert(iso[1], iso[2]),
           Insert(iso[2], iso[0]), Insert(iso[3], 0)]
    par.apply(UpdateBatch(ops))
    ser.apply(UpdateBatch(ops))
    np.testing.assert_array_equal(par.core, ser.core)
    np.testing.assert_array_equal(par.cnt, ser.cnt)


def test_group_cap_forces_serial_fallback_and_stays_exact():
    """group_cap=1 marks every insert component heavy: the round falls back
    to the serial warm settle, which must stay exact (and be counted)."""
    g = chung_lu(200, 800, seed=9)
    par = CoreMaintainer(BufferedGraph(g), backend="xla", group_cap=1)
    ser = CoreMaintainer(
        BufferedGraph(g), settings=Settings(parallel_maint=False))
    rng = np.random.default_rng(1)
    live = _live_edges(g)
    ops = []
    for _ in range(8):
        e = _rand_missing(rng, 200, live)
        live.add(e)
        ops.append(Insert(*e))
    sp = par.apply(UpdateBatch(ops))
    ser.apply(UpdateBatch(ops))
    assert sp.fallbacks >= 1
    np.testing.assert_array_equal(par.core, ser.core)
    np.testing.assert_array_equal(par.cnt, ser.cnt)


# ------------------------------------------------- candidate-bound soundness
def test_candidate_bound_covers_every_changed_node(monkeypatch):
    """Soundness of the planner's bounds: every node whose core changed is
    covered by some round's plan — a rise inside a planned candidate set,
    a drop inside a planned delete prefix (``core0 <= c``)."""
    g = chung_lu(300, 1200, seed=21)
    par = CoreMaintainer(BufferedGraph(g), backend="xla")
    core_before = par.core.copy()

    plans = []
    orig_batch, orig_risers = pm.plan_batch, pm.plan_risers

    def rec_batch(*a, **k):
        p = orig_batch(*a, **k)
        plans.append((p, a[1].copy()))  # (plan, round-start core0)
        return p

    def rec_risers(*a, **k):
        p = orig_risers(*a, **k)
        plans.append((p, a[1].copy()))
        return p

    monkeypatch.setattr(pm, "plan_batch", rec_batch)
    monkeypatch.setattr(pm, "plan_risers", rec_risers)

    rng = np.random.default_rng(2)
    live = _live_edges(g)
    ops = []
    for _ in range(24):
        if rng.random() < 0.5 and live:
            e = sorted(live)[int(rng.integers(len(live)))]
            live.discard(e)
            ops.append(Delete(*e))
        else:
            e = _rand_missing(rng, 300, live)
            live.add(e)
            ops.append(Insert(*e))
    stats = par.apply(UpdateBatch(ops))
    assert stats.algorithm.startswith("parallel(")
    assert plans, "parallel path did not plan?"

    covered = np.zeros(300, dtype=bool)
    for plan, core_r in plans:
        for up in plan.updates:
            covered[np.asarray(up.cand, dtype=np.int64)] = True
            if up.prefix_level >= 0:
                covered |= core_r <= up.prefix_level
    changed = par.core != core_before
    stray = np.flatnonzero(changed & ~covered)
    assert stray.size == 0, f"changed outside every plan bound: {stray[:10]}"


# ------------------------------------------------------ replica replay parity
def test_replica_replay_parity_under_parallel_maint(tmp_path):
    """Writer ingests with the parallel settle; a replica replays the op-
    vocabulary WAL through its own maintainer and lands bit-identical."""
    g = chung_lu(400, 1600, seed=17)
    svc = CoreService(
        g, block_edges=128,
        wal_path=str(tmp_path / "wal.jsonl"),
        snapshot_dir=str(tmp_path / "snaps"),
        settings=Settings(backend="xla", parallel_maint=True),
    )
    svc.snapshot()
    rng = np.random.default_rng(4)
    live = _live_edges(g)
    for _ in range(4):
        ops = []
        for _ in range(16):
            if rng.random() < 0.5 and live:
                e = sorted(live)[int(rng.integers(len(live)))]
                live.discard(e)
                ops.append(("-",) + e)
            else:
                e = _rand_missing(rng, 400, live)
                live.add(e)
                ops.append(("+",) + e)
        svc.ingest(ops)
    rep = CoreReplica(
        snapshot_dir=str(tmp_path / "snaps"),
        wal_path=str(tmp_path / "wal.jsonl"), block_edges=128)
    rep.sync()
    assert rep.epoch == svc.epoch
    np.testing.assert_array_equal(rep.maintainer.core, svc.maintainer.core)
    np.testing.assert_array_equal(rep.maintainer.cnt, svc.maintainer.cnt)


# --------------------------------------------------------- deprecation shims
def test_apply_batch_shim_warns_and_matches_apply():
    g = chung_lu(150, 600, seed=8)
    a = CoreMaintainer(BufferedGraph(g), backend="xla")
    b = CoreMaintainer(BufferedGraph(g), backend="xla")
    dels = sorted(_live_edges(g))[:5]
    ins = [(0, 149), (1, 148)]
    with pytest.warns(DeprecationWarning, match="apply_batch.*deprecated"):
        a.apply_batch(dels, ins)
    b.apply(UpdateBatch.from_pairs(dels, ins))
    np.testing.assert_array_equal(a.core, b.core)
    np.testing.assert_array_equal(a.cnt, b.cnt)


def test_wal_append_shim_warns_and_replays_identically(tmp_path):
    new = str(tmp_path / "new.jsonl")
    old = str(tmp_path / "old.jsonl")
    batch = UpdateBatch.from_pairs([(0, 1), (2, 3)], [(4, 5)])
    w = WriteAheadLog(new)
    w.append(1, batch)
    w.close()
    w = WriteAheadLog(old)
    with pytest.warns(DeprecationWarning, match="pass an UpdateBatch"):
        w.append(1, [(0, 1), (2, 3)], [(4, 5)])
    w.close()
    got_new = list(WriteAheadLog.replay(new))
    got_old = list(WriteAheadLog.replay(old))
    assert got_new == got_old == [(1, batch)]


def test_legacy_del_ins_records_still_replay(tmp_path):
    """A pre-op-vocabulary WAL (``del``/``ins`` records) decodes to the
    canonical deletes-then-inserts UpdateBatch."""
    import json

    from repro.stream.integrity import frame_record

    path = str(tmp_path / "legacy.jsonl")
    rec = {"epoch": 3, "del": [[1, 2]], "ins": [[3, 4], [5, 6]]}
    with open(path, "wb") as f:
        f.write(frame_record(json.dumps(rec).encode()))
        f.write(json.dumps({"epoch": 4, "del": [], "ins": [[7, 8]]})
                .encode() + b"\n")  # unframed legacy line
    got = list(WriteAheadLog.replay(path))
    assert got == [
        (3, UpdateBatch.from_pairs([(1, 2)], [(3, 4), (5, 6)])),
        (4, UpdateBatch.from_pairs([], [(7, 8)])),
    ]


# ------------------------------------------------------------- runtime knob
def test_parallel_maint_env_toggle(monkeypatch):
    g = chung_lu(150, 600, seed=10)
    monkeypatch.setenv("REPRO_PARALLEL_MAINT", "0")
    m = CoreMaintainer(BufferedGraph(g), backend="xla")
    s = m.apply(UpdateBatch.from_pairs([], [(0, 149)]))
    assert not s.algorithm.startswith("parallel(")
    monkeypatch.setenv("REPRO_PARALLEL_MAINT", "1")
    s = m.apply(UpdateBatch.from_pairs([(0, 149)], []))
    assert s.algorithm.startswith("parallel(")


def test_compile_cache_dir_env_wins_else_fixed_path(monkeypatch, tmp_path):
    """Entry points keep JAX's compile cache where the env says, else at a
    fixed path in the checkout (never a temporary or per-run name)."""
    import jax

    from repro.runtime import enable_compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert enable_compile_cache(str(tmp_path)) == str(tmp_path / "env")
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = str(tmp_path / ".jax_cache")
    assert enable_compile_cache(str(tmp_path)) == want
    assert calls == [("jax_compilation_cache_dir", want)]
