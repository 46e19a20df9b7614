"""Sharded on-mesh backend (engine.ShardedBackend + resident.run_sharded,
DESIGN.md §13).

Pins the four properties the fold-in claims:

* **trace parity** — the on-mesh fixpoint walks the numpy backend's exact
  batch passes (paper Fig. 2/4/5 pins, warm-settle charge parity), and the
  walk is *shard-count invariant*: 1/2/8 shards on a forced 8-device host
  produce bit-identical core/cnt/iters/planner-I/O traces;
* **compile count** — jit traces per decompose stay O(1) (one chunk fn),
  independent of pass count;
* **structure residency** — the sharded edge table is version-keyed like the
  flat resident table: reused across runs and no-op batches, re-sharded
  exactly once per structural change;
* **layout hygiene** — contiguous shards are minimax-balanced by edge count
  (the rectangular (S, E) padding bugfix), padding is surfaced on the
  result, and int32 offset overflow fails loudly.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import resident
from repro.core.distributed import (
    balanced_bounds,
    distributed_decompose,
    shard_arrays,
    shard_graph,
)
from repro.core.engine import ShardedBackend, warm_settle
from repro.core.imcore import imcore_bz
from repro.core.maintenance import CoreMaintainer
from repro.core.semicore import HostEngine, decompose
from repro.graph import BufferedGraph, CSRGraph, chung_lu, paper_example_graph
from repro.stream.service import CoreService


# -------------------------------------------------------------- trace parity
def test_shard_pins_paper_example_batch_traces():
    """The on-mesh path must walk the paper's running example through the
    exact batch-schedule traces the numpy backend pins (Figs. 2/4/5)."""
    pinned = {
        "semicore": (36, 4, 4, 4),
        "semicore+": (26, 4, 4, 4),
        "semicore*": (11, 3, 3, 3),
    }
    for algo, (comps, iters, ebr, ntr) in pinned.items():
        r = decompose(paper_example_graph(), algo, "batch", block_edges=64,
                      pool_blocks=1, backend="shard")
        np.testing.assert_array_equal(r.core, [3, 3, 3, 3, 2, 2, 2, 2, 1])
        assert r.node_computations == comps, algo
        assert r.iterations == iters, algo
        assert r.edge_block_reads == ebr, algo
        assert r.node_table_reads == ntr, algo
        assert r.num_shards >= 1


def test_shard_full_history_parity_vs_numpy():
    g = chung_lu(250, 900, gamma=2.3, seed=11)
    for algo in ("semicore", "semicore+", "semicore*"):
        ref = decompose(g, algo, "batch", block_edges=64, backend="numpy")
        r = decompose(g, algo, "batch", block_edges=64, backend="shard")
        np.testing.assert_array_equal(r.core, ref.core)
        if ref.cnt is not None:
            np.testing.assert_array_equal(r.cnt, ref.cnt)
        assert r.iterations == ref.iterations
        assert r.node_computations == ref.node_computations
        assert r.updates_per_iter == ref.updates_per_iter
        assert r.computations_per_iter == ref.computations_per_iter
        assert r.edge_block_reads == ref.edge_block_reads
        assert r.node_table_reads == ref.node_table_reads


def test_warm_settle_shard_matches_numpy_settle():
    """The on-mesh warm settle (exact-cnt prologue on the bound sharded
    structure + SemiCore* passes) must match the numpy settle
    state-for-state and charge-for-charge."""
    g = chung_lu(300, 1200, seed=5)
    core0 = decompose(g, "semicore*", "batch", backend="numpy").core
    e = g.edge_list()

    def perturbed():
        bg = BufferedGraph(g)
        for i in range(6):
            assert bg.delete_edge(*map(int, e[i * 11]))
        ins = [(1, 250), (2, 251), (3, 252)]
        ni = sum(bg.insert_edge(u, v) for u, v in ins)
        return bg, ni

    bg_np, ni = perturbed()
    r_np = warm_settle(HostEngine(bg_np, block_edges=64), core0, ni, "numpy")
    bg_sh, ni_sh = perturbed()
    assert ni_sh == ni
    r_sh = warm_settle(HostEngine(bg_sh, block_edges=64), core0, ni, "shard")
    np.testing.assert_array_equal(r_sh.core, r_np.core)
    np.testing.assert_array_equal(r_sh.cnt, r_np.cnt)
    assert r_sh.iterations == r_np.iterations
    assert r_sh.edge_block_reads == r_np.edge_block_reads
    assert r_sh.node_table_reads == r_np.node_table_reads
    np.testing.assert_array_equal(r_sh.core, imcore_bz(bg_sh.materialize()))


# ------------------------------------------------------ shard-count invariance
_INVARIANCE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
assert len(jax.devices()) == 8
from repro.graph import chung_lu
from repro.core.imcore import imcore_bz
from repro.core.semicore import decompose
from repro.core.engine import ShardedBackend

g = chung_lu(250, 900, gamma=2.3, seed=11)
expect = imcore_bz(g)
for algo in ("semicore", "semicore+", "semicore*"):
    ref = decompose(g, algo, "batch", block_edges=64, backend="numpy")
    traces = set()
    for S in (1, 2, 8):
        r = decompose(g, algo, "batch", block_edges=64,
                      backend=ShardedBackend(num_shards=S))
        assert np.array_equal(r.core, expect), (algo, S)
        assert r.num_shards == S
        if ref.cnt is not None:
            assert np.array_equal(r.cnt, ref.cnt), (algo, S)
        traces.add((r.iterations, r.node_computations, r.edge_block_reads,
                    r.node_table_reads, tuple(r.updates_per_iter),
                    tuple(r.computations_per_iter)))
    assert traces == {(ref.iterations, ref.node_computations,
                       ref.edge_block_reads, ref.node_table_reads,
                       tuple(ref.updates_per_iter),
                       tuple(ref.computations_per_iter))}, (algo, traces)
# default mesh width = every visible device
r = decompose(g, "semicore*", "batch", block_edges=64, backend="shard")
assert r.num_shards == 8 and np.array_equal(r.core, expect)
print("SHARD_INVARIANCE_OK")
"""


@pytest.mark.slow
def test_shard_count_invariance_under_8_forced_devices():
    """1/2/8 shards must produce the identical core/cnt/iters/planner-I/O
    trace — the mesh cut is pure layout, never scheduling."""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", _INVARIANCE_SCRIPT],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(__file__)),
    )
    assert "SHARD_INVARIANCE_OK" in out.stdout, out.stderr[-2000:]


# ------------------------------------------------------------- compile count
def test_shard_compile_count_independent_of_pass_count():
    g = chung_lu(4000, 16000, seed=6)
    before = resident.trace_count()
    r1 = decompose(g, "semicore*", "batch", block_edges=256, backend="shard")
    first = resident.trace_count() - before
    assert r1.iterations >= 20  # far more passes than allowed traces
    assert first <= 2, f"{first} traces for {r1.iterations} passes"
    before = resident.trace_count()
    r2 = decompose(g, "semicore*", "batch", block_edges=256, backend="shard")
    assert resident.trace_count() - before == 0
    np.testing.assert_array_equal(r1.core, r2.core)


# -------------------------------------------------------- structure caching
def test_shard_structure_cache_reused_across_apply_batch():
    g = chung_lu(200, 800, seed=7)
    m = CoreMaintainer(g, block_edges=64, backend="shard")
    assert m.backend.retain_structure
    assert m.backend.structure_builds == 1  # the initial decompose
    # a batch of pure no-ops applies nothing: no settle, no re-shard
    non_edge = next((u, v) for u in range(3) for v in range(100, 200)
                    if not m.bg.base.has_edge(u, v))
    s = m.apply_batch([non_edge], [])
    assert s.num_noops == 1 and s.num_deletes == 0
    assert m.backend.structure_builds == 1
    # a real batch bumps the version: exactly one re-shard for the settle
    e = m.bg.base.edge_list()
    s = m.apply_batch([tuple(map(int, e[3]))], [(0, 150)])
    assert s.num_deletes == 1
    assert m.backend.structure_builds == 2
    np.testing.assert_array_equal(m.core, imcore_bz(m.bg.materialize()))


def test_shard_one_shot_run_drops_structure_on_unbind():
    be = ShardedBackend()
    from repro.core.engine import run_batch

    eng = HostEngine(chung_lu(150, 500, seed=2), block_edges=64)
    run_batch(eng, "semicore*", be)
    assert be._resident is None


# ------------------------------------------------------------- service path
def test_core_service_on_shard_backend_stays_exact():
    g = chung_lu(220, 900, seed=9)
    svc = CoreService(g, block_edges=64, backend="shard")
    e = g.edge_list()
    svc.ingest([("-", *map(int, e[0])), ("-", *map(int, e[7])),
                ("+", 0, 100)])
    svc.ingest([("+", 2, 150), ("-", *map(int, e[21]))])
    np.testing.assert_array_equal(
        svc.maintainer.core, imcore_bz(svc.bg.materialize()))
    stats = svc.service_stats()
    assert stats["backend"] == "shard"
    assert stats["backend_structure_builds"] >= 1


# ------------------------------------------------------------ layout hygiene
def test_balanced_bounds_is_minimax_optimal():
    """The binary-search cut must match the brute-force minimax optimum for
    contiguous ranges (the (S, E) padding is driven by the heaviest shard)."""
    rng = np.random.default_rng(3)
    for _ in range(20):
        deg = rng.integers(0, 9, size=rng.integers(3, 12))
        seg_ptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int64)
        n = len(deg)
        S = int(rng.integers(1, 5))
        bounds = balanced_bounds(seg_ptr, S)
        assert bounds[0] == 0 and bounds[-1] == n
        assert (np.diff(bounds) >= 0).all()
        got = int((seg_ptr[bounds[1:]] - seg_ptr[bounds[:-1]]).max())
        # brute force over all contiguous S-partitions
        from itertools import combinations

        best = min(
            max(seg_ptr[b] - seg_ptr[a]
                for a, b in zip((0,) + cuts, cuts + (n,)))
            for cuts in combinations(range(1, n), min(S - 1, n - 1))
        ) if S > 1 and n > 1 else int(seg_ptr[-1])
        assert got == best, (deg.tolist(), S, got, best)


def test_shard_graph_balance_and_padding_stats():
    g = chung_lu(5000, 40000, seed=3)
    sg = shard_graph(g, 8)
    per_shard = sg.edge_mask.sum(axis=1)
    np.testing.assert_array_equal(per_shard, sg.per_shard_edges)
    assert per_shard.sum() == g.num_directed
    assert sg.owned_mask.sum() == g.n
    assert per_shard.max() <= 1.6 * per_shard.mean()  # balanced cuts
    assert sg.pad_edges == 8 * sg.dst.shape[1] - g.num_directed
    # local segment offsets must tile each shard's real edge span exactly
    for s in range(8):
        nv = int(sg.owned_mask[s].sum())
        assert sg.lsegptr[s, 0] == 0
        assert sg.lsegptr[s, nv] == per_shard[s]
        assert (np.diff(sg.lsegptr[s]) >= 0).all()
    # padding stats reach the DecompResult
    r = decompose(g, "semicore*", "batch", block_edges=256, backend="shard")
    assert r.num_shards >= 1
    assert r.shard_pad_edges >= 0


def test_skewed_graph_rebalance_beats_naive_split():
    """A hub-heavy graph: minimax cuts keep the rectangular padding at the
    information-theoretic floor (heaviest node's adjacency)."""
    # one hub with 400 edges + a long path
    hub = np.stack([np.zeros(400, np.int64),
                    np.arange(1, 401, dtype=np.int64)], 1)
    path = np.stack([np.arange(401, 800, dtype=np.int64),
                     np.arange(402, 801, dtype=np.int64)], 1)
    g = CSRGraph.from_edges(801, np.concatenate([hub, path]))
    sg = shard_graph(g, 4)
    # the hub shard is unavoidable; every other shard must stay near the mean
    assert sg.per_shard_edges.max() <= g.degrees().max() + \
        -(-g.num_directed // 4)


def test_shard_int32_validation_raises_loudly():
    with pytest.raises(ValueError, match="int32"):
        shard_arrays(np.zeros(0, np.int32), np.zeros(2, np.int64), 1,
                     n=1 << 31)


def test_num_shards_validation_and_env(monkeypatch):
    g = paper_example_graph()
    with pytest.raises(ValueError, match="device"):
        decompose(g, "semicore*", "batch",
                  backend=ShardedBackend(num_shards=4096))
    monkeypatch.setenv("REPRO_NUM_SHARDS", "1")
    monkeypatch.setenv("REPRO_BACKEND", "shard")
    r = decompose(g, "semicore*", "batch", block_edges=64)
    assert r.backend == "shard" and r.num_shards == 1


# --------------------------------------------------------- budgeted prefix
def test_distributed_decompose_budgeted_prefix_and_warm_restart():
    g = chung_lu(1000, 4000, seed=5)
    expect = imcore_bz(g)
    core, iters = distributed_decompose(g)
    np.testing.assert_array_equal(core, expect)
    budget = max(2, iters // 2)
    partial, done = distributed_decompose(g, max_supersteps=budget)
    assert done < iters
    assert (partial >= expect).all()  # any prefix is a valid upper bound
    core2, extra = distributed_decompose(g, core0=partial)
    np.testing.assert_array_equal(core2, expect)
    assert extra <= iters


# ------------------------------------- four devices: counters and spans
_FOUR_DEVICE_SCRIPT = r"""
import json
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax
assert len(jax.devices()) == 4
from repro.core.distributed import shard_arrays
from repro.core.imcore import imcore_bz
from repro.core.semicore import decompose
from repro.graph import chung_lu
from repro.obs import trace
from repro.obs.metrics import get_registry

# LJ's density (m/n = 14.2) and power law (gamma 2.5) at 3,000 nodes
g = chung_lu(3000, 42700, gamma=2.5, seed=20151102)
ref = decompose(g, "semicore*", "batch", block_edges=256, backend="numpy")
decompose(g, "semicore*", "batch", block_edges=256, backend="shard")
before = get_registry().snapshot()
trace.clear_trace()
trace.start_trace()
r = decompose(g, "semicore*", "batch", block_edges=256, backend="shard")
trace.stop_trace()
spans = [(e["name"], e["ts"], e["ts"] + e["dur"])
         for e in trace.get_collector().events if e["ph"] == "X"]
sg = shard_arrays(np.asarray(g.adj), g.indptr, 4, n=g.n)


def walk(x):
    return [x.iterations, x.node_computations, x.edge_block_reads,
            x.node_table_reads, list(x.updates_per_iter),
            list(x.computations_per_iter)]


print(json.dumps({
    "n": g.n, "E": int(g.num_directed), "S": int(sg.owned_ids.shape[0]),
    "V": int(sg.owned_ids.shape[1]), "pad": int(sg.pad_edges),
    "Emax": int(sg.dst.shape[1]), "dmax": int(g.degrees().max()),
    "num_shards": r.num_shards, "shard_pad_edges": r.shard_pad_edges,
    "core_matches_numpy": bool(np.array_equal(r.core, ref.core)),
    "core_matches_peel": bool(np.array_equal(r.core, imcore_bz(g))),
    "cnt_matches_numpy": bool(np.array_equal(r.cnt, ref.cnt)),
    "walk": walk(r), "walk_numpy": walk(ref),
    "delta": get_registry().delta(before), "spans": spans,
}))
"""


@pytest.fixture(scope="module")
def four_devices():
    """One decompose of an LJ-shaped graph on a forced 4-device host: its
    result, its registry deltas and its spans."""
    import json

    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", _FOUR_DEVICE_SCRIPT],
        capture_output=True, text=True, env=env, timeout=600,
        cwd=os.path.dirname(os.path.dirname(__file__)),
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _chunk_spans(run):
    return [s for s in run["spans"] if s[0] == "resident.chunk"]


def test_four_shard_lj_shaped_decompose_matches_numpy(four_devices):
    run = four_devices
    assert run["num_shards"] == run["S"] == 4
    assert run["core_matches_numpy"] and run["core_matches_peel"]
    assert run["cnt_matches_numpy"]
    assert run["walk"] == run["walk_numpy"]  # passes and the I/O trace
    assert run["walk"][0] > 8  # more than one chunk call


def test_four_shard_allgather_bytes_are_ids_per_call_and_core_per_pass(
        four_devices):
    run = four_devices
    calls, passes = len(_chunk_spans(run)), run["walk"][0]
    assert calls == -(-passes // resident.chunk_len())
    assert run["delta"]["repro_shard_allgather_bytes_total"] == \
        (calls + passes) * run["S"] * run["V"] * 4


def test_four_shard_slots_count_the_layout(four_devices):
    run = four_devices
    d = run["delta"]
    assert d['repro_shard_slots_total{kind="real"}'] == run["E"]
    assert d['repro_shard_slots_total{kind="pad"}'] == run["pad"] == \
        run["shard_pad_edges"]


def test_four_shard_state_h2d_bytes_are_the_arrays_nbytes(four_devices):
    run = four_devices
    S, V = run["S"], run["V"]
    # core (n int32), cnt (S, V) int32, the frontier (S, V) bool, nact
    assert run["delta"]['repro_resident_h2d_bytes_total{what="state"}'] == \
        run["n"] * 4 + S * V * 4 + S * V + 4


def test_four_shard_row_bcasts_are_segptr_probes_plus_two(four_devices):
    """Every pass spreads owned values to edge slots from ``lseg``: its
    probes, the cnt refresh and the push rule; no gather by ``rows``."""
    run = four_devices
    d = run["delta"]
    probes = max(1, int(np.ceil(np.log2(run["dmax"] + 2))))
    assert d['repro_resident_row_bcast_total{how="segptr"}'] == \
        run["walk"][0] * (probes + 2)
    assert d.get('repro_resident_row_bcast_total{how="gather"}', 0.0) == 0.0


def test_four_shard_edge_table_h2d_bytes_hold_no_rows(four_devices):
    run = four_devices
    S, V, Emax = run["S"], run["V"], run["Emax"]
    assert Emax * S == run["E"] + run["pad"]
    # dst (int32), emask (bool), lseg (int32), owned ids (int32), owned mask
    assert run["delta"]['repro_resident_h2d_bytes_total{what="edge_table"}'] \
        == S * Emax * 4 + S * Emax + S * (V + 1) * 4 + S * V * 4 + S * V


def test_four_shard_globalize_spans_nest_in_chunk_spans(four_devices):
    run = four_devices
    chunks = _chunk_spans(run)
    glob = [s for s in run["spans"] if s[0] == "resident.globalize"]
    # each call's frontier masks, and the last call's cnt
    assert len(glob) == len(chunks) + 1
    for _, a, b in glob:
        assert any(c0 <= a and b <= c1 for _, c0, c1 in chunks)


def test_shard_settle_mask_uploads_once():
    """A masked settle over many chunk calls uploads its state once: the
    settle mask goes up sharded before the first call, not with each."""
    from repro.obs.metrics import get_registry

    g = chung_lu(400, 1600, seed=8)
    eng = HostEngine(g, block_edges=64)
    core = g.degrees().astype(np.int64)
    mask = np.ones(g.n, dtype=bool)
    mask[::3] = False
    before = get_registry().snapshot()
    r = resident.run_resident(eng, "semicore*", ShardedBackend(num_shards=1),
                              core=core, cnt=np.zeros(g.n, np.int64),
                              superstep_chunk=1, settle_mask=mask)
    d = get_registry().delta(before)
    assert r.iterations > 3  # as many chunk calls
    V = shard_graph(g, 1).owned_ids.shape[1]
    # core, cnt, frontier, settle mask, nact
    assert d['repro_resident_h2d_bytes_total{what="state"}'] == \
        g.n * 4 + V * 4 + V + V + 4
