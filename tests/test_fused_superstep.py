"""Fused Pallas superstep (DESIGN.md §16).

Three layers of evidence that the one-pallas_call-per-pass path is exact:

* a differential battery of ``fused_pass`` / ``fused_hindex`` /
  ``fused_counts`` against the eager jnp oracle (``kernels/ref.py``) on
  block-boundary shapes — empty/all/random frontiers, a single partial tail
  block, n not a multiple of the tile, isolated nodes;
* the paper's Fig. 2/4/5 cells end-to-end through the pallas backend, pinned
  bit-identical to the numpy planner traces;
* kernel_blocks_active/skipped parity against the per-probe
  ``segment_sum_active`` path (``REPRO_PALLAS_FUSED=0``) on all three
  algorithms — the replayed accounting may not notice which kernel ran.
"""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.graph import paper_example_graph, chung_lu, erdos_renyi
from repro.core.semicore import decompose
from repro.kernels.fused_superstep import (
    build_fused_table, fused_pass, fused_hindex, fused_counts,
    fused_block_edges)
from repro.kernels.ref import fused_superstep_ref

EXPECTED_CORES = np.array([3, 3, 3, 3, 2, 2, 2, 2, 1])
ALGORITHMS = ("semicore", "semicore+", "semicore*")


# ------------------------------------------------------------- differential
def _rand_csr(n, m, rng, iso_frac=0.0):
    """Random multigraph CSR; neighbors always point at present nodes."""
    deg = rng.integers(0, max(1, 2 * m // max(n, 1)), size=n)
    if iso_frac:
        deg[rng.random(n) < iso_frac] = 0
    seg_ptr = np.zeros(n + 1, dtype=np.int64)
    seg_ptr[1:] = np.cumsum(deg)
    E = int(seg_ptr[-1])
    pres = np.flatnonzero(deg > 0)
    if len(pres) == 0:
        return seg_ptr, np.zeros(0, np.int32)
    nbr = rng.choice(pres, size=E).astype(np.int32)
    return seg_ptr, nbr


def _one_case(n, m, cbe, rng, iso_frac, frontier_mode, algorithm):
    seg_ptr, nbr = _rand_csr(n, m, rng, iso_frac)
    deg = np.diff(seg_ptr)
    rows = np.repeat(np.arange(n, dtype=np.int32), deg)
    core = np.minimum(deg, rng.integers(0, 12, size=n)).astype(np.int32)
    core = np.where(deg > 0, np.maximum(core, 1), 0).astype(np.int32)
    cnt = rng.integers(0, 8, size=n).astype(np.int32)
    if frontier_mode == "empty":
        active = np.zeros(n, bool)
    elif frontier_mode == "all":
        active = core > 0
    else:
        active = (core > 0) & (rng.random(n) < 0.4)
    cmax = int(core[active].max()) if active.any() else 0
    num_probes = max(1, math.ceil(math.log2(cmax + 2)))

    ft = build_fused_table(seg_ptr, nbr, n, cbe)
    got = fused_pass(jnp.asarray(core), jnp.asarray(cnt), jnp.asarray(active),
                     ft.arrays, dims=ft.dims, num_probes=num_probes,
                     algorithm=algorithm, interpret=True)
    want = fused_superstep_ref(core, cnt, active, nbr, rows, n, algorithm)
    for name, g_, w_ in zip(("core2", "cnt2", "active2", "upd"), got, want):
        if w_ is None:
            continue
        np.testing.assert_array_equal(
            np.asarray(g_), np.asarray(w_),
            err_msg=f"{algorithm}/{frontier_mode} n={n} cbe={cbe} {name}")
    return ft, core, active, cmax, nbr, rows


# (n, m, cbe, iso_frac, frontier): multi-block, single partial tail block,
# n not a multiple of anything, isolated nodes, empty/all/random frontiers
CASES = [
    (50, 200, 16, 0.0, "all"),
    (50, 200, 16, 0.0, "rand"),
    (50, 200, 16, 0.0, "empty"),
    (40, 60, 512, 0.0, "rand"),       # one partial tail block
    (33, 130, 16, 0.3, "rand"),       # isolated nodes, odd n
    (7, 9, 8, 0.0, "all"),            # tiny
]


@pytest.mark.parametrize("algorithm", ALGORITHMS)
def test_fused_pass_matches_ref(algorithm):
    rng = np.random.default_rng(0)
    for (n, m, cbe, iso, fr) in CASES:
        _one_case(n, m, cbe, rng, iso, fr, algorithm)


def test_fused_hindex_and_counts_match_ref():
    rng = np.random.default_rng(1)
    for (n, m, cbe, iso, fr) in CASES:
        ft, core, active, cmax, nbr, rows = _one_case(
            n, m, cbe, rng, iso, fr, "semicore*")
        num_probes = max(1, math.ceil(math.log2(cmax + 2)))
        h_g, cnth_g = fused_hindex(
            jnp.asarray(core), jnp.asarray(active), ft.arrays, dims=ft.dims,
            num_probes=num_probes, interpret=True)
        want = fused_superstep_ref(core, None, active, nbr, rows, n,
                                   "semicore")
        h_want = np.where(active, np.asarray(want[0]), 0)
        np.testing.assert_array_equal(np.asarray(h_g) * active, h_want)
        # counts at arbitrary thresholds vs a numpy scatter
        thr = np.where(active, rng.integers(0, cmax + 1, size=n), 0)
        want_cnt = np.zeros(n, np.int64)
        np.add.at(want_cnt, rows,
                  (core[nbr] >= thr[rows]).astype(np.int64))
        got_cnt = np.asarray(fused_counts(
            jnp.asarray(core), jnp.asarray(thr), jnp.asarray(active),
            ft.arrays, dims=ft.dims, interpret=True))
        np.testing.assert_array_equal(got_cnt[active], want_cnt[active])


def test_adaptive_tile_default(monkeypatch):
    monkeypatch.delenv("REPRO_FUSED_BLOCK_EDGES", raising=False)
    assert fused_block_edges() == 512
    monkeypatch.setenv("REPRO_FUSED_BLOCK_EDGES", "64")
    assert fused_block_edges() == 64
    monkeypatch.setenv("REPRO_FUSED_BLOCK_EDGES", "4")
    with pytest.raises(ValueError):
        fused_block_edges()


# ------------------------------------------------------- Fig. 2/4/5 pins
@pytest.mark.parametrize("algorithm", ALGORITHMS)
def test_paper_example_trace_pins_through_fused_backend(algorithm):
    """Fig. 2/4/5 cells: the fused pallas batch run must walk the numpy
    planner's exact passes — same cores, iterations, planner I/O, and
    per-pass update counts."""
    g = paper_example_graph()
    rn = decompose(g, algorithm, "batch", block_edges=8, backend="numpy")
    rp = decompose(g, algorithm, "batch", block_edges=8, backend="pallas")
    np.testing.assert_array_equal(rp.core, EXPECTED_CORES)
    np.testing.assert_array_equal(rp.core, rn.core)
    assert rp.iterations == rn.iterations
    assert rp.edge_block_reads == rn.edge_block_reads
    assert rp.node_table_reads == rn.node_table_reads
    assert rp.updates_per_iter == rn.updates_per_iter
    if algorithm == "semicore*":
        np.testing.assert_array_equal(rp.cnt, rn.cnt)


# --------------------------------------- accounting parity vs per-probe
@pytest.mark.parametrize("algorithm", ALGORITHMS)
def test_kernel_block_accounting_parity_vs_per_probe(algorithm, monkeypatch):
    """kernel_blocks_active/skipped replay identically whether the pallas
    backend runs the fused kernel or the PR 3 per-probe dispatch."""
    g = chung_lu(400, 1600, seed=3)
    monkeypatch.setenv("REPRO_PALLAS_FUSED", "0")
    r_probe = decompose(g, algorithm, "batch", block_edges=64,
                        backend="pallas")
    monkeypatch.setenv("REPRO_PALLAS_FUSED", "1")
    r_fused = decompose(g, algorithm, "batch", block_edges=64,
                        backend="pallas")
    np.testing.assert_array_equal(r_fused.core, r_probe.core)
    assert r_fused.iterations == r_probe.iterations
    assert r_fused.edge_block_reads == r_probe.edge_block_reads
    assert r_fused.kernel_blocks_active == r_probe.kernel_blocks_active
    assert r_fused.kernel_blocks_skipped == r_probe.kernel_blocks_skipped
    if algorithm == "semicore*":
        assert r_fused.kernel_blocks_skipped > 0


def test_fused_backend_matches_oracle_random():
    from repro.core.imcore import imcore_peel
    for seed in range(2):
        g = erdos_renyi(300, 900, seed=seed)
        expect = imcore_peel(g)
        for algorithm in ALGORITHMS:
            r = decompose(g, algorithm, "batch", block_edges=64,
                          backend="pallas")
            np.testing.assert_array_equal(r.core, expect,
                                          err_msg=f"{algorithm}/{seed}")
