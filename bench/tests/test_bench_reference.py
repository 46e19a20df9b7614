"""The reference, the work count, the generators and the log reader."""
import numpy as np
import pytest

from bench import graph
from bench.generators.sampled_ops import UpdateStream
from bench.graphs.chung_lu import chung_lu
from bench.reference import cores, wal, work

# the paper's running example (Fig. 1), nine nodes and fifteen edges
PAPER_EDGES = [(0, 1), (0, 2), (0, 3), (1, 2), (1, 3), (2, 3), (2, 4),
               (3, 4), (3, 5), (3, 6), (4, 5), (5, 6), (5, 7), (5, 8), (6, 7)]


def paper_graph():
    e = np.asarray(PAPER_EDGES, dtype=np.int64)
    return graph.Graph.from_pairs(9, e[:, 0], e[:, 1])


def test_paper_example_cores_cnt_and_bytes_by_hand():
    g = paper_graph()
    assert cores.peel(g).tolist() == [3, 3, 3, 3, 2, 2, 2, 2, 1]
    core, frontiers = work.semicore_star(g)
    assert core.tolist() == [3, 3, 3, 3, 2, 2, 2, 2, 1]
    # pass 1 every node (degree sum 30), pass 2 v5 (degree 5), pass 3 v4
    # (degree 3): 8 B a neighbour, 12 B a node
    assert [f.tolist() for f in frontiers] == [list(range(9)), [5], [4]]
    assert work.pass_bytes(g, frontiers) == [8 * 30 + 12 * 9, 8 * 5 + 12,
                                             8 * 3 + 12]
    assert sum(work.pass_bytes(g, frontiers)) == 436
    # cnt(v) = neighbours with core >= core(v)
    assert cores.cnt(g, core).tolist() == [3, 3, 3, 3, 3, 4, 3, 2, 1]


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 17])
def test_reference_agrees_with_the_program(seed):
    """Peeling, the reference SemiCore* and the program's numpy engine give
    the same cores, cnt, and frontier sizes pass by pass."""
    from repro.core.semicore import decompose
    from repro.graph.storage import CSRGraph

    g = chung_lu(1500, 9000, 2.5, seed)
    core = cores.peel(g)
    ref_core, frontiers = work.semicore_star(g)
    r = decompose(CSRGraph(g.indptr.copy(), g.adj.copy()), "semicore*",
                  "batch", backend="numpy")
    assert np.array_equal(ref_core, core)
    assert np.array_equal(r.core, core)
    assert np.array_equal(r.cnt, cores.cnt(g, core))
    assert [len(f) for f in frontiers] == r.computations_per_iter


def test_pass_limit_stops_short_of_the_fixpoint():
    g = chung_lu(1500, 9000, 2.5, 3)
    _, frontiers = work.semicore_star(g)
    short, _ = work.semicore_star(g, max_passes=len(frontiers) - 1)
    assert (short != cores.peel(g)).sum() > 0


def test_chung_lu_exact_edges_and_seeded():
    a = chung_lu(3000, 20000, 2.5, 5)
    b = chung_lu(3000, 20000, 2.5, 5)
    c = chung_lu(3000, 20000, 2.5, 6)
    assert a.m == c.m == 20000
    assert np.array_equal(a.adj, b.adj) and not np.array_equal(a.adj, c.adj)
    lo, hi = a.pairs()
    assert (lo < hi).all() and len(np.unique(lo * a.n + hi)) == a.m
    assert np.array_equal(a.degrees(), np.bincount(
        np.concatenate([lo, hi]), minlength=a.n))


@pytest.mark.parametrize("p_delete", [0.45, 1.0])
def test_stream_ops_all_change_the_graph(p_delete):
    base = chung_lu(2000, 12000, 2.5, 1)
    perm = np.random.default_rng(4).permutation(base.n)
    g = graph.relabel(base, perm)
    traffic = {"batch_ops": 64, "p_delete": p_delete}
    s = UpdateStream(base, traffic, 9, perm, 5)
    batches = [s.request(i) for i in range(20)]
    dels = round(p_delete * 64)
    assert all(sum(k == "-" for k, _, _ in b) == dels for b in batches)
    keys = set(g.keys().tolist())
    for b in batches:
        for k, u, v in b:
            assert u < v
            key = u * g.n + v
            assert (key in keys) == (k == "-")
            keys.symmetric_difference_update({key})
    assert sorted(keys) == graph.apply_ops(g, batches).keys().tolist()
    again = UpdateStream(base, traffic, 9, perm, 5)
    assert again.request(3) == batches[3]
    # another seed: the same ops under the identity labels, in another order
    plain = UpdateStream(base, traffic, 9, np.arange(base.n), 6)
    inv = np.argsort(perm)
    assert sorted((k, int(min(inv[u], inv[v])), int(max(inv[u], inv[v])))
                  for k, u, v in batches[3]) == sorted(plain.request(3))


def test_a_stream_that_runs_out_of_edges_says_so():
    g = chung_lu(50, 100, 2.5, 1)
    s = UpdateStream(g, {"batch_ops": 10, "p_delete": 1.0}, 1,
                             np.arange(g.n), 1)
    for i in range(10):
        s.request(i)
    with pytest.raises(RuntimeError):
        s.request(10)


def test_relabelled_graphs_do_the_same_work():
    base = chung_lu(1500, 9000, 2.5, 8)
    perm = np.random.default_rng(1).permutation(base.n)
    g = graph.relabel(base, perm)
    assert np.array_equal(g.degrees()[perm], base.degrees())
    c0, f0 = work.semicore_star(base)
    c1, f1 = work.semicore_star(g)
    assert np.array_equal(c1[perm], c0)
    assert [len(f) for f in f0] == [len(f) for f in f1]


def test_log_reader_reads_the_programs_log(tmp_path):
    from repro.core.update import UpdateBatch
    from repro.stream.integrity import crc32c
    from repro.stream.wal import WriteAheadLog

    assert wal.crc32c(b"123456789") == crc32c(b"123456789") == 0xE3069283
    batches = [[("-", 1, 2), ("+", 3, 9)], [("+", 0, 5)]]
    log = WriteAheadLog(str(tmp_path / "w.wal"), fsync=True)
    for e, ops in enumerate(batches, 1):
        log.append(e, UpdateBatch.from_wire(ops))
    log.close()
    path = str(tmp_path / "w.wal")
    assert wal.missing_batches(path, batches) == 0
    assert wal.missing_batches(path, batches + [[("+", 1, 7)]]) == 1
    assert wal.missing_batches(path, batches[::-1]) == 2
    with open(path, "rb") as f:
        lines = f.readlines()
    assert lines[0] == wal.frame(1, batches[0])
    lines[1] = lines[1].replace(b'"+"', b'"-"')  # damaged: crc fails
    with open(path, "wb") as f:
        f.writelines(lines)
    assert wal.missing_batches(path, batches) == 1
