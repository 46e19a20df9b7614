"""The resident table's row broadcast (core/resident.py ``_row_bcast``).

The xla chunk programs spread a node vector to the edge slots of the sorted
resident table (``x[rows]``) from ``segptr`` alone, and ``rows`` never
reaches the device for them:

* the broadcast equals ``np.repeat(x, diff(segptr))`` on the live slots,
  whatever the empty segments and the padded tail; the substrates that keep
  the per-slot gather give the same;
* the xla chunk and ``counts_all`` programs take ``(nbr, segptr)`` and
  gather nothing E-sized but by ``nbr``; the probe loop gathers nothing
  E-sized at all;
* decompose and both warm settles stay exact against numpy on a graph with
  isolated nodes at both ends and an edge count off the bucket grid; pallas
  builds ``rows`` only when it asks for it;
* ``repro_resident_row_bcast_total{how}`` counts the broadcasts issued.

The sharded chunk programs spread each shard's owned values to its edge
slots the same way, from the shard's local offsets ``lseg``:

* the broadcast equals ``np.repeat(x, diff(lseg))`` on every shard's real
  slots of ``distributed.shard_arrays`` layouts: a shard whose local E is
  ``Emax``, owned pad slots, empty segments first and in the middle, a
  shard with no edges;
* the sharded chunk and ``counts_all`` programs take no ``rows`` and gather
  nothing E-sized but by ``dst``, and nothing E-sized in the probe loop;
* decompose and both warm settles on ``backend="shard"`` stay exact and
  count ``how="segptr"`` broadcasts only.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.core import resident
from repro.core.distributed import shard_arrays
from repro.core.engine import XLABackend, run_batch, warm_settle
from repro.core.imcore import imcore_bz
from repro.core.maintenance import CoreMaintainer
from repro.core.semicore import HostEngine, decompose
from repro.core.update import Delete, UpdateBatch
from repro.graph import BufferedGraph, CSRGraph, chung_lu
from repro.obs import get_registry

SEGPTR = 'repro_resident_row_bcast_total{how="segptr"}'
GATHER = 'repro_resident_row_bcast_total{how="gather"}'


# ------------------------------------------------------------ the broadcast
def _table(case: str):
    """(lens, E_pad) of one resident table layout."""
    rng = np.random.default_rng(sum(map(ord, case)))
    if case == "edgeless":
        return np.zeros(7, np.int64), 0
    if case == "one_node":
        return np.array([5]), 8
    if case == "empty_first_mid_last":
        lens = rng.integers(0, 9, size=60)
        lens[:3] = 0
        lens[25:31] = 0
        lens[-4:] = 0
    elif case == "exact_bucket":
        lens = rng.integers(1, 9, size=40)
        lens[-1] += resident._EDGE_BUCKET - lens.sum()
        assert lens[-1] > 0
    elif case.startswith("random"):
        lens = rng.integers(0, 40, size=int(rng.integers(1, 400)))
        lens[rng.random(len(lens)) < 0.3] = 0
    else:
        raise ValueError(case)
    return lens, resident._edge_pad(int(lens.sum()))


CASES = ["edgeless", "one_node", "empty_first_mid_last", "exact_bucket"] + [
    f"random{i}" for i in range(6)]


def _spread(substrate: str, lens, E_pad, x):
    """``x`` spread to the slots of the table by one substrate's broadcast:
    the resident xla and pallas chunk bodies, and the shared probe ops'
    default gather (the sharded engine, the per-pass backends)."""
    n, E = len(lens), int(lens.sum())
    segptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    rows = np.repeat(np.arange(n, dtype=np.int32), lens)
    rows_pad = np.zeros(E_pad, np.int32)
    rows_pad[:E] = rows
    nbr = jnp.zeros((E_pad,), jnp.int32)
    x = jnp.asarray(x)
    if substrate == "xla":
        for_pass = resident._substrate("xla", 0, True)
        _, _, _, bcast = for_pass((nbr, jnp.asarray(segptr)),
                                  jnp.ones((n,), bool), n)
    elif substrate == "pallas":
        for_pass = resident._substrate("pallas", 64, True)
        _, _, _, bcast = for_pass((nbr[:E], jnp.asarray(rows),
                                   jnp.asarray(segptr)),
                                  jnp.ones((n,), bool), n)
    else:  # edge_ge_counts / hindex_bsearch without a row_bcast_fn
        return np.asarray(jnp.take(x, jnp.asarray(rows_pad), mode="clip"))
    return np.asarray(jax.jit(bcast)(x))


# the pallas substrate never binds an edgeless table (run_resident settles
# it on the host), and its segment-sum kernel needs an edge
@pytest.mark.parametrize("case,substrate", [
    (c, s) for c in CASES for s in ("xla", "pallas", "default")
    if (c, s) != ("edgeless", "pallas")])
def test_row_broadcast_equals_repeat(case, substrate):
    lens, E_pad = _table(case)
    E = int(lens.sum())
    assert E_pad >= E
    x = np.random.default_rng(E).integers(
        -2 ** 31, 2 ** 31, size=len(lens)).astype(np.int32)
    got = _spread(substrate, lens, E_pad, x)
    np.testing.assert_array_equal(got[:E], np.repeat(x, lens))


def test_row_broadcast_counts_match_the_gather():
    """``edge_ge_counts`` with the segptr broadcast gives the gather's
    counts on a padded table with empty segments."""
    from repro.core.engine import edge_ge_counts

    lens, E_pad = _table("empty_first_mid_last")
    n, E = len(lens), int(lens.sum())
    rng = np.random.default_rng(3)
    segptr = jnp.asarray(np.concatenate([[0], np.cumsum(lens)]), jnp.int32)
    rows = np.zeros(E_pad, np.int32)
    rows[:E] = np.repeat(np.arange(n), lens)
    vals = jnp.asarray(rng.integers(0, 9, E_pad), jnp.int32)
    thr = jnp.asarray(rng.integers(0, 9, n), jnp.int32)
    segsum = resident._sorted_segsum(segptr)
    kw = dict(segment_sum_fn=lambda v, _r, _n: segsum(v))
    mask = jnp.ones((E_pad,), bool)
    want = edge_ge_counts(vals, jnp.asarray(rows), mask, thr, n, **kw)
    got = edge_ge_counts(vals, None, mask, thr, n, **kw,
                         row_bcast_fn=resident._row_bcast(segptr, E_pad))
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------ the xla programs' operands
def _subjaxprs(eqn):
    for v in eqn.params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            if isinstance(x, ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, Jaxpr):
                yield x


def _walk(jaxpr, inside_probe_loop, num_probes, out):
    """Every ``gather`` equation of ``jaxpr`` and its sub-jaxprs, as (output
    shape, whether a ``num_probes``-long scan, the probe loop, holds it)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            out.append((eqn.outvars[0].aval.shape, inside_probe_loop))
        probe = (eqn.primitive.name == "scan"
                 and eqn.params.get("length") == num_probes)
        for sub in _subjaxprs(eqn):
            _walk(sub, inside_probe_loop or probe, num_probes, out)
    return out


def _gathers(fn, args, **static):
    closed = fn.trace(*args, **static).jaxpr
    return _walk(closed.jaxpr, False, static.get("num_probes"), [])


# E-sized gathers each program keeps: all of them by nbr (pass-start core,
# and for semicore* the push rule's active, h and core; semicore+'s changed)
NBR_GATHERS = {"semicore": 1, "semicore+": 2, "semicore*": 4,
               "semicore*-masked": 4}


@pytest.mark.parametrize("variant", sorted(NBR_GATHERS))
def test_xla_chunk_takes_no_rows_and_gathers_only_by_nbr(variant):
    n, E_pad, probes, chunk = 37, 256, 5, 3
    algorithm = variant.split("-")[0]
    masked = variant.endswith("masked")
    fn = resident._chunk_fns("xla", 0, False, algorithm, False, masked)
    i32 = jax.ShapeDtypeStruct((n,), jnp.int32)
    b = jax.ShapeDtypeStruct((n,), jnp.bool_)
    table = (jax.ShapeDtypeStruct((E_pad,), jnp.int32),
             jax.ShapeDtypeStruct((n + 1,), jnp.int32))
    state = {"semicore": (i32, jax.ShapeDtypeStruct((), jnp.bool_)),
             "semicore+": (i32, b),
             "semicore*": (i32, i32, b) + ((b,) if masked else ())}[algorithm]
    found = _gathers(fn, state + table, num_probes=probes, num_segments=n,
                     chunk=chunk)
    edge_sized = [inside for shape, inside in found if shape == (E_pad,)]
    assert any(inside for _, inside in found)  # the probe loop was found
    assert not any(edge_sized), "an E-sized gather inside the probe loop"
    assert len(edge_sized) == NBR_GATHERS[variant]


def test_xla_counts_all_takes_no_rows():
    n, E_pad = 37, 256
    fn = resident._counts_all_fn("xla", 0, False)
    found = _gathers(fn, (jax.ShapeDtypeStruct((n,), jnp.int32),
                          jax.ShapeDtypeStruct((E_pad,), jnp.int32),
                          jax.ShapeDtypeStruct((n + 1,), jnp.int32)),
                     num_segments=n)
    assert [s for s, _ in found if s == (E_pad,)] == [(E_pad,)]  # core[nbr]


def test_xla_structure_uploads_no_rows():
    eng = HostEngine(_ends_isolated(), block_edges=64)
    rs = resident.build_structure(eng.planner)
    assert not hasattr(rs, "rows_j")
    nbr, segptr = rs.edge_table("xla")
    assert nbr.shape == (rs.E_pad,) and segptr.shape == (rs.n + 1,)
    assert rs.pallas_table is None


# ------------------------------------------------------------ exact results
def _ends_isolated() -> CSRGraph:
    """Nodes 0 and n-1 isolated, E off the edge bucket grid (padded)."""
    inner = chung_lu(1500, 5000, seed=21)
    g = CSRGraph.from_edges(inner.n + 2, inner.edge_list() + 1)
    assert g.degree(0) == 0 and g.degree(g.n - 1) == 0
    E = len(g.adj)
    assert E > resident._EDGE_BUCKET and resident._edge_pad(E) > E
    return g


def _probes(core_bound) -> int:
    return max(1, int(np.ceil(np.log2(int(np.max(core_bound)) + 2))))


def _same_run(r, ref):
    np.testing.assert_array_equal(r.core, ref.core)
    if ref.cnt is not None:
        np.testing.assert_array_equal(r.cnt, ref.cnt)
    assert r.iterations == ref.iterations
    assert r.updates_per_iter == ref.updates_per_iter
    assert r.computations_per_iter == ref.computations_per_iter
    assert r.edge_block_reads == ref.edge_block_reads
    assert r.node_table_reads == ref.node_table_reads


@pytest.mark.parametrize("algorithm", ["semicore", "semicore+", "semicore*"])
def test_xla_decompose_exact_and_counts_segptr_broadcasts(algorithm):
    g = _ends_isolated()
    ref = decompose(g, algorithm, "batch", block_edges=64, backend="numpy")
    before = get_registry().snapshot()
    r = decompose(g, algorithm, "batch", block_edges=64, backend="xla")
    d = get_registry().delta(before)
    _same_run(r, ref)
    per_pass = _probes(g.degrees()) + (2 if algorithm == "semicore*" else 0)
    assert d[SEGPTR] == r.iterations * per_pass
    assert d.get(GATHER, 0.0) == 0.0


def test_xla_serial_warm_settle_exact():
    """The serial warm settle (``counts_all`` prologue, then the
    ``chunk_semicore_star`` program) against numpy's."""
    g = _ends_isolated()
    core0 = decompose(g, "semicore*", "batch", backend="numpy").core
    e = g.edge_list()

    def perturbed():
        bg = BufferedGraph(g)
        for i in range(40):
            assert bg.delete_edge(*map(int, e[i * 37]))
        ins = [(1, 700), (2, 701), (3, 1200), (1499, 5)]
        return bg, sum(bg.insert_edge(u, v) for u, v in ins)

    bg_np, ni = perturbed()
    r_np = warm_settle(HostEngine(bg_np, block_edges=64), core0, ni, "numpy")
    bg_x, _ = perturbed()
    eng = HostEngine(bg_x, block_edges=64)
    before = get_registry().snapshot()
    r_x = warm_settle(eng, core0, ni, "xla")
    d = get_registry().delta(before)
    _same_run(r_x, r_np)
    np.testing.assert_array_equal(r_x.core, imcore_bz(bg_x.materialize()))
    warm = np.minimum(core0 + ni, eng.degrees())
    assert d[SEGPTR] == r_x.iterations * (_probes(warm) + 2) + 1


def test_xla_masked_settle_exact(monkeypatch):
    """A delete batch on an xla maintainer settles through the masked
    chunk program and lands on the numpy maintainer's core and cnt."""
    g = _ends_isolated()
    built = []
    real = resident._chunk_fns

    def spy(*args):
        built.append(args)
        return real(*args)

    monkeypatch.setattr(resident, "_chunk_fns", spy)
    m_x = CoreMaintainer(g, block_edges=64, backend="xla")
    m_np = CoreMaintainer(g, block_edges=64, backend="numpy")
    e = g.edge_list()
    batch = UpdateBatch(tuple(Delete(*map(int, e[i * 29])) for i in range(64)))
    before = get_registry().snapshot()
    s = m_x.apply(batch)
    d = get_registry().delta(before)
    m_np.apply(batch)
    assert s.iterations > 0
    assert ("xla", 0, False, "semicore*", False, True) in built
    np.testing.assert_array_equal(m_x.core, m_np.core)
    np.testing.assert_array_equal(m_x.cnt, m_np.cnt)
    np.testing.assert_array_equal(m_x.core, imcore_bz(m_x.bg.materialize()))
    assert d[SEGPTR] > 0 and d.get(GATHER, 0.0) == 0.0


def test_pallas_builds_rows_on_request_and_stays_exact(monkeypatch):
    """The per-probe pallas path gathers by ``rows``: built on its first
    request from the host offsets, exact length, and still exact."""
    monkeypatch.setenv("REPRO_PALLAS_FUSED", "0")
    inner = chung_lu(200, 700, gamma=2.3, seed=11)
    g = CSRGraph.from_edges(inner.n + 2, inner.edge_list() + 1)
    ref = decompose(g, "semicore*", "batch", block_edges=64, backend="numpy")
    eng = HostEngine(g, block_edges=64)
    rs = resident.build_structure(eng.planner)
    assert rs.pallas_table is None
    nbr, rows, segptr = rs.edge_table("pallas")
    assert nbr.shape == rows.shape == (rs.E,)
    np.testing.assert_array_equal(
        rows, np.repeat(np.arange(g.n), np.diff(rs.seg_ptr)))
    assert rs.edge_table("pallas")[1] is rows  # cached
    before = get_registry().snapshot()
    r = decompose(g, "semicore*", "batch", block_edges=64,
                  backend="pallas-interpret")
    d = get_registry().delta(before)
    _same_run(r, ref)
    assert d[GATHER] == r.iterations * (_probes(g.degrees()) + 2)
    assert d.get(SEGPTR, 0.0) == 0.0
    # the rows upload is counted when pallas asks for it
    assert d['repro_resident_h2d_bytes_total{what="edge_table"}'] == \
        rs.E_pad * 4 + (g.n + 1) * 4 + rs.E * 4


def test_run_batch_xla_reuses_the_structure_without_rows():
    be = XLABackend()
    be.retain_structure = True
    eng = HostEngine(_ends_isolated(), block_edges=64)
    r = run_batch(eng, "semicore*", be)
    assert be._resident.pallas_table is None
    np.testing.assert_array_equal(r.core, imcore_bz(eng.graph))


# ------------------------------------------------ the sharded programs
def _shard_layout(case: str):
    """``distributed.shard_arrays`` of one layout (the targets are zeros:
    the broadcast reads only the offsets)."""
    rng = np.random.default_rng(sum(map(ord, case)))
    if case == "full_with_owned_pad":  # local E == Emax in every shard
        lens, S = np.array([1] * 8 + [8, 4, 4]), 3
    elif case == "empty_first_mid":
        lens, S = np.array([0, 0, 3, 2, 0, 0, 4, 1, 2, 0, 3, 3, 0, 5]), 2
    elif case == "edgeless_shards":  # more shards than nodes
        lens, S = np.array([3, 2]), 4
    elif case == "edgeless":  # one shard owns every node, no edge at all
        lens, S = np.zeros(5, np.int64), 3
    elif case.startswith("random"):
        lens = rng.integers(0, 30, size=int(rng.integers(5, 300)))
        lens[rng.random(len(lens)) < 0.3] = 0
        S = int(rng.integers(2, 5))
    else:
        raise ValueError(case)
    seg = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    return shard_arrays(np.zeros(int(seg[-1]), np.int32), seg, S,
                        n=len(lens))


def _shows(sg, case: str) -> bool:
    """Whether the layout holds the shard shape ``case`` is there for."""
    Emax = sg.dst.shape[1]
    lens = np.diff(sg.lsegptr, axis=1)
    full = sg.per_shard_edges == Emax
    owned_pad = ~sg.owned_mask.all(axis=1)
    if case == "full_with_owned_pad":
        return bool((full & owned_pad).any())
    if case == "empty_first_mid":
        return bool(((lens[:, 0] == 0) & (lens[:, 4] == 0)
                     & sg.owned_mask[:, 4]).any())
    if case == "edgeless_shards":
        return bool(((sg.per_shard_edges == 0) & owned_pad).any())
    if case == "edgeless":
        return bool(((sg.per_shard_edges == 0) & sg.owned_mask.any(axis=1)
                     ).any())
    return True


SHARD_CASES = ["full_with_owned_pad", "empty_first_mid", "edgeless_shards",
               "edgeless"] + [f"random{i}" for i in range(4)]


@pytest.mark.parametrize("case", SHARD_CASES)
def test_shard_row_broadcast_equals_repeat(case):
    sg = _shard_layout(case)
    assert _shows(sg, case)
    S, V = sg.owned_ids.shape
    Emax = sg.dst.shape[1]
    rng = np.random.default_rng(S * V)
    for s in range(S):
        lseg, E = sg.lsegptr[s], int(sg.per_shard_edges[s])
        assert lseg[-1] == E <= Emax
        x = rng.integers(-2 ** 31, 2 ** 31, size=V).astype(np.int32)
        got = np.asarray(jax.jit(resident._row_bcast(jnp.asarray(lseg), Emax))(
            jnp.asarray(x)))
        assert got.shape == (Emax,)
        np.testing.assert_array_equal(got[:E], np.repeat(x, np.diff(lseg)))


def _one_device_mesh():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("shard",))


def _shard_program(variant: str, n, V, Emax, probes, chunk):
    """One sharded program on a one-device mesh and the shapes of its
    operands: the state, then the table ``ShardedStructure`` holds."""
    S = 1
    i32, b = jnp.int32, jnp.bool_

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    table = (sds((S, Emax), i32), sds((S, Emax), b), sds((S, V + 1), i32),
             sds((S, V), i32), sds((S, V), b))
    core = sds((n,), i32)
    mesh = _one_device_mesh()
    if variant == "counts_all":
        return resident._shard_counts_fn(mesh, n), (core,) + table
    algorithm = variant.split("-")[0]
    masked = variant.endswith("masked")
    fn = resident._shard_chunk_fn(mesh, algorithm, n, probes, chunk, False,
                                  masked)
    state = {"semicore": (core, sds((), b)),
             "semicore+": (core, sds((S, V), b), sds((), i32)),
             "semicore*": (core, sds((S, V), i32), sds((S, V), b),
                           sds((), i32)) + ((sds((S, V), b),) if masked
                                            else ())}[algorithm]
    return fn, state + table


# E-sized gathers each sharded program keeps: all of them by dst (the
# pass-start core; semicore+'s changed mask; semicore*'s post-pass core)
DST_GATHERS = {"semicore": 1, "semicore+": 2, "semicore*": 2,
               "semicore*-masked": 2, "counts_all": 1}


@pytest.mark.parametrize("variant", sorted(DST_GATHERS))
def test_shard_programs_take_no_rows_and_gather_only_by_dst(variant):
    n, V, Emax, probes, chunk = 37, 40, 256, 5, 3
    fn, args = _shard_program(variant, n, V, Emax, probes, chunk)
    closed = fn.trace(*args).jaxpr
    operands = [v.aval for v in closed.jaxpr.invars]
    assert len(operands) == len(args)
    # one E-sized int32 operand: dst
    assert sum(a.shape == (1, Emax) and a.dtype == jnp.int32
               for a in operands) == 1
    found = _walk(closed.jaxpr, False, probes, [])
    edge_sized = [inside for shape, inside in found if shape == (Emax,)]
    if variant != "counts_all":
        assert any(inside for _, inside in found)  # the probe loop was found
    assert not any(edge_sized), "an E-sized gather inside the probe loop"
    assert len(edge_sized) == DST_GATHERS[variant]


def test_shard_structure_uploads_no_rows():
    import dataclasses

    assert "rows_j" not in {
        f.name for f in dataclasses.fields(resident.ShardedStructure)}
    eng = HostEngine(_ends_isolated(), block_edges=64)
    before = get_registry().snapshot()
    ss = resident.build_sharded_structure(eng.planner, 1,
                                          devices=jax.devices()[:1])
    d = get_registry().delta(before)
    held = (ss.dst_j, ss.emask_j, ss.lseg_j, ss.owned_ids_j, ss.owned_mask_j)
    assert d['repro_resident_h2d_bytes_total{what="edge_table"}'] == \
        sum(a.nbytes for a in held)
    assert ss.dst_j.shape == (1, ss.E)  # one shard: no pad slot


@pytest.mark.parametrize("algorithm", ["semicore", "semicore+", "semicore*"])
def test_shard_decompose_exact_and_counts_segptr_broadcasts(algorithm):
    g = _ends_isolated()
    ref = decompose(g, algorithm, "batch", block_edges=64, backend="numpy")
    before = get_registry().snapshot()
    r = decompose(g, algorithm, "batch", block_edges=64, backend="shard")
    d = get_registry().delta(before)
    _same_run(r, ref)
    per_pass = _probes(g.degrees()) + (2 if algorithm == "semicore*" else 0)
    assert d[SEGPTR] == r.iterations * per_pass
    assert d.get(GATHER, 0.0) == 0.0


def test_shard_serial_warm_settle_exact():
    """The on-mesh warm settle (the sharded ``counts_all`` prologue, then
    ``chunk_semicore_star_shard``) against numpy's."""
    g = _ends_isolated()
    core0 = decompose(g, "semicore*", "batch", backend="numpy").core
    e = g.edge_list()

    def perturbed():
        bg = BufferedGraph(g)
        for i in range(40):
            assert bg.delete_edge(*map(int, e[i * 37]))
        ins = [(1, 700), (2, 701), (3, 1200), (1499, 5)]
        return bg, sum(bg.insert_edge(u, v) for u, v in ins)

    bg_np, ni = perturbed()
    r_np = warm_settle(HostEngine(bg_np, block_edges=64), core0, ni, "numpy")
    bg_s, _ = perturbed()
    eng = HostEngine(bg_s, block_edges=64)
    before = get_registry().snapshot()
    r_s = warm_settle(eng, core0, ni, "shard")
    d = get_registry().delta(before)
    _same_run(r_s, r_np)
    np.testing.assert_array_equal(r_s.core, imcore_bz(bg_s.materialize()))
    warm = np.minimum(core0 + ni, eng.degrees())
    assert d[SEGPTR] == r_s.iterations * (_probes(warm) + 2) + 1
    assert d.get(GATHER, 0.0) == 0.0


def test_shard_masked_settle_exact(monkeypatch):
    """A delete batch on a shard maintainer settles through the masked
    sharded chunk program and lands on the numpy maintainer's core and
    cnt."""
    g = _ends_isolated()
    built = []
    real = resident._shard_chunk_fn

    def spy(*args):
        built.append(args)
        return real(*args)

    monkeypatch.setattr(resident, "_shard_chunk_fn", spy)
    m_s = CoreMaintainer(g, block_edges=64, backend="shard")
    m_np = CoreMaintainer(g, block_edges=64, backend="numpy")
    e = g.edge_list()
    batch = UpdateBatch(tuple(Delete(*map(int, e[i * 29])) for i in range(64)))
    before = get_registry().snapshot()
    s = m_s.apply(batch)
    d = get_registry().delta(before)
    m_np.apply(batch)
    assert s.iterations > 0
    assert any(args[1] == "semicore*" and args[-1] for args in built)
    np.testing.assert_array_equal(m_s.core, m_np.core)
    np.testing.assert_array_equal(m_s.cnt, m_np.cnt)
    np.testing.assert_array_equal(m_s.core, imcore_bz(m_s.bg.materialize()))
    assert d[SEGPTR] > 0 and d.get(GATHER, 0.0) == 0.0
