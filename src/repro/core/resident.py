"""Device-resident fixpoint: the whole batch superstep loop on the device.

PR 3's backend layer made the arithmetic pluggable but kept the *loop* on the
host: every pass re-uploaded node state (and, for the xla backend, re-packed
the frontier's edge segments), ran one jitted op, and downloaded the result —
~27 host↔device round-trips and O(passes) retraces per decompose, which made
the accelerator backends 20–100× slower than numpy in wall-clock despite
walking identical passes.  This module is the fix (DESIGN.md §12):

* **Residency** — ``core``, ``cnt``, the active/frontier mask, and the flat
  edge table ``(nbr, segptr)`` are uploaded once at bind.  The edge table is
  cached in a :class:`ResidentStructure` keyed by the planner's structure
  token (base CSR identity + ``BufferedGraph.version``), so a long-lived
  ``CoreMaintainer`` re-binding after a no-op batch — or re-running on an
  unchanged graph — re-uploads nothing.

* **Fused superstep** — one pass (h-index binary-search probes → cnt refresh
  → push rule → ``cnt(v) < core(v)`` frontier gating → convergence flag) is
  a single traced function; ``lax.scan`` runs ``chunk`` passes per host
  round-trip, each gated by ``lax.cond`` so post-convergence slots cost
  nothing.  The jit is cached per (substrate, algorithm, probe count), so
  compiles per decompose are O(1) — independent of pass count — and O(log
  kmax) across graphs of one shape (the probe count is the only
  value-dependent static).

* **Accounting parity** — the chunk returns a small summary (per-pass update
  counts + the pinned per-pass frontier masks) pulled back once per chunk;
  the host *replays* frontier evolution through the same
  :class:`~repro.core.engine.PassPlanner` charges the per-pass path makes
  (edge-block coverage, node-table scans, pallas kernel-block activity).
  Because every backend computes the same exact integer fixpoint, the
  replayed frontiers are identical sets to the numpy backend's — so
  ``edge_block_reads`` / ``node_table_reads`` / ``kernel_blocks_*`` stay
  bit-identical, as the differential sweep asserts.

The shared :func:`fused_hindex` / :func:`fused_counts` helpers (gather
neighbor cores + probe loop in one traced body) are also what the SPMD
engine's per-shard superstep consumes (``distributed.py``).
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from functools import lru_cache

import numpy as np

from .. import runtime as _runtime
from ..obs import metrics as _metrics, trace as _trace
# registry series shared with the per-pass path: the replay increments the
# exact counters engine.run_batch / PallasBackend.begin_pass would have
from .engine import _KB_ACTIVE, _KB_SKIPPED, _MAINT_PROLOGUE, _pass_obs

__all__ = [
    "ResidentStructure",
    "ShardedStructure",
    "build_structure",
    "build_sharded_structure",
    "build_shard_chunk_fn",
    "run_resident",
    "run_sharded",
    "resident_enabled",
    "trace_count",
    "chunk_len",
    "fused_hindex",
    "fused_counts",
    "RESIDENT_ENV_VAR",
    "CHUNK_ENV_VAR",
    "DEFAULT_CHUNK",
]

RESIDENT_ENV_VAR = "REPRO_DEVICE_RESIDENT"
CHUNK_ENV_VAR = "REPRO_RESIDENT_CHUNK"
# Passes per host round-trip.  Small enough that the per-chunk frontier
# record (chunk × n bools) stays negligible next to the edge table; large
# enough that dispatch overhead amortizes (a typical decompose converges in
# ~2-4 chunks).  CoreGraphConfig.superstep_chunk / REPRO_RESIDENT_CHUNK tune.
DEFAULT_CHUNK = 8

_TRACES = _metrics.counter(
    "repro_resident_traces_total",
    "Traces of the resident jit bodies (compiles, not calls), by function",
)
_H2D = _metrics.counter(
    "repro_resident_h2d_bytes_total",
    "Bytes of host arrays the resident runner turned into device arrays",
)
_H2D_EDGES = _H2D.labels(what="edge_table")
_H2D_STATE = _H2D.labels(what="state")
_ROW_BCAST = _metrics.counter(
    "repro_resident_row_bcast_total",
    "Node vectors spread to edge slots by the resident chunk programs, by "
    "how: from segment offsets (segptr) or by a per-slot owner gather",
)
_SHARD_ALLGATHER = _metrics.counter(
    "repro_shard_allgather_bytes_total",
    "Bytes of the int32 arrays the sharded chunk programs' all_gathers "
    "return: the owned ids once a chunk call, the owned core slices once a "
    "pass that ran",
)
_SHARD_SLOTS = _metrics.counter(
    "repro_shard_slots_total",
    "Edge slots of each sharded structure built, by kind: real edges or the "
    "padding of the rectangular (S, Emax) layout",
)


def _count_row_bcasts(series, ran, per_pass: int) -> None:
    """Add the broadcasts of one chunk call's executed passes (``ran``, the
    per-pass flags it returned) to ``series``; ``None`` (the fused pallas
    kernel, which reads no per-slot owner) counts nothing."""
    if series is not None:
        series.inc(int(np.asarray(ran).sum()) * per_pass)


def _count_trace(fn: str) -> None:
    """Called at *trace* time by every resident jit body ``fn``: retraces —
    not calls — count, so tests and the benchmark can count compiles per
    decompose (the O(passes)-retrace regression guard).  Unlike the other
    series this one ignores ``REPRO_OBS=0``: the compile guards read it
    whatever the kill switch says."""
    _TRACES.labels(fn=fn).value += 1


def trace_count() -> int:
    """Total resident-path jit traces so far in this process."""
    return int(_TRACES.value)


def _h2d(arr: np.ndarray, series) -> np.ndarray:
    """``arr`` unchanged, its ``nbytes`` added to an h2d ``series``: wraps
    every host array the caller turns into a device array (the host copy is
    counted; the device array is never read)."""
    series.inc(arr.nbytes)
    return arr


def resident_enabled() -> bool:
    """Device residency is the default for device backends;
    ``REPRO_DEVICE_RESIDENT=0`` falls back to the per-pass PR 3 path.
    Resolved through :func:`repro.runtime.setting`."""
    return _runtime.setting("device_resident")


def chunk_len(explicit: int | None = None) -> int:
    """Effective passes-per-round-trip: explicit argument (the
    ``superstep_chunk`` threaded from configs/owners) > env > default."""
    if explicit is not None:
        return max(1, int(explicit))
    return _runtime.setting("resident_chunk")


# ===========================================================================
# Fused ops: neighbor gather + probe loop in one traced body.  Shared between
# the resident superstep below and the SPMD engine's per-shard superstep.
# ===========================================================================
def fused_counts(core, dst, rows, edge_mask, thresholds, num_rows,
                 *, segment_sum_fn, row_bcast_fn=None):
    """#{edges (v,u) : core[u] >= thresholds[row(v)]} per row (Eq. 2)."""
    import jax.numpy as jnp

    from .engine import edge_ge_counts

    return edge_ge_counts(
        jnp.take(core, dst, mode="clip"), rows, edge_mask, thresholds,
        num_rows, segment_sum_fn=segment_sum_fn, row_bcast_fn=row_bcast_fn)


def fused_hindex(core, dst, rows, edge_mask, c_old, num_probes,
                 *, segment_sum_fn, unroll: bool = False, row_bcast_fn=None):
    """Binary-search h = max k <= c_old with count_ge(k) >= k (Eq. 1)."""
    import jax.numpy as jnp

    from .engine import hindex_bsearch

    return hindex_bsearch(
        jnp.take(core, dst, mode="clip"), rows, edge_mask, c_old, num_probes,
        segment_sum_fn=segment_sum_fn, unroll=unroll,
        row_bcast_fn=row_bcast_fn)


# ===========================================================================
# Resident structure: the flat merged edge table, uploaded once per version
# ===========================================================================
@dataclass
class ResidentStructure:
    """The device-resident working set of one graph version.

    Host-side ``seg_ptr`` stays for the accounting replay (block coverage of
    a frontier); ``graph``/``version`` form the validity token — holding the
    graph reference keeps its identity stable for the ``is`` test.
    """

    graph: object            # base CSRGraph this structure was built from
    version: int             # BufferedGraph.version at build time (0 if none)
    n: int
    E: int                   # merged flat edge count (buffered deltas applied)
    dmax: int                # max merged degree (pallas float32-range check)
    seg_ptr: np.ndarray      # (n+1,) int64 flat-table offsets, host
    nbr_j: object            # (E_pad,) int32 device — edge targets
    segptr_j: object         # (n+1,) int32 device — flat-table offsets
    E_pad: int = 0           # bucket-padded device length (>= E)
    fused_tables: dict = field(default_factory=dict)
    pallas_table: tuple | None = None  # cached (nbr, rows) exact-E views

    def matches(self, planner) -> bool:
        buffered = planner.eng.buffered
        ver = buffered.version if buffered is not None else 0
        return self.graph is planner.eng.graph and self.version == ver

    def fused(self, block_edges: int):
        """Compact-rank kernel table for the fused superstep (DESIGN.md
        §16), built once per (structure, tile size) and cached for the
        structure's lifetime — the same upload-once contract as the flat
        edge table above."""
        ft = self.fused_tables.get(block_edges)
        if ft is None:
            from ..kernels.fused_superstep import build_fused_table

            ft = build_fused_table(self.seg_ptr,
                                   np.asarray(self.nbr_j)[:self.E],
                                   self.n, block_edges)
            self.fused_tables[block_edges] = ft
        return ft

    def edge_table(self, kind: str) -> tuple:
        """The table operands of one substrate's chunk programs.

        xla: ``(nbr, segptr)``.  It reduces edges exclusively through
        segptr-bounded prefix sums (:func:`_sorted_segsum`) and spreads node
        values to edge slots from segptr (:func:`_row_bcast`), so it needs
        no per-slot owner table and takes the bucket-padded ``nbr`` as-is:
        the padded tail can never reach a segment sum, and the stable shape
        keeps the chunk jits cached across structural versions (the
        maintenance hot loop would otherwise recompile on every edge
        insert/delete).

        pallas: ``(nbr, rows, segptr)``, exact length.  Its blocked kernels
        scatter by edge slot, so they need ``rows`` (each slot's owner);
        that is built and uploaded on the first request and cached for the
        structure's lifetime."""
        if kind != "pallas":
            return self.nbr_j, self.segptr_j
        if self.pallas_table is None:
            import jax.numpy as jnp

            rows = np.repeat(np.arange(self.n, dtype=np.int32),
                             np.diff(self.seg_ptr))
            nbr = self.nbr_j if self.E == self.E_pad else self.nbr_j[:self.E]
            self.pallas_table = (nbr, jnp.asarray(_h2d(rows, _H2D_EDGES)))
        return self.pallas_table + (self.segptr_j,)


_EDGE_BUCKET = 8192


def _edge_pad(E: int) -> int:
    """Device-table length for ``E`` edge slots: next power of two below one
    bucket, then bucket multiples.  Small graphs recompile O(log E) times as
    they grow; at scale the shape only changes when E crosses a bucket
    boundary, so the maintenance undo/redo churn (±batch edges per round)
    almost never invalidates the chunk jit cache."""
    if E <= 0:
        return 0
    if E < _EDGE_BUCKET:
        return 1 << (E - 1).bit_length()
    return -(-E // _EDGE_BUCKET) * _EDGE_BUCKET


def build_structure(planner) -> ResidentStructure:
    """Merged flat adjacency of all nodes, uploaded once (charge-free, like
    the per-pass pallas bind it replaces — disk I/O stays per-pass,
    replayed planner-side)."""
    with _trace.span("resident.bind", cat="engine") as sp:
        rs = _build_structure(planner)
        if sp.active:
            sp.set(E=rs.E, E_pad=rs.E_pad,
                   bytes=rs.nbr_j.nbytes + rs.segptr_j.nbytes)
    return rs


def _build_structure(planner) -> ResidentStructure:
    import jax.numpy as jnp

    planner.eng._sync()
    nbr_flat, seg_ptr = planner.full_structure()
    n = planner.n
    if len(nbr_flat) >= (1 << 31) or n >= (1 << 31):
        # the device table is int32 end-to-end (ids, rows, seg_ptr offsets;
        # jax x64 is off) — fail loudly instead of wrapping offsets negative
        # and converging to a silently-wrong core array
        raise ValueError(
            f"device-resident table needs int32 offsets: 2m={len(nbr_flat)} "
            f"n={n} exceeds 2**31; use the numpy backend (or shard via "
            "distributed.py) for this graph")
    lens = np.diff(seg_ptr)
    E = int(len(nbr_flat))
    E_pad = _edge_pad(E)
    nbr = np.zeros(E_pad, dtype=np.int32)
    nbr[:E] = nbr_flat
    buffered = planner.eng.buffered
    return ResidentStructure(
        graph=planner.eng.graph,
        version=buffered.version if buffered is not None else 0,
        n=n,
        E=E,
        E_pad=E_pad,
        dmax=int(lens.max()) if len(lens) else 0,
        seg_ptr=np.asarray(seg_ptr, dtype=np.int64),
        nbr_j=jnp.asarray(_h2d(nbr, _H2D_EDGES)),
        segptr_j=jnp.asarray(_h2d(np.asarray(seg_ptr, dtype=np.int32),
                                  _H2D_EDGES)),
    )


# ===========================================================================
# The fused, chunked superstep jits (cached per substrate × algorithm)
# ===========================================================================
def _sorted_segsum(segptr):
    """Segment-sum over the resident table's *sorted* rows: prefix-sum +
    boundary gathers instead of a scatter (XLA CPU scatters serialize; the
    cumsum path is what makes the resident loop run at numpy-like speed).
    Exact: integer cumsum, E < 2**31."""
    import jax.numpy as jnp

    def segsum(vals):
        cs = jnp.concatenate(
            [jnp.zeros((1,), vals.dtype), jnp.cumsum(vals)])
        return (jnp.take(cs, segptr[1:], mode="clip")
                - jnp.take(cs, segptr[:-1], mode="clip"))

    return segsum


def _row_bcast(segptr, num_slots: int):
    """``x[rows]`` for the resident table's *sorted* rows, from ``segptr``
    alone: scatter-add the n differences ``x[v] - x[v-1]`` at ``segptr[v]``
    (sorted, not unique: empty segments share a start), then one cumsum over
    the slots — an n-element scatter and a prefix sum in place of an
    E-element gather.  Exact: integer adds, and the wrap-around of a
    difference cancels in the sum.  Slots ``>= E`` (the bucket padding) get
    whatever value results; they never reach a segment sum, which
    :func:`_sorted_segsum` bounds by ``segptr``."""
    import jax.numpy as jnp

    def bcast(x):
        step = x - jnp.concatenate([jnp.zeros((1,), x.dtype), x[:-1]])
        marks = jnp.zeros((num_slots,), x.dtype).at[segptr[:-1]].add(
            step, mode="drop", indices_are_sorted=True)
        return jnp.cumsum(marks)

    return bcast


def _substrate(kind: str, block_edges: int, interpret: bool):
    """How one substrate's chunk programs read the resident table.

    Returns ``for_pass(table, node_active, num_segments) -> (nbr, rows,
    segsum, bcast)`` for the operands ``ResidentStructure.edge_table(kind)``
    gives: ``segsum`` is the (vals, rows, num_segments) reduction the shared
    probe ops consume, ``bcast(x)`` spreads a node vector to the edge slots
    (``x[rows]``).  pallas: the blocked DMA-skipping kernel and a gather by
    ``rows``; xla: the sorted prefix-sum reduction and :func:`_row_bcast`,
    with no ``rows`` at all."""
    import jax.numpy as jnp

    if kind == "pallas":
        from ..kernels.ops import make_superstep_segsum

        def for_pass(table, node_active, num_segments):
            nbr, rows, _ = table
            apply_ = make_superstep_segsum(
                rows, node_active, num_segments,
                block_edges=block_edges, interpret=interpret)
            return (nbr, rows, lambda vals, _rows, _ns: apply_(vals),
                    lambda x: jnp.take(x, rows, mode="clip"))
    else:
        def for_pass(table, node_active, num_segments):
            nbr, segptr = table
            apply_ = _sorted_segsum(segptr)
            return (nbr, None, lambda vals, _rows, _ns: apply_(vals),
                    _row_bcast(segptr, nbr.shape[0]))
    return for_pass


def _program_name(base: str, algorithm: str | None, *tags: str) -> str:
    """Stable name of one resident program variant, e.g.
    ``chunk_semicore_star_masked``: jit calls it ``jit_<name>`` in the device
    trace (the benchmark keys fixpoint time on the ``jit_chunk`` prefix), and
    ``repro_resident_traces_total`` labels its traces with it."""
    parts = [base]
    if algorithm is not None:
        parts.append(algorithm.replace("*", "_star").replace("+", "_plus"))
    return "_".join(parts + [t for t in tags if t])


def _named(fn, name: str):
    """``fn`` renamed to ``name``, the name jit gives its program."""
    fn.__name__ = fn.__qualname__ = name
    return fn


@lru_cache(maxsize=None)
def _chunk_fns(kind: str, block_edges: int, interpret: bool, algorithm: str,
               fused: bool = False, masked: bool = False):
    """Build + jit the chunked superstep for one substrate × algorithm.

    With ``masked`` (semicore* only — the grouped-maintenance settle,
    DESIGN.md §18) the chunk takes one extra ``cand`` bool operand and every
    pass ANDs it into the next frontier: non-candidate nodes are frozen —
    their core is never recomputed (the frontier is the only thing that
    writes core) while their cnt still receives exact push decrements from
    falling candidate neighbors, so independent groups converge inside the
    same ``lax.scan`` without interacting.

    ``num_probes`` / ``num_segments`` / ``chunk`` are static: one compile per
    decompose (jax re-traces only on new shapes or probe counts — O(log kmax)
    across graphs, never O(passes)).

    Node-state bookkeeping that scatters along unsorted ``nbr`` (the push
    rule, changed-neighbor propagation) is rewritten through the undirected
    symmetry — edge (v→u) exists iff (u→v) does — as a *sorted* row
    reduction, so no E-sized scatter is left (prefix sums + gathers; XLA
    CPU scatters would serialize it): the one scatter, the row broadcast's
    (:func:`_row_bcast`), writes n elements.

    With ``fused`` (the pallas hot path, DESIGN.md §16) each superstep is
    one ``kernels.fused_superstep.fused_pass`` (one histogram kernel call,
    two for semicore+/*) in place of the whole per-probe body; the scan/cond convergence scaffolding and
    every returned summary are identical, so the host replay is untouched.
    The static ``dims`` tuple rides the kernel table (same trace-count
    contract: only shapes and the probe count retrace).
    """
    import jax
    import jax.numpy as jnp

    if masked and algorithm != "semicore*":
        raise ValueError("masked settle is a semicore* (cnt-gated) "
                         f"discipline; got {algorithm!r}")
    name = _program_name("chunk", algorithm, "fused" if fused else "",
                         "masked" if masked else "")

    if fused:
        from ..kernels import fused_superstep as fsk

        if algorithm == "semicore":
            def chunk(core, done, arrs, *, num_probes, num_segments, chunk,
                      dims):
                _count_trace(name)
                all_active = jnp.ones((num_segments,), jnp.bool_)

                def run(args):
                    core, _ = args
                    core2, _, _, upd = fsk.fused_pass(
                        core, core, all_active, arrs, dims=dims,
                        num_probes=num_probes, algorithm="semicore",
                        interpret=interpret)
                    return (core2, upd == 0), upd

                def skip(args):
                    core, done = args
                    return (core, done), jnp.int32(0)

                def step(carry, _):
                    core, done = carry
                    carry2, upd = jax.lax.cond(done, skip, run, (core, done))
                    return carry2, (upd, ~done)

                (core, done), (upds, ran) = jax.lax.scan(
                    step, (core, done), None, length=chunk)
                return core, done, upds, ran

        elif algorithm == "semicore+":
            def chunk(core, active, arrs, *, num_probes, num_segments, chunk,
                      dims):
                _count_trace(name)

                def run(args):
                    core, active = args
                    core2, _, active2, upd = fsk.fused_pass(
                        core, core, active, arrs, dims=dims,
                        num_probes=num_probes, algorithm="semicore+",
                        interpret=interpret)
                    return (core2, active2), upd

                def skip(args):
                    return args, jnp.int32(0)

                def step(carry, _):
                    _, active = carry
                    ran = jnp.any(active)
                    carry2, upd = jax.lax.cond(ran, run, skip, carry)
                    return carry2, (active, upd, ran)

                (core, active), (fronts, upds, ran) = jax.lax.scan(
                    step, (core, active), None, length=chunk)
                done = ~jnp.any(active)
                return core, active, done, fronts, upds, ran

        elif algorithm == "semicore*":
            def _scan_star(core, cnt, active, cand, arrs, num_probes, chunk,
                           dims):
                def run(args):
                    core, cnt, active = args
                    core2, cnt2, active2, upd = fsk.fused_pass(
                        core, cnt, active, arrs, dims=dims,
                        num_probes=num_probes, algorithm="semicore*",
                        interpret=interpret)
                    if cand is not None:
                        active2 = active2 & cand
                    return (core2, cnt2, active2), upd

                def skip(args):
                    return args, jnp.int32(0)

                def step(carry, _):
                    _, _, active = carry
                    ran = jnp.any(active)
                    carry2, upd = jax.lax.cond(ran, run, skip, carry)
                    return carry2, (active, upd, ran)

                (core, cnt, active), (fronts, upds, ran) = jax.lax.scan(
                    step, (core, cnt, active), None, length=chunk)
                done = ~jnp.any(active)
                return core, cnt, active, done, fronts, upds, ran

            if masked:
                def chunk(core, cnt, active, cand, arrs, *, num_probes,
                          num_segments, chunk, dims):
                    _count_trace(name)
                    return _scan_star(core, cnt, active, cand, arrs,
                                      num_probes, chunk, dims)
            else:
                def chunk(core, cnt, active, arrs, *, num_probes,
                          num_segments, chunk, dims):
                    _count_trace(name)
                    return _scan_star(core, cnt, active, None, arrs,
                                      num_probes, chunk, dims)

        else:
            raise ValueError(f"unknown algorithm {algorithm!r}")

        return jax.jit(_named(chunk, name),
                       static_argnames=("num_probes", "num_segments", "chunk",
                                        "dims"))

    # the non-fused chunks take the substrate's table operands positionally
    # (``*table``): ``(nbr, segptr)`` for xla, ``(nbr, rows, segptr)`` for
    # pallas — ResidentStructure.edge_table
    for_pass = _substrate(kind, block_edges, interpret)

    def hindex_pass(core, active, table, num_probes, n):
        nbr, rows, segsum, bcast = for_pass(table, active, n)
        mask = jnp.ones(nbr.shape, jnp.bool_)
        c_old = jnp.where(active, core, 0)
        return fused_hindex(core, nbr, rows, mask, c_old, num_probes,
                            segment_sum_fn=segsum, row_bcast_fn=bcast)

    if algorithm == "semicore":
        # every node, every pass; done after the first no-update pass
        def chunk(core, done, *table, num_probes, num_segments, chunk):
            _count_trace(name)
            all_active = jnp.ones((num_segments,), jnp.bool_)

            def run(args):
                core, _ = args
                h = hindex_pass(core, all_active, table, num_probes,
                                num_segments)
                upd = jnp.sum((h != core).astype(jnp.int32))
                return (h, upd == 0), upd

            def skip(args):
                core, done = args
                return (core, done), jnp.int32(0)

            def step(carry, _):
                core, done = carry
                carry2, upd = jax.lax.cond(done, skip, run, (core, done))
                return carry2, (upd, ~done)

            (core, done), (upds, ran) = jax.lax.scan(
                step, (core, done), None, length=chunk)
            return core, done, upds, ran

        return jax.jit(_named(chunk, name),
                       static_argnames=("num_probes", "num_segments", "chunk"))

    if algorithm == "semicore+":
        # neighbors of changed nodes (Lemma 4.1), alive nodes only
        def chunk(core, active, *table, num_probes, num_segments, chunk):
            _count_trace(name)
            nbr, segptr = table[0], table[-1]
            row_sum = _sorted_segsum(segptr)

            def run(args):
                core, active = args
                h = hindex_pass(core, active, table, num_probes,
                                num_segments)
                changed = active & (h != core)
                core2 = jnp.where(active, h, core)
                # u is next-frontier iff some neighbor changed — by symmetry
                # a row reduction over u's own (sorted) segment
                touched = row_sum(
                    jnp.take(changed, nbr, mode="clip").astype(jnp.int32))
                active2 = (touched > 0) & (core2 > 0)
                return (core2, active2), jnp.sum(changed.astype(jnp.int32))

            def skip(args):
                return args, jnp.int32(0)

            def step(carry, _):
                _, active = carry
                ran = jnp.any(active)
                carry2, upd = jax.lax.cond(ran, run, skip, carry)
                return carry2, (active, upd, ran)

            (core, active), (fronts, upds, ran) = jax.lax.scan(
                step, (core, active), None, length=chunk)
            done = ~jnp.any(active)
            return core, active, done, fronts, upds, ran

        return jax.jit(_named(chunk, name),
                       static_argnames=("num_probes", "num_segments", "chunk"))

    if algorithm == "semicore*":
        # cnt-gated (Lemma 4.2) with exact cnt maintenance under
        # simultaneous updates: refresh vs pass-start values, then the
        # UpdateNbrCnt push rule (DESIGN.md §2) — all on device
        def _scan_star(core, cnt, active, cand, table, num_probes,
                       num_segments, chunk):
            row_sum = _sorted_segsum(table[-1])

            def run(args):
                core, cnt, active = args
                nbr, rows, segsum, bcast = for_pass(table, active,
                                                    num_segments)
                mask = jnp.ones(nbr.shape, jnp.bool_)
                nbr_vals = jnp.take(core, nbr, mode="clip")  # pass-start
                c_old = jnp.where(active, core, 0)
                from .engine import edge_ge_counts, hindex_bsearch
                h = hindex_bsearch(nbr_vals, rows, mask, c_old, num_probes,
                                   segment_sum_fn=segsum, row_bcast_fn=bcast)
                upd = jnp.sum((active & (h != core)).astype(jnp.int32))
                core2 = jnp.where(active, h, core)
                # (1) recompute cnt of the frontier vs pass-start values
                thr = jnp.where(active, h, 0)
                refreshed = edge_ge_counts(nbr_vals, rows, mask, thr,
                                           num_segments,
                                           segment_sum_fn=segsum,
                                           row_bcast_fn=bcast)
                # (2) push decrements: dec[u] = #{edges (v in F -> u) :
                #     core_now(u) in (h(v), c_old(v)]} — by symmetry summed
                #     over u's own sorted segment, v = nbr[e]
                core2_row = bcast(core2)
                act_nbr = jnp.take(active, nbr, mode="clip")
                h_nbr = jnp.take(h, nbr, mode="clip")
                c_old_nbr = jnp.take(core, nbr, mode="clip")
                push = act_nbr & (core2_row > h_nbr) & (core2_row <= c_old_nbr)
                dec = row_sum(push.astype(jnp.int32))
                cnt2 = jnp.where(active, refreshed, cnt) - dec
                active2 = (cnt2 < core2) & (core2 > 0)
                if cand is not None:
                    active2 = active2 & cand
                return (core2, cnt2, active2), upd

            def skip(args):
                return args, jnp.int32(0)

            def step(carry, _):
                _, _, active = carry
                ran = jnp.any(active)
                carry2, upd = jax.lax.cond(ran, run, skip, carry)
                return carry2, (active, upd, ran)

            (core, cnt, active), (fronts, upds, ran) = jax.lax.scan(
                step, (core, cnt, active), None, length=chunk)
            done = ~jnp.any(active)
            return core, cnt, active, done, fronts, upds, ran

        if masked:
            def chunk(core, cnt, active, cand, *table, num_probes,
                      num_segments, chunk):
                _count_trace(name)
                return _scan_star(core, cnt, active, cand, table, num_probes,
                                  num_segments, chunk)
        else:
            def chunk(core, cnt, active, *table, num_probes, num_segments,
                      chunk):
                _count_trace(name)
                return _scan_star(core, cnt, active, None, table, num_probes,
                                  num_segments, chunk)

        return jax.jit(_named(chunk, name),
                       static_argnames=("num_probes", "num_segments", "chunk"))

    raise ValueError(f"unknown algorithm {algorithm!r}")


@lru_cache(maxsize=None)
def _counts_all_fn(kind: str, block_edges: int, interpret: bool,
                   fused: bool = False):
    """Full-table exact-cnt scan (warm_settle's Eq. 2 prologue), resident."""
    import jax
    import jax.numpy as jnp

    name = _program_name("counts_all", None, "fused" if fused else "")
    if fused:
        from ..kernels import fused_superstep as fsk

        def counts_all(core, arrs, *, num_segments, dims):
            _count_trace(name)
            all_active = jnp.ones((num_segments,), jnp.bool_)
            return fsk.fused_counts(core, core, all_active, arrs, dims=dims,
                                    interpret=interpret)

        return jax.jit(_named(counts_all, name),
                       static_argnames=("num_segments", "dims"))

    for_pass = _substrate(kind, block_edges, interpret)

    def counts_all(core, *table, num_segments):
        _count_trace(name)
        all_active = jnp.ones((num_segments,), jnp.bool_)
        nbr, rows, segsum, bcast = for_pass(table, all_active, num_segments)
        mask = jnp.ones(nbr.shape, jnp.bool_)
        return fused_counts(core, nbr, rows, mask, core, num_segments,
                            segment_sum_fn=segsum, row_bcast_fn=bcast)

    return jax.jit(_named(counts_all, name),
                   static_argnames=("num_segments",))


# ===========================================================================
# Host-side accounting replay
# ===========================================================================
def _replay_kernel_blocks(tally: dict | None, rs: ResidentStructure,
                          be: int, nb: int, frontier: np.ndarray) -> None:
    """Kernel-block activity of one pass over ``frontier`` — the pallas
    ``begin_pass`` coverage formula (spans over the merged flat table),
    verbatim, so the resident report matches the per-pass path bit-for-bit
    (including its ``if self.E`` guard: an edgeless table has no kernel
    blocks to charge)."""
    if tally is None or not len(frontier) or rs.E == 0:
        return
    lo = rs.seg_ptr[frontier]
    hi = rs.seg_ptr[frontier + 1]
    nz = lo < hi
    cov = np.zeros(nb + 1, dtype=np.int64)
    if nz.any():
        np.add.at(cov, lo[nz] // be, 1)
        np.add.at(cov, (hi[nz] - 1) // be + 1, -1)
    na = int((np.cumsum(cov[:-1]) > 0).sum())
    tally["kernel_blocks_active"] += na
    tally["kernel_blocks_skipped"] += nb - na
    _KB_ACTIVE.inc(na)
    _KB_SKIPPED.inc(nb - na)


def _replay_pass(planner, frontier: np.ndarray, tally: dict | None,
                 rs: ResidentStructure, be: int, nb: int) -> None:
    """Re-issue the exact planner charges one per-pass iteration makes for
    ``frontier`` (sorted node ids): edge-block coverage over the *raw* CSR
    ranges (what ``gather``/``charge_only`` charge), the node-table scan,
    and the pallas kernel-block activity."""
    if not len(frontier):
        return
    planner.charge_only(frontier)
    planner.account_node_scan(int(frontier[0]), int(frontier[-1]))
    _replay_kernel_blocks(tally, rs, be, nb, frontier)


# ===========================================================================
# The runner
# ===========================================================================
def run_resident(engine, algorithm: str, backend, *,
                 core: np.ndarray | None = None,
                 cnt: np.ndarray | None = None,
                 initial_cnt_scan: bool = False,
                 superstep_chunk: int | None = None,
                 max_supersteps: int | None = None,
                 settle_mask: np.ndarray | None = None):
    """Run a batch-schedule decomposition with the fixpoint device-resident.

    Mirrors :func:`engine.run_batch` pass-for-pass (same frontiers, same
    update/computation histories, same planner accounting) but with node
    state and the edge table living on the device across passes.  With
    ``initial_cnt_scan`` (the warm-settle discipline), ``cnt`` is recomputed
    exactly on device from the warm ``core`` upper bound — one accounted
    full scan — before the SemiCore* passes.

    ``settle_mask`` (semicore* only) freezes every node outside the mask:
    the frontier starts at ``(cnt < core) & (core > 0) & mask`` and stays
    inside the mask for the whole run — the grouped-maintenance settle
    (DESIGN.md §18).  Frozen nodes keep their core; their cnt still takes
    exact push decrements from falling masked neighbors.

    A mesh-sharded backend (``ShardedBackend``) dispatches to
    :func:`run_sharded`: same contract, edge table sharded over the mesh.
    """
    if getattr(backend, "mesh_sharded", False):
        return run_sharded(engine, algorithm, backend, core=core, cnt=cnt,
                           initial_cnt_scan=initial_cnt_scan,
                           superstep_chunk=superstep_chunk,
                           max_supersteps=max_supersteps,
                           settle_mask=settle_mask)
    if max_supersteps is not None:
        raise ValueError("max_supersteps is only supported on the shard "
                         "backend (chunk-granular budgeted runs)")
    if settle_mask is not None and algorithm != "semicore*":
        raise ValueError("settle_mask is a semicore* (cnt-gated) discipline")

    import jax.numpy as jnp

    from .engine import DecompResult

    planner = engine.planner
    n = engine.n
    rs = backend.bind_resident(planner)
    kind, be, interpret = backend.resident_substrate(planner)
    # kernel blocks (pallas replay only; be is unused elsewhere).  The
    # accounting block size stays the planner's regardless of the fused
    # kernel's tile size — kernel_blocks_active/skipped replay is the PR 3
    # coverage formula at ``be`` granularity either way.
    nb = -(-max(rs.E, 1) // be) if kind == "pallas" else 0
    tally = ({"kernel_blocks_active": 0, "kernel_blocks_skipped": 0}
             if kind == "pallas" else None)
    chunk = chunk_len(superstep_chunk)
    om = _pass_obs(algorithm, backend.name)

    if kind == "pallas":
        from ..kernels import fused_superstep as fsk

        fused = fsk.fused_enabled() and rs.E > 0
    else:
        fused = False

    def substrate_args():
        """Positional + static-kw tail of the chunk fns for this substrate:
        the fused path ships the compact-rank kernel table, the per-probe
        paths the flat edge table (bucket-padded ``(nbr, segptr)`` for xla,
        exact ``(nbr, rows, segptr)`` for pallas)."""
        if fused:
            ft = rs.fused(fsk.fused_block_edges())
            return (ft.arrays,), {"dims": ft.dims}
        return rs.edge_table(kind), {}

    warm = core is not None
    if warm:
        core = np.asarray(core, dtype=np.int64).copy()
    else:
        core = engine.degrees().astype(np.int64)
    cmax = int(core.max()) if n else 0
    num_probes = max(1, int(np.ceil(np.log2(cmax + 2))))
    core_j = jnp.asarray(_h2d(core.astype(np.int32), _H2D_STATE))
    # node vectors spread to edge slots: one a probe, plus the cnt refresh
    # and the push rule's core in a SemiCore* pass
    bcasts = None if fused else _ROW_BCAST.labels(
        how="segptr" if kind == "xla" else "gather")
    per_pass = num_probes + (2 if algorithm == "semicore*" else 0)

    upd_hist: list = []
    comp_hist: list = []
    iters = 0
    comp = 0
    all_nodes = np.arange(n, dtype=np.int64)

    def result(core_f, cnt_f):
        rep = tally or {}
        backend.unbind()
        return DecompResult(
            core=np.asarray(core_f, dtype=np.int64),
            cnt=None if cnt_f is None else np.asarray(cnt_f, dtype=np.int64),
            iterations=iters,
            node_computations=comp,
            edge_block_reads=planner.reader.reads,
            node_table_reads=planner.reader.node_table_reads,
            algorithm=algorithm,
            schedule="batch",
            updates_per_iter=upd_hist,
            computations_per_iter=comp_hist,
            backend=backend.name,
            kernel_blocks_active=rep.get("kernel_blocks_active", 0),
            kernel_blocks_skipped=rep.get("kernel_blocks_skipped", 0),
        )

    # ------------------------------------------------------------ semicore*
    if algorithm == "semicore*":
        if initial_cnt_scan:
            # warm_settle prologue: one accounted full scan recomputes cnt
            # exactly (Eq. 2) w.r.t. the warm upper bound — on device
            t0 = time.perf_counter()
            with _trace.span("cnt_prologue", cat="maintenance",
                             backend=backend.name, nodes=n):
                planner.charge_only(all_nodes)
                planner.account_node_scan(0, n - 1)
                _replay_kernel_blocks(tally, rs, be, nb, all_nodes)
                if rs.E:
                    counts_all = _counts_all_fn(kind, be, interpret, fused)
                    sargs, skw = substrate_args()
                    cnt_j = counts_all(core_j, *sargs, num_segments=n, **skw)
                    if bcasts is not None:
                        bcasts.inc()
                else:
                    cnt_j = jnp.zeros((n,), jnp.int32)
                cnt = np.asarray(cnt_j, dtype=np.int64)
            _MAINT_PROLOGUE.observe(time.perf_counter() - t0)
        elif warm:
            cnt = np.asarray(cnt, dtype=np.int64).copy()
            cnt_j = jnp.asarray(_h2d(cnt.astype(np.int32), _H2D_STATE))
        else:
            cnt = np.zeros(n, dtype=np.int64)
            cnt_j = jnp.zeros((n,), jnp.int32)
        active0 = (cnt < core) & (core > 0)
        if settle_mask is not None:
            active0 &= np.asarray(settle_mask, dtype=bool)
        if rs.E == 0:
            # edgeless table: any deficient node drops straight to h = 0 in
            # one pass, and nothing can re-activate — numpy's loop verbatim
            if active0.any():
                f = np.flatnonzero(active0)
                iters, comp = 1, len(f)
                upd_hist.append(int((core[f] != 0).sum()))
                comp_hist.append(len(f))
                _replay_pass(planner, f, tally, rs, be, nb)
                om[0].inc()
                om[1].inc(len(f))
                om[2].inc(int((core[f] != 0).sum()))
                core[f] = 0
                cnt[f] = 0
            return result(core, cnt)
        if not active0.any():
            # settled warm state: zero passes, like numpy's while-loop
            return result(core, cnt)
        masked = settle_mask is not None
        fn = _chunk_fns(kind, be, interpret, algorithm, fused, masked)
        sargs, skw = substrate_args()
        if masked:
            cand_j = jnp.asarray(_h2d(np.asarray(settle_mask, dtype=bool),
                                      _H2D_STATE))
            sargs = (cand_j,) + sargs
        active_j = jnp.asarray(_h2d(active0, _H2D_STATE))
        while True:
            with _trace.span("resident.chunk", cat="engine",
                             algorithm="semicore*", backend=backend.name,
                             chunk=chunk) as sp:
                core_j, cnt_j, active_j, done, fronts, upds, ran = fn(
                    core_j, cnt_j, active_j, *sargs,
                    num_probes=num_probes, num_segments=n, chunk=chunk,
                    **skw)
                iters, comp = _replay_chunk(
                    planner, rs, be, nb, tally, np.asarray(fronts),
                    np.asarray(upds), np.asarray(ran), upd_hist, comp_hist,
                    iters, comp, om, "semicore*")
                _count_row_bcasts(bcasts, ran, per_pass)
                if sp.active:
                    sp.set(passes_run=int(np.asarray(ran).sum()))
            if bool(done):
                break
        return result(core_j, cnt_j)

    # ------------------------------------------------- semicore / semicore+
    if rs.E == 0:
        # h == core == degrees == 0 everywhere: semicore runs exactly one
        # all-node pass; semicore+ starts from the all-node frontier and
        # likewise converges on pass one (numpy loop, charge-for-charge)
        if algorithm == "semicore" or n:
            iters, comp = 1, n
            upd_hist.append(0)
            comp_hist.append(n)
            planner.charge_only(all_nodes)
            planner.account_node_scan(0, n - 1)
            _replay_kernel_blocks(tally, rs, be, nb, all_nodes)
            om[0].inc()
            om[1].inc(n)
        return result(core, None)

    if algorithm == "semicore":
        # every node, every pass — the final no-update pass included
        fn = _chunk_fns(kind, be, interpret, algorithm, fused)
        sargs, skw = substrate_args()
        done_j = jnp.asarray(False)
        while True:
            with _trace.span("resident.chunk", cat="engine",
                             algorithm="semicore", backend=backend.name,
                             chunk=chunk) as sp:
                core_j, done_j, upds, ran = fn(
                    core_j, done_j, *sargs,
                    num_probes=num_probes, num_segments=n, chunk=chunk,
                    **skw)
                ran = np.asarray(ran)
                iters, comp = _replay_all_nodes_chunk(
                    planner, rs, be, nb, tally, np.asarray(upds), ran,
                    upd_hist, comp_hist, iters, comp, om)
                _count_row_bcasts(bcasts, ran, per_pass)
                if sp.active:
                    sp.set(passes_run=int(ran.sum()))
            if bool(done_j):
                break
        return result(core_j, None)

    if algorithm == "semicore+":
        fn = _chunk_fns(kind, be, interpret, algorithm, fused)
        sargs, skw = substrate_args()
        active_j = jnp.ones((n,), jnp.bool_)
        while True:
            with _trace.span("resident.chunk", cat="engine",
                             algorithm="semicore+", backend=backend.name,
                             chunk=chunk) as sp:
                core_j, active_j, done, fronts, upds, ran = fn(
                    core_j, active_j, *sargs,
                    num_probes=num_probes, num_segments=n, chunk=chunk,
                    **skw)
                iters, comp = _replay_chunk(
                    planner, rs, be, nb, tally, np.asarray(fronts),
                    np.asarray(upds), np.asarray(ran), upd_hist, comp_hist,
                    iters, comp, om, "semicore+")
                _count_row_bcasts(bcasts, ran, per_pass)
                if sp.active:
                    sp.set(passes_run=int(np.asarray(ran).sum()))
            if bool(done):
                break
        return result(core_j, None)

    raise ValueError(f"unknown algorithm {algorithm!r}")


def _replay_chunk(planner, rs, be, nb, tally, fronts, upds, ran,
                  upd_hist, comp_hist, iters, comp, om=None, algorithm=""):
    """Replay the planner charges for the executed passes of one chunk.

    ``om`` is the (passes, frontier, updates) counter triple from
    :func:`engine._pass_obs`; the replayed per-pass markers are emitted as
    trace instants from the same pinned frontier masks the planner charges
    come from, so tracing never perturbs the bit-identical guarantee.  The
    chunk's outputs are host arrays by now: the ``resident.replay`` span
    times host work alone."""
    with _trace.span("resident.replay", cat="engine") as sp:
        iters0 = iters
        for k in range(len(ran)):
            if not ran[k]:
                break
            frontier = np.flatnonzero(fronts[k]).astype(np.int64)
            iters += 1
            comp += len(frontier)
            upd_hist.append(int(upds[k]))
            comp_hist.append(int(len(frontier)))
            _replay_pass(planner, frontier, tally, rs, be, nb)
            if om is not None:
                om[0].inc()
                om[1].inc(len(frontier))
                om[2].inc(int(upds[k]))
            _trace.instant("superstep.replay", cat="engine",
                           algorithm=algorithm, index=iters,
                           frontier=int(len(frontier)), updates=int(upds[k]))
        if sp.active:
            sp.set(passes=iters - iters0)
    return iters, comp


def _replay_all_nodes_chunk(planner, rs, be, nb, tally, upds, ran,
                            upd_hist, comp_hist, iters, comp, om):
    """:func:`_replay_chunk` for SemiCore, whose every pass scans all
    nodes: no frontier masks come back, only the per-pass update counts."""
    n = planner.n
    all_nodes = np.arange(n, dtype=np.int64)
    with _trace.span("resident.replay", cat="engine") as sp:
        iters0 = iters
        for k in range(len(ran)):
            if not ran[k]:
                break
            iters += 1
            comp += n
            upd_hist.append(int(upds[k]))
            comp_hist.append(n)
            planner.charge_only(all_nodes)
            planner.account_node_scan(0, n - 1)
            _replay_kernel_blocks(tally, rs, be, nb, all_nodes)
            om[0].inc()
            om[1].inc(n)
            om[2].inc(int(upds[k]))
            _trace.instant("superstep.replay", cat="engine",
                           algorithm="semicore", index=iters, frontier=n,
                           updates=int(upds[k]))
        if sp.active:
            sp.set(passes=iters - iters0)
    return iters, comp


# ===========================================================================
# Mesh-sharded execution (the `shard` backend, DESIGN.md §13)
# ===========================================================================
@dataclass
class ShardedStructure:
    """The on-mesh working set of one graph version.

    The merged flat adjacency is cut into contiguous node-range shards
    (``distributed.shard_arrays``: minimax edge balance, int32-validated)
    and device_put once per structural version — the same version-keyed
    cache contract as :class:`ResidentStructure`.  Host copies of the
    owned-slot maps stay for reassembling global masks/arrays from the
    per-shard slices the chunk fns emit.  No per-slot owner table goes up:
    the chunk programs spread owned values to edge slots from ``lseg``.
    """

    graph: object            # base CSRGraph this structure was built from
    version: int             # BufferedGraph.version at build time (0 if none)
    n: int
    E: int                   # merged flat edge count (buffered deltas applied)
    S: int                   # mesh width (number of shards)
    V: int                   # owned-node slots per shard (padded)
    seg_ptr: np.ndarray      # (n+1,) int64 merged flat offsets, host
    owned_ids_h: np.ndarray  # (S, V) int32 host — global id per slot (pad n)
    owned_mask_h: np.ndarray # (S, V) bool host
    owned_flat: np.ndarray   # (S*V,) int32 host — all_gather-ordered ids
    pad_edges: int           # S * Emax - E (rectangular-layout waste)
    per_shard_edges: np.ndarray  # (S,) int64
    mesh: object             # jax Mesh over the first S devices
    dst_j: object            # (S, Emax) int32, sharded
    emask_j: object          # (S, Emax) bool, sharded
    lseg_j: object           # (S, V+1) int32, sharded — local CSR offsets
    owned_ids_j: object      # (S, V) int32, sharded
    owned_mask_j: object     # (S, V) bool, sharded

    def matches(self, planner) -> bool:
        buffered = planner.eng.buffered
        ver = buffered.version if buffered is not None else 0
        return self.graph is planner.eng.graph and self.version == ver


def build_sharded_structure(planner, num_shards: int,
                            devices=None) -> ShardedStructure:
    """Merged flat adjacency of all nodes, sharded and uploaded once
    (charge-free, like :func:`build_structure` — disk I/O stays per-pass,
    replayed planner-side).  ``devices`` pins the mesh to an explicit
    device list (default: the first ``num_shards`` visible devices)."""
    with _trace.span("resident.bind", cat="engine", shards=num_shards) as sp:
        ss = _build_sharded_structure(planner, num_shards, devices)
        _SHARD_SLOTS.labels(kind="real").inc(ss.E)
        _SHARD_SLOTS.labels(kind="pad").inc(ss.pad_edges)
        if sp.active:
            sp.set(E=ss.E, E_pad=ss.E + ss.pad_edges, bytes=sum(
                a.nbytes for a in (ss.dst_j, ss.emask_j, ss.lseg_j,
                                   ss.owned_ids_j, ss.owned_mask_j)))
    return ss


def _build_sharded_structure(planner, num_shards: int,
                             devices) -> ShardedStructure:
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from .distributed import shard_arrays

    planner.eng._sync()
    nbr_flat, seg_ptr = planner.full_structure()
    n = planner.n
    sg = shard_arrays(nbr_flat, seg_ptr, num_shards, n=n)
    S = sg.owned_ids.shape[0]
    pool = list(devices) if devices is not None else jax.devices()
    mesh = Mesh(np.asarray(pool[:S]), ("shard",))
    sh = NamedSharding(mesh, P("shard"))

    def put(arr):
        return jax.device_put(_h2d(arr, _H2D_EDGES), sh)

    owned_flat = sg.owned_ids.reshape(-1).astype(np.int32)
    buffered = planner.eng.buffered
    return ShardedStructure(
        graph=planner.eng.graph,
        version=buffered.version if buffered is not None else 0,
        n=n,
        E=int(len(nbr_flat)),
        S=S,
        V=int(sg.owned_ids.shape[1]),
        seg_ptr=np.asarray(seg_ptr, dtype=np.int64),
        owned_ids_h=sg.owned_ids,
        owned_mask_h=sg.owned_mask,
        owned_flat=owned_flat,
        pad_edges=int(sg.pad_edges),
        per_shard_edges=sg.per_shard_edges,
        mesh=mesh,
        dst_j=put(sg.dst),
        emask_j=put(sg.edge_mask),
        lseg_j=put(sg.lsegptr),
        owned_ids_j=put(sg.owned_ids),
        owned_mask_j=put(sg.owned_mask),
    )


def _local_segsum(lseg):
    """Per-shard segment sum over the shard's *sorted* local rows:
    :func:`_sorted_segsum` over the shard's local offsets, in the probe
    ops' (vals, rows, num_segments) form (padding slots are empty trailing
    segments, so padded edges never contribute)."""
    apply_ = _sorted_segsum(lseg)
    return lambda vals, _rows, _num_segments: apply_(vals)


@lru_cache(maxsize=None)
def _shard_chunk_fn(mesh, algorithm: str, n: int, num_probes: int,
                    chunk: int, unroll: bool, masked: bool = False):
    """Build + jit the on-mesh chunked superstep for one mesh × algorithm.

    The per-shard superstep body is the same fused arithmetic the flat
    resident path scans (:func:`fused_hindex` / :func:`fused_counts` probe
    code via the shared engine ops) applied to the shard's local edge
    arrays; one ``jax.lax.all_gather`` of the owned core slices per
    superstep rebuilds the replicated core, and one scalar ``psum`` carries
    the convergence count.  The push rule / changed-neighbor propagation
    read the *gathered* post-update core instead of a local ``h`` (for an
    inactive neighbor ``core2 == core`` makes the push predicate
    unsatisfiable, so no activity mask crosses shards), which keeps every
    superstep at exactly one all_gather.

    Owned-slot values reach the shard's edge slots by :func:`_row_bcast`
    over the local offsets ``lseg``, as the flat xla path's do over
    ``segptr``, so the programs take no per-slot owner table.  ``lseg`` is
    sorted, not unique: owned pad slots are empty trailing segments at the
    local E, and where the local E is ``Emax`` the broadcast's scatter drops
    them.  Slots at or past the local E get whatever the broadcast leaves;
    ``emask`` and the ``lseg``-bounded segment sums keep them out.

    Per-pass owned frontier slices come back through the scan's ys —
    sharded outputs, no extra collective — for the host accounting replay.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .engine import edge_ge_counts, hindex_bsearch

    axes = tuple(mesh.axis_names)
    shard = P(axes)
    repl = P()
    name = _program_name("chunk", algorithm, "shard",
                         "masked" if masked else "")

    def strip(*arrs):
        return tuple(a[0] for a in arrs)

    def gather_core(core, c_new, owned_flat):
        gathered = jax.lax.all_gather(c_new, axes, tiled=True)
        return jnp.zeros((n + 1,), core.dtype).at[owned_flat].set(gathered)[:n]

    def flat_ids(owned_ids):
        # the static scatter index map: gathered ONCE per chunk call (not
        # per superstep, and not shipped replicated from the host — the
        # §13 memory model keeps replicated inputs at core-in + core-out)
        return jax.lax.all_gather(owned_ids, axes, tiled=True)

    if algorithm == "semicore":
        # every node, every pass; done after the first no-update pass
        def body(core, done, dst, emask, lseg, owned_ids, owned_mask):
            _count_trace(name)
            dst, emask, lseg, owned_ids, owned_mask = strip(
                dst, emask, lseg, owned_ids, owned_mask)
            segsum = _local_segsum(lseg)
            bcast = _row_bcast(lseg, dst.shape[0])
            owned_flat = flat_ids(owned_ids)

            def run(args):
                core, _ = args
                nbr_vals = jnp.take(core, dst, mode="clip")
                c_old = jnp.where(owned_mask,
                                  jnp.take(core, owned_ids, mode="clip"), 0)
                h = hindex_bsearch(nbr_vals, None, emask, c_old, num_probes,
                                   segment_sum_fn=segsum, unroll=unroll,
                                   row_bcast_fn=bcast)
                core2 = gather_core(core, h, owned_flat)
                upd = jnp.sum((core2 != core).astype(jnp.int32))
                return (core2, upd == 0), upd

            def skip(args):
                return args, jnp.int32(0)

            def step(carry, _):
                _, done = carry
                carry2, upd = jax.lax.cond(done, skip, run, carry)
                return carry2, (upd, ~done)

            (core, done), (upds, ran) = jax.lax.scan(
                step, (core, done), None, length=chunk)
            return core, done, upds, ran

        in_specs = (repl, repl, shard, shard, shard, shard, shard)
        out_specs = (repl, repl, repl, repl)

    elif algorithm == "semicore+":
        # neighbors of changed nodes (Lemma 4.1), alive nodes only; the
        # changed mask is derived globally from the gathered core
        # (core2 != core), so propagation is a local row reduction
        def body(core, active_b, nact, dst, emask, lseg, owned_ids,
                 owned_mask):
            _count_trace(name)
            dst, emask, lseg, owned_ids, owned_mask, active0 = strip(
                dst, emask, lseg, owned_ids, owned_mask, active_b)
            segsum = _local_segsum(lseg)
            bcast = _row_bcast(lseg, dst.shape[0])
            owned_flat = flat_ids(owned_ids)

            def run(args):
                core, active, _ = args
                nbr_vals = jnp.take(core, dst, mode="clip")
                c_owned = jnp.where(owned_mask,
                                    jnp.take(core, owned_ids, mode="clip"), 0)
                c_old = jnp.where(active, c_owned, 0)
                h = hindex_bsearch(nbr_vals, None, emask, c_old, num_probes,
                                   segment_sum_fn=segsum, unroll=unroll,
                                   row_bcast_fn=bcast)
                c_new = jnp.where(active, h, c_owned)
                core2 = gather_core(core, c_new, owned_flat)
                upd = jnp.sum((core2 != core).astype(jnp.int32))
                changed_e = jnp.take(core2 != core, dst, mode="clip") & emask
                touched = segsum(changed_e.astype(jnp.int32), None, 0)
                active2 = (touched > 0) & (c_new > 0) & owned_mask
                nact2 = jax.lax.psum(
                    jnp.sum(active2.astype(jnp.int32)), axes)
                return (core2, active2, nact2), upd

            def skip(args):
                return args, jnp.int32(0)

            def step(carry, _):
                _, active, nact = carry
                ran = nact > 0
                carry2, upd = jax.lax.cond(ran, run, skip, carry)
                return carry2, (active, upd, ran)

            (core, active, nact), (fronts, upds, ran) = jax.lax.scan(
                step, (core, active0, nact), None, length=chunk)
            return (core, active[None], nact, fronts[:, None, :], upds, ran)

        in_specs = (repl, shard, repl, shard, shard, shard, shard, shard)
        out_specs = (repl, shard, repl, P(None, axes, None), repl, repl)

    elif algorithm == "semicore*":
        # cnt-gated (Lemma 4.2) with exact cnt maintenance: cnt stays
        # owner-local (each shard maintains its owned slice), the push rule
        # reads the gathered core2 in place of the neighbor's local h.
        # ``masked`` adds a per-slot candidate operand ANDed into every
        # next frontier (the grouped-maintenance settle, DESIGN.md §18).
        def body(core, cnt_b, active_b, nact, *tail):
            _count_trace(name)
            if masked:
                cand_b, dst, emask, lseg, owned_ids, owned_mask = tail
                (cand,) = strip(cand_b)
            else:
                dst, emask, lseg, owned_ids, owned_mask = tail
                cand = None
            dst, emask, lseg, owned_ids, owned_mask, cnt0, active0 = strip(
                dst, emask, lseg, owned_ids, owned_mask, cnt_b, active_b)
            segsum = _local_segsum(lseg)
            bcast = _row_bcast(lseg, dst.shape[0])
            owned_flat = flat_ids(owned_ids)

            def run(args):
                core, cnt, active, _ = args
                nbr_vals = jnp.take(core, dst, mode="clip")  # pass-start
                c_owned = jnp.where(owned_mask,
                                    jnp.take(core, owned_ids, mode="clip"), 0)
                c_old = jnp.where(active, c_owned, 0)
                h = hindex_bsearch(nbr_vals, None, emask, c_old, num_probes,
                                   segment_sum_fn=segsum, unroll=unroll,
                                   row_bcast_fn=bcast)
                c_new = jnp.where(active, h, c_owned)
                core2 = gather_core(core, c_new, owned_flat)
                upd = jnp.sum((core2 != core).astype(jnp.int32))
                # (1) recompute cnt of the frontier vs pass-start values
                thr = jnp.where(active, h, 0)
                refreshed = edge_ge_counts(nbr_vals, None, emask, thr,
                                           c_old.shape[0],
                                           segment_sum_fn=segsum,
                                           row_bcast_fn=bcast)
                # (2) push decrements: dec[u] = #{edges (v in F -> u) :
                #     core_now(u) in (h(v), c_old(v)]} — core2[v] stands in
                #     for h(v) (equal where v is active; for inactive v,
                #     core2 == core makes the interval empty)
                c2_row = bcast(c_new)
                push = (emask & (c2_row > jnp.take(core2, dst, mode="clip"))
                        & (c2_row <= nbr_vals))
                dec = segsum(push.astype(jnp.int32), None, 0)
                cnt2 = jnp.where(active, refreshed, cnt) - dec
                active2 = (cnt2 < c_new) & (c_new > 0) & owned_mask
                if cand is not None:
                    active2 = active2 & cand
                nact2 = jax.lax.psum(
                    jnp.sum(active2.astype(jnp.int32)), axes)
                return (core2, cnt2, active2, nact2), upd

            def skip(args):
                return args, jnp.int32(0)

            def step(carry, _):
                _, _, active, nact = carry
                ran = nact > 0
                carry2, upd = jax.lax.cond(ran, run, skip, carry)
                return carry2, (active, upd, ran)

            (core, cnt, active, nact), (fronts, upds, ran) = jax.lax.scan(
                step, (core, cnt0, active0, nact), None, length=chunk)
            return (core, cnt[None], active[None], nact,
                    fronts[:, None, :], upds, ran)

        in_specs = (repl, shard, shard, repl) \
            + ((shard,) if masked else ()) \
            + (shard, shard, shard, shard, shard)
        out_specs = (repl, shard, shard, repl, P(None, axes, None), repl,
                     repl)

    else:
        raise ValueError(f"unknown algorithm {algorithm!r}")

    sharded = jax.shard_map(_named(body, name), mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
    return jax.jit(
        sharded,
        in_shardings=tuple(NamedSharding(mesh, s) for s in in_specs),
    )


def build_shard_chunk_fn(mesh, algorithm: str, n: int, num_probes: int,
                         chunk: int | None = None):
    """Public builder of the on-mesh chunked superstep jit (also the
    dry-run cost-analysis entry, launch/steps.py).  ``REPRO_UNROLL_SCANS=1``
    unrolls the h-index probe loop so cost analysis sees every scan."""
    return _shard_chunk_fn(mesh, algorithm, n, num_probes, chunk_len(chunk),
                           os.environ.get("REPRO_UNROLL_SCANS") == "1")


@lru_cache(maxsize=None)
def _shard_counts_fn(mesh, n: int):
    """Full-table exact-cnt scan (warm_settle's Eq. 2 prologue), on-mesh:
    each shard counts its owned nodes' thresholds over local edges, the
    thresholds spread to edge slots from ``lseg`` (:func:`_row_bcast`)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .engine import edge_ge_counts

    axes = tuple(mesh.axis_names)
    shard = P(axes)
    repl = P()
    name = _program_name("counts_all", None, "shard")

    def body(core, dst, emask, lseg, owned_ids, owned_mask):
        _count_trace(name)
        dst = dst[0]; emask = emask[0]; lseg = lseg[0]
        owned_ids = owned_ids[0]; owned_mask = owned_mask[0]
        c_owned = jnp.where(owned_mask,
                            jnp.take(core, owned_ids, mode="clip"), 0)
        nbr_vals = jnp.take(core, dst, mode="clip")
        cnt = edge_ge_counts(nbr_vals, None, emask, c_owned,
                             c_owned.shape[0],
                             segment_sum_fn=_local_segsum(lseg),
                             row_bcast_fn=_row_bcast(lseg, dst.shape[0]))
        return cnt[None]

    in_specs = (repl, shard, shard, shard, shard, shard)
    sharded = jax.shard_map(_named(body, name), mesh=mesh, in_specs=in_specs,
                            out_specs=shard, check_vma=False)
    return jax.jit(
        sharded,
        in_shardings=tuple(NamedSharding(mesh, s) for s in in_specs),
    )


def run_sharded(engine, algorithm: str, backend, *,
                core: np.ndarray | None = None,
                cnt: np.ndarray | None = None,
                initial_cnt_scan: bool = False,
                superstep_chunk: int | None = None,
                max_supersteps: int | None = None,
                settle_mask: np.ndarray | None = None):
    """Run a batch-schedule decomposition with the fixpoint on-mesh.

    The shard-layout sibling of the flat resident runner: identical passes,
    histories, and planner replay (the differential sweep asserts parity at
    every shard count), with the edge table sharded over the mesh and cnt
    maintained owner-local.  ``max_supersteps`` budgets the run exactly
    (the final chunk's scan length is clamped to the remaining budget) for
    checkpoint demos — the partial core is a valid upper bound by monotone
    convergence.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .engine import DecompResult

    if settle_mask is not None and algorithm != "semicore*":
        raise ValueError("settle_mask is a semicore* (cnt-gated) discipline")
    planner = engine.planner
    n = engine.n
    ss = backend.bind_resident(planner)
    chunk = chunk_len(superstep_chunk)
    unroll = os.environ.get("REPRO_UNROLL_SCANS") == "1"
    om = _pass_obs(algorithm, backend.name)

    warm = core is not None
    if warm:
        core = np.asarray(core, dtype=np.int64).copy()
    else:
        core = engine.degrees().astype(np.int64)
    cmax = int(core.max()) if n else 0
    num_probes = max(1, int(np.ceil(np.log2(cmax + 2))))
    core_j = jnp.asarray(_h2d(core.astype(np.int32), _H2D_STATE))
    bcasts = _ROW_BCAST.labels(how="segptr")  # from each shard's lseg
    per_pass = num_probes + (2 if algorithm == "semicore*" else 0)
    gathered = ss.S * ss.V * 4  # bytes of one all_gather's int32 result

    upd_hist: list = []
    comp_hist: list = []
    iters = 0
    comp = 0
    all_nodes = np.arange(n, dtype=np.int64)
    own_ids = ss.owned_ids_h[ss.owned_mask_h]  # global id per real slot

    def localize(arr, fill, dtype):
        """Scatter a global (n,) array into the (S, V) owned-slot layout."""
        out = np.full((ss.S, ss.V), fill, dtype=dtype)
        out[ss.owned_mask_h] = arr[own_ids].astype(dtype)
        return out

    def globalize(slices, fill, dtype):
        """Gather (S, V) owned-slot slices back to a global (n,) array."""
        out = np.full(n, fill, dtype=dtype)
        out[own_ids] = np.asarray(slices)[ss.owned_mask_h]
        return out

    def front_masks(fronts):
        """(chunk, S, V) pass-start owned slices -> (chunk, n) bool masks."""
        fronts = np.asarray(fronts)  # waits for the chunk, outside the span
        with _trace.span("resident.globalize", cat="engine"):
            return np.stack([globalize(fronts[k], False, bool)
                             for k in range(len(fronts))])

    def count_chunk(ran):
        """Counters of one chunk call's passes that ran (``ran``, the
        per-pass flags it returned): row broadcasts, and the all_gathers
        (the owned ids once, the owned core slices once a pass)."""
        _count_row_bcasts(bcasts, ran, per_pass)
        _SHARD_ALLGATHER.inc((1 + int(np.asarray(ran).sum())) * gathered)

    def budget_hit():
        return max_supersteps is not None and iters >= max_supersteps

    def budget_fn():
        """The chunk jit, with the scan length clamped to the remaining
        superstep budget so a budget below the chunk size is honored
        exactly (each distinct length hits the lru'd jit cache)."""
        c = chunk if max_supersteps is None else \
            max(1, min(chunk, max_supersteps - iters))
        return _shard_chunk_fn(ss.mesh, algorithm, n, num_probes, c, unroll,
                               settle_mask is not None)

    def result(core_f, cnt_f):
        backend.unbind()
        return DecompResult(
            core=np.asarray(core_f, dtype=np.int64),
            cnt=None if cnt_f is None else np.asarray(cnt_f, dtype=np.int64),
            iterations=iters,
            node_computations=comp,
            edge_block_reads=planner.reader.reads,
            node_table_reads=planner.reader.node_table_reads,
            algorithm=algorithm,
            schedule="batch",
            updates_per_iter=upd_hist,
            computations_per_iter=comp_hist,
            backend=backend.name,
            num_shards=ss.S,
            shard_pad_edges=ss.pad_edges,
        )

    # ------------------------------------------------------------ semicore*
    if algorithm == "semicore*":
        if initial_cnt_scan:
            # warm_settle prologue: one accounted full scan recomputes cnt
            # exactly (Eq. 2) w.r.t. the warm upper bound — on the mesh,
            # against the bound sharded structure
            t0 = time.perf_counter()
            with _trace.span("cnt_prologue", cat="maintenance",
                             backend=backend.name, nodes=n):
                planner.charge_only(all_nodes)
                planner.account_node_scan(0, n - 1)
                if ss.E:
                    counts = _shard_counts_fn(ss.mesh, n)
                    cnt_lj = counts(core_j, ss.dst_j, ss.emask_j, ss.lseg_j,
                                    ss.owned_ids_j, ss.owned_mask_j)
                    bcasts.inc()
                    cnt = globalize(cnt_lj, 0, np.int64)
                else:
                    cnt = np.zeros(n, dtype=np.int64)
            _MAINT_PROLOGUE.observe(time.perf_counter() - t0)
        elif warm:
            cnt = np.asarray(cnt, dtype=np.int64).copy()
        else:
            cnt = np.zeros(n, dtype=np.int64)
        active0 = (cnt < core) & (core > 0)
        if settle_mask is not None:
            active0 &= np.asarray(settle_mask, dtype=bool)
        if ss.E == 0:
            # edgeless table: any deficient node drops straight to h = 0 in
            # one pass, and nothing can re-activate — numpy's loop verbatim
            if active0.any():
                f = np.flatnonzero(active0)
                iters, comp = 1, len(f)
                upd_hist.append(int((core[f] != 0).sum()))
                comp_hist.append(len(f))
                _replay_pass(planner, f, None, ss, 0, 0)
                om[0].inc()
                om[1].inc(len(f))
                om[2].inc(int((core[f] != 0).sum()))
                core[f] = 0
                cnt[f] = 0
            return result(core, cnt)
        if not active0.any():
            # settled warm state: zero passes, like numpy's while-loop
            return result(core, cnt)
        # host state the first chunk call uploads (its outputs stay on the
        # mesh); the settle mask goes up once, sharded as the chunk takes it
        cnt_lj = _h2d(localize(cnt, 0, np.int32), _H2D_STATE)
        act_lj = _h2d(localize(active0, False, bool), _H2D_STATE)
        cand_args = ()
        if settle_mask is not None:
            cand_args = (jax.device_put(
                _h2d(localize(np.asarray(settle_mask, dtype=bool), False,
                              bool), _H2D_STATE),
                NamedSharding(ss.mesh, P(tuple(ss.mesh.axis_names)))),)
        nact = _h2d(np.int32(active0.sum()), _H2D_STATE)
        cnt = None
        while cnt is None:
            with _trace.span("resident.chunk", cat="engine",
                             algorithm="semicore*", backend=backend.name,
                             shards=ss.S, chunk=chunk) as sp:
                core_j, cnt_lj, act_lj, nact, fronts, upds, ran = budget_fn()(
                    core_j, cnt_lj, act_lj, nact, *cand_args, ss.dst_j,
                    ss.emask_j, ss.lseg_j, ss.owned_ids_j, ss.owned_mask_j)
                iters, comp = _replay_chunk(
                    planner, ss, 0, 0, None, front_masks(fronts),
                    np.asarray(upds), np.asarray(ran), upd_hist, comp_hist,
                    iters, comp, om, "semicore*")
                count_chunk(ran)
                if int(nact) == 0 or budget_hit():
                    cnt_lj = np.asarray(cnt_lj)
                    with _trace.span("resident.globalize", cat="engine"):
                        cnt = globalize(cnt_lj, 0, np.int64)
                if sp.active:
                    sp.set(passes_run=int(np.asarray(ran).sum()))
        return result(core_j, cnt)

    # ------------------------------------------------- semicore / semicore+
    if ss.E == 0:
        # h == core == degrees == 0 everywhere: semicore runs exactly one
        # all-node pass; semicore+ starts from the all-node frontier and
        # likewise converges on pass one (numpy loop, charge-for-charge)
        if algorithm == "semicore" or n:
            iters, comp = 1, n
            upd_hist.append(0)
            comp_hist.append(n)
            planner.charge_only(all_nodes)
            planner.account_node_scan(0, n - 1)
            om[0].inc()
            om[1].inc(n)
        return result(core, None)

    if algorithm == "semicore":
        # every node, every pass — the final no-update pass included
        done_j = jnp.asarray(False)
        while True:
            with _trace.span("resident.chunk", cat="engine",
                             algorithm="semicore", backend=backend.name,
                             shards=ss.S, chunk=chunk) as sp:
                core_j, done_j, upds, ran = budget_fn()(
                    core_j, done_j, ss.dst_j, ss.emask_j, ss.lseg_j,
                    ss.owned_ids_j, ss.owned_mask_j)
                ran = np.asarray(ran)
                iters, comp = _replay_all_nodes_chunk(
                    planner, ss, 0, 0, None, np.asarray(upds), ran,
                    upd_hist, comp_hist, iters, comp, om)
                count_chunk(ran)
                if sp.active:
                    sp.set(passes_run=int(ran.sum()))
            if bool(done_j) or budget_hit():
                break
        return result(core_j, None)

    if algorithm == "semicore+":
        act_lj = _h2d(localize(np.ones(n, dtype=bool), False, bool),
                      _H2D_STATE)
        nact = _h2d(np.int32(n), _H2D_STATE)
        while True:
            with _trace.span("resident.chunk", cat="engine",
                             algorithm="semicore+", backend=backend.name,
                             shards=ss.S, chunk=chunk) as sp:
                core_j, act_lj, nact, fronts, upds, ran = budget_fn()(
                    core_j, act_lj, nact, ss.dst_j, ss.emask_j, ss.lseg_j,
                    ss.owned_ids_j, ss.owned_mask_j)
                iters, comp = _replay_chunk(
                    planner, ss, 0, 0, None, front_masks(fronts),
                    np.asarray(upds), np.asarray(ran), upd_hist, comp_hist,
                    iters, comp, om, "semicore+")
                count_chunk(ran)
                if sp.active:
                    sp.set(passes_run=int(np.asarray(ran).sum()))
            if int(nact) == 0 or budget_hit():
                break
        return result(core_j, None)

    raise ValueError(f"unknown algorithm {algorithm!r}")
