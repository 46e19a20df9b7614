"""Schedule-agnostic superstep engine: one pass-planner, pluggable compute.

The paper's whole contribution is a single access discipline — scan node
state, refresh h-indices gated by ``cnt(v) < core(v)``, skip untouched edge
blocks — and this module is its single implementation (DESIGN.md §11):

* :class:`PassPlanner` owns everything *about* a pass that is not arithmetic:
  frontier selection, scan-range bookkeeping, and all :class:`BlockReader`
  I/O accounting (edge-block coverage of a frontier, node-table scans).  The
  planner's accounting is backend-independent, so every backend reports the
  same ``edge_block_reads`` / ``node_table_reads`` trace for the same run —
  and the numpy backend's trace is bit-identical to the historical
  ``HostEngine`` batch loops it replaced.

* :class:`ComputeBackend` is the arithmetic: three ops over flattened CSR
  segments — ``h_index(vals, seg_ptr, c_old)`` (LocalCore, Eq. 1, capped at
  the old value), ``compute_cnt(vals, seg_ptr, thresholds)`` (Eq. 2), and
  ``push_decrements`` (the UpdateNbrCnt push rule).  All three are exact
  integer computations, so every backend converges through *identical*
  passes to the identical fixpoint.

* Backends: :class:`NumpyBackend` (the vectorized host reference from
  ``localcore.py``), :class:`XLABackend` (jit'd binary-search h-index over
  ``jax.ops.segment_sum``), :class:`PallasBackend` (h-index probes
  through ``kernels.ops.segment_sum_active``: the frontier-derived
  block-activity mask skips the DMA of untouched edge blocks, the paper's
  I/O saving expressed at the HBM->VMEM level; skipped blocks are reported
  alongside ``edge_block_reads``), and :class:`ShardedBackend` (the mesh
  substrate, DESIGN.md §13: per-device contiguous edge shards, replicated
  O(n) core, one ``all_gather`` of owned slices per superstep — the whole
  fixpoint runs on-mesh through ``resident.run_sharded``).

``push_decrements`` deliberately has a host-side default: cnt is O(n) node
state held *in memory* in the paper's model, and the push rule only touches
cnt using adjacency already scanned by the same pass — it is node-state
bookkeeping, not edge I/O.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from functools import lru_cache

import numpy as np

from .. import runtime as _runtime
from ..obs import metrics as _metrics, trace as _trace
from .localcore import h_index_batch, compute_cnt_batch

__all__ = [
    "DecompResult",
    "PassPlanner",
    "ComputeBackend",
    "NumpyBackend",
    "XLABackend",
    "PallasBackend",
    "ShardedBackend",
    "resolve_backend",
    "run_batch",
    "warm_settle",
    "edge_ge_counts",
    "hindex_bsearch",
    "hindex_bucketed",
    "BACKEND_ENV_VAR",
]

BACKEND_ENV_VAR = "REPRO_BACKEND"

# Registry mirrors of the pallas block-activity tallies (DESIGN.md §14);
# incremented at the same sites as the backend's own counters (begin_pass
# here, the pinned-mask replay in resident.py) so registry deltas reconcile
# exactly with DecompResult.kernel_blocks_active/skipped.
_KB_ACTIVE = _metrics.counter(
    "repro_kernel_blocks_active_total",
    "Pallas kernel blocks whose DMA was issued, summed over passes",
).labels()
_KB_SKIPPED = _metrics.counter(
    "repro_kernel_blocks_skipped_total",
    "Pallas kernel blocks skipped by the frontier activity mask",
).labels()

_MAINT_PROLOGUE = _metrics.histogram(
    "repro_maintenance_cnt_prologue_seconds",
    "Exact-cnt full-scan prologue cost of warm settles (Eq. 2 over all nodes)",
)


def _pass_obs(algorithm: str, backend_name: str, schedule: str = "batch"):
    """The per-pass counter series for one (algorithm, backend, schedule):
    (passes, frontier nodes, core updates).  Hoisted out of the superstep
    loops so each pass costs three plain ``inc`` calls."""
    lab = dict(algorithm=algorithm, backend=backend_name, schedule=schedule)
    return (
        _metrics.counter(
            "repro_engine_passes_total",
            "Supersteps executed (== DecompResult.iterations per run)",
        ).labels(**lab),
        _metrics.counter(
            "repro_engine_frontier_nodes_total",
            "Nodes recomputed, summed over passes (== node_computations)",
        ).labels(**lab),
        _metrics.counter(
            "repro_engine_updates_total",
            "Core-value updates, summed over passes",
        ).labels(**lab),
    )


def _kernel_counts(backend) -> tuple:
    return (getattr(backend, "kernel_blocks_active", 0),
            getattr(backend, "kernel_blocks_skipped", 0))


def _finish_pass_span(sp, backend, c_old_f, changed, ka0, ks0) -> None:
    """Attach pass args shown in the Perfetto side panel: updates, h-index
    probe depth (ceil(log2(cmax+2)) — the device backends' binary-search
    scan count for this frontier), and pallas block activity."""
    cmax = int(c_old_f.max()) if len(c_old_f) else 0
    sp.set(updates=int(changed),
           hindex_probes=int(np.ceil(np.log2(cmax + 2))) if cmax else 0)
    ka1, ks1 = _kernel_counts(backend)
    if (ka1 - ka0) or (ks1 - ks0):
        sp.set(kernel_blocks_active=ka1 - ka0,
               kernel_blocks_skipped=ks1 - ks0)


@dataclass
class DecompResult:
    core: np.ndarray
    cnt: np.ndarray | None
    iterations: int
    node_computations: int
    edge_block_reads: int
    node_table_reads: int
    algorithm: str
    schedule: str
    updates_per_iter: list = field(default_factory=list)
    computations_per_iter: list = field(default_factory=list)
    backend: str = "numpy"
    # Pallas backend only: per-pass kernel-block activity (DESIGN.md §11).
    # Active + skipped = total kernel blocks summed over passes; skipped
    # blocks issue no HBM->VMEM DMA (segsum_active.py).
    kernel_blocks_active: int = 0
    kernel_blocks_skipped: int = 0
    # Shard backend only (DESIGN.md §13): mesh width and the padding cost of
    # the rectangular (S, E) shard layout (slots wasted by balancing all
    # shards to the heaviest one's edge count).
    num_shards: int = 0
    shard_pad_edges: int = 0

    @property
    def kmax(self) -> int:
        return int(self.core.max()) if len(self.core) else 0

    @property
    def memory_bytes(self) -> int:
        """O(n) node-state bytes held in memory (the paper's bound)."""
        per_node = 8 + (8 if self.cnt is not None else 0) + 1
        return len(self.core) * per_node


# ===========================================================================
# Shared jittable ops (consumed by XLABackend AND the SPMD engine)
# ===========================================================================
def edge_ge_counts(nbr_vals, rows, edge_mask, thresholds, num_segments,
                   *, segment_sum_fn, row_bcast_fn=None):
    """#{edges e : nbr_vals[e] >= thresholds[rows[e]]} per segment (Eq. 2).

    Traceable under jit; ``segment_sum_fn(vals, rows, num_segments)`` selects
    the reduction substrate (``jax.ops.segment_sum`` for XLA/SPMD, the Pallas
    blocked kernel for the TPU path).  ``row_bcast_fn(x)`` gives ``x[rows]``
    its own way (the resident xla table spreads it from segment offsets);
    the default gathers by ``rows``.
    """
    import jax.numpy as jnp

    thr = (jnp.take(thresholds, rows, mode="clip") if row_bcast_fn is None
           else row_bcast_fn(thresholds))
    ok = (nbr_vals >= thr) & edge_mask
    return segment_sum_fn(ok.astype(jnp.int32), rows, num_segments)


def hindex_bsearch(nbr_vals, rows, edge_mask, c_old, num_probes,
                   *, segment_sum_fn, unroll: bool = False,
                   row_bcast_fn=None):
    """Vectorized binary search for h = max k <= c_old with count_ge(k) >= k.

    Exactly LocalCore (Eq. 1) capped at ``c_old``: count_ge is non-increasing
    in k, so the feasibility predicate is monotone and the search converges
    to ``min(h_index, c_old)`` in ``num_probes`` segment-sum scans.
    ``unroll`` expands the probe loop so cost analysis sees every scan
    (REPRO_UNROLL_SCANS, launch/dryrun.py); ``row_bcast_fn`` as in
    :func:`edge_ge_counts`.
    """
    import jax
    import jax.numpy as jnp

    num_rows = c_old.shape[0]
    lo = jnp.zeros_like(c_old)
    hi = c_old

    def probe(_, state):
        lo, hi = state
        mid = (lo + hi + 1) // 2
        cnt = edge_ge_counts(nbr_vals, rows, edge_mask, mid, num_rows,
                             segment_sum_fn=segment_sum_fn,
                             row_bcast_fn=row_bcast_fn)
        ok = (cnt >= mid) & (mid > 0)
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    if unroll:
        state = (lo, hi)
        for i in range(num_probes):
            state = probe(i, state)
        lo, hi = state
    else:
        lo, hi = jax.lax.fori_loop(0, num_probes, probe, (lo, hi))
    return lo


def hindex_bucketed(nbr_vals, rows, edge_mask, c_old, owned_mask):
    """Single-pass h-index: bucketed histogram + segmented suffix counts.

    O(E + V) per superstep instead of log2(kmax) masked edge scans — the
    §Perf memory-term optimization of the SPMD engine.  Buckets: node v owns
    positions [off[v], off[v] + c_old[v]] holding counts of
    min(nbr_vals, c_old(v)); suffix counts come from one global cumsum;
    h(v) = max k with s >= k.
    """
    import jax
    import jax.numpy as jnp

    V = c_old.shape[0]
    E = rows.shape[0]
    width = c_old + 1
    ends = jnp.cumsum(width)
    off = ends - width                      # exclusive offsets
    B = E + V + 1                           # static bucket-buffer bound
    capped = jnp.minimum(nbr_vals, jnp.take(c_old, rows, mode="clip"))
    idx = jnp.take(off, rows, mode="clip") + capped
    idx = jnp.where(edge_mask, idx, B - 1)  # masked edges -> dump slot
    hist = jnp.zeros((B,), jnp.int32).at[idx].add(1)
    g = jnp.cumsum(hist)                    # inclusive prefix counts
    # evaluate every bucket position: position p belongs to node v_of(p),
    # candidate k = p - off[v]; s = g[end_v - 1] - g[p - 1]
    pos = jnp.arange(B, dtype=jnp.int32)
    v_of = jnp.clip(jnp.searchsorted(ends, pos, side="right"), 0, V - 1)
    k = pos - jnp.take(off, v_of)
    end_idx = jnp.take(ends, v_of) - 1
    g_prev = jnp.where(pos > 0, jnp.take(g, jnp.maximum(pos - 1, 0)), 0)
    s = jnp.take(g, end_idx) - g_prev
    valid = (k >= 1) & (k <= jnp.take(c_old, v_of)) & (s >= k) & (
        pos < ends[V - 1]) & jnp.take(owned_mask, v_of)
    return jax.ops.segment_max(
        jnp.where(valid, k, 0), v_of, num_segments=V)


@lru_cache(maxsize=None)
def _pallas_full_ops(block_edges: int, interpret: bool):
    """jit'd full-table scans for the pallas backend: the shared
    :func:`hindex_bsearch` / :func:`edge_ge_counts` probe code with
    ``segment_sum_active`` as the reduction substrate, so the frontier's
    block-activity mask gates every probe's DMA and the whole probe loop
    (neighbor gather included) is one traced computation per pass."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from ..kernels.ops import segment_sum_active

    def segsum(vals, rows, num_segments, *, node_active):
        return segment_sum_active(vals, rows, node_active, num_segments,
                                  block_edges=block_edges, interpret=interpret)

    @partial(jax.jit, static_argnames=("num_probes", "num_segments"))
    def hindex(core0, nbr, rows, node_active, c_old, num_probes, num_segments):
        nbr_vals = jnp.take(core0, nbr, mode="clip")
        mask = jnp.ones(rows.shape, jnp.bool_)
        return hindex_bsearch(
            nbr_vals, rows, mask, c_old, num_probes,
            segment_sum_fn=partial(segsum, node_active=node_active))

    @partial(jax.jit, static_argnames=("num_segments",))
    def counts(core0, nbr, rows, node_active, thresholds, num_segments):
        nbr_vals = jnp.take(core0, nbr, mode="clip")
        mask = jnp.ones(rows.shape, jnp.bool_)
        return edge_ge_counts(
            nbr_vals, rows, mask, thresholds, num_segments,
            segment_sum_fn=partial(segsum, node_active=node_active))

    return hindex, counts


@lru_cache(maxsize=None)
def _xla_host_ops():
    """jit'd host-side wrappers over the shared ops (built lazily so the
    numpy-only path never imports jax)."""
    from functools import partial

    import jax

    def segsum(vals, rows, num_segments):
        return jax.ops.segment_sum(vals, rows, num_segments=num_segments)

    @partial(jax.jit, static_argnames=("num_probes",))
    def hindex(nbr_vals, rows, edge_mask, c_old, num_probes):
        return hindex_bsearch(nbr_vals, rows, edge_mask, c_old, num_probes,
                              segment_sum_fn=segsum)

    @jax.jit
    def counts(nbr_vals, rows, edge_mask, thresholds):
        return edge_ge_counts(nbr_vals, rows, edge_mask, thresholds,
                              thresholds.shape[0], segment_sum_fn=segsum)

    return hindex, counts


def _next_pow2(x: int) -> int:
    return 1 << max(0, int(x - 1).bit_length())


# ===========================================================================
# Compute backends
# ===========================================================================
class ComputeBackend:
    """Arithmetic of one superstep over flattened CSR segments.

    ``vals``/``seg_ptr`` follow the ``PassPlanner.gather`` layout: ``vals``
    holds the neighbor core values of the P frontier nodes segment-contiguous,
    ``seg_ptr`` the (P+1,) offsets.  All ops are exact over integers, so
    backends are interchangeable pass-for-pass.
    """

    name = "abstract"
    # whether the backend reads the gathered (vals, seg_ptr) arrays; a
    # full-table backend (pallas) can skip the host gather where the driver
    # needs nothing but the I/O charge (plain SemiCore).
    consumes_gather = True
    # device backends run the whole fixpoint device-resident (resident.py):
    # node state + edge table uploaded once, many passes per host round-trip
    # (REPRO_DEVICE_RESIDENT=0 falls back to the per-pass loop below).
    device_resident = False

    # -- lifecycle hooks (no-ops by default) --------------------------------
    def bind(self, planner: "PassPlanner") -> None:
        """Called once per run, before the first pass."""

    def unbind(self) -> None:
        """Called when a run's result is built; drop any bound working set."""

    def begin_pass(self, frontier: np.ndarray, core: np.ndarray) -> None:
        """Called at the start of every pass with the frontier node ids and
        the pass-start core array (before any in-pass mutation)."""

    def io_report(self) -> dict:
        """Backend-side I/O effects (e.g. skipped kernel blocks)."""
        return {}

    # -- ops ----------------------------------------------------------------
    def h_index(self, vals: np.ndarray, seg_ptr: np.ndarray,
                c_old: np.ndarray) -> np.ndarray:
        """min(h-index of each segment, c_old) — LocalCore (Eq. 1)."""
        raise NotImplementedError

    def compute_cnt(self, vals: np.ndarray, seg_ptr: np.ndarray,
                    thresholds: np.ndarray) -> np.ndarray:
        """#{u in segment : vals(u) >= threshold(segment)} — Eq. 2."""
        raise NotImplementedError

    def push_decrements(self, nbr_flat: np.ndarray, seg_ptr: np.ndarray,
                        h: np.ndarray, c_old: np.ndarray, core: np.ndarray,
                        n: int) -> np.ndarray:
        """UpdateNbrCnt push rule: dec[u] = #{edges (v -> u) in the frontier
        adjacency : core_now(u) in (h(v), c_old(v)]}.

        Host-side by default for every backend: cnt is in-memory O(n) node
        state and the push reuses adjacency the pass already scanned — no
        edge I/O is involved (DESIGN.md §11).
        """
        lens = np.diff(seg_ptr)
        h_rep = np.repeat(h, lens)
        c_old_rep = np.repeat(c_old, lens)
        core_now_u = core[nbr_flat]
        mask = (core_now_u > h_rep) & (core_now_u <= c_old_rep)
        if mask.any():
            return np.bincount(nbr_flat[mask].astype(np.int64), minlength=n)
        return np.zeros(n, dtype=np.int64)


class NumpyBackend(ComputeBackend):
    """The vectorized host reference (localcore.py) — the historical batch
    schedule, preserved bit-for-bit."""

    name = "numpy"

    def h_index(self, vals, seg_ptr, c_old):
        return np.minimum(h_index_batch(vals, seg_ptr), c_old)

    def compute_cnt(self, vals, seg_ptr, thresholds):
        return compute_cnt_batch(vals, seg_ptr, thresholds)


class DeviceBackend(ComputeBackend):
    """Shared device-residency machinery of the xla / pallas backends.

    The flat merged edge table is built and uploaded once per *graph
    version* — a :class:`~repro.core.resident.ResidentStructure` keyed by
    the planner's structure token — and reused across runs, supersteps, and
    (on a long-lived ``CoreMaintainer`` with ``retain_structure``) across
    ``apply_batch`` calls whose batches turned out structure-free.  This is
    the fix for PR 3's per-pass re-upload (`XLABackend._pack`) and per-bind
    O(m) ``np.repeat`` rebuild (`PallasBackend.bind`): structure moves to
    the device exactly once per distinct graph version.

    ``retain_structure=False`` (the default) keeps the one-shot
    ``decompose`` memory guarantee: ``unbind`` drops the host + device
    edge-table copies when the result is built.
    """

    device_resident = True
    # set by long-lived owners (CoreMaintainer): keep the structure cache
    # across unbind so the next batch on an unchanged graph re-uploads nothing
    retain_structure = False

    def __init__(self):
        self._resident = None
        self.structure_builds = 0  # cache-miss counter (tests / bench)

    def bind_resident(self, planner: "PassPlanner"):
        """The device-resident working set for the planner's current graph
        version; cached, rebuilt only on structural change."""
        from .resident import build_structure

        planner.eng._sync()
        rs = self._resident
        if rs is not None and rs.matches(planner):
            return rs
        rs = build_structure(planner)
        self._validate_structure(rs)
        self.structure_builds += 1
        self._resident = rs
        return rs

    def _validate_structure(self, rs) -> None:
        """Backend-specific structure checks (pallas float32 range)."""

    def resident_substrate(self, planner: "PassPlanner") -> tuple:
        """(kind, block_edges, interpret) — the static key of the resident
        superstep jit for this backend."""
        raise NotImplementedError

    def release_resident(self) -> None:
        if not self.retain_structure:
            self._resident = None

    def unbind(self):
        self.release_resident()


class XLABackend(DeviceBackend):
    """jit'd binary-search h-index over ``jax.ops.segment_sum`` — the same
    shared ops (:func:`edge_ge_counts` / :func:`hindex_bsearch`) the SPMD
    engine consumes.

    The default path is device-resident (resident.py): the edge table is
    uploaded once at bind and the whole fixpoint runs on device.  The
    per-pass methods below remain as the legacy / direct-use path
    (``REPRO_DEVICE_RESIDENT=0``): they operate on host-gathered frontier
    segments padded to powers of two (edges and segments independently) so
    jit recompiles O(log) times per graph instead of once per frontier size.
    """

    name = "xla"

    def __init__(self):
        super().__init__()
        # one-slot pack memo: a SemiCore* pass calls h_index then compute_cnt
        # with the *same* (vals, seg_ptr) arrays — pack and ship them once.
        # Holding the key arrays keeps their ids valid for the identity test.
        self._pack_memo: tuple | None = None

    def resident_substrate(self, planner):
        return ("xla", 0, False)

    def _pack(self, vals, seg_ptr):
        import jax.numpy as jnp

        memo = self._pack_memo
        if memo is not None and memo[0] is vals and memo[1] is seg_ptr:
            return memo[2]
        P = len(seg_ptr) - 1
        lens = np.diff(seg_ptr)
        E = int(len(vals))
        Ep = _next_pow2(max(E, 1))
        rows = np.zeros(Ep, dtype=np.int32)
        rows[:E] = np.repeat(np.arange(P, dtype=np.int32), lens)
        mask = np.zeros(Ep, dtype=bool)
        mask[:E] = True
        v = np.zeros(Ep, dtype=np.int32)
        v[:E] = vals
        packed = (jnp.asarray(v), jnp.asarray(rows), jnp.asarray(mask))
        self._pack_memo = (vals, seg_ptr, packed)
        return packed

    def unbind(self):
        self._pack_memo = None
        self.release_resident()

    def h_index(self, vals, seg_ptr, c_old):
        P = len(seg_ptr) - 1
        c_old = np.asarray(c_old, dtype=np.int64)
        cmax = int(c_old.max()) if P else 0
        if P == 0 or len(vals) == 0 or cmax == 0:
            return np.zeros(P, dtype=np.int64)
        import jax.numpy as jnp

        hindex, _ = _xla_host_ops()
        v, rows, mask = self._pack(vals, seg_ptr)
        Pp = _next_pow2(P)
        c = np.zeros(Pp, dtype=np.int32)
        c[:P] = c_old
        num_probes = int(np.ceil(np.log2(cmax + 2)))
        h = hindex(v, rows, mask, jnp.asarray(c), num_probes)
        return np.asarray(h[:P]).astype(np.int64)

    def compute_cnt(self, vals, seg_ptr, thresholds):
        P = len(seg_ptr) - 1
        if P == 0 or len(vals) == 0:
            return np.zeros(P, dtype=np.int64)
        import jax.numpy as jnp

        _, counts = _xla_host_ops()
        v, rows, mask = self._pack(vals, seg_ptr)
        Pp = _next_pow2(P)
        thr = np.zeros(Pp, dtype=np.int32)
        thr[:P] = thresholds
        cnt = counts(v, rows, mask, jnp.asarray(thr))
        return np.asarray(cnt[:P]).astype(np.int64)


class PallasBackend(DeviceBackend):
    """The paper's block discipline at the kernel layer (DESIGN.md §6, §11).

    The full edge table lives as one flat blocked axis (HBM); every pass
    derives a block-activity mask from the frontier and runs the h-index
    probes / cnt scans through ``kernels.ops.segment_sum_active``, whose
    ``index_map`` re-points inactive blocks at an already-resident tile — no
    DMA is issued for them.  Skipped blocks are counted once per pass (the
    mask is fixed across the probes of a pass, mirroring the paper's one
    read I/O per touched block per pass) and reported on the result as
    ``kernel_blocks_skipped`` alongside the planner's ``edge_block_reads``.

    The default path runs the whole fixpoint device-resident (resident.py)
    with the block-activity mask derived on-device from the frontier state;
    the per-pass methods below serve the ``REPRO_DEVICE_RESIDENT=0`` legacy
    loop.  Either way the edge table is the shared
    :class:`~repro.core.resident.ResidentStructure` — built and uploaded
    once per graph version, not per bind (the old per-``apply_batch``
    O(m) ``np.repeat`` rebuild).

    The hot path runs the superstep through the fused histogram kernel
    (``kernels.fused_superstep``, DESIGN.md §16): both the device-resident
    fixpoint and the legacy per-pass methods below dispatch one kernel call
    for the h-index (two per pass for SemiCore+/*) instead of one
    ``segment_sum_active`` launch per h-index probe
    (``REPRO_PALLAS_FUSED=0`` restores the per-probe oracle).

    The platform picks the kernel mode (``kernels.resolve_interpret``):
    compiled on an accelerator, the Pallas interpreter on CPU; asking for
    the other mode (``interpret=``, the ``"pallas-interpret"`` name or
    ``REPRO_PALLAS_INTERPRET``) raises.  Accounting kernel blocks are capped at
    512 edges; the fused kernel's tile size is independently tunable via
    ``REPRO_FUSED_BLOCK_EDGES``.
    """

    name = "pallas"
    consumes_gather = False  # scans its own resident full table

    def __init__(self, *, block_edges: int | None = None,
                 interpret: bool | None = None):
        super().__init__()
        self.block_edges = block_edges
        self.interpret = interpret
        self.kernel_blocks_active = 0
        self.kernel_blocks_skipped = 0
        self.passes = 0

    def _resolve_interpret(self) -> bool:
        from ..kernels import resolve_interpret

        return resolve_interpret(self.interpret)

    def _block_edges(self, planner) -> int:
        be = self.block_edges or min(planner.reader.block_edges, 512)
        return max(1, int(be))

    def resident_substrate(self, planner):
        return ("pallas", self._block_edges(planner),
                self._resolve_interpret())

    def _validate_structure(self, rs) -> None:
        # the kernel accumulates per-node counts in float32 (one-hot matmul +
        # scatter epilogue, kernels/ops.py): exact only below 2**24 — fail
        # loudly instead of converging to a silently-wrong core array
        if rs.dmax >= (1 << 24):
            raise ValueError(
                f"pallas backend: max degree {rs.dmax} exceeds the float32 "
                "integer-exact range (2**24) of the blocked segment-sum "
                "kernel; use the xla or numpy backend for this graph"
            )

    # -- lifecycle ----------------------------------------------------------
    def bind(self, planner):
        self._interpret = self._resolve_interpret()
        # per-run report: active + skipped = total kernel blocks x passes
        self.kernel_blocks_active = 0
        self.kernel_blocks_skipped = 0
        self.passes = 0
        rs = self.bind_resident(planner)  # cached across unchanged versions
        self.n = planner.n
        self.E = rs.E
        self.seg_ptr = rs.seg_ptr  # flat-table offsets, for block coverage
        self.be = self._block_edges(planner)
        self.nb = -(-max(rs.E, 1) // self.be)
        self._nbr_j, self._rows_j, _ = rs.edge_table("pallas")

    def unbind(self):
        # don't keep per-pass state alive on a long-lived maintainer between
        # runs; the version-keyed structure cache obeys retain_structure
        for attr in ("seg_ptr", "_rows_j", "_nbr_j",
                     "_core0_j", "_active_j", "_frontier", "_cnt_cache"):
            if hasattr(self, attr):
                delattr(self, attr)
        self.release_resident()

    def begin_pass(self, frontier, core):
        import jax.numpy as jnp

        self.passes += 1
        self._cnt_cache = None  # (thresholds, cnt) from the fused h_index
        self._core0_j = jnp.asarray(np.asarray(core, dtype=np.int32))
        active = np.zeros(self.n, dtype=bool)
        active[np.asarray(frontier, dtype=np.int64)] = True
        self._active_j = jnp.asarray(active)
        self._frontier = np.asarray(frontier, dtype=np.int64)
        if self.E:
            # block activity from the frontier's flat-table spans, O(F + nb)
            # (a kernel block is active iff some frontier node's contiguous
            # edge range covers it — same mask the kernel derives per-row)
            lo = self.seg_ptr[self._frontier]
            hi = self.seg_ptr[self._frontier + 1]
            nz = lo < hi
            cov = np.zeros(self.nb + 1, dtype=np.int64)
            if nz.any():
                np.add.at(cov, lo[nz] // self.be, 1)
                np.add.at(cov, (hi[nz] - 1) // self.be + 1, -1)
            na = int((np.cumsum(cov[:-1]) > 0).sum())
            self.kernel_blocks_active += na
            self.kernel_blocks_skipped += self.nb - na
            _KB_ACTIVE.inc(na)
            _KB_SKIPPED.inc(self.nb - na)

    def io_report(self):
        return {
            "kernel_blocks_active": self.kernel_blocks_active,
            "kernel_blocks_skipped": self.kernel_blocks_skipped,
        }

    # -- full-table scans ---------------------------------------------------
    # Hot path (REPRO_PALLAS_FUSED != 0): one fused kernel call per pass —
    # the fused kernel returns (h, cnt_at_h) together, so the SemiCore*
    # pass's compute_cnt(thresholds == h) is served from a per-pass cache
    # with no extra dispatch.  REPRO_PALLAS_FUSED=0 reverts to the PR 3
    # per-probe dispatch (_pallas_full_ops), kept as the parity oracle.
    def h_index(self, vals, seg_ptr, c_old):
        import jax.numpy as jnp

        F = len(self._frontier)
        c_old = np.asarray(c_old, dtype=np.int64)
        cmax = int(c_old.max()) if F else 0
        if F == 0 or cmax == 0 or self.E == 0:
            return np.zeros(F, dtype=np.int64)
        num_probes = int(np.ceil(np.log2(cmax + 2)))
        from ..kernels import fused_superstep as fsk

        if fsk.fused_enabled():
            ft = self._resident.fused(fsk.fused_block_edges())
            h_j, cnth_j = fsk.fused_hindex(
                self._core0_j, self._active_j, ft.arrays, dims=ft.dims,
                num_probes=num_probes, interpret=self._interpret)
            h = np.asarray(h_j).astype(np.int64)[self._frontier]
            self._cnt_cache = (
                h, np.asarray(cnth_j).astype(np.int64)[self._frontier])
            return h
        hindex, _ = _pallas_full_ops(self.be, self._interpret)
        hi = np.zeros(self.n, dtype=np.int32)
        hi[self._frontier] = c_old
        h = hindex(self._core0_j, self._nbr_j, self._rows_j, self._active_j,
                   jnp.asarray(hi), num_probes, self.n)
        return np.asarray(h).astype(np.int64)[self._frontier]

    def compute_cnt(self, vals, seg_ptr, thresholds):
        import jax.numpy as jnp

        F = len(self._frontier)
        if F == 0 or self.E == 0:
            return np.zeros(F, dtype=np.int64)
        thr = np.zeros(self.n, dtype=np.int32)
        thr[self._frontier] = thresholds
        from ..kernels import fused_superstep as fsk

        if fsk.fused_enabled():
            cache = getattr(self, "_cnt_cache", None)
            if cache is not None and np.array_equal(
                    cache[0], np.asarray(thresholds, dtype=np.int64)):
                return cache[1]
            ft = self._resident.fused(fsk.fused_block_edges())
            cnt = fsk.fused_counts(
                self._core0_j, jnp.asarray(thr), self._active_j, ft.arrays,
                dims=ft.dims, interpret=self._interpret)
            return np.asarray(cnt).astype(np.int64)[self._frontier]
        _, counts = _pallas_full_ops(self.be, self._interpret)
        cnt = counts(self._core0_j, self._nbr_j, self._rows_j, self._active_j,
                     jnp.asarray(thr), self.n)
        return np.asarray(cnt).astype(np.int64)[self._frontier]


class ShardedBackend(DeviceBackend):
    """The mesh substrate: the paper's semi-external contract on a device
    mesh (DESIGN.md §5, §13).

    Edge shards never move: :func:`~repro.core.distributed.shard_arrays`
    cuts the merged flat table into contiguous node ranges minimax-balanced
    by edge count, so every owned node's complete adjacency is local and the
    h-index / cnt arithmetic needs no cross-device reduction.  Node state
    (``core``) is replicated O(n) per device — the "< 4.2 GB" headline bound.
    The whole fixpoint runs on-mesh (``resident.run_sharded``): one
    ``shard_map``'d fused superstep per pass (the same
    ``resident.fused_hindex`` / ``fused_counts`` bodies the flat resident
    path scans), ``lax.scan`` chunks of cond-gated passes per host
    round-trip, and a *single* ``all_gather`` of the owned core slices per
    superstep (plus one scalar ``psum`` for convergence).  The planner's I/O
    trace is replayed bit-identically on host from the per-chunk pinned
    owned-frontier slices, so the shard backend walks the exact numpy
    passes — the differential sweep asserts it at every shard count.

    The bound :class:`~repro.core.resident.ShardedStructure` is cached per
    base-CSR version exactly like the flat resident table: a long-lived
    ``CoreMaintainer`` re-binding after a no-op batch re-shards nothing.

    ``num_shards=None`` uses every visible device; the mesh spans
    ``jax.devices()[:num_shards]`` (``REPRO_NUM_SHARDS`` /
    ``CoreGraphConfig.num_shards`` select it by env / config).  There is no
    per-pass host fallback: the shard backend is resident-only
    (``REPRO_DEVICE_RESIDENT=0`` does not apply).
    """

    name = "shard"
    consumes_gather = False
    mesh_sharded = True      # run_resident dispatches to run_sharded
    requires_resident = True  # no per-pass legacy loop exists for this one

    def __init__(self, num_shards: int | None = None, devices=None):
        super().__init__()
        self.num_shards = None if num_shards is None else int(num_shards)
        # explicit device list (e.g. from a caller's Mesh): the mesh is
        # built over exactly these, letting multi-tenant hosts pin the run
        # to a device subset instead of always taking jax.devices()[:S]
        self.devices = None if devices is None else list(devices)

    def resolve_shards(self) -> int:
        import jax

        avail = len(self.devices if self.devices is not None
                    else jax.devices())
        S = avail if self.num_shards is None else self.num_shards
        if not 1 <= S <= avail:
            raise ValueError(
                f"shard backend: num_shards={S} but only {avail} device(s) "
                "are visible; force host devices with "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N or "
                "lower CoreGraphConfig.num_shards / REPRO_NUM_SHARDS")
        return S

    def bind_resident(self, planner: "PassPlanner"):
        from .resident import build_sharded_structure

        planner.eng._sync()
        S = self.resolve_shards()
        rs = self._resident
        if rs is not None and rs.S == S and rs.matches(planner):
            return rs
        rs = build_sharded_structure(planner, S, devices=self.devices)
        self.structure_builds += 1
        self._resident = rs
        return rs


def resolve_backend(backend) -> ComputeBackend:
    """Backend instance passthrough, or by name; ``None`` defers to the
    ``REPRO_BACKEND`` environment variable (default: numpy), resolved
    through :func:`repro.runtime.setting` like every other knob."""
    if isinstance(backend, ComputeBackend):
        return backend
    if backend is None:
        backend = _runtime.setting("backend") or "numpy"
    name = str(backend)
    if name == "numpy":
        return NumpyBackend()
    if name == "xla":
        return XLABackend()
    if name == "pallas":
        return PallasBackend()
    if name == "pallas-interpret":
        # the CPU spelling of "pallas": refused on an accelerator
        backend = PallasBackend(interpret=True)
        backend._resolve_interpret()
        return backend
    if name == "shard":
        ns = os.environ.get("REPRO_NUM_SHARDS")
        return ShardedBackend(num_shards=int(ns) if ns else None)
    raise ValueError(f"unknown compute backend {backend!r}")


# ===========================================================================
# Pass planner: frontier / vrange / I/O accounting
# ===========================================================================
class PassPlanner:
    """Owns the I/O side of a pass over blocked storage.

    Wraps a :class:`HostEngine` (graph + BlockReader + update buffer) and
    provides the two primitives every batch schedule is made of: gather the
    frontier's flattened adjacency (charging exact block I/O), and account a
    node-table scan over the frontier's id range.  Compute never touches the
    reader; backends never touch the planner's accounting.
    """

    def __init__(self, engine):
        self.eng = engine

    @property
    def reader(self):
        return self.eng.reader

    @property
    def n(self) -> int:
        return self.eng.n

    # ------------------------------------------------------------- structure
    def _segments(self, nodes: np.ndarray):
        """Flattened raw-CSR adjacency of ``nodes`` (no I/O charge, no
        buffered-delta merge): (nbr_flat, seg_ptr, lo, hi)."""
        g = self.eng.graph
        lo = g.indptr[nodes]
        hi = g.indptr[nodes + 1]
        lens = (hi - lo).astype(np.int64)
        total = int(lens.sum())
        seg_ptr = np.zeros(len(nodes) + 1, dtype=np.int64)
        np.cumsum(lens, out=seg_ptr[1:])
        if total:
            flat = np.repeat(lo - seg_ptr[:-1], lens) + np.arange(
                total, dtype=np.int64)
            nbr_flat = np.asarray(g.adj)[flat]
        else:
            nbr_flat = np.empty(0, dtype=np.int32)
        return nbr_flat, seg_ptr, lo, hi

    def _merge_buffered(self, nodes, nbr_flat, seg_ptr):
        """Splice buffered edge deltas into the flattened segments (in-memory,
        no extra block I/O): locate the dirty nodes vectorized and rebuild
        only their segments, so a handful of buffered updates costs
        O(|dirty|) Python work plus the unavoidable flat-array copy."""
        buffered = self.eng.buffered
        if buffered is None or not buffered._size:
            return nbr_flat, seg_ptr
        with _trace.span("engine.merge_buffered", cat="engine",
                         buffered=buffered._size) as sp:
            dirty = np.fromiter(
                buffered._ins.keys() | buffered._del.keys(), dtype=np.int64)
            hit = np.flatnonzero(np.isin(nodes, dirty))
            if sp.active:
                sp.set(dirty=len(hit))
            if not len(hit):
                return nbr_flat, seg_ptr
            merged = [
                np.asarray(
                    buffered.merged_neighbors(
                        int(nodes[i]), nbr_flat[seg_ptr[i]: seg_ptr[i + 1]]
                    ),
                    dtype=np.int32,
                )
                for i in hit
            ]
            new_lens = np.diff(seg_ptr)
            new_lens[hit] = [len(s) for s in merged]
            new_ptr = np.zeros(len(nodes) + 1, dtype=np.int64)
            np.cumsum(new_lens, out=new_ptr[1:])
            out = np.empty(int(new_ptr[-1]), dtype=np.int32)
            prev_old = 0
            prev_new = 0
            for seg, i in zip(merged, hit):
                span = int(seg_ptr[i]) - prev_old  # untouched run before i
                out[prev_new: prev_new + span] = \
                    nbr_flat[prev_old: prev_old + span]
                prev_new += span
                out[prev_new: prev_new + len(seg)] = seg
                prev_new += len(seg)
                prev_old = int(seg_ptr[i + 1])
            out[prev_new:] = nbr_flat[prev_old:]
        return out, new_ptr

    def full_structure(self):
        """Merged flat adjacency of *all* nodes, charge-free: the backend's
        HBM-resident working set (disk I/O stays per-pass, planner-side)."""
        self.eng._sync()
        nodes = np.arange(self.n, dtype=np.int64)
        nbr_flat, seg_ptr, _, _ = self._segments(nodes)
        return self._merge_buffered(nodes, nbr_flat, seg_ptr)[:2]

    # ------------------------------------------------------------------ I/O
    def charge_blocks(self, lo: np.ndarray, hi: np.ndarray) -> None:
        """Charge one pass over the union of [lo//B, (hi-1)//B] block
        intervals, streamed through the reader's buffer pool in ascending
        order (single buffer when pool_blocks == 1, LRU page cache
        otherwise)."""
        reader = self.reader
        B = reader.block_edges
        lens = hi - lo
        nz = lens > 0
        if nz.any():
            first = (lo[nz] // B).astype(np.int64)
            last = ((hi[nz] - 1) // B).astype(np.int64)
            nb = reader.num_blocks
            diff = np.zeros(nb + 1, dtype=np.int64)
            np.add.at(diff, first, 1)
            np.add.at(diff, last + 1, -1)
            covered = np.cumsum(diff[:-1]) > 0
            reader.charge_pass(np.flatnonzero(covered))

    def gather(self, nodes: np.ndarray, core: np.ndarray):
        """Flattened adjacency of ``nodes`` + exact block-I/O accounting.

        Returns (neighbor core values, segment offsets, flat neighbor ids).
        """
        self.eng._sync()
        nbr_flat, seg_ptr, lo, hi = self._segments(nodes)
        self.charge_blocks(lo, hi)
        nbr_flat, seg_ptr = self._merge_buffered(nodes, nbr_flat, seg_ptr)
        return core[nbr_flat], seg_ptr, nbr_flat

    def charge_only(self, nodes: np.ndarray) -> None:
        """The I/O charge of :meth:`gather` without materializing the
        adjacency — for passes whose backend scans its own resident table
        and the driver needs nothing but the accounting."""
        self.eng._sync()
        g = self.eng.graph
        self.charge_blocks(g.indptr[nodes], g.indptr[nodes + 1])

    def gather_structure(self, nodes: np.ndarray):
        """Like :meth:`gather` (same I/O charge, same merged segments) but
        without the neighbor-value fancy-index — for full-table backends
        that need only frontier structure (propagation, push rule).

        Returns (seg_ptr, nbr_flat).
        """
        self.eng._sync()
        nbr_flat, seg_ptr, lo, hi = self._segments(nodes)
        self.charge_blocks(lo, hi)
        nbr_flat, seg_ptr = self._merge_buffered(nodes, nbr_flat, seg_ptr)
        return seg_ptr, nbr_flat

    def account_node_scan(self, v_lo: int, v_hi: int) -> None:
        self.reader.account_node_table_scan(v_lo, v_hi)


# ===========================================================================
# The generic batch superstep loop (Jacobi; one superstep == one pass)
# ===========================================================================
def run_batch(engine, algorithm: str, backend=None, *,
              core: np.ndarray | None = None,
              cnt: np.ndarray | None = None,
              rebind: bool = True,
              superstep_chunk: int | None = None) -> DecompResult:
    """Run a batch-schedule decomposition on ``engine`` with ``backend``.

    The three paper algorithms differ only in frontier policy:

    * ``semicore``   — every node, every pass (Alg. 3);
    * ``semicore+``  — neighbors of changed nodes (Alg. 4 / Lemma 4.1);
    * ``semicore*``  — cnt-gated: recompute v only while cnt(v) < core(v)
      (Alg. 5 / Lemma 4.2), with exact cnt maintenance under simultaneous
      updates (DESIGN.md §2).

    With (core, cnt) given for ``semicore*``, runs the warm-started settle
    loop (maintenance / recovery path).  ``rebind=False`` continues on a
    backend the caller already bound to this engine (:func:`warm_settle`'s
    extra cnt pass stays inside one bind scope, so the kernel-block report
    covers it just like the planner's read counters do).

    Device backends default to the device-resident fixpoint (resident.py):
    state and edge table upload once, many fused passes per host round-trip,
    planner accounting replayed bit-identically from the per-pass frontier
    summaries.  ``REPRO_DEVICE_RESIDENT=0`` selects the per-pass loop below.
    """
    backend = resolve_backend(backend)
    if backend.device_resident and rebind:
        from .resident import resident_enabled, run_resident

        if resident_enabled() or getattr(backend, "requires_resident", False):
            return run_resident(engine, algorithm, backend, core=core,
                                cnt=cnt, superstep_chunk=superstep_chunk)
    planner = engine.planner
    n = engine.n
    if rebind:
        backend.bind(planner)
    comp, iters = 0, 0
    upd_hist: list = []
    comp_hist: list = []

    if algorithm == "semicore":
        core = engine.degrees().astype(np.int64)
        all_nodes = np.arange(n, dtype=np.int64)
        om_p, om_f, om_u = _pass_obs("semicore", backend.name)
        while True:
            iters += 1
            with _trace.span("superstep", cat="engine", algorithm="semicore",
                             backend=backend.name, index=iters,
                             frontier=n) as sp:
                ka0, ks0 = _kernel_counts(backend)
                backend.begin_pass(all_nodes, core)
                if backend.consumes_gather:
                    vals, seg_ptr, _ = planner.gather(all_nodes, core)
                else:  # full-table backend; this driver only needs the charge
                    planner.charge_only(all_nodes)
                    vals = seg_ptr = None
                planner.account_node_scan(0, n - 1)
                h = backend.h_index(vals, seg_ptr, core)
                changed = int((h != core).sum())
                if sp.active:
                    _finish_pass_span(sp, backend, core, changed, ka0, ks0)
            om_p.inc()
            om_f.inc(n)
            om_u.inc(changed)
            upd_hist.append(changed)
            comp_hist.append(n)
            comp += n
            core = h
            if changed == 0:
                break
        return _result(planner, backend, core, None, iters, comp,
                       "semicore", upd_hist, comp_hist)

    if algorithm == "semicore+":
        core = engine.degrees().astype(np.int64)
        frontier = np.arange(n, dtype=np.int64)
        om_p, om_f, om_u = _pass_obs("semicore+", backend.name)
        while len(frontier):
            iters += 1
            with _trace.span("superstep", cat="engine", algorithm="semicore+",
                             backend=backend.name, index=iters,
                             frontier=len(frontier)) as sp:
                ka0, ks0 = _kernel_counts(backend)
                backend.begin_pass(frontier, core)
                if backend.consumes_gather:
                    vals, seg_ptr, nbr_flat = planner.gather(frontier, core)
                else:  # structure only: propagation needs nbr_flat, not values
                    seg_ptr, nbr_flat = planner.gather_structure(frontier)
                    vals = None
                planner.account_node_scan(int(frontier[0]), int(frontier[-1]))
                h = backend.h_index(vals, seg_ptr, core[frontier])
                changed_mask = h != core[frontier]
                if sp.active:
                    _finish_pass_span(sp, backend, core[frontier],
                                      changed_mask.sum(), ka0, ks0)
            om_p.inc()
            om_f.inc(len(frontier))
            om_u.inc(int(changed_mask.sum()))
            comp += len(frontier)
            comp_hist.append(len(frontier))
            upd_hist.append(int(changed_mask.sum()))
            core[frontier] = h
            # Lemma 4.1: only neighbors of changed nodes can change next pass
            lens = np.diff(seg_ptr)
            seg_changed = np.repeat(changed_mask, lens)
            frontier = np.unique(nbr_flat[seg_changed].astype(np.int64))
            frontier = frontier[core[frontier] > 0]
        return _result(planner, backend, core, None, iters, comp,
                       "semicore+", upd_hist, comp_hist)

    if algorithm == "semicore*":
        warm = core is not None
        if not warm:
            core = engine.degrees().astype(np.int64)
            cnt = np.zeros(n, dtype=np.int64)
        else:
            core = np.asarray(core, dtype=np.int64).copy()
            cnt = np.asarray(cnt, dtype=np.int64).copy()
        frontier = np.flatnonzero((cnt < core) & (core > 0))
        om_p, om_f, om_u = _pass_obs("semicore*", backend.name)
        while len(frontier):
            iters += 1
            with _trace.span("superstep", cat="engine", algorithm="semicore*",
                             backend=backend.name, index=iters,
                             frontier=len(frontier)) as sp:
                ka0, ks0 = _kernel_counts(backend)
                backend.begin_pass(frontier, core)
                if backend.consumes_gather:
                    vals_old, seg_ptr, nbr_flat = planner.gather(frontier, core)
                else:  # structure only: push rule needs nbr_flat, not values
                    seg_ptr, nbr_flat = planner.gather_structure(frontier)
                    vals_old = None
                planner.account_node_scan(int(frontier[0]), int(frontier[-1]))
                c_old_f = core[frontier].copy()
                h = backend.h_index(vals_old, seg_ptr, c_old_f)
                if sp.active:
                    _finish_pass_span(sp, backend, c_old_f,
                                      (h != c_old_f).sum(), ka0, ks0)
            om_p.inc()
            om_f.inc(len(frontier))
            om_u.inc(int((h != c_old_f).sum()))
            comp += len(frontier)
            comp_hist.append(len(frontier))
            upd_hist.append(int((h != c_old_f).sum()))
            core[frontier] = h
            # exact cnt under simultaneous updates (DESIGN.md §2):
            # (1) recompute cnt of frontier against pass-start neighbor values
            cnt[frontier] = backend.compute_cnt(vals_old, seg_ptr, h)
            # (2) push decrements: edge (v in F -> u) with
            #     core_now(u) in (h(v), c_old(v)]
            cnt -= backend.push_decrements(nbr_flat, seg_ptr, h, c_old_f,
                                           core, n)
            frontier = np.flatnonzero((cnt < core) & (core > 0))
        return _result(planner, backend, core, cnt, iters, comp,
                       "semicore*", upd_hist, comp_hist)

    raise ValueError(f"unknown algorithm {algorithm!r}")


def warm_settle(engine, core0: np.ndarray, applied_inserts: int,
                backend=None, *,
                superstep_chunk: int | None = None) -> DecompResult:
    """Settle to the exact decomposition from a stale ``core0`` after
    structural updates: the shared maintenance / recovery discipline
    (DESIGN.md §9, §11).

    ``min(core0 + I, deg)`` — I the number of applied insertions — is a
    pointwise upper bound of the new decomposition (one insertion raises any
    core by at most one, deletions never raise it; ``deg`` always bounds).
    One full scan recomputes cnt exactly w.r.t. the warm bounds (Eq. 2),
    then SemiCore* batch passes converge from above (Thm 4.1) to the exact
    fixpoint.
    """
    backend = resolve_backend(backend)
    n = engine.n
    warm = np.minimum(
        np.asarray(core0, dtype=np.int64) + int(applied_inserts),
        engine.degrees(),
    ).astype(np.int64)
    if backend.device_resident:
        from .resident import resident_enabled, run_resident

        if resident_enabled() or getattr(backend, "requires_resident", False):
            # same discipline, device-resident: the exact-cnt scan runs on
            # the bound structure (charged identically) and the settle
            # passes continue on device without re-downloading (core, cnt)
            return run_resident(engine, "semicore*", backend, core=warm,
                                initial_cnt_scan=True,
                                superstep_chunk=superstep_chunk)
    backend.bind(engine.planner)
    all_nodes = np.arange(n, dtype=np.int64)
    t0 = time.perf_counter()
    with _trace.span("cnt_prologue", cat="maintenance",
                     backend=backend.name, nodes=n):
        backend.begin_pass(all_nodes, warm)
        if backend.consumes_gather:
            vals, seg_ptr, _ = engine.planner.gather(all_nodes, warm)
        else:  # full-table backend scans its own resident copy
            engine.planner.charge_only(all_nodes)
            vals = seg_ptr = None
        engine.planner.account_node_scan(0, n - 1)
        cnt = backend.compute_cnt(vals, seg_ptr, warm)
    _MAINT_PROLOGUE.observe(time.perf_counter() - t0)
    return run_batch(engine, "semicore*", backend, core=warm, cnt=cnt,
                     rebind=False)


def _result(planner, backend, core, cnt, iters, comp, algo, upd, cph
            ) -> DecompResult:
    rep = backend.io_report()
    backend.unbind()
    return DecompResult(
        core=core,
        cnt=cnt,
        iterations=iters,
        node_computations=comp,
        edge_block_reads=planner.reader.reads,
        node_table_reads=planner.reader.node_table_reads,
        algorithm=algo,
        schedule="batch",
        updates_per_iter=upd,
        computations_per_iter=cph,
        backend=backend.name,
        kernel_blocks_active=rep.get("kernel_blocks_active", 0),
        kernel_blocks_skipped=rep.get("kernel_blocks_skipped", 0),
    )
