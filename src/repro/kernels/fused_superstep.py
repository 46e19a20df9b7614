"""Fused Pallas superstep (DESIGN.md §16).

One decomposition pass is one histogram ``pallas_call`` for the h-index and
the refreshed cnt, plus (semicore+ / semicore*) one counting call for the
neighbour rule.  The same kernel serves both, and it has a single lowering:
the Pallas interpreter on CPU runs exactly the code that Mosaic compiles for
the TPU.

  grid = (nbk,)   nbk = ceil(E / block_edges) edge blocks, in order.

  accumulate  every active block adds its edges to a per-row histogram of
              *capped* neighbour values (``min(nbr_core, core_row)``) kept in
              VMEM scratch.  The scatter is two one-hot matrices multiplied
              on the MXU: values x edges (bins) against rows x edges (the
              block's row window), bf16 one-hots with f32 accumulation, so
              every count is an exact integer.
  finalize    the last block turns each row's histogram into h (binary
              search over the monotone predicate #(vals >= k) >= k, each
              probe a masked sublane sum) and into #(vals >= h).

Everything that gathers across the whole node table (neighbour cores, the
per-edge caps, the semicore* push predicate, the semicore+ changed-neighbour
flag) runs in XLA between the calls; the kernel only streams per-edge values
and row offsets.  A count is the same kernel on values in {0, -1}: h is 0 and
the second output is the number of edges whose value is 0.

Activity masking happens inside the index maps: a scalar-prefetched
per-block flag keeps an inactive block's index map pinned to block 0, so the
pipeline never issues its HBM->VMEM DMA (same trick as ``segsum_active.py``),
and ``pl.when`` skips its compute.  Double buffering comes from the Pallas
grid pipeline itself.

Layout
------
Rows are addressed by their compact rank among rows with >= 1 edge, so
consecutive edges' ranks differ by <= 1 and a block of ``cbe`` edges spans at
most ``cbe`` ranks.  Per-row state is lane-dense: rank r lives at
``[r // 128, :, r % 128]``, the histogram scratch is ``(Rb, K, 128)`` (bins
on sublanes) and each per-row output ``(Rb, 1, 128)``.  A block's window
starts at the 128-aligned group holding its first rank and covers ``nwin``
groups; every dynamic index is on the untiled leading axis.

Counts are f32 (exact below 2**24; ``dmax < 2**24`` is validated at
structure-build time, engine.py).  The VMEM footprint and the SMEM of the
per-block tables are computed from the dims and checked against the limits
the kernel requests before anything is traced, so a graph that does not fit
raises ``ValueError`` instead of a compiler error.

Everything the engine observes — core/cnt/iters traces, planner I/O
accounting, kernel_blocks_active/skipped — is bit-identical to the per-probe
path: frontiers are identical, and block accounting replays from the pinned
frontier masks at the *accounting* block size, decoupled from the kernel
tile size (``REPRO_FUSED_BLOCK_EDGES``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "FusedArrays", "FusedTable", "build_fused_table", "fused_pass",
    "fused_hindex", "fused_counts", "fused_enabled", "fused_block_edges",
    "vmem_bytes", "check_memory", "VMEM_LIMIT_BYTES", "SMEM_LIMIT_BYTES",
]

LANES = 128
#: scoped VMEM the kernel requests (a v5e TensorCore has 128 MiB)
VMEM_LIMIT_BYTES = 100 * 2 ** 20
#: SMEM budget of the two scalar-prefetched per-block tables (1 MiB on v5e)
SMEM_LIMIT_BYTES = 512 * 2 ** 10
_BIN_CHUNK = 256  # value bins per one-hot matmul
_DEFAULT_TILE = 512


def fused_enabled() -> bool:
    """Hot-path switch: ``REPRO_PALLAS_FUSED=0`` reverts the pallas backend
    to the per-probe ``segment_sum_active`` dispatch (kept as the parity
    oracle for the differential tests).  Resolved through
    :func:`repro.runtime.setting`."""
    from repro import runtime as _runtime

    return _runtime.setting("pallas_fused")


def fused_block_edges() -> int:
    """Kernel tile size in edges (``REPRO_FUSED_BLOCK_EDGES``); independent
    of the planner's accounting block size.

    Without an env override the tile is 512 edges: the row window is a
    one-hot matrix, so a block's MXU work and its VMEM temporaries grow
    with the square of the tile.
    """
    from repro import runtime as _runtime

    v = _runtime.setting("fused_block_edges")
    if v is not None:
        if v < 8:
            raise ValueError(
                f"REPRO_FUSED_BLOCK_EDGES must be >= 8, got {v}")
        return v
    return _DEFAULT_TILE


class FusedArrays(NamedTuple):
    """Device-resident kernel operands (a jit-friendly pytree of arrays).

    Ep = nbk * cbe padded edges; pad edges have ``ev`` False.
    """
    nbr: jnp.ndarray      # (Ep,)         i32 neighbour node id (pad: 0)
    row: jnp.ndarray      # (Ep,)         i32 source node id (pad: 0)
    ev: jnp.ndarray       # (Ep,)         bool edge validity
    local: jnp.ndarray    # (nbk, 1, cbe) i32 row rank - 128 * base[b]
    base: jnp.ndarray     # (nbk,)        i32 first lane group of block b
    rank: jnp.ndarray     # (n,)          i32 compact rank (0 if absent)
    present: jnp.ndarray  # (n,)          bool node has >= 1 edge


@dataclasses.dataclass(frozen=True)
class FusedTable:
    """Static dims + device arrays for one (structure, tile-size) pair."""
    dims: tuple  # (cbe, nbk, nwin, Rb, n, E) — all python ints, hashable
    arrays: FusedArrays


def build_fused_table(seg_ptr, nbr, n: int, block_edges: int) -> FusedTable:
    """Host-side build of the compact-rank edge table (once per structure
    per tile size; cached on ResidentStructure)."""
    seg_ptr = np.asarray(seg_ptr, dtype=np.int64)
    nbr_h = np.asarray(nbr, dtype=np.int32)
    E = int(nbr_h.shape[0])
    cbe = int(block_edges)
    if cbe < 8:
        raise ValueError(f"block_edges must be >= 8, got {cbe}")
    lens = np.diff(seg_ptr)
    present = lens > 0
    pres_idx = np.flatnonzero(present)
    U = int(pres_idx.shape[0])
    nbk = max(1, -(-E // cbe))
    Ep = nbk * cbe
    # a block spans <= cbe ranks from an arbitrary offset within its first
    # lane group, so its window covers this many 128-row groups
    nwin = (cbe + LANES - 2) // LANES + 1
    Rb = -(-max(U, 1) // LANES) + nwin

    rank = np.zeros(n, dtype=np.int32)
    rank[pres_idx] = np.arange(U, dtype=np.int32)
    rows = np.repeat(np.arange(n, dtype=np.int32), lens)
    compact = np.zeros(Ep, dtype=np.int32)
    compact[:E] = rank[rows]
    compact[E:] = compact[E - 1] if E else 0
    base = compact[0::cbe] // LANES
    local = compact.reshape(nbk, cbe) - (base * LANES)[:, None]
    row_p = np.zeros(Ep, dtype=np.int32)
    row_p[:E] = rows
    nbr_p = np.zeros(Ep, dtype=np.int32)
    nbr_p[:E] = nbr_h

    arrays = FusedArrays(
        nbr=jnp.asarray(nbr_p),
        row=jnp.asarray(row_p),
        ev=jnp.asarray(np.arange(Ep) < E),
        local=jnp.asarray(local.astype(np.int32)[:, None, :]),
        base=jnp.asarray(base.astype(np.int32)),
        rank=jnp.asarray(rank),
        present=jnp.asarray(present),
    )
    return FusedTable(dims=(cbe, nbk, nwin, Rb, int(n), E), arrays=arrays)


def _bins(num_probes: int) -> int:
    return max(8, 1 << int(num_probes))


def vmem_bytes(dims, num_probes: int) -> int:
    """VMEM the histogram kernel needs at these dims: the scratch, the
    double-buffered blocks and the largest temporaries of each phase."""
    cbe, nbk, nwin, Rb, n, E = dims
    K = _bins(num_probes)
    kc = min(K, _BIN_CHUNK)
    lanes = -(-cbe // LANES) * LANES
    hist = Rb * K * LANES * 4
    outs = 2 * 2 * Rb * 8 * LANES * 4        # (1, 128) rows pad to 8 sublanes
    blocks = 2 * 2 * 8 * lanes * 4           # vals + local, double-buffered
    onehots = (nwin * LANES * lanes * 2      # row one-hots, bf16
               + kc * lanes * (4 + 2)        # value one-hot + its compare
               + LANES * lanes * 4)          # row compare being converted
    partial = 2 * kc * LANES * 4             # matmul result + window slab
    finalize = 4 * K * LANES * 4             # histogram slab + masked copies
    return hist + outs + blocks + onehots + partial + finalize


def check_memory(dims, num_probes: int) -> None:
    """Raise ``ValueError`` where the kernel at these dims would not fit the
    VMEM it requests or the SMEM of its per-block tables."""
    cbe, nbk, nwin, Rb, n, E = dims
    need = vmem_bytes(dims, num_probes)
    if need > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"fused superstep needs {need} B of VMEM for {Rb * LANES} rows x "
            f"{_bins(num_probes)} bins, over the {VMEM_LIMIT_BYTES} B it may "
            "request; this graph's size and max core want the xla backend")
    if 2 * 4 * nbk > SMEM_LIMIT_BYTES:
        raise ValueError(
            f"fused superstep: {nbk} edge blocks overflow the "
            f"{SMEM_LIMIT_BYTES} B of SMEM for its per-block tables; use a "
            "larger REPRO_FUSED_BLOCK_EDGES or the xla backend")


def _hist_kernel(on_ref, base_ref, vals_ref, local_ref, h_ref, cnt_ref,
                 hist_ref, *, K: int, kc: int, nwin: int, Rb: int, nbk: int):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    @pl.when(on_ref[b] > 0)
    def _accumulate():
        vals = vals_ref[...]     # (1, cbe) capped value per edge, -1: none
        local = local_ref[...]   # (1, cbe) row rank relative to the window
        cbe = vals.shape[1]
        base = base_ref[b]
        rows_oh = [
            (jax.lax.broadcasted_iota(jnp.int32, (LANES, cbe), 0)
             + w * LANES == local).astype(jnp.bfloat16)
            for w in range(nwin)]

        def bin_chunk(c, carry):
            k0 = pl.multiple_of(c * kc, kc)
            vals_oh = (jax.lax.broadcasted_iota(jnp.int32, (kc, cbe), 0)
                       + k0 == vals).astype(jnp.bfloat16)
            for w in range(nwin):
                part = jax.lax.dot_general(
                    vals_oh, rows_oh[w], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)   # (kc, LANES)
                hist_ref[base + w, pl.ds(k0, kc), :] += part
            return carry

        jax.lax.fori_loop(0, K // kc, bin_chunk, 0)

    @pl.when(b == nbk - 1)
    def _finalize():
        kio = jax.lax.broadcasted_iota(jnp.int32, (K, LANES), 0)

        def row_group(r, carry):
            hist = hist_ref[r]   # (K, LANES)

            def count_ge(t):
                return jnp.sum(jnp.where(kio >= t, hist, 0.0), axis=0,
                               keepdims=True)

            # h = max k with #(vals >= k) >= k; the predicate is monotone in
            # k and vals are capped by the row's core, so this is
            # min(h_true, cap) — hindex_bsearch's bounded answer
            h = jnp.zeros((1, LANES), jnp.int32)
            step = K // 2
            while step:
                cand = h + step
                h = jnp.where(count_ge(cand) >= cand.astype(jnp.float32),
                              cand, h)
                step //= 2
            h_ref[r] = h
            cnt_ref[r] = count_ge(h).astype(jnp.int32)
            return carry

        jax.lax.fori_loop(0, Rb, row_group, 0)


@functools.lru_cache(maxsize=None)
def _hist_call(dims, num_probes: int, interpret: bool):
    cbe, nbk, nwin, Rb, n, E = dims
    check_memory(dims, num_probes)
    K = _bins(num_probes)
    kernel = functools.partial(_hist_kernel, K=K, kc=min(K, _BIN_CHUNK),
                               nwin=nwin, Rb=Rb, nbk=nbk)

    def stream(b, on, base):
        # activity-masked DMA: an inactive block re-points its fetch at
        # block 0, so the pipeline never pulls its bytes from HBM
        return (jnp.where(on[b] > 0, b, 0), 0, 0)

    def whole(b, on, base):
        return (0, 0, 0)

    per_row = jax.ShapeDtypeStruct((Rb, 1, LANES), jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nbk,),
        in_specs=[pl.BlockSpec((None, 1, cbe), stream),    # capped values
                  pl.BlockSpec((None, 1, cbe), stream)],   # window rows
        out_specs=[pl.BlockSpec((Rb, 1, LANES), whole),    # h
                   pl.BlockSpec((Rb, 1, LANES), whole)],   # #(vals >= h)
        scratch_shapes=[pltpu.VMEM((Rb, K, LANES), jnp.float32)],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=[per_row, per_row],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret, name="fused_superstep")


def _per_block(edge_on, dims):
    cbe, nbk = dims[0], dims[1]
    return jnp.any(edge_on.reshape(nbk, cbe), axis=1).astype(jnp.int32)


def _histogram(vals, block_on, arrs: FusedArrays, dims, num_probes,
               interpret):
    """(h, #(vals >= h)) per node, globalized (0 for absent nodes)."""
    cbe, nbk = dims[0], dims[1]
    fn = _hist_call(dims, int(num_probes), bool(interpret))
    h, cnt = fn(block_on, arrs.base, vals.reshape(nbk, 1, cbe), arrs.local)

    def globalize(x):
        return jnp.where(arrs.present,
                         jnp.take(x.reshape(-1), arrs.rank, mode="clip"), 0)

    return globalize(h), globalize(cnt)


def _count(edge_on, block_on, arrs, dims, interpret):
    """#(edges with ``edge_on``) per node: the histogram of {0, -1}."""
    vals = jnp.where(edge_on, 0, -1).astype(jnp.int32)
    return _histogram(vals, block_on, arrs, dims, 0, interpret)[1]


def _frontier_hindex(core, active, arrs, dims, num_probes, interpret):
    """Capped h-index and #(nbr core >= h) of every active node (0 off the
    frontier), plus the pass-start neighbour cores per edge."""
    nv = jnp.take(core, arrs.nbr, mode="clip")
    row_on = jnp.take(active, arrs.row, mode="clip") & arrs.ev
    vals = jnp.where(
        row_on, jnp.minimum(nv, jnp.take(core, arrs.row, mode="clip")), -1)
    h, cnt_h = _histogram(vals, _per_block(row_on, dims), arrs, dims,
                          num_probes, interpret)
    return nv, h, cnt_h


def fused_pass(core, cnt, active, arrs: FusedArrays, *, dims, num_probes,
               algorithm: str, interpret: bool):
    """One engine superstep through the fused kernel; traceable (jit-safe).

    Args match the resident reference pass: ``core``/``cnt`` int32 (n,),
    ``active`` bool (n,).  Returns ``(core2, cnt2, active2, upd)`` with the
    exact semantics of the per-probe reference (resident.py) — including
    ``cnt``/``active`` passthrough for algorithms that don't track them.
    """
    if algorithm not in ("semicore", "semicore+", "semicore*"):
        raise ValueError(f"unknown algorithm {algorithm!r}")
    core = core.astype(jnp.int32)
    nv, h, refr = _frontier_hindex(core, active, arrs, dims, num_probes,
                                   interpret)
    changed = active & (h != core)
    core2 = jnp.where(active, h, core)
    upd = jnp.sum(changed.astype(jnp.int32))
    if algorithm == "semicore":
        return core2, cnt, active, upd
    # the neighbour rule only reads blocks holding an active neighbour
    nbr_blocks = _per_block(
        jnp.take(active, arrs.nbr, mode="clip") & arrs.ev, dims)
    if algorithm == "semicore+":
        hit = jnp.take(changed, arrs.nbr, mode="clip") & arrs.ev
        touched = _count(hit, nbr_blocks, arrs, dims, interpret)
        return core2, cnt, (touched > 0) & (core2 > 0), upd
    # push decrements: edge (v -> u) with u active and core2(v) in
    # (h(u), c_old(u)]; an inactive neighbour has core2 == c_old, an empty
    # interval
    c2_row = jnp.take(core2, arrs.row, mode="clip")
    push = arrs.ev & (c2_row > jnp.take(core2, arrs.nbr, mode="clip")) \
        & (c2_row <= nv)
    dec = _count(push, nbr_blocks, arrs, dims, interpret)
    cnt2 = jnp.where(active, refr, cnt) - dec
    return core2, cnt2, (cnt2 < core2) & (core2 > 0), upd


@functools.partial(jax.jit, static_argnames=("dims", "num_probes",
                                             "interpret"))
def fused_hindex(core, active, arrs: FusedArrays, *, dims, num_probes,
                 interpret):
    """Legacy per-pass path: (h, cnt_at_h) for the frontier in one call.

    ``h`` is the cap-bounded h-index of pass-start neighbor values and
    ``cnt_at_h`` the refreshed #(nbr_core >= h) — both global (n,), zero
    off-frontier.  PallasBackend serves ``compute_cnt(thresholds == h)``
    from the second output without another kernel launch.
    """
    _, h, cnt_h = _frontier_hindex(core.astype(jnp.int32), active, arrs,
                                   dims, num_probes, interpret)
    return h, cnt_h


@functools.partial(jax.jit, static_argnames=("dims", "interpret"))
def fused_counts(core, thresholds, active, arrs: FusedArrays, *, dims,
                 interpret):
    """#(nbr pass-start core >= threshold) per active row (0 elsewhere).

    Used by the warm-settle prologue and by ``compute_cnt`` calls whose
    thresholds differ from the pass's h (cache miss).
    """
    row_on = jnp.take(active, arrs.row, mode="clip") & arrs.ev
    ge = (jnp.take(core, arrs.nbr, mode="clip")
          >= jnp.take(thresholds, arrs.row, mode="clip"))
    return _count(row_on & ge, _per_block(row_on, dims), arrs, dims,
                  interpret)
