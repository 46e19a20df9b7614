"""jit'd public wrappers around the Pallas kernels (+ XLA fallbacks).

``use_pallas`` selects the kernel path.  The platform picks the mode
(``kernels.resolve_interpret``): compiled by Mosaic on a TPU, the Pallas
interpreter on CPU.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .segsum import segsum_pallas_partials
from .segsum_active import segsum_active_partials
from .embedding_bag import embedding_bag_pallas
from .flash_decode import flash_decode_pallas
from . import ref

__all__ = ["segment_sum", "segment_sum_active", "make_superstep_segsum",
           "embedding_bag", "flash_decode"]


@partial(jax.jit, static_argnames=("num_segments", "block_edges", "use_pallas", "interpret"))
def segment_sum(
    vals: jax.Array,
    rows: jax.Array,
    num_segments: int,
    *,
    block_edges: int = 512,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Segment-sum over *sorted* rows; (E,) or (E, D) values -> (n[, D]).

    Pallas path: compact ranks -> blocked one-hot-matmul kernel -> window
    scatter-add epilogue (see segsum.py).
    """
    if not use_pallas:
        return ref.segment_sum_ref(vals, rows, num_segments)
    squeeze = vals.ndim == 1
    if squeeze:
        vals = vals[:, None]
    E, D = vals.shape
    in_dtype = vals.dtype
    Ep = -(-max(E, 1) // block_edges) * block_edges
    pad = Ep - E
    if pad:
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
        rows = jnp.pad(rows, (0, pad), mode="edge")
    rows = rows.astype(jnp.int32)
    # dense compact ranks of the sorted segment ids
    boundary = jnp.concatenate(
        [jnp.ones((1,), jnp.int32), (rows[1:] != rows[:-1]).astype(jnp.int32)]
    )
    compact = jnp.cumsum(boundary) - 1  # (Ep,), in [0, Ep)
    partials = segsum_pallas_partials(
        vals.astype(jnp.float32), compact[:, None], block_edges=block_edges,
        interpret=interpret,
    )  # (nb, BE, D)
    nb = Ep // block_edges
    firsts = compact[:: block_edges]  # (nb,) first compact rank per block
    # epilogue: windows overlap by at most the boundary row -> scatter-add
    win = firsts[:, None] + jnp.arange(block_edges)[None, :]  # (nb, BE)
    r_cap = Ep + block_edges
    dense = jnp.zeros((r_cap, D), jnp.float32).at[win.reshape(-1)].add(
        partials.reshape(-1, D)
    )
    # compact rank -> global segment id
    seg_of = jnp.zeros((r_cap,), jnp.int32).at[compact].set(rows)
    out = jnp.zeros((num_segments, D), jnp.float32).at[seg_of[: Ep]].add(dense[: Ep])
    # rank 0..U-1 used; unused slots are zero contributions to segment 0
    if jnp.issubdtype(in_dtype, jnp.integer):
        out = jnp.rint(out)
    out = out.astype(in_dtype)
    return out[:, 0] if squeeze else out


def make_superstep_segsum(
    rows: jax.Array,
    node_active: jax.Array,
    num_segments: int,
    *,
    block_edges: int = 512,
    interpret: bool | None = None,
):
    """Superstep-granular entry to the block-skipping segment-sum.

    One superstep (pass) runs several reductions over the *same* sorted
    ``rows`` with the *same* frontier mask — log2(kmax) h-index probes plus
    the cnt refresh.  This precomputes everything that depends only on
    (rows, node_active) — padding, dense compact ranks, the on-device
    block-activity mask, the window scatter targets — once, and returns an
    ``apply(vals)`` closure for the per-probe sums.  Traceable: intended to
    be called *inside* a jit (the device-resident superstep, resident.py).

    Requires ``rows.shape[0] >= 1`` (edgeless graphs never reach the kernel
    layer — the engine resolves them host-side).
    """
    E = rows.shape[0]
    Ep = -(-E // block_edges) * block_edges
    pad = Ep - E
    if pad:
        rows = jnp.pad(rows, (0, pad), mode="edge")
    rows = rows.astype(jnp.int32)
    boundary = jnp.concatenate(
        [jnp.ones((1,), jnp.int32), (rows[1:] != rows[:-1]).astype(jnp.int32)])
    compact = jnp.cumsum(boundary) - 1
    nb = Ep // block_edges
    # per-block activity from the per-node mask — derived on-device, so the
    # resident superstep's frontier never round-trips to the host for it
    row_active = jnp.take(node_active, rows, mode="clip").astype(jnp.int32)
    block_active = jnp.max(row_active.reshape(nb, block_edges), axis=1)
    firsts = compact[::block_edges]
    win = firsts[:, None] + jnp.arange(block_edges)[None, :]
    r_cap = Ep + block_edges
    seg_of = jnp.zeros((r_cap,), jnp.int32).at[compact].set(rows)

    def apply(vals: jax.Array) -> jax.Array:
        squeeze = vals.ndim == 1
        if squeeze:
            vals = vals[:, None]
        in_dtype = vals.dtype
        if pad:
            vals = jnp.pad(vals, ((0, pad), (0, 0)))
        D = vals.shape[1]
        partials = segsum_active_partials(
            vals.astype(jnp.float32), compact[:, None], block_active,
            block_edges=block_edges, interpret=interpret)
        dense = jnp.zeros((r_cap, D), jnp.float32).at[win.reshape(-1)].add(
            partials.reshape(-1, D))
        out = jnp.zeros((num_segments, D), jnp.float32).at[seg_of[:Ep]].add(
            dense[:Ep])
        if jnp.issubdtype(in_dtype, jnp.integer):
            out = jnp.rint(out)
        out = out.astype(in_dtype)
        return out[:, 0] if squeeze else out

    return apply


@partial(jax.jit, static_argnames=("num_segments", "block_edges", "interpret"))
def segment_sum_active(
    vals: jax.Array,
    rows: jax.Array,
    node_active: jax.Array,
    num_segments: int,
    *,
    block_edges: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Block-skipping segment-sum (SemiCore*'s saved I/O on TPU).

    Blocks whose rows are all inactive are neither fetched nor computed;
    their contributions are exactly zero (the caller's invariant — Lemma
    4.2 — guarantees no needed update lives in a skipped block).  One-shot
    wrapper over :func:`make_superstep_segsum`; supersteps issuing several
    sums per frontier should build the closure once instead.
    """
    return make_superstep_segsum(
        rows, node_active, num_segments,
        block_edges=block_edges, interpret=interpret)(vals)


@partial(jax.jit, static_argnames=("mode", "use_pallas", "interpret"))
def embedding_bag(
    table: jax.Array,
    indices: jax.Array,
    weights: jax.Array | None = None,
    *,
    mode: str = "sum",
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """EmbeddingBag: out[b] = pool_l w[b,l] * table[idx[b,l]]; idx<0 masked."""
    B, L = indices.shape
    if weights is None:
        weights = jnp.ones((B, L), table.dtype)
    if not use_pallas:
        return ref.embedding_bag_ref(table, indices, weights, mode)
    mask = (indices >= 0).astype(table.dtype)
    w = weights * mask
    out = embedding_bag_pallas(table, indices.astype(jnp.int32), w, interpret=interpret)
    if mode == "mean":
        denom = jnp.maximum(w.sum(axis=1, keepdims=True), 1e-9)
        out = out / denom
    return out


@partial(jax.jit, static_argnames=("block_kv", "use_pallas", "interpret"))
def flash_decode(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    cache_len: jax.Array,
    *,
    block_kv: int = 512,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Single-token GQA decode attention over a long KV cache."""
    if not use_pallas:
        return ref.flash_decode_ref(q, k, v, cache_len)
    return flash_decode_pallas(
        q, k, v, cache_len, block_kv=block_kv, interpret=interpret
    )
